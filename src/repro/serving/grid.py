"""Plan grid: precompiled (batch bucket × band tier) serving executors.

The band ladder (``serving.ladder``) made quality a runtime knob; this
module makes **batch shape** one too.  aphrodite/vLLM precapture a ladder
of padded batch sizes so serving never recompiles; the plan grid is the
2-D version of that idea — one executor per (batch bucket × band tier)
cell, all captured at warmup:

* **buckets** follow the aphrodite capture schedule: 1, 2, 4, then
  multiples of 8 up to ``max_batch`` (:func:`batch_buckets`); a batch of
  ``n`` requests runs in the smallest covering bucket
  (:func:`bucket_for` — 1→1, 3→4, 9→16, 17→24 …), so low-occupancy
  traffic stops paying ``max_batch``-wide GEMMs;
* **tiers** are the ladder's band tiers; every cell in a tier column
  passes the *same* prefix-sliced Ξ buffers to its executable as
  arguments (``core.plan.jit_over``), so device memory stays O(one
  ladder) no matter how many buckets are captured;
* each cell is a **static-shape, donated** entry point
  (``core.plan.capture_compiled``): the input device buffer is donated
  to the executable and the host side stages rows into a reusable
  pinned buffer (:class:`PinnedPool`) — steady-state serving does zero
  reshapes, zero retraces, and no per-batch host allocations beyond the
  one staged copy;
* **compile accounting** rides on the capture: every trace fires the
  grid's ``on_compile(cell_name)`` hook exactly once, so the scheduler
  can report ``compiles_total`` / ``compiles_post_warmup`` and CI can
  assert the post-warmup count is zero.

:class:`GridColumn` keeps the attribute surface of the scheduler's old
per-tier executor (``coef_fn`` / ``packed_fn`` / ``compiled`` / ``w_in``)
so the column is a drop-in replacement that additionally routes each
call to the covering bucket's cell.
"""
from __future__ import annotations

import bisect
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import plan as planlib
from repro.serving.trace import NULL_TRACER

__all__ = [
    "batch_buckets",
    "validate_buckets",
    "bucket_for",
    "cover_buckets",
    "PinnedPool",
    "GridCell",
    "GridColumn",
    "PlanGrid",
]

KINDS = ("coefficients", "bytes")


# --------------------------------------------------------------------------
# Bucket math (aphrodite _BATCH_SIZES_TO_CAPTURE / _get_graph_batch_size)
# --------------------------------------------------------------------------


def batch_buckets(max_batch: int) -> tuple[int, ...]:
    """The aphrodite-style capture schedule up to ``max_batch``:
    ``1, 2, 4`` then multiples of 8, with ``max_batch`` itself always the
    last bucket (so every admissible batch has a cover)."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    buckets = [b for b in (1, 2, 4) if b <= max_batch]
    buckets += list(range(8, max_batch + 1, 8))
    if buckets[-1] != max_batch:
        buckets.append(max_batch)
    return tuple(buckets)


def validate_buckets(buckets) -> tuple[int, ...]:
    """Normalize an explicit bucket list: ints, strictly increasing,
    all positive."""
    out = tuple(int(b) for b in buckets)
    if not out:
        raise ValueError("need at least one bucket")
    if any(b < 1 for b in out):
        raise ValueError(f"buckets must be positive: {out}")
    if any(a >= b for a, b in zip(out, out[1:])):
        raise ValueError(f"buckets must be strictly increasing: {out}")
    return out


def bucket_for(n: int, buckets) -> int:
    """Smallest bucket covering ``n`` requests (aphrodite's
    ``_get_graph_batch_size``): 1→1, 3→4, 9→16, 17→24 under the default
    schedule.  A batch no bucket covers is a caller bug — the scheduler
    never forms batches past the largest bucket."""
    if n < 1:
        raise ValueError(f"batch must be >= 1, got {n}")
    i = bisect.bisect_left(buckets, n)
    if i == len(buckets):
        raise ValueError(
            f"batch {n} exceeds the largest capture bucket {buckets[-1]}")
    return buckets[i]


def cover_buckets(buckets, batch: int) -> tuple[int, ...]:
    """The bucket set a scheduler with ``batch`` slots actually captures:
    the default schedule when ``buckets`` is None, else the explicit list
    clipped to ``batch`` — and ``batch`` itself is always present, so the
    full batch has a cell."""
    if buckets is None:
        return batch_buckets(batch)
    out = tuple(b for b in validate_buckets(buckets) if b <= batch)
    if not out or out[-1] != batch:
        out = out + (batch,)
    return out


# --------------------------------------------------------------------------
# Pinned host staging + captured cells
# --------------------------------------------------------------------------


class PinnedPool:
    """Reusable host staging buffers, keyed by (shape, dtype).

    One buffer per distinct full-batch shape, shared by every cell that
    stages through it — the grid has one dispatching thread (the
    scheduler worker), so sharing is safe and keeps host memory at
    O(distinct shapes), not O(cells).
    """

    def __init__(self) -> None:
        self._bufs: dict[tuple, np.ndarray] = {}

    def get(self, shape, dtype=np.float32) -> np.ndarray:
        key = (tuple(int(s) for s in shape), np.dtype(dtype))
        buf = self._bufs.get(key)
        if buf is None:
            buf = self._bufs[key] = np.zeros(key[0], key[1])
        return buf

    @property
    def nbytes(self) -> int:
        return sum(b.nbytes for b in self._bufs.values())

    def __len__(self) -> int:
        return len(self._bufs)


class GridCell:
    """One (kind, bucket) executor of a grid column.

    ``__call__`` stages up to ``bucket`` rows into the pooled pinned
    buffer (zero-filling the pad tail), copies it to device
    (``jnp.array`` always copies — the staging buffer stays reusable
    while the fresh device buffer is donated into the executable), and
    returns the logits for all ``bucket`` slots; callers slice off the
    first ``n``.  ``hits`` counts dispatches for the metrics report.
    """

    __slots__ = ("name", "bucket", "item_shape", "hits", "_fn", "_pool",
                 "_shape", "_tracer", "_compiled", "_executor", "_packed")

    def __init__(self, name: str, bucket: int, item_shape,
                 fn: Callable, pool: PinnedPool, tracer=None, *,
                 compiled=None, executor=None, packed=False):
        self.name = name
        self.bucket = int(bucket)
        self.item_shape = tuple(int(s) for s in item_shape)
        self._shape = (self.bucket, *self.item_shape)
        self._fn = fn
        self._pool = pool
        self._tracer = tracer if tracer is not None else NULL_TRACER
        self._compiled = compiled
        self._executor = executor
        self._packed = bool(packed)
        self.hits = 0

    def __call__(self, rows: np.ndarray, rids=None) -> jnp.ndarray:
        rows = np.asarray(rows, np.float32)
        n = rows.shape[0]
        if n > self.bucket or tuple(rows.shape[1:]) != self.item_shape:
            raise ValueError(
                f"cell {self.name} serves shape {self._shape}, "
                f"got {tuple(rows.shape)}")
        tr = self._tracer
        ta = tr.now() if tr.enabled else 0.0
        host = self._pool.get(self._shape)
        host[:n] = rows
        if n < self.bucket:
            host[n:] = 0.0
        dev = jnp.array(host)
        if tr.enabled:
            # nested under the scheduler's device-dispatch span: the
            # host-staging + host->device copy share of the dispatch
            tr.span("device", "pad/stage", ta, tr.now(),
                    args={"cell": self.name, "n": n,
                          "pad": self.bucket - n, "rids": rids})
        self.hits += 1
        return self._fn(dev)

    def warmup(self) -> None:
        host = self._pool.get(self._shape)
        host[:] = 0.0
        self._fn(jnp.array(host)).block_until_ready()

    def lower(self):
        """Lowering of this cell's own captured entry
        (``.compile().as_text()`` is the program its dispatches run)."""
        return self._fn.lower()

    def time_wall(self, *, iters: int = 3) -> float:
        """Median wall (seconds) of the captured executable on a zero
        bucket batch — the staged host→device copy stays outside the
        wall, exactly as :meth:`__call__` dispatches.  Uses only the
        already-captured entry: zero new compiles on a warmed cell."""
        import statistics
        import time

        host = self._pool.get(self._shape)
        host[:] = 0.0
        out = self._fn(jnp.array(host))  # untimed: ensures compiled
        jax.block_until_ready(out)
        walls = []
        for _ in range(max(1, iters)):
            dev = jnp.array(host)
            t0 = time.perf_counter()
            out = self._fn(dev)
            jax.block_until_ready(out)
            walls.append(time.perf_counter() - t0)
        return statistics.median(walls)

    def profile(self, rows: np.ndarray | None = None, *,
                iters: int = 3, warmup: int = 1) -> dict:
        """Per-block measured walls for this cell's schedule, plus the
        whole-cell wall through its own captured (donated) executable.

        Runs the cell's compiled plan in the profiling execution mode
        (``core.plan.StepProfile`` — per-step jit with device fences;
        logits bit-identical to the captured executable's) on ``rows``
        staged exactly as :meth:`__call__` stages them (zero-pad to the
        bucket; an all-zero batch when ``rows`` is None), then times the
        unprofiled captured entry on the same staged input.  Returns
        ``{"cell", "bucket", "steps": [{"name", "measured_us"}...],
        "profiled_total_us", "cell_wall_us", "logits"}`` with medians
        over ``iters`` timed calls after ``warmup`` discarded ones.
        """
        import statistics
        import time

        if self._compiled is None:
            raise RuntimeError(
                f"cell {self.name} was built without a compiled-plan "
                "reference; profiling needs the schedule, not just the "
                "captured entry")
        host = self._pool.get(self._shape)
        host[:] = 0.0
        if rows is not None:
            rows = np.asarray(rows, np.float32)
            n = rows.shape[0]
            if n > self.bucket or tuple(rows.shape[1:]) != self.item_shape:
                raise ValueError(
                    f"cell {self.name} serves shape {self._shape}, "
                    f"got {tuple(rows.shape)}")
            host[:n] = rows
        apply_fn = (planlib.apply_compiled_packed if self._packed
                    else planlib.apply_compiled)
        prof = planlib.StepProfile()
        for _ in range(max(1, warmup)):
            apply_fn(self._compiled, jnp.array(host),
                     executor=self._executor, profile=prof)
        prof.reset()
        logits = None
        for _ in range(max(1, iters)):
            logits = apply_fn(self._compiled, jnp.array(host),
                              executor=self._executor, profile=prof)
        # the captured executable donates its input: fresh device buffer
        # per call, staged copy outside the timed wall (as __call__ does)
        walls = []
        out = self._fn(jnp.array(host))  # untimed: ensures it is compiled
        jax.block_until_ready(out)
        for _ in range(max(1, iters)):
            dev = jnp.array(host)
            t0 = time.perf_counter()
            out = self._fn(dev)
            jax.block_until_ready(out)
            walls.append(time.perf_counter() - t0)
        steps = prof.summary()
        return {
            "cell": self.name,
            "bucket": self.bucket,
            "steps": [{"name": k, "measured_us": v * 1e6}
                      for k, v in steps.items()],
            "profiled_total_us": sum(steps.values()) * 1e6,
            "cell_wall_us": statistics.median(walls) * 1e6,
            "logits": np.asarray(logits),
        }


class GridColumn:
    """All bucket cells of one *distinct* compiled schedule (band tier).

    Drop-in for the scheduler's former per-tier executor:
    :meth:`coef_fn` / :meth:`packed_fn` take an **unpadded** row batch,
    route it to the smallest covering bucket's cell, and return the full
    bucket's logits.  Cells materialize lazily on first use (so a column
    serving only ``coefficients`` traffic never compiles packed cells)
    and eagerly under :meth:`PlanGrid.warmup`.
    """

    def __init__(self, compiled: planlib.CompiledPlan,
                 executor: str | None = None, *,
                 buckets=None, pool: PinnedPool | None = None,
                 donate: bool = True,
                 on_compile: Callable[[str], None] | None = None,
                 tier_name: str = "tier", tracer=None):
        self.compiled = compiled
        self.executor = executor
        self.w_in = compiled.stem.w_in
        self.buckets = None if buckets is None else validate_buckets(buckets)
        self.donate = donate
        self.tier_name = tier_name
        self.pool = pool if pool is not None else PinnedPool()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._on_compile = on_compile
        self.cells: dict[tuple[str, int], GridCell] = {}

    def cell(self, kind: str, bucket: int, item_shape) -> GridCell:
        key = (kind, int(bucket))
        c = self.cells.get(key)
        if c is None:
            name = f"{self.tier_name}/{kind}/b{int(bucket)}"
            on_compile = self._on_compile
            fn = planlib.capture_compiled(
                self.compiled, (int(bucket), *item_shape),
                packed=(kind == "bytes"), executor=self.executor,
                donate=self.donate,
                on_trace=(None if on_compile is None
                          else (lambda: on_compile(name))))
            c = self.cells[key] = GridCell(name, bucket, item_shape, fn,
                                           self.pool, tracer=self.tracer,
                                           compiled=self.compiled,
                                           executor=self.executor,
                                           packed=(kind == "bytes"))
        return c

    def _route(self, kind: str, rows: np.ndarray,
               rids=None) -> jnp.ndarray:
        rows = np.asarray(rows, np.float32)
        n = rows.shape[0]
        bucket = n if self.buckets is None else bucket_for(n, self.buckets)
        return self.cell(kind, bucket, rows.shape[1:])(rows, rids=rids)

    def coef_fn(self, rows: np.ndarray, rids=None) -> jnp.ndarray:
        """Serve a ``(n, bh, bw, C, 64)`` coefficient batch (n need not
        match any bucket — the covering cell pads).  ``rids`` labels the
        rows' request ids on the flight-recorder span, nothing more."""
        return self._route("coefficients", rows, rids=rids)

    def packed_fn(self, rows: np.ndarray, rids=None) -> jnp.ndarray:
        """Serve a ``(n, bh, bw, C·w_in)`` tile-packed batch."""
        return self._route("bytes", rows, rids=rids)


class PlanGrid:
    """The full (batch bucket × band tier) executor grid over a ladder.

    ``columns[i]`` serves ``ladder.tiers[i]``; tiers sharing a
    ``CompiledPlan`` share a column (and its cells, pinned buffers, and
    compile cache).  ``grid``/``channels`` fix the serving resolution so
    :meth:`warmup` can sweep every cell eagerly; without them cells
    still materialize lazily from the first batch's shape.
    """

    def __init__(self, ladder, *, batch: int, buckets=None,
                 grid: tuple[int, int] | None = None, channels: int = 3,
                 executor: str | None = None, donate: bool = True,
                 on_compile: Callable[[str], None] | None = None,
                 tracer=None):
        if batch < 1:
            raise ValueError("batch must be >= 1")
        self.ladder = ladder
        self.batch = int(batch)
        if buckets is None:
            buckets = getattr(ladder, "buckets", None)
        self.buckets = cover_buckets(buckets, self.batch)
        self.grid = grid
        self.channels = channels
        self.pool = PinnedPool()
        by_id: dict[int, GridColumn] = {}
        self.columns: list[GridColumn] = []
        for tier in ladder.tiers:
            key = id(tier.compiled)
            if key not in by_id:
                by_id[key] = GridColumn(
                    tier.compiled, executor, buckets=self.buckets,
                    pool=self.pool, donate=donate, on_compile=on_compile,
                    tier_name=tier.name, tracer=tracer)
            self.columns.append(by_id[key])
        self.distinct = list(by_id.values())
        # optional per-cell cost annotations (introspect.profile_plan_grid
        # fills these in under serve --profile-grid): cell name ->
        # {"flops", "predicted_us", ...}; the scheduler stamps them onto
        # its device-dispatch trace spans
        self.cell_costs: dict[str, dict] = {}

    def annotate_costs(self, costs: dict[str, dict]) -> None:
        """Attach per-cell cost annotations (merged by cell name)."""
        self.cell_costs.update(costs)

    def cost_for(self, cell_name: str) -> dict | None:
        return self.cell_costs.get(cell_name)

    def bucket_for(self, n: int) -> int:
        return bucket_for(n, self.buckets)

    def warmup(self, kinds=KINDS) -> None:
        """Compile every (kind, bucket) cell of every distinct column.
        After this sweep the set of compiled shapes is closed: any
        further trace is a bug the compile accounting will surface."""
        if self.grid is None:
            raise ValueError("warmup needs grid= at construction")
        bh, bw = self.grid
        for col in self.distinct:
            for bucket in self.buckets:
                if "coefficients" in kinds:
                    col.cell("coefficients", bucket,
                             (bh, bw, self.channels, 64)).warmup()
                if "bytes" in kinds:
                    col.cell("bytes", bucket,
                             (bh, bw, self.channels * col.w_in)).warmup()

    def cell_hits(self) -> dict[str, int]:
        return {c.name: c.hits
                for col in self.distinct for c in col.cells.values()}

    def summary(self) -> dict[str, Any]:
        """Startup-log / report block: grid extent and staging cost."""
        return {
            "buckets": list(self.buckets),
            "tiers": [t.name for t in self.ladder.tiers],
            "distinct_columns": len(self.distinct),
            "cells": sum(len(col.cells) for col in self.distinct),
            "host_staging_bytes": self.pool.nbytes,
        }
