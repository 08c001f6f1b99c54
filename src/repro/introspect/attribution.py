"""Per-block cost attribution over a compiled plan's *optimized* HLO.

The compiled schedule is an explicit step list (``core.plan.
compiled_steps``: stem → one step per residual block → head).  Each step
is lowered and compiled on its own at the chained activation shapes, its
optimized HLO fed through ``launch.hlo_analysis.analyze_hlo`` (the
trip-count-aware text analyzer), and the result joined with the
schedule's own metadata — band budgets, retained qtable energy, the
executor the compiler chose, its VMEM estimate — into one
:class:`BlockCost` row per step.  A whole-module analysis of the same
entry point cross-checks the decomposition: per-block FLOP sums must
agree with the single-module count (XLA only folds/fuses *within* a jit
boundary here, so the sums reconcile to a few percent — validated in
``tests/test_introspect.py``).

:func:`predicted_vs_measured` is the headline driver: static attribution
plus a profiled execution (``core.plan.StepProfile``: per-step device
walls, bit-identical logits) plus the unprofiled whole-schedule wall,
reconciled into the report ``launch.inspect`` renders and CI validates.
"""
from __future__ import annotations

import statistics
import time
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import plan as planlib
from repro.introspect.roofline import (HardwareProfile, resolve_profile,
                                       roofline)
from repro.launch.hlo_analysis import analyze_hlo

__all__ = [
    "BlockCost",
    "block_costs",
    "predicted_vs_measured",
]

REPORT_KIND = "introspect_report"
REPORT_VERSION = 1


@dataclass
class BlockCost:
    """One schedule step's static cost row (plus measured wall, when a
    profiled run has been joined in)."""

    name: str
    kind: str                   # "stem" | "fused" | "layers" | "head"
    executor: str               # resolved executor for this step
    flops: float
    bytes: float
    collective_bytes: float
    transcendentals: float
    bands_in: int
    bands_out: int
    layer_bands: dict           # per-layer band budgets inside the step
    energy_kept: float | None   # cumulative qtable energy at bands_out
    vmem_bytes: int
    predicted_s: float
    term: str                   # dominant roofline term
    measured_s: float | None = None
    warnings: list = field(default_factory=list)

    @property
    def ratio(self) -> float | None:
        """measured / predicted (>1: slower than the roofline bound)."""
        if self.measured_s is None or self.predicted_s <= 0:
            return None
        return self.measured_s / self.predicted_s

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "kind": self.kind,
            "executor": self.executor,
            "flops": self.flops,
            "bytes": self.bytes,
            "collective_bytes": self.collective_bytes,
            "transcendentals": self.transcendentals,
            "bands_in": self.bands_in,
            "bands_out": self.bands_out,
            "layer_bands": dict(self.layer_bands),
            "energy_kept": self.energy_kept,
            "vmem_bytes": self.vmem_bytes,
            "predicted_us": self.predicted_s * 1e6,
            "measured_us": (None if self.measured_s is None
                            else self.measured_s * 1e6),
            "ratio": self.ratio,
            "term": self.term,
            "warnings": list(self.warnings),
        }


def _step_bands(cp, step_name: str) -> tuple[int, int, dict, int]:
    """(bands_in, bands_out, per-layer bands, vmem estimate)."""
    if step_name == "stem":
        st = cp.stem
        return st.bands_out, st.bands_out, {"stem": st.bands_out}, 0
    if step_name == "head":
        last = cp.blocks[-1].bands_out if cp.blocks else cp.stem.bands_out
        return last, last, {}, 0
    blk = next(b for b in cp.blocks if b.name == step_name)
    layer_bands = {}
    if blk.ops:
        layer_bands = {slot: int(op.bands) for slot, op in blk.ops.items()
                       if hasattr(op, "bands")}
    return blk.bands_in, blk.bands_out, layer_bands, int(blk.vmem_bytes)


def _plan_quality(cp) -> int | None:
    op = cp.stem.op
    return getattr(op, "quality", None) if op is not None else None


def _lower_hlo(fn, avals) -> str:
    """Optimized HLO text of ``fn`` jitted at the given abstract args."""
    return jax.jit(fn).lower(*avals).compile().as_text()


def block_costs(cp, shape, *, executor: str | None = None,
                packed: bool = False,
                hw: HardwareProfile | None = None,
                cross_check: bool = True,
                total_devices: int = 1):
    """Static per-step cost attribution for a compiled plan.

    ``shape`` is the full input batch shape (``(N, bh, bw, C, 64)``, or
    the tile-packed ``(N, bh, bw, C·w_in)`` with ``packed=True``).  Each
    step of ``core.plan.compiled_steps`` is lowered and compiled alone
    at its chained activation shape and analyzed with ``analyze_hlo``;
    roofline terms come from ``hw`` (default: the resolved hardware
    profile for this backend).

    Returns ``(blocks, whole)``: the :class:`BlockCost` list in schedule
    order and the whole-module ``HloCost`` of the single-jit entry point
    (``None`` with ``cross_check=False``).
    """
    hw = resolve_profile() if hw is None else hw
    steps = planlib.compiled_steps(cp, executor=executor, packed=packed)
    energy = None
    quality = _plan_quality(cp)
    if quality is not None:
        energy = planlib.qtable_band_energy(quality)

    blocks: list[BlockCost] = []
    aval = jax.ShapeDtypeStruct(tuple(int(s) for s in shape), jnp.float32)
    for name, fn in steps:
        hlo = _lower_hlo(fn, (aval,))
        cost = analyze_hlo(hlo, total_devices=total_devices)
        kind, exec_label = planlib.step_executor(cp, name, executor)
        bands_in, bands_out, layer_bands, vmem = _step_bands(cp, name)
        roof = roofline(cost.flops, cost.bytes, cost.collective_bytes, hw)
        blocks.append(BlockCost(
            name=name, kind=kind, executor=exec_label,
            flops=cost.flops, bytes=cost.bytes,
            collective_bytes=cost.collective_bytes,
            transcendentals=cost.transcendentals,
            bands_in=bands_in, bands_out=bands_out,
            layer_bands=layer_bands,
            energy_kept=(None if energy is None or kind == "head"
                         else float(energy[bands_out - 1])),
            vmem_bytes=vmem,
            predicted_s=roof["predicted_s"], term=roof["term"],
            warnings=list(cost.warnings)))
        aval = jax.eval_shape(fn, aval)

    whole = None
    if cross_check:
        apply_fn = (planlib.apply_compiled_packed if packed
                    else planlib.apply_compiled)
        aval0 = jax.ShapeDtypeStruct(tuple(int(s) for s in shape),
                                     jnp.float32)
        hlo = _lower_hlo(lambda x: apply_fn(cp, x, executor=executor),
                         (aval0,))
        whole = analyze_hlo(hlo, total_devices=total_devices)
    return blocks, whole


def predicted_vs_measured(cp, x, *, executor: str | None = None,
                          packed: bool = False,
                          hw: HardwareProfile | None = None,
                          iters: int = 5, warmup: int = 1,
                          total_devices: int = 1) -> dict:
    """The headline report: per-block predicted vs measured latency.

    Static attribution (:func:`block_costs`) joined with a profiled
    execution (per-step device walls via ``core.plan.StepProfile``,
    medians over ``iters`` calls after ``warmup`` discarded ones) and
    the *unprofiled* whole-schedule wall (single jitted entry, medians
    over the same ``iters``).  The report's
    ``totals.reconciliation`` is (sum of per-block measured walls) /
    (unprofiled wall) — the CI bound asserts it stays within ±10% — and
    ``totals.logits_match`` records that the profiled logits were
    bit-identical to the unprofiled ones.
    """
    hw = resolve_profile() if hw is None else hw
    x = jnp.asarray(x, jnp.float32)
    blocks, whole = block_costs(cp, x.shape, executor=executor,
                                packed=packed, hw=hw,
                                total_devices=total_devices)

    apply_fn = (planlib.apply_compiled_packed if packed
                else planlib.apply_compiled)
    prof = planlib.StepProfile()
    for _ in range(max(1, warmup)):
        apply_fn(cp, x, executor=executor, profile=prof)
    prof.reset()
    profiled = None
    for _ in range(max(1, iters)):
        profiled = apply_fn(cp, x, executor=executor, profile=prof)
    measured = prof.summary()

    whole_fn = jax.jit(lambda v: apply_fn(cp, v, executor=executor))
    unprofiled = whole_fn(x)
    jax.block_until_ready(unprofiled)
    walls = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        out = whole_fn(x)
        jax.block_until_ready(out)
        walls.append(time.perf_counter() - t0)
    unprofiled_wall = statistics.median(walls)
    logits_match = bool(jnp.array_equal(profiled, unprofiled))

    by_name = {b.name: b for b in blocks}
    for name, s in measured.items():
        if name in by_name:
            by_name[name].measured_s = s
    measured_total = sum(measured.values())
    sum_flops = sum(b.flops for b in blocks)
    sum_bytes = sum(b.bytes for b in blocks)

    return {
        "kind": REPORT_KIND,
        "version": REPORT_VERSION,
        "meta": {
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "input_shape": list(x.shape),
            "packed": bool(packed),
            "executor": executor,
            "iters": int(iters),
            "hw_profile": hw.to_json(),
        },
        "blocks": [b.to_json() for b in blocks],
        "totals": {
            "flops": sum_flops,
            "bytes": sum_bytes,
            "predicted_us": sum(b.predicted_s for b in blocks) * 1e6,
            "measured_us": measured_total * 1e6,
            "unprofiled_wall_us": unprofiled_wall * 1e6,
            "reconciliation": (measured_total / unprofiled_wall
                               if unprofiled_wall > 0 else float("inf")),
            "whole_flops": None if whole is None else whole.flops,
            "whole_bytes": None if whole is None else whole.bytes,
            "static_flops_ratio": (
                None if whole is None or whole.flops == 0
                else sum_flops / whole.flops),
            "logits_match": logits_match,
        },
    }
