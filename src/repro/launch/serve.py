"""Batched serving driver: prefill + decode with slot-based batching.

A fixed pool of ``--batch`` decode slots; finished sequences (random length
budget per request — synthetic workload) are replaced by newly prefilling
requests, i.e. continuous batching at slot granularity.  Reports prefill
and decode throughput.  Also serves the paper's jpeg-resnet as a batched
image-classification service (``--arch jpeg-resnet``): batches of JPEG
coefficients in, labels out — the paper's "skip the decompression step"
deployment story.

Two request formats (``--ingest``): ``coefficients`` (pre-materialized
coefficient tensors from the synthetic pipeline — the parity/benchmark
workload) and ``bytes`` — **real baseline JPEG files**, entropy-decoded
and quantization-normalized by ``repro.codec`` on the host (no spatial
decode anywhere) and packed straight into the compiled plan's tile-packed
stem layout.  Byte requests come from ``--jpeg-dir`` when given, else
from a deterministic synthetic stream of *mixed-quality* encodes
(qualities 35/50/75/90 through ``codec.encode_pixels``), exercising the
per-image quantization normalization that lets one plan serve them all.

With ``--qos`` the process serves through the **band-elastic runtime**
(``repro.serving``): the plan is compiled into a ladder of band tiers
(``--tiers``, default autotuned/48/32/24) and an async scheduler with
admission control and per-request deadlines (``--deadline-ms``) picks the
tier per batch from queue depth + deadline slack — degrading bands under
overload, recovering as the queue drains.  Execution runs on the **plan
grid** (``repro.serving.grid``): every (batch bucket × band tier) cell is
precompiled at warmup with pinned, donated buffers (``--batch-buckets``
picks the capture schedule), so steady-state serving performs zero JIT
compiles and pads partial batches only to the covering bucket.  The
report then carries per-request latency percentiles, per-tier throughput
and padding fractions, tier-switch events, compile accounting
(``compiles_total`` / ``compiles_post_warmup`` / ``grid_cell_hits``),
and ingest occupancy (``--report-out`` writes it to a file).  Without
``--qos`` the original fixed-band slot loop serves, but still reports
p50/p95/p99 per-request latency through ``serving.metrics``.

jpeg-resnet serving is **plan-backed** (convert-once): the process restores
an :class:`repro.core.plan.InferencePlan` from ``--plan-dir`` — fused
batch norm, per-layer autotuned bands, apply paths resolved at build time
— and never calls ``precompute_operators`` (let alone re-explodes Ξ) at
serve time.  When the directory holds no usable plan, one is built once,
saved through the checkpoint manager, and *re-loaded from disk* so every
serve run exercises the restore path.  By default the forward runs the
**compiled schedule** (``core.plan.compile_plan``: fused residual-block
steps over tile-packed banded operators, restored from the plan dir's
``compiled/`` subdirectory and compiled+saved whenever the plan itself is
built); ``--no-compiled`` falls back to the per-layer plan walk.  Requests
then run through the same slot pool as the LM driver: each request
classifies a random number of images, finished slots are refilled from the
pending queue.

CPU example:
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --reduced --batch 4 --requests 12 --max-new 16
    PYTHONPATH=src python -m repro.launch.serve --arch jpeg-resnet \
        --reduced --batch 8 --requests 12 --autotune-bands
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import subprocess
import time
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import get_config, reduced_config
from repro.core import dispatch as dispatchlib
from repro.models.registry import build_model

__all__ = ["main", "build_parser", "serve_lm", "serve_jpeg_resnet",
           "prepare_plan",
           "prepare_ladder", "parse_tiers", "jpeg_byte_requests",
           "run_metadata"]


def _git_sha() -> str | None:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
    except Exception:
        return None


def run_metadata(args, *, plan=None, ladder=None, buckets=None) -> dict:
    """Run-identity block embedded in every serve report (``meta``): git
    sha, backend, device count, dispatch config, band tiers, and bucket
    schedule — the same provenance the fig5 benchmark rows carry, so
    reports from different runs/machines are comparable artifacts."""
    meta: dict[str, Any] = {
        "git_sha": _git_sha(),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "seed": args.seed,
        "batch": args.batch,
        "requests": args.requests,
        "reduced": bool(getattr(args, "reduced", False)),
        "ingest": getattr(args, "ingest", "coefficients"),
    }
    if plan is not None:
        meta["dispatch"] = plan.cfg.path
        meta["bands_min"] = min(plan.bands.values())
        meta["bands_max"] = max(plan.bands.values())
    if ladder is not None:
        meta["band_tiers"] = [
            {"name": t.name, "cap": t.cap,
             "bands": sorted(set(t.bands.values()))} for t in ladder.tiers]
    if buckets is not None:
        meta["batch_buckets"] = list(buckets)
    return meta

#: quality mix of the synthetic byte stream — one compiled plan serves all
#: of them through codec.normalize's per-image qtable rescale.
BYTE_QUALITIES = (35, 50, 75, 90)


def jpeg_byte_requests(args, cfg, seed: int):
    """Request source for ``--ingest bytes``: ``fn(step) -> list[bytes]``.

    With ``--jpeg-dir``: deterministic (seed, step) sampling from the
    sorted file list (same semantics as ``data.jpeg_file_iterator``).
    Otherwise: the synthetic image corpus entropy-encoded to *real*
    baseline JFIF bytes at a rotating quality mix — genuine compressed
    traffic with per-image quantization tables.
    """
    from repro.data.synthetic import _rng, image_batch

    jpeg_dir = getattr(args, "jpeg_dir", None)
    if jpeg_dir:
        from repro.data.pipeline import list_jpeg_files

        paths = list_jpeg_files(jpeg_dir)
        if not paths:
            raise FileNotFoundError(f"no JPEG files under {jpeg_dir}")

        def from_files(step: int) -> list[bytes]:
            idx = _rng(seed, step).integers(0, len(paths), size=args.batch)
            out = []
            for j in idx:
                with open(paths[j], "rb") as f:
                    out.append(f.read())
            return out

        return from_files

    from repro.codec import encode_pixels
    from repro.core import dct as dctlib

    def from_synthetic(step: int) -> list[bytes]:
        b = image_batch(seed, step, args.batch, cfg.image_size,
                        cfg.in_channels, cfg.num_classes)
        out = []
        for i, img in enumerate(b["images"]):
            q = BYTE_QUALITIES[(step * args.batch + i) % len(BYTE_QUALITIES)]
            # the *true* IJG table (no dc_is_mean) — foreign files don't
            # share the plan's DC convention; normalize rescales exactly
            qt = np.rint(dctlib.quantization_table(
                q, dc_is_mean=False)).astype(np.int64)
            out.append(encode_pixels(np.clip(img, -1.0, 127.0 / 128.0),
                                     qtable=qt))
        return out

    return from_synthetic


def serve_lm(args) -> dict:
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    b = args.batch
    cache = model.init_cache(b, args.ctx)

    decode = jax.jit(model.decode_step)

    # synthetic request stream; never start more than args.requests
    started = min(b, args.requests)
    pending = args.requests - started
    budgets = rng.integers(4, args.max_new + 1, size=(b,))
    active = np.arange(b) < started
    produced = np.zeros((b,), np.int64)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(b, 1)), jnp.int32)

    n_tokens = 0
    completed = 0
    t0 = time.time()
    while completed < args.requests:
        logits, cache = decode(params, cache, {"tokens": tokens})
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        tokens = next_tok[:, None]
        n_tokens += int(active.sum())
        produced += active
        done = active & (produced >= budgets)
        for i in np.where(done)[0]:
            completed += 1
            produced[i] = 0
            if pending > 0:
                pending -= 1
                budgets[i] = rng.integers(4, args.max_new + 1)
            else:
                active[i] = False
        if not active.any():
            break
    wall = time.time() - t0
    out = {"arch": cfg.name, "decode_tokens": n_tokens, "wall_s": wall,
           "tokens_per_s": n_tokens / max(wall, 1e-9),
           "completed": completed}
    print(json.dumps(out))
    return out


def prepare_plan(args, cfg, dcfg):
    """Restore the serving plan from ``--plan-dir``, building it first only
    when the directory holds no compatible plan.

    Returns ``(plan, compiled, info)`` where the plan always comes from a
    *disk restore* — a fresh build is saved and re-loaded, so the
    save → CheckpointManager → load round trip is on the serve path by
    construction.  ``compiled`` is the fused static schedule
    (``core.plan.CompiledPlan``) restored from the plan dir's ``compiled/``
    subdirectory: built+saved alongside a fresh plan (and on explicit
    ``--compiled``); ``--no-compiled`` serves the per-layer plan walk, and
    the default uses a compiled schedule whenever the directory holds one.

    Plans and schedules record the backend their apply paths were
    resolved on; one resolved on another backend (a CPU-built plan
    restored on a TPU would run its reference executors there) is
    rebuilt, as is one that predates the record.
    """
    from repro.core import plan as planlib
    from repro.core import resnet as R
    from repro.models.registry import jpeg_resnet_spec

    spec = jpeg_resnet_spec(cfg)
    autotune = getattr(args, "autotune_bands", False)
    want_compiled = getattr(args, "compiled", None)
    from_bytes = getattr(args, "ingest", "coefficients") == "bytes"
    plan_dir = args.plan_dir or os.path.join("plans", cfg.name)
    platform = dispatchlib.platform()
    plan, built = None, False
    try:
        plan = planlib.load_plan(plan_dir)
    except (FileNotFoundError, ValueError, KeyError):
        plan = None
    if plan is not None and (
            plan.spec != spec
            or (plan.provenance or {}).get("platform") != platform
            or (args.dispatch is not None and plan.cfg.path != args.dispatch)
            or (args.bands is not None
                and set(plan.bands.values()) != {args.bands})
            or (autotune
                and (plan.provenance or {}).get("bands_mode") != "auto")):
        plan = None  # stale artifact for a different config — rebuild
    if plan is None:
        built = True
        params, state = R.init_resnet(jax.random.PRNGKey(args.seed), spec)
        probe, profile, occupancy = None, None, None
        if autotune:
            if from_bytes:
                # probe the *byte* traffic itself: the empirical energy /
                # occupancy stats replace the 1/q² qtable prior, so band
                # truncation is tuned to what the stream actually carries
                from repro.codec import ingest as ingestlib

                n_blocks = cfg.image_size // 8
                probe_np, stats = ingestlib.ingest_batch(
                    jpeg_byte_requests(args, cfg, args.seed + 1)(0),
                    quality=spec.quality, grid=(n_blocks, n_blocks),
                    channels=cfg.in_channels)
                probe = jnp.asarray(probe_np)
                profile, occupancy = stats.energy, stats.occupancy
            else:
                from repro.data import jpeg_iterator

                probe_it = jpeg_iterator(args.seed + 1, 4, cfg.image_size,
                                         cfg.in_channels, cfg.num_classes)
                probe = jnp.asarray(next(probe_it)["coefficients"])
        bands = "auto" if autotune else args.bands
        plan = planlib.build_plan(params, state, spec, dispatch=dcfg,
                                  bands=bands, probe_coef=probe,
                                  profile=profile, occupancy=occupancy)
        planlib.save_plan(plan, plan_dir)
        plan = planlib.load_plan(plan_dir)  # serve from the restored artifact

    compiled = None
    compiled_dir = os.path.join(plan_dir, "compiled")
    if want_compiled is not False:
        had_artifact = False
        if not built:
            try:
                compiled = planlib.load_compiled_plan(compiled_dir)
                had_artifact = True
                if (compiled.spec != plan.spec
                        or compiled.bands != plan.bands
                        or (compiled.meta or {}).get("platform") != platform):
                    compiled = None  # stale schedule for a different plan
            except FileNotFoundError:
                pass
            except (ValueError, KeyError):
                had_artifact = True  # unreadable/foreign — recompile below
        if compiled is None and (built or want_compiled or had_artifact):
            # convert-once: a fresh plan gets its schedule compiled, saved,
            # and re-restored in the same pass; a stale or corrupt schedule
            # is recompiled rather than silently serving the per-layer walk
            planlib.save_compiled_plan(
                planlib.compile_plan(plan, image_size=cfg.image_size),
                compiled_dir)
            compiled = planlib.load_compiled_plan(compiled_dir)
    info = {"dir": plan_dir, "built": built, "bands": plan.bands,
            "path": plan.cfg.path, "platform": platform, "fused_bn": True,
            "compiled": compiled is not None}
    if compiled is not None:
        meta = compiled.meta or {}
        info["fused_blocks"] = list(meta.get("fused", []))
        # "steps", not "blocks": a factored stem lands here too
        info["fallback_steps"] = sorted(meta.get("layers", {}))
    return plan, compiled, info


def parse_buckets(spec, batch: int) -> tuple | None:
    """``--batch-buckets`` string → capture buckets: ``auto``/None → the
    aphrodite schedule up to ``--batch`` (derived at grid build);
    ``fixed`` → the single full-batch bucket (pre-grid pad-to-max
    behaviour); else comma ints, e.g. ``1,2,4,8``."""
    if spec in (None, ""):
        return None
    tok = str(spec).strip().lower()
    if tok == "auto":
        return None
    if tok == "fixed":
        return (batch,)
    return tuple(int(t) for t in tok.split(","))


def parse_tiers(spec) -> tuple:
    """``--tiers`` string → ladder caps: ``"auto,48,32,24"`` →
    ``(None, 48, 32, 24)`` (``auto``/``top``/``none`` = the plan's own
    band assignment, untouched).  None/empty → the default ladder."""
    from repro.serving import DEFAULT_CAPS

    if not spec:
        return DEFAULT_CAPS
    caps = []
    for tok in str(spec).split(","):
        tok = tok.strip().lower()
        caps.append(None if tok in ("auto", "top", "none") else int(tok))
    return tuple(caps)


def prepare_ladder(args, cfg, plan, plan_dir):
    """Restore the tier ladder from ``plan_dir``, rebuilding when absent
    or when its caps disagree with ``--tiers`` / its capture buckets
    with ``--batch-buckets`` (same convert-once contract as
    :func:`prepare_plan` — tiers re-derive bit-exactly from the restored
    plan, and the manifest keeps the grid extent so a restart warms up
    the same cells)."""
    from repro import serving

    caps = parse_tiers(getattr(args, "tiers", None))
    buckets = serving.cover_buckets(
        parse_buckets(getattr(args, "batch_buckets", None), args.batch),
        args.batch)
    ladder = None
    try:
        ladder = serving.load_ladder(plan_dir, plan=plan)
        if ladder.caps != caps:
            ladder = None  # different ladder requested — rebuild
        elif ladder.buckets != buckets:
            # same tiers, different grid extent: the buckets live only
            # in the manifest — update it without recompiling any tier.
            # (Not _replace: PlanLadder.__len__ counts tiers, which
            # breaks namedtuple._make's arity check.)
            ladder = serving.PlanLadder(
                ladder.tiers, ladder.base, ladder.caps, ladder.image_size,
                ladder.vmem_budget, buckets)
            serving.save_ladder(ladder, plan_dir, save_base=False)
    except (FileNotFoundError, ValueError, KeyError):
        ladder = None
    if ladder is None:
        ladder = serving.build_ladder(plan, caps=caps,
                                      image_size=cfg.image_size,
                                      buckets=buckets)
        serving.save_ladder(ladder, plan_dir, save_base=False)
    return ladder


def _qos_request_source(args, cfg, seed: int):
    """Per-request payload stream for the QoS runtime: ``fn(i)`` returns
    one image's payload — a coefficient tensor ``(bh, bw, C, 64)`` or one
    JPEG file's bytes — drawn from the same sources the slot loop uses."""

    def per_item(fetch_batch):
        # requests are submitted strictly in order, so one batch of
        # payloads is materialised at a time and evicted on rollover
        cache: dict[int, Any] = {}

        def fn(i: int):
            step = i // args.batch
            if step not in cache:
                cache.clear()
                cache[step] = fetch_batch(step)
            return cache[step][i % args.batch]

        return fn

    if getattr(args, "ingest", "coefficients") == "bytes":
        return per_item(jpeg_byte_requests(args, cfg, seed)), "bytes"

    from repro.data import jpeg_iterator

    it = jpeg_iterator(seed, args.batch, cfg.image_size, cfg.in_channels,
                       cfg.num_classes)
    return per_item(
        lambda step: np.asarray(next(it)["coefficients"])), "coefficients"


def _chaos_faults(args, serving):
    """Build the chaos run's deterministic fault plan (``--chaos``).

    ``--chaos-rate`` of request indices get guaranteed-fail byte
    corruption; one ingest-pool worker is SIGKILLed before the third
    decode batch (driving the BrokenProcessPool supervisor); dispatches
    2..2+``--chaos-exec-faults`` raise in the executor (driving
    containment, retry exhaustion, and the breaker).
    """
    n_exec = getattr(args, "chaos_exec_faults", 2)
    spec = serving.FaultSpec(
        seed=getattr(args, "chaos_seed", 1234),
        corrupt_rate=getattr(args, "chaos_rate", 0.2),
        kill_worker_before_batch=(
            3 if getattr(args, "chaos_kill_worker", True) else None),
        executor_fail_batches=(2, 2 + n_exec) if n_exec else None,
    )
    # thresholds sized so the injected executor-fault burst visibly trips
    # the breaker and the run closes it again: open after 2 consecutive
    # service failures, half-open after 0.5 s, close on the first probe
    policy = serving.BreakerPolicy(window=16, failure_rate=0.5,
                                   min_samples=8, max_consecutive=2,
                                   open_s=0.5, half_open_successes=1)
    return serving.FaultInjector(spec), policy


def _submit_retry(sched, serving, payload, kind, deadline_s,
                  timeout_s: float = 60.0):
    """Chaos-client submit: retry through open-breaker fast-rejects and
    admission-control rejections (what a real client's backoff does)."""
    t0 = time.time()
    while True:
        try:
            r = sched.submit(payload, kind=kind, deadline_s=deadline_s)
        except serving.ServiceUnavailable:
            if time.time() - t0 > timeout_s:
                raise
            time.sleep(0.05)  # breaker open — wait for the half-open probe
            continue
        if r is not None:
            return r
        if time.time() - t0 > timeout_s:
            return None
        time.sleep(0.01)      # queue full — admission backpressure


def _serve_jpeg_qos(args, cfg, plan, plan_info, record=None) -> dict:
    """Serve through the band-elastic runtime: saturating burst of
    single-image requests → admission control, per-batch tier selection,
    degradation under overload, recovery on drain.

    Outside ``--chaos`` a request that fails in the executor or in ingest
    infrastructure, or a run that completes nothing, raises after the
    report is emitted (a non-zero exit).  ``record`` (a dict, for
    in-process callers) receives ``served``, a ``(payload, tier name,
    logits)`` tuple per completed request, and ``grid``, the
    :class:`~repro.serving.grid.PlanGrid` whose cells served them.

    ``--chaos`` turns the burst into a fault drill: a deterministic
    fraction of requests get corrupted bytes, one ingest worker is
    killed mid-stream, and a window of dispatches fails in the executor
    — the run then proves healthy requests still completed (with
    bounded client retries through the breaker) while every fault
    surfaced as a typed per-request error.
    """
    from repro import serving
    from repro.core import plan as planlib

    ladder = prepare_ladder(args, cfg, plan, plan_info["dir"])
    names = [t.name for t in ladder.tiers]
    print(f"[serve] band-elastic ladder: "
          + " > ".join(f"{t.name}(bands {min(t.bands.values())}-"
                       f"{max(t.bands.values())})" for t in ladder.tiers))
    n_blocks = cfg.image_size // 8
    total = args.requests
    deadline_s = (args.deadline_ms / 1e3
                  if getattr(args, "deadline_ms", None) else None)
    max_pending = getattr(args, "max_queue", None) or total
    metrics = serving.ServeMetrics()
    payload_of, kind = _qos_request_source(args, cfg, args.seed)

    # observability sidecars — all torn down on *any* exit (flight
    # recorder semantics: a crashed run still leaves its trace behind)
    obs = contextlib.ExitStack()
    tracer = None
    trace_path = getattr(args, "trace_out", None)
    if trace_path:
        tracer = serving.Tracer(
            capacity=int(getattr(args, "trace_capacity", None) or 65536))
        obs.callback(lambda: tracer.write(trace_path))
    metrics_path = getattr(args, "metrics_out", None)
    if metrics_path:
        obs.callback(serving.MetricsWriter(
            metrics, metrics_path,
            interval_s=float(getattr(args, "metrics_interval", None)
                             or 1.0)).close)
    obs.enter_context(
        serving.jax_profile(getattr(args, "jax_profile", None)))

    chaos = getattr(args, "chaos", False)
    faults, breaker_policy = None, None
    if chaos:
        if kind != "bytes":
            raise ValueError("--chaos corrupts JPEG bytes; needs "
                             "--ingest bytes")
        faults, breaker_policy = _chaos_faults(args, serving)
        print(f"[serve] chaos: corrupt_rate="
              f"{faults.spec.corrupt_rate:g} seed={faults.spec.seed} "
              f"kill_worker_before_batch="
              f"{faults.spec.kill_worker_before_batch} "
              f"executor_fail_batches={faults.spec.executor_fail_batches}")

    sched = serving.BandElasticScheduler(
        ladder, batch=args.batch, metrics=metrics, max_pending=max_pending,
        grid=(n_blocks, n_blocks), channels=cfg.in_channels,
        breaker=breaker_policy, faults=faults, tracer=tracer)
    with obs, sched:
        t_warm = time.time()
        sched.warmup(kinds=(kind,))
        warmup_s = time.time() - t_warm
        gs = sched.grid_engine.summary()
        print(f"[serve] plan grid: {gs['distinct_columns']} tier columns x "
              f"buckets {gs['buckets']} = {gs['cells']} captured cells "
              f"({gs['host_staging_bytes'] / 2**20:.1f} MiB pinned host "
              f"staging); post-warmup compiles will be reported")
        profile_grid = None
        if getattr(args, "profile_grid", False):
            # pre-traffic capacity sweep: every warmed cell gets a
            # roofline-predicted and a measured wall (captured
            # executables only — zero post-warmup grid compiles), then
            # per-cell predicted capacity lands on the
            # serve_predicted_capacity gauges and device-dispatch spans
            from repro import introspect

            hw = introspect.resolve_profile(getattr(args, "hw_profile",
                                                    None))
            profile_grid = introspect.profile_plan_grid(
                sched.grid_engine, hw=hw)
            for c in profile_grid["cells"]:
                metrics.record_predicted_capacity(
                    c["cell"], c["predicted_req_s"])
            sched.grid_engine.annotate_costs(
                {c["cell"]: {"flops": c["flops"],
                             "predicted_us": c["predicted_us"]}
                 for c in profile_grid["cells"]})
            print(f"[serve] grid profile ({hw.name}): "
                  + "  ".join(
                      f"{c['cell']}={c['predicted_req_s']:.0f}req/s"
                      for c in profile_grid["cells"][:6])
                  + ("  ..." if len(profile_grid["cells"]) > 6 else ""))
        t0 = time.time()
        requests = []  # (request index, ServeRequest)
        payloads = {}
        for i in range(total):
            p = payload_of(i)
            if faults is not None:
                p = faults.corrupt(i, p)
            payloads[i] = p
            if chaos:
                r = _submit_retry(sched, serving, p, kind, deadline_s)
            else:
                r = sched.submit(p, kind=kind, deadline_s=deadline_s)
            if r is not None:
                requests.append((i, r))
        sched.drain()
        if chaos:
            # a real client retries service-level failures; requests the
            # injected executor/ingest faults killed (healthy bytes, bad
            # luck) are resubmitted until the fleet settles.  Corrupt
            # requests are NOT retried — their typed codec errors are
            # the success criterion, not a transient.
            def _retryable(i, r):
                e = r.error()
                return (isinstance(e, serving.RequestFailed)
                        and e.stage in ("executor", "ingest")
                        and i not in faults.corrupted)

            for _round in range(4):
                retry = [k for k, (i, r) in enumerate(requests)
                         if _retryable(i, r)]
                if not retry:
                    break
                for k in retry:
                    i, _ = requests[k]
                    nr = _submit_retry(sched, serving, payloads[i], kind,
                                       deadline_s)
                    if nr is not None:
                        requests[k] = (i, nr)
                sched.drain()
        wall = time.time() - t0
        health = sched.health()

    # top-tier fidelity probe: requests served at the *top* tier must
    # agree (top-1) with the uncompiled per-layer plan walk — the same
    # parity the fixed-band serve path is held to.
    probe = [r for _, r in requests if r.tier == names[0]][: args.batch]
    agree = None
    if probe:
        if kind == "bytes":
            from repro.codec import ingest as ingestlib

            coefs, _ = ingestlib.ingest_batch(
                [r.payload for r in probe], quality=plan.spec.quality,
                grid=(n_blocks, n_blocks), channels=cfg.in_channels,
                with_stats=False)
        else:
            coefs = np.stack([np.asarray(r.payload) for r in probe])
        ref = np.asarray(planlib.apply_plan(plan, jnp.asarray(coefs)))
        got = np.stack([np.asarray(r.result()) for r in probe])
        agree = float(np.mean(ref.argmax(-1) == got.argmax(-1)))

    qos_report = metrics.report()
    qos_report["grid"] = gs
    qos_report["warmup_s"] = warmup_s
    qos_report["executor"] = sched.executor
    qos_report["tiers"] = [
        {"name": t.name, "cap": t.cap,
         "bands": sorted(set(t.bands.values())),
         "rules": dict((t.compiled.meta or {}).get("rules", {})),
         "steps": _step_labels(t.compiled, sched.executor)}
        for t in ladder.tiers]
    qos_report["top1_agree_top_tier"] = agree
    served_n = len(requests)
    completed = sum(1 for _, r in requests if r.tier is not None)
    if record is not None:
        record["served"] = [(r.payload, r.tier, r.result())
                            for _, r in requests if r.tier is not None]
        record["grid"] = sched.grid_engine
    out = {"arch": cfg.name, "images": served_n, "wall_s": wall,
           "images_per_s": completed / max(wall, 1e-9),
           "completed": completed, "rejected": total - served_n,
           "dispatch": plan.cfg.path, "ingest": kind,
           "latency_ms": qos_report["latency_ms"],
           "qos": qos_report, "plan": plan_info,
           "health": health,
           "meta": run_metadata(args, plan=plan, ladder=ladder,
                                buckets=sched.buckets)}
    if profile_grid is not None:
        out["profile_grid"] = profile_grid
    if tracer is not None:
        s = tracer.summary()
        out["trace"] = {"path": trace_path, "events": s["events"],
                        "dropped": s["dropped"],
                        "capacity": s["capacity"]}
        print(f"[serve] flight recorder: {s['events']} events "
              f"({s['dropped']} dropped) -> {trace_path}")
    if metrics_path:
        out["metrics_out"] = metrics_path
    if chaos:
        stages: dict[str, int] = {}
        for _, r in requests:
            e = r.error()
            if isinstance(e, serving.RequestFailed):
                stages[e.stage] = stages.get(e.stage, 0) + 1
            elif e is not None:
                stages[type(e).__name__] = stages.get(
                    type(e).__name__, 0) + 1
        healthy = [i for i in range(total) if i not in faults.corrupted]
        out["chaos"] = {
            "corrupted": len(faults.corrupted),
            "corrupt_modes": {m: sum(1 for v in faults.corrupted.values()
                                     if v == m)
                              for m in set(faults.corrupted.values())},
            "killed_worker_pid": faults.killed_pid,
            "failed_by_stage": stages,
            "healthy_total": len(healthy),
            "healthy_completed": sum(
                1 for i, r in requests
                if i not in faults.corrupted and r.tier is not None),
        }
    _emit_report(args, out)
    if not chaos:
        broken = [r.error() for _, r in requests
                  if isinstance(r.error(), serving.RequestFailed)
                  and r.error().stage in ("executor", "ingest")]
        if broken:
            raise RuntimeError(
                f"{len(broken)} request(s) failed in the service") \
                from broken[0]
        if total and not completed:
            raise RuntimeError("no request completed")
    return out


def _step_labels(cp, executor) -> dict[str, str]:
    """Report label per schedule step: its executor
    (:func:`core.plan.step_executor`), and on a per-layer walk each conv's
    dispatch path."""
    from repro.core import plan as planlib

    out = {}
    for name in ["stem", *(b.name for b in cp.blocks)]:
        label = planlib.step_executor(cp, name, executor)[1]
        if label == "layers":
            ops = ({"stem": cp.stem.op} if name == "stem" else
                   next(b for b in cp.blocks if b.name == name).ops)
            label += " (" + ", ".join(f"{slot}:{op.path}" for slot, op
                                      in sorted(ops.items())) + ")"
        out[name] = label
    return out


def _emit_report(args, out: dict) -> None:
    print(json.dumps(out))
    path = getattr(args, "report_out", None)
    if path:
        with open(path, "w") as f:
            json.dump(out, f, indent=2)


def serve_jpeg_resnet(args, *, record=None) -> dict:
    """Serve jpeg-resnet per ``args`` (the CLI namespace); ``record`` is
    passed to the ``--qos`` runtime (see :func:`_serve_jpeg_qos`)."""
    from repro.core import plan as planlib
    from repro.data import jpeg_iterator

    # The dispatch flags pick the operator path (reference / pallas /
    # factored) and the §6 band truncation before anything is traced or
    # compiled; omitted flags defer to JPEG_DISPATCH / JPEG_BANDS.  They
    # only matter when a plan has to be *built* — a restored plan carries
    # its own frozen config.
    changes = {}
    if args.dispatch is not None:
        changes["path"] = args.dispatch
    if args.bands is not None:
        changes["bands"] = args.bands
    dcfg = dispatchlib.configure(**changes)
    cfg = reduced_config("jpeg-resnet") if args.reduced else get_config("jpeg-resnet")
    plan, compiled, plan_info = prepare_plan(args, cfg, dcfg)

    if getattr(args, "qos", False):
        # thin-CLI handoff: the band-elastic runtime owns batching, tier
        # selection, deadlines, and metrics from here on
        return _serve_jpeg_qos(args, cfg, plan, plan_info, record=record)
    if getattr(args, "trace_out", None) or getattr(args, "metrics_out",
                                                   None) \
            or getattr(args, "profile_grid", False):
        print("[serve] --trace-out/--metrics-out/--profile-grid instrument "
              "the QoS runtime; ignored without --qos")

    if compiled is not None:
        meta = compiled.meta or {}
        fused = meta.get("fused", [])
        fallback = sorted(meta.get("layers", {}))
        print(f"[serve] compiled schedule: {len(fused)} blocks fused "
              f"({','.join(fused) or '-'}), {len(fallback)} steps per-layer "
              f"({','.join(fallback) or '-'})")
        fwd = jax.jit(lambda c: planlib.apply_compiled(compiled, c))
    else:
        print("[serve] per-layer plan execution (no compiled schedule)")
        fwd = jax.jit(lambda c: planlib.apply_plan(plan, c))

    spec = plan.spec
    n_blocks = cfg.image_size // 8
    ingest_mode = getattr(args, "ingest", "coefficients")
    jpeg_dir = getattr(args, "jpeg_dir", None)
    if ingest_mode == "bytes":
        # bytes-in request path: entropy decode + per-image quantization
        # normalization on the host (repro.codec — never a spatial
        # decode), packed straight into the compiled stem's tile-packed
        # layout when a compiled schedule is serving
        from repro.codec import ingest as ingestlib

        requests = jpeg_byte_requests(args, cfg, args.seed)
        pack_w = compiled.stem.w_in if compiled is not None else None
        if compiled is not None:
            fwd = jax.jit(
                lambda c: planlib.apply_compiled_packed(compiled, c))
        collected = []
        ingest_kw = dict(quality=spec.quality, grid=(n_blocks, n_blocks),
                         channels=cfg.in_channels, pack_width=pack_w)
        pipe = {"it": None}

        def byte_stream():
            step = 1  # step 0 feeds the warmup inline
            while True:
                yield requests(step)
                step += 1

        def next_batch(step: int) -> jnp.ndarray:
            if step == 0:
                batch, _ = ingestlib.ingest_batch(requests(0), **ingest_kw)
                return jnp.asarray(batch)
            if pipe["it"] is None:
                # double-buffered: decode of batch N+1 overlaps the
                # device walk of batch N on the prefetch producer thread
                pipe["it"] = ingestlib.ingest_pipeline(
                    byte_stream(), depth=2, **ingest_kw)
            batch, stats = next(pipe["it"])
            collected.append(stats)
            return jnp.asarray(batch)

        layout = f"tile-packed w={pack_w}" if pack_w else "64-wide"
        source = (f"files from {jpeg_dir}" if jpeg_dir
                  else "synthetic mixed-quality stream")
        print(f"[serve] bytes-in ingest: {layout} ({source}), "
              f"overlapped decode ({ingestlib.ingest_workers()} workers)")
    else:
        it = jpeg_iterator(args.seed, args.batch, cfg.image_size,
                           cfg.in_channels, cfg.num_classes)

        def next_batch(step: int) -> jnp.ndarray:
            return jnp.asarray(next(it)["coefficients"])

    # warmup/compile
    fwd(next_batch(0)).block_until_ready()
    if ingest_mode == "bytes":
        collected.clear()  # the timed window starts after warmup

    # slot-based continuous batching (same structure as serve_lm): each
    # request classifies a random number of images; finished slots refill
    # from the pending queue so the batch stays full until the tail.
    from repro.serving import metrics as servemetrics

    rng = np.random.default_rng(args.seed)
    b = args.batch
    max_imgs = max(args.max_new, 1)
    # never start more requests than were asked for (requests < batch
    # leaves the tail slots idle)
    started = min(b, args.requests)
    pending = args.requests - started
    budgets = rng.integers(1, max_imgs + 1, size=(b,))
    active = np.arange(b) < started
    produced = np.zeros((b,), np.int64)
    n_imgs = 0
    completed = 0
    step = 1  # step 0 fed the warmup
    t0 = time.time()
    # per-request latency: a slot's request starts when the slot is
    # (re)filled and completes when its image budget is met
    slot_start = np.full((b,), t0)
    latencies: list[float] = []
    try:
        while completed < args.requests and active.any():
            logits = fwd(next_batch(step))
            step += 1
            logits.block_until_ready()  # labels would ship to clients here
            now = time.time()
            n_imgs += int(active.sum())
            produced += active
            done = active & (produced >= budgets)
            for i in np.where(done)[0]:
                completed += 1
                produced[i] = 0
                latencies.append(now - slot_start[i])
                slot_start[i] = now
                if pending > 0:
                    pending -= 1
                    budgets[i] = rng.integers(1, max_imgs + 1)
                else:
                    active[i] = False
    finally:
        if ingest_mode == "bytes" and pipe["it"] is not None:
            pipe["it"].close()  # joins the decode producer thread
    wall = time.time() - t0
    out = {"arch": cfg.name, "images": n_imgs, "wall_s": wall,
           "images_per_s": n_imgs / max(wall, 1e-9),
           "completed": completed, "dispatch": plan.cfg.path,
           "ingest": ingest_mode,
           "latency_ms": servemetrics.percentiles(latencies),
           "plan": plan_info,
           "meta": run_metadata(args, plan=plan)}
    if ingest_mode == "bytes" and collected:
        from repro.codec import merge_stats

        ingest_stats = merge_stats(collected)
        out["ingest_stats"] = {
            "images": ingest_stats.images,
            "bytes_in": ingest_stats.bytes_in,
            "mb_per_s": ingest_stats.bytes_in / max(wall, 1e-9) / 2**20,
            "mean_nonzero_per_block": round(ingest_stats.mean_nonzero, 2),
            "workers": ingestlib.ingest_workers(),
            "overlap": "pipeline(depth=2)",
        }
    _emit_report(args, out)
    return out


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI's argument parser (``main`` parses ``sys.argv`` with
    it; in-process callers parse their own argv)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ctx", type=int, default=256)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dispatch", default=None,
                    choices=("auto",) + dispatchlib.PATHS,
                    help="jpeg-resnet operator path (core.dispatch; "
                         "default: JPEG_DISPATCH env or auto)")
    ap.add_argument("--bands", type=int, default=None,
                    help="zigzag coefficients kept (paper §6 sparsity; "
                         "default: JPEG_BANDS env or 64)")
    ap.add_argument("--plan-dir", default=None,
                    help="jpeg-resnet InferencePlan checkpoint directory "
                         "(default plans/<arch>); restored at startup, "
                         "built+saved once if absent")
    ap.add_argument("--ingest", default="coefficients",
                    choices=("coefficients", "bytes"),
                    help="jpeg-resnet request format: pre-materialized "
                         "coefficient tensors, or real baseline JPEG "
                         "bytes through the repro.codec ingest path "
                         "(entropy decode + quantization normalization, "
                         "no spatial decode)")
    ap.add_argument("--jpeg-dir", default=None,
                    help="directory of .jpg files to serve with "
                         "--ingest bytes (default: synthetic "
                         "mixed-quality encoded stream)")
    ap.add_argument("--autotune-bands", action="store_true",
                    help="when building the plan, pick per-layer bands "
                         "from the quantization table + a parity sweep "
                         "instead of the global knob")
    ap.add_argument("--qos", action="store_true",
                    help="serve jpeg-resnet through the band-elastic "
                         "runtime (repro.serving): compiled-plan ladder "
                         "+ async scheduler + queue-depth/deadline tier "
                         "policy; --requests single-image requests are "
                         "submitted as a saturating burst")
    ap.add_argument("--tiers", default=None,
                    help="ladder band caps for --qos, best first, e.g. "
                         "'auto,48,32,24' (auto = the plan's own "
                         "autotuned assignment; default that ladder)")
    ap.add_argument("--batch-buckets", default=None,
                    help="batch capture buckets of the --qos plan grid: "
                         "'auto' (default; aphrodite schedule 1,2,4 then "
                         "multiples of 8 up to --batch), 'fixed' (single "
                         "full-batch bucket — the pre-grid pad-to-max "
                         "behaviour), or comma ints e.g. '1,2,4,8'")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline for --qos; feeds the "
                         "QoS tier policy and the deadline-miss metric")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission-control bound on queued requests "
                         "for --qos (default: accept the whole burst)")
    ap.add_argument("--report-out", default=None,
                    help="also write the serve report JSON to this path")
    ap.add_argument("--trace-out", default=None,
                    help="write the --qos flight-recorder trace (Chrome "
                         "trace-event JSON, Perfetto-loadable: per-request "
                         "admission/queue/decode/dispatch spans, tier and "
                         "breaker instants, batch->request flow links) "
                         "to this path — written on any exit, crash "
                         "included")
    ap.add_argument("--trace-capacity", type=int, default=65536,
                    help="flight-recorder ring size in events; when full "
                         "the oldest events are dropped (and counted)")
    ap.add_argument("--metrics-out", default=None,
                    help="write Prometheus-style text metrics snapshots "
                         "(serving.ServeMetrics.metrics_text) to this "
                         "path periodically during --qos serving")
    ap.add_argument("--metrics-interval", type=float, default=1.0,
                    help="seconds between --metrics-out snapshots")
    ap.add_argument("--jax-profile", default=None,
                    help="directory for a jax.profiler device trace "
                         "covering the same window as --trace-out")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-drill the --qos byte stream: corrupt a "
                         "fraction of requests (guaranteed-fail byte "
                         "mutations), SIGKILL an ingest-pool worker "
                         "mid-stream, and fail a window of executor "
                         "dispatches — healthy requests must still "
                         "complete; faults must surface as typed "
                         "per-request errors (serving.faults)")
    ap.add_argument("--chaos-rate", type=float, default=0.2,
                    help="fraction of requests whose bytes are corrupted "
                         "under --chaos (default 0.2)")
    ap.add_argument("--chaos-seed", type=int, default=1234,
                    help="fault-injection seed: corruption placement is "
                         "deterministic in (seed, request index)")
    ap.add_argument("--chaos-kill-worker", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="SIGKILL one ingest-pool worker before the third "
                         "decode batch (exercises the BrokenProcessPool "
                         "supervisor; needs JPEG_INGEST_WORKERS > 1)")
    ap.add_argument("--chaos-exec-faults", type=int, default=2,
                    help="how many worker dispatches raise injected "
                         "executor faults (window starts at dispatch 2; "
                         "sized to trip the chaos breaker policy)")
    ap.add_argument("--profile-grid", action="store_true",
                    help="after --qos warmup, sweep every captured "
                         "(tier x bucket) grid cell: roofline-predicted "
                         "+ measured latency per cell, predicted "
                         "capacity (req/s) on the "
                         "serve_predicted_capacity gauge family and in "
                         "the report's profile_grid section; dispatch "
                         "trace spans gain flops/predicted_us args")
    ap.add_argument("--hw-profile", default=None,
                    help="roofline hardware profile for --profile-grid: "
                         "registry name (introspect.PROFILES), "
                         "'peak_flops,hbm_bw,link_bw' triple, or unset "
                         "for backend detection / $JPEG_HW_PROFILE")
    ap.add_argument("--compiled", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="serve the compiled fused-block schedule "
                         "(plan.compile_plan).  Default: on when the plan "
                         "dir holds a compiled schedule (one is compiled "
                         "and saved whenever the plan itself is built); "
                         "--no-compiled forces the per-layer plan walk")
    return ap


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.arch == "jpeg-resnet":
        return serve_jpeg_resnet(args)
    return serve_lm(args)


if __name__ == "__main__":
    main()
