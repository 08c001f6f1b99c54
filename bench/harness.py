"""One run of one cell: set-up, warm traffic, the measured window, the
metrics, and the comparison with the reference that decides ``correct``.

``run`` returns the result line; ``bench/run.py`` is the command.  The
steps, in order:

1. inputs from the seed: a pool of images held as JPEG integers;
2. weights from the seed and the program's serving stack, warmed for
   the cell's own shapes (``system.build``);
3. warm traffic, then ``seconds`` of measured window (``load``); with
   ``trace`` the profiler records the window and the program's tracer
   its spans;
4. peak device memory, the compile count of the window, then the
   program is closed and freed;
5. end-to-end metrics (host clock) or per-layer metrics (trace and
   spans, one reader each);
6. a sample of the window's answers, drawn from the seed, against the
   plain reference capped to the tier that served each.

With ``control`` the run is the same but the answers compared are the
control's (``reference.logits(control=True)``) in place of the served
ones: ``bench/control.py`` runs it, to show that the comparison fails
it at the cell's own sizes.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import spec as speclib

#: names of the comparison's numbers, printed with their limits
CHECKS = ("logit_gap", "compiles_in_window", "failed")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class RunView:
    """What a per-layer metric reader sees (``metrics/<name>.py``)."""

    def __init__(self, *, window, spans, trace, trace_offset, kernels,
                 modules, config, arch, peak):
        self.window = window
        self.spans = spans
        self.trace = trace
        self.trace_offset = trace_offset
        self.kernels = kernels
        self.modules = modules
        self.config = config
        self.arch = arch
        self.peak = peak
        self.notes: list[str] = []

    def note(self, text: str) -> None:
        self.notes.append(text)


def make_inputs(seed: int, cfg: dict, traffic: dict, timings: dict) -> dict:
    """The cell's pool of images as JPEG integers, from the seed: item
    ``i`` is stored at quality ``qualities[i % len(qualities)]``."""
    import jax

    from bench import data
    from bench.system import seed_key

    t = time.monotonic()
    n, qs = traffic["pool"], traffic["qualities"]
    meta = [qs[i % len(qs)] for i in range(n)]
    chunk = 64 if cfg["image_size"] >= 128 else 1024
    luma: dict[int, np.ndarray] = {}
    chroma: dict[int, np.ndarray] = {}
    for g, q in enumerate(qs):
        items = [i for i in range(n) if meta[i] == q]
        table = np.rint(data.ijg_table(q))
        for c0 in range(0, len(items), chunk):
            part = items[c0:c0 + chunk]
            key = jax.random.fold_in(seed_key(seed, "images"),
                                     g * 100003 + c0)
            imgs = data.images(key, n=len(part), size=cfg["image_size"],
                               channels=cfg["in_channels"],
                               classes=cfg["num_classes"])
            y, c = (np.asarray(a) for a in data.quantize(imgs, table))
            for j, i in enumerate(part):
                luma[i], chroma[i] = y[j], c[j]
    timings["images_s"] = time.monotonic() - t
    t = time.monotonic()
    payloads = [data.coefficients(luma[i][None], chroma[i][None],
                                  np.rint(data.ijg_table(meta[i])),
                                  cfg["quality"])[0] for i in range(n)]
    timings["payloads_s"] = time.monotonic() - t
    return {"payloads": payloads, "luma": luma, "chroma": chroma,
            "meta": meta}


def _spans(tracer) -> list:
    """The program tracer's spans as ``(track, name, t0, t1, args)`` on
    the host monotonic clock."""
    t0 = tracer.origin
    return [(track, name, t0 + t, t0 + t + d, args or {})
            for ph, track, _tid, name, t, d, args in tracer.events()
            if ph == "X"]


def _served_programs(sched) -> tuple[list[dict], set]:
    """Mosaic kernels and module names of every grid cell that served."""
    from bench import flops

    kernels, modules, seen = [], set(), set()
    for col in sched.grid_engine.distinct:
        for cell in col.cells.values():
            if not cell.hits:
                continue
            text = cell.lower().compile().as_text()
            modules.add(flops.module_name(text))
            for k in flops.custom_calls(text):
                if k["name"] not in seen:
                    seen.add(k["name"])
                    kernels.append(k)
    return kernels, modules


def _percentile(values, p) -> float | None:
    return float(np.percentile(values, p)) if len(values) else None


def images_per_s(records, win) -> float:
    """Images answered in the window over whole periods of answers: the
    count answered in ``[t0, t1)`` over the time from the last answer
    before ``t0`` to the last one inside.  Answers come a batch at a
    time, so a count over the window's own length would move in steps
    of one batch with where the batches happen to fall."""
    done = sorted(r.t_done for r in records
                  if r.t_done is not None and r.error is None)
    inside = [t for t in done if win.t0 <= t < win.t1]
    if not inside:
        return 0.0
    before = [t for t in done if t < win.t0]
    start = before[-1] if before else win.t0
    return len(inside) / (inside[-1] - start)


def check(answers, inputs, params, state, cfg, arch, traffic, seed, *,
          control: bool = False) -> dict:
    """Widest gap between served and reference logits over a sample,
    drawn from the seed, of ``answers`` (``(item, tier, logits)``), as a
    share of the sample's logit scale; the reference is the network of
    ``arch``, the configuration's architecture module.  With ``control``
    the logits compared are the control's, in the served ones' place."""
    from bench import data, reference
    from bench.system import tier_caps

    rng = np.random.default_rng([seed % (2 ** 32), seed // (2 ** 32), 7])
    k = min(traffic["check_sample"], len(answers))
    sample = [answers[i] for i in sorted(rng.choice(len(answers), k,
                                                    replace=False))]
    caps = dict(zip([t if t == "top" else f"b{t}" for t in traffic["tiers"]],
                    tier_caps(traffic)))
    groups: dict[tuple, list] = {}
    for item, tier, logits in sample:
        cap = caps[tier]
        bands = cfg["bands"] if cap is None else min(cap, cfg["bands"])
        groups.setdefault((inputs["meta"][item], bands), []).append(
            (item, logits))
    served, ref = [], []
    for (q, bands), rs in sorted(groups.items()):
        items = [item for item, _ in rs]
        args = (params, state, cfg, arch,
                np.stack([inputs["luma"][i] for i in items]),
                np.stack([inputs["chroma"][i] for i in items]),
                np.rint(data.ijg_table(q)))
        kw = dict(bands=bands, block=traffic["check_block"])
        ref.append(reference.logits(*args, **kw))
        served.append(reference.logits(*args, control=True, **kw)
                      if control else
                      np.stack([np.asarray(x(), np.float32) for _, x in rs]))
    if not served:
        return {"logit_gap": float("inf"), "sample": 0}
    served, ref = np.concatenate(served), np.concatenate(ref)
    scale = max(1.0, float(np.abs(ref).max()))
    gap = float(np.abs(served - ref).max()) / scale
    top1 = float(np.mean(served.argmax(-1) == ref.argmax(-1)))
    return {"logit_gap": gap, "sample": int(len(served)), "scale": scale,
            "top1_agree": top1, "groups": {f"q{q}/b{b}": len(v) for (q, b), v
                                           in groups.items()}}


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, t_process: float, require_chips: bool = True,
        system_hook=None, keep_trace: str | None = None,
        control: bool = False, cell: dict | None = None) -> dict:
    """One run of ``workload``; returns the result line's object.

    ``system_hook(sched)`` lets a test break the timed path underneath
    after set-up; the benchmark's own runs pass nothing.  ``keep_trace``
    copies a traced run's profile to that directory.  ``control`` puts
    the control's logits in the served ones' place in the comparison.
    ``cell`` stands in for the workload's entry resolved from
    ``BENCHMARK.json`` (``spec.cell``)."""
    cell = cell or speclib.cell(root, workload)
    cfg, traffic, w = cell["config"], cell["traffic"], cell["workload"]
    arch = cell["arch"]
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if require_chips and (platform != "tpu" or len(devs) < w["chips"]):
        raise SystemExit(f"bench: {workload} needs {w['chips']} TPU "
                         f"chip(s); JAX found {len(devs)} {platform} "
                         f"device(s) ({devs[0].device_kind})")
    kind_name = devs[0].device_kind
    peak = speclib.peaks(kind_name) if require_chips else None
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")
    from bench import load, system
    from repro import serving

    timings: dict = {}
    inputs = make_inputs(seed, cfg, traffic, timings)
    t = time.monotonic()
    params, state = system.weights(seed, cfg, arch)
    timings["weights_s"] = time.monotonic() - t
    tracer = None
    if trace:
        origin: list[float] = []

        def clock() -> float:
            now = time.monotonic()
            if not origin:
                origin.append(now)
            return now

        tracer = serving.Tracer(capacity=1 << 22, clock=clock)
        tracer.origin = origin[0]
    sched = system.build(cfg, traffic, arch, params, state, tracer=tracer,
                         timings=timings)
    kind = traffic["kind"]
    payloads = inputs["payloads"]
    if system_hook is not None:
        system_hook(sched)
    rng = np.random.default_rng([seed % (2 ** 32), seed // (2 ** 32)])
    perm = rng.permutation(len(payloads))

    def order(i: int) -> int:
        return perm[i % len(perm)]

    prof_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    report0 = sched.metrics.report()
    # Set-up's objects (modules, plan, compiled programs, the input
    # pool) live to the end of the window: freeze them out of the
    # collector, so that a full collection in the window scans only what
    # the traffic made, instead of pausing every thread for 0.1 s or
    # more at a point that falls differently in each run.
    gc.collect()
    gc.freeze()
    try:
        with load.counting_compiles() as compiles:
            records, win = load.closed_loop(
                sched, kind, payloads, clients=traffic["clients"],
                warm_s=traffic["warm_s"], seconds=seconds, order=order,
                counter=compiles, trace_dir=prof_dir,
                trace_options=_profile_options() if trace else None)
    finally:
        gc.unfreeze()
    setup_s = win.t0 - t_process
    win.stop_trace()
    report1 = sched.metrics.report()
    stats = devs[0].memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    kernels, modules = (_served_programs(sched) if trace else ([], set()))
    sched.close()
    in_win = [r for r in records if win.t0 <= r.t_sent < win.t1]
    answers = [(r.item, r.req.tier, r.req.result) for r in in_win
               if r.t_done is not None and r.error is None]
    del sched
    gc.collect()

    failed = [r for r in in_win if r.error is not None or r.t_done is None]
    lat_ms = [(r.t_done - r.t_sent) * 1e3 for r in in_win
              if r.t_done is not None and r.error is None]
    post = (report1["compiles_post_warmup"]
            - report0["compiles_post_warmup"])
    window_compiles = compiles["events"] + post
    log("set-up " + ", ".join(f"{k} {v!r}" for k, v in timings.items())
        + f"; setup_s {setup_s!r}")
    n_done = sum(1 for r in records if r.t_done is not None
                 and r.error is None and win.t0 <= r.t_done < win.t1)
    log(f"window {seconds} s: {len(in_win)} sent, {n_done} answered in "
        f"the window, {len(failed)} failed; compiles in the window "
        f"{window_compiles} (JAX events {compiles['events']} "
        f"{sorted(set(compiles['names']))}, program post-warmup {post})")
    tiers: dict = {}
    for _, tier, _ in answers:
        tiers[tier] = tiers.get(tier, 0) + 1
    log(f"tiers that served the window's requests {tiers}")

    metrics: dict = {}
    device = {"platform": platform, "kind": kind_name, "count": w["chips"],
              "memory_peak_bytes": memory_peak}
    breakdown = None
    if not trace:
        e2e = {"images_per_s": images_per_s(records, win),
               "p50_ms": _percentile(lat_ms, 50),
               "p95_ms": _percentile(lat_ms, 95),
               "setup_s": setup_s}
        for m in cell["end_to_end"]:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        from bench import xplane

        reduced = xplane.reduce(xplane.find(prof_dir))
        if keep_trace:
            shutil.copytree(prof_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(prof_dir, ignore_errors=True)
        view = RunView(window=(win.t0, win.t1), spans=_spans(tracer),
                       trace=reduced,
                       trace_offset=reduced["window"][0] - win.opened,
                       kernels=kernels, modules=modules, config=cfg,
                       arch=arch, peak=peak)
        for m in cell["per_layer"]:
            value = speclib.reader(cell["metrics_dir"], m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        for text in view.notes:
            log(text)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = reduced["breakdown"]
        log(f"trace: window {reduced['window_s']!r} s, device busy "
            f"{reduced['busy_s']!r} s, modules "
            f"{ {k: len(v) for k, v in reduced['modules'].items()} }")

    t = time.monotonic()
    result = check(answers, inputs, params, state, cfg, arch, traffic, seed,
                   control=control)
    limits = {"logit_gap": cfg["limits"]["logit_gap"],
              "compiles_in_window": 0, "failed": 0}
    values = {"logit_gap": result["logit_gap"],
              "compiles_in_window": window_compiles,
              "failed": len(failed)}
    correct = all(values[k] <= limits[k] for k in CHECKS)
    log(f"check{' (control)' if control else ''} sample "
        f"{result.get('sample')} answers {result.get('groups')}, logit "
        f"scale {result.get('scale')!r}, top-1 agreement "
        f"{result.get('top1_agree')!r}, {time.monotonic() - t:.1f} s")
    for k in CHECKS:
        log(f"check {k} {values[k]!r} limit {limits[k]!r}")
    out = {"correct": bool(correct), "attempted": len(in_win),
           "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": values[k], "limit": limits[k]}
                     for k in CHECKS}
    return out


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts
