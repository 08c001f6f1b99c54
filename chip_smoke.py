#!/usr/bin/env python3
"""Bring-up check: the JPEG-domain serving path, end to end, on one TPU.

Drives ``repro.launch.serve`` in this process through its normal route —
plan build, ``compile_plan``, band ladder, plan grid, band-elastic
scheduler — on the synthetic mixed-quality JPEG byte stream, and checks
what it served:

* **Phase A, published widths.**  The ``jpeg-resnet`` config (256 px,
  widths 64/128/256/512, 1000 classes, random weights from ``--seed``)
  serves 16 single-image requests at ``--batch 8`` through ``--qos
  --ingest bytes --tiers auto --batch-buckets fixed`` (one grid cell).
* **Phase B, reduced.**  The CIFAR-scale config serves 16 requests at
  ``--batch 4`` with the default tier ladder and capture buckets.

Every request must complete, and every grid cell that served one must
hold Mosaic kernels (``tpu_custom_call`` in the compiled text of the
cell's own captured entry).  Each served logits row is compared with a
plan walk built from the same weights, capped to the tier that served
it, run under ``jax.default_matmul_precision("highest")``: in Phase B on
``DispatchConfig(path="reference")`` (every Ξ materialised, no Pallas);
in Phase A on ``DispatchConfig(path="factored")`` (no Pallas), because
at published widths Ξ cannot be materialised (38 GB for one 512x512
conv), so for Phase A's block convs the reference differs from the
served path only in precision.

Tolerance: ``max |Δlogit| <= TOL_REL * max(1, max |reference logit|)``
with ``TOL_REL = 1.5e-2``.  The served path runs float32 dots at the
TPU's default precision, one bf16 pass (operands rounded to 8
significant bits); on one TPU v5e that put both phases at 4.2e-3 and
4.3e-3 of the logit scale, and the tolerance is 3.5 times the larger.
Top-1 must agree on every image whose reference top-2 margin exceeds
the tolerance.  Phase B also runs two lower-precision controls through
the same check: the reference walk with its weights and input in
bfloat16 (bf16 activations), printed, and with them rounded to float8
e4m3, which must fail the check.  Agreement with ``resnet.spatial_apply``
(pixels, exact ReLU; run at "highest") is printed, not asserted: the
served network uses the approximate ASM ReLU.

Plans are built from ``--seed`` in a fresh directory under ``.smoke/``
and removed afterwards; nothing is restored from ``plans/``.  The script
refuses to run without a TPU and never falls back to the CPU.  The last
line of its output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

    python3 chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
TOL_REL = 1.5e-2

if __name__ != "__main__":
    # a spawned ingest worker re-importing this file: it decodes with
    # numpy only and must never claim the chip the parent holds
    os.environ["JAX_PLATFORMS"] = "cpu"


def _require_repo() -> None:
    src = ROOT / "src"
    if not (src / "repro" / "launch" / "serve.py").is_file():
        raise SystemExit(f"chip_smoke.py: no repository beside it ({src} "
                         "does not hold repro/); nothing to run")
    sys.path.insert(0, str(src))


def _require_tpu():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke.py: needs a TPU, JAX found "
                         f"{devs[0].platform!r} ({devs[0].device_kind}); "
                         "refusing to run anywhere else")
    return devs


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _cast_tree(tree, cast):
    """``tree`` with ``cast`` applied to each float32 array."""
    import numpy as np
    import jax

    return jax.tree_util.tree_map(
        lambda a: cast(a) if isinstance(a, (jax.Array, np.ndarray))
        and a.dtype == np.float32 else a, tree)


def _reference_logits(ref_plan, coefs, tiers, served_tiers, *,
                      precision="highest", cast=None):
    """The reference plan walk, each row capped to the tier that served it
    (weights enter the jit as arguments); ``cast`` maps each floating
    array of the plan and the input (the precision controls)."""
    import contextlib
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import plan as planlib
    from repro.serving.ladder import cap_plan

    caps = {t["name"]: t["cap"] for t in tiers}
    out = np.zeros((coefs.shape[0], ref_plan.head_w.shape[1]), np.float32)
    ctx = (jax.default_matmul_precision(precision) if precision
           else contextlib.nullcontext())
    with ctx:
        for name in sorted(set(served_tiers)):
            rows = [i for i, t in enumerate(served_tiers) if t == name]
            capped, x = cap_plan(ref_plan, caps[name]), jnp.asarray(coefs[rows])
            if cast is not None:
                capped, x = _cast_tree(capped, cast), cast(x)
            fn = planlib.jit_over(capped, planlib.apply_plan)
            out[rows] = np.asarray(fn(x), np.float32)
    return out


def _parity(served, ref):
    """max |Δlogit| against the tolerance, and top-1 agreement on the
    images whose reference top-2 margin exceeds it."""
    import numpy as np

    delta = float(np.abs(served - ref).max())
    scale = max(1.0, float(np.abs(ref).max()))
    tol = TOL_REL * scale
    top2 = np.sort(ref, axis=-1)[:, -2:]
    decided = top2[:, 1] - top2[:, 0] > tol
    agree = served.argmax(-1) == ref.argmax(-1)
    return {"max_abs_dlogit": delta, "rel_dlogit": delta / scale,
            "tolerance": tol, "top1_agree": float(agree.mean()),
            "images_decided": int(decided.sum()),
            "top1_differs_past_margin":
                np.flatnonzero(decided & ~agree).tolist()}


def _check_parity(label, served, ref):
    """:func:`_parity`, raising unless it holds.  Returns its numbers."""
    p = _parity(served, ref)
    if p["max_abs_dlogit"] > p["tolerance"]:
        raise AssertionError(f"{label}: max |dlogit| "
                             f"{p['max_abs_dlogit']:.3g} > tolerance "
                             f"{p['tolerance']:.3g}")
    if p["top1_differs_past_margin"]:
        raise AssertionError(
            f"{label}: top-1 differs on images "
            f"{p['top1_differs_past_margin']} whose reference margin "
            f"exceeds {p['tolerance']:.3g}")
    return p


def _precision_controls(ref_plan, coefs, tiers, served_tiers, ref):
    """Lower-precision stand-ins for the served path, at its (default)
    matmul precision, through the parity check: bf16 weights, input and
    activations (printed), and weights and input rounded to float8 e4m3
    on the host (must fail the check, or the check is blind to it)."""
    import numpy as np
    import jax.numpy as jnp

    controls = {
        "bf16": lambda a: jnp.asarray(a, jnp.bfloat16),
        "fp8_e4m3": lambda a: jnp.asarray(np.asarray(a).astype(
            jnp.float8_e4m3fn).astype(np.float32)),
    }
    out = {}
    for name, cast in controls.items():
        got = _reference_logits(ref_plan, coefs, tiers, served_tiers,
                                precision=None, cast=cast)
        try:
            out[name] = {"passes": True, **_check_parity(name, got, ref)}
        except AssertionError:
            out[name] = {"passes": False, **_parity(got, ref)}
    if out["fp8_e4m3"]["passes"]:
        raise AssertionError("the float8 control passes the parity check: "
                             "the tolerance cannot see that precision loss")
    return out


def _phase(name, argv, seed, workdir, ref_path, controls):
    """Serve one configuration through ``serve.serve_jpeg_resnet`` and
    check it against a plan walk on ``DispatchConfig(path=ref_path)``.
    Returns the summary printed for the phase."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.codec import ingest as ingestlib
    from repro.configs.base import get_config, reduced_config
    from repro.core import dispatch as dispatchlib
    from repro.core import jpeg as jpeglib
    from repro.core import plan as planlib
    from repro.core import resnet as R
    from repro.launch import serve
    from repro.models.registry import jpeg_resnet_spec

    plan_dir = os.path.join(workdir, name)
    args = serve.build_parser().parse_args(
        argv + ["--seed", str(seed), "--plan-dir", plan_dir])
    t0 = time.time()
    record: dict = {}
    out = serve.serve_jpeg_resnet(args, record=record)
    serve_s = time.time() - t0
    qos, served = out["qos"], record["served"]
    if out["completed"] != args.requests or len(served) != args.requests:
        raise AssertionError(f"phase {name}: {out['completed']} of "
                             f"{args.requests} requests completed")
    if qos["compiles_post_warmup"]:
        raise AssertionError(f"phase {name}: {qos['compiles_post_warmup']} "
                             "compiles after warmup")
    kernels = _kernels_in_served_cells(record["grid"])

    cfg = reduced_config("jpeg-resnet") if args.reduced \
        else get_config("jpeg-resnet")
    spec = jpeg_resnet_spec(cfg)
    nb = cfg.image_size // 8
    coefs, _ = ingestlib.ingest_batch(
        [p for p, _, _ in served], quality=spec.quality, grid=(nb, nb),
        channels=cfg.in_channels, with_stats=False)
    got = np.stack([np.asarray(l, np.float32) for _, _, l in served])
    tiers_served = [t for _, t, _ in served]

    params, state = R.init_resnet(jax.random.PRNGKey(seed), spec)
    ref_plan = planlib.build_plan(
        params, state, spec,
        dispatch=dispatchlib.DispatchConfig(path=ref_path))
    ref = _reference_logits(ref_plan, coefs, qos["tiers"], tiers_served)
    parity = _check_parity(f"phase {name}", got, ref)
    if controls:
        parity["controls"] = _precision_controls(
            ref_plan, coefs, qos["tiers"], tiers_served, ref)

    pixels = jpeglib.jpeg_decode(jnp.moveaxis(jnp.asarray(coefs), 3, 1),
                                 quality=spec.quality, scaled=True)
    with jax.default_matmul_precision("highest"):
        spatial = jax.jit(lambda p, s, x: R.spatial_apply(
            p, s, x, training=False, spec=spec)[0])(params, state, pixels)
    parity["top1_agree_spatial"] = float(np.mean(
        np.asarray(spatial).argmax(-1) == got.argmax(-1)))

    return {
        "config": cfg.name, "image_size": cfg.image_size,
        "widths": list(cfg.widths), "classes": cfg.num_classes,
        "requests": args.requests, "batch": args.batch,
        "completed": out["completed"],
        "tiers_served": {t: tiers_served.count(t)
                         for t in sorted(set(tiers_served))},
        "executor": qos["executor"],
        "steps": {t["name"]: t["steps"] for t in qos["tiers"]},
        "rules": {t["name"]: t["rules"] for t in qos["tiers"]},
        "reference": ref_path,
        "compile_s": qos["warmup_s"], "wall_s": out["wall_s"],
        "phase_s": time.time() - t0, "serve_s": serve_s,
        "compiles_total": qos["compiles_total"],
        "compiles_post_warmup": qos["compiles_post_warmup"],
        "tpu_custom_calls": kernels,
        **parity,
    }


def _kernels_in_served_cells(grid):
    """Mosaic kernels (``tpu_custom_call``) in the compiled program of
    every grid cell that served a request, read from the cell's own
    captured entry.  Raises if any such cell holds none."""
    counts = {cell.name: cell.lower().compile().as_text()
              .count("tpu_custom_call")
              for col in grid.distinct for cell in col.cells.values()
              if cell.hits}
    bare = [n for n, c in counts.items() if not c]
    if not counts or bare:
        raise AssertionError(f"no tpu_custom_call in served cells "
                             f"{bare or '(none served)'}")
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and request stream seed")
    opts = ap.parse_args(argv)
    _require_repo()
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devs = _require_tpu()
    dev = devs[0]
    cache_before = _cache_entries(cache_dir)
    (ROOT / ".smoke").mkdir(exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="plans-", dir=ROOT / ".smoke")
    from repro.codec import ingest as ingestlib

    try:
        phases = {
            "A": _phase("A", ["--arch", "jpeg-resnet", "--qos",
                              "--ingest", "bytes", "--batch", "8",
                              "--requests", "16", "--tiers", "auto",
                              "--batch-buckets", "fixed"],
                        opts.seed, workdir, ref_path="factored",
                        controls=False),
            "B": _phase("B", ["--arch", "jpeg-resnet", "--reduced", "--qos",
                              "--ingest", "bytes", "--batch", "4",
                              "--requests", "16"],
                        opts.seed, workdir, ref_path="reference",
                        controls=True),
        }
    finally:
        ingestlib.shutdown_pool()
        shutil.rmtree(workdir, ignore_errors=True)

    print(f"[smoke] device: {dev.platform} {dev.device_kind} "
          f"x{len(devs)}; compile cache {cache_dir}: {cache_before} -> "
          f"{_cache_entries(cache_dir)} entries")
    for name, s in phases.items():
        print(f"[smoke] phase {name} ({s['config']}, {s['image_size']} px, "
              f"widths {s['widths']}, {s['classes']} classes): "
              f"{s['completed']}/{s['requests']} served at batch "
              f"{s['batch']}, tiers {s['tiers_served']}")
        for tier, steps in s["steps"].items():
            rules = "; ".join(s["rules"][tier].values()) or "-"
            print(f"[smoke]   tier {tier} executors: "
                  + ", ".join(f"{k}={v}" for k, v in steps.items()))
            print(f"[smoke]   tier {tier} rules: {rules}")
        print(f"[smoke]   parity vs {s['reference']} walk (highest): "
              f"max|dlogit| {s['max_abs_dlogit']!r} = {s['rel_dlogit']!r} "
              f"of scale (tolerance {s['tolerance']!r}), top-1 "
              f"{s['top1_agree']!r} ({s['images_decided']} images past "
              f"the margin); top-1 vs spatial_apply "
              f"{s['top1_agree_spatial']!r}")
        for c, v in s.get("controls", {}).items():
            print(f"[smoke]   control {c}: max|dlogit| "
                  f"{v['max_abs_dlogit']!r} = {v['rel_dlogit']!r} of scale, "
                  f"top-1 {v['top1_agree']!r}; "
                  f"{'passes' if v['passes'] else 'fails'} the check")
        print(f"[smoke]   compile {s['compile_s']!r} s, serve wall "
              f"{s['wall_s']!r} s, phase {s['phase_s']!r} s; compiles "
              f"{s['compiles_total']} total, {s['compiles_post_warmup']} "
              f"post-warmup; tpu_custom_call per served cell "
              f"{s['tpu_custom_calls']}")
    print(json.dumps({"phases": phases}, default=str))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
