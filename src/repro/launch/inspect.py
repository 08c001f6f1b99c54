"""Plan introspection CLI: per-block predicted-vs-measured attribution.

Restores (or builds, convert-once — the same :func:`serve.prepare_plan`
path the serving driver uses) the compiled plan from ``--plan-dir``,
runs :func:`repro.introspect.predicted_vs_measured` on a deterministic
coefficient batch, prints the per-block table, and writes the validated
JSON report to ``--report-out``.  The report is the versioned schema
``introspect.validate_report`` checks — the CI ``introspect-smoke`` job
runs exactly this command and re-validates the artifact.

CPU example:
    PYTHONPATH=src python -m repro.launch.inspect --arch jpeg-resnet \
        --reduced --plan-dir plans/inspect --batch 16 \
        --report-out introspect_report.json
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, reduced_config
from repro.core import dispatch as dispatchlib
from repro.data import jpeg_iterator
from repro import introspect
from repro.launch import serve as servelib

__all__ = ["main", "run_inspect"]


def resolve_executor(spec: str | None) -> str | None:
    """``--executor`` → the ``apply_compiled`` executor argument.

    ``auto`` mirrors the serving scheduler: the compiled schedule's own
    dispatch path on TPU, the band-elastic GEMM reference off-TPU."""
    tok = (spec or "auto").strip().lower()
    if tok == "auto":
        return None if jax.default_backend() == "tpu" else "gemm"
    if tok in ("plan", "dispatch", "none"):
        return None
    if tok == "gemm":
        return "gemm"
    raise SystemExit(f"unknown --executor {spec!r} "
                     "(expected auto | gemm | plan)")


def run_inspect(args) -> dict:
    changes = {}
    if args.dispatch is not None:
        changes["path"] = args.dispatch
    if args.bands is not None:
        changes["bands"] = args.bands
    dcfg = dispatchlib.configure(**changes)
    cfg = (reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    plan, compiled, plan_info = servelib.prepare_plan(args, cfg, dcfg)
    if compiled is None:
        raise SystemExit("[inspect] no compiled schedule for this plan "
                         "(per-layer walk has no step table to attribute)")

    it = jpeg_iterator(args.seed, args.batch, cfg.image_size,
                       cfg.in_channels, cfg.num_classes)
    coef = jnp.asarray(next(it)["coefficients"])

    executor = resolve_executor(args.executor)
    hw = introspect.resolve_profile(args.hw_profile)
    print(f"[inspect] plan {plan_info['dir']} "
          f"({'built' if plan_info['built'] else 'restored'}), "
          f"{len(plan_info.get('fused_blocks', []))} fused blocks, "
          f"executor={executor or 'plan'}, hw={hw.name}")
    print(f"[inspect] convolutions by route {compiled.meta.get('routes')}"
          + (f", pointwise on {compiled.meta['pointwise']}"
             if compiled.meta.get("pointwise") else ""))
    report = introspect.predicted_vs_measured(
        compiled, coef, executor=executor, hw=hw, iters=args.iters,
        warmup=args.warmup)
    report["meta"]["plan"] = plan_info

    print(introspect.render_text(report))
    summary = introspect.validate_report(report)  # raises on violations
    if args.report_out:
        with open(args.report_out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"[inspect] report written to {args.report_out} "
              f"({summary['blocks']} blocks, reconciliation "
              f"{summary['reconciliation']:.3f})")
    return report


def main() -> None:
    ap = argparse.ArgumentParser(
        description="per-block cost attribution for a compiled plan")
    ap.add_argument("--arch", default="jpeg-resnet")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--iters", type=int, default=5,
                    help="profiled/unprofiled timing iterations (medians)")
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan-dir", default=None,
                    help="plan checkpoint directory (restored when "
                         "present, built+saved once otherwise)")
    ap.add_argument("--dispatch", default=None,
                    help="operator path when the plan must be built "
                         "(reference | pallas | factored)")
    ap.add_argument("--bands", type=int, default=None,
                    help="band truncation when the plan must be built")
    ap.add_argument("--autotune-bands", action="store_true")
    ap.add_argument("--executor", default="auto",
                    help="schedule executor: auto (backend-resolved) | "
                         "gemm | plan")
    ap.add_argument("--hw-profile", default=None,
                    help="roofline hardware profile: registry name "
                         f"({', '.join(sorted(introspect.PROFILES))}), "
                         "'peak_flops,hbm_bw,link_bw' triple, or unset "
                         "for backend detection / $JPEG_HW_PROFILE")
    ap.add_argument("--report-out", default=None,
                    help="write the validated JSON report here")
    args = ap.parse_args()
    # prepare_plan reads these off the serve namespace; pin them to the
    # introspection defaults (compiled schedule forced on — attribution
    # needs the step table — and coefficient ingest)
    args.compiled = True
    args.ingest = "coefficients"
    try:
        run_inspect(args)
    except ValueError as e:
        print(f"[inspect] INVALID: {e}", file=sys.stderr)
        raise SystemExit(2)


if __name__ == "__main__":
    main()
