"""Plan introspection: where does each microsecond and each FLOP of a
compiled plan go — and does the cost model agree?

Three layers, built on ``core.plan.compiled_steps`` (the compiled
schedule as an explicit step list — the *same* closures the production
walk folds):

* **static attribution** (:mod:`~repro.introspect.attribution`): each
  schedule step lowered alone to optimized HLO, analyzed with
  ``launch.hlo_analysis.analyze_hlo``, joined with band budgets /
  retained energy / executor / VMEM metadata into a :class:`BlockCost`
  table, cross-checked against the whole-module analysis;
* **roofline prediction** (:mod:`~repro.introspect.roofline`):
  pluggable :class:`HardwareProfile` peaks (registry keyed by detected
  backend, ``JPEG_HW_PROFILE``/CLI override) turn each block's
  FLOPs/bytes into a predicted latency and dominant term;
* **measured attribution**: ``core.plan.StepProfile`` (per-step device
  walls, bit-identical logits) and ``serving.grid.GridCell.profile`` /
  :func:`profile_plan_grid` reconcile prediction against reality —
  :func:`predicted_vs_measured` is the headline report,
  ``launch.inspect`` the CLI, :func:`validate_report` the schema
  checker CI enforces.
"""
from repro.core.plan import StepProfile, compiled_steps
from repro.introspect.attribution import (BlockCost, block_costs,
                                          predicted_vs_measured)
from repro.introspect.gridprof import profile_plan_grid
from repro.introspect.report import render_text, validate_report, worst_ratio
from repro.introspect.roofline import (PROFILES, HardwareProfile,
                                       DEVICE_KINDS, detect_device_kind,
                                       resolve_profile,
                                       roofline)

__all__ = [
    "BlockCost",
    "HardwareProfile",
    "PROFILES",
    "StepProfile",
    "block_costs",
    "compiled_steps",
    "DEVICE_KINDS",
    "detect_device_kind",
    "predicted_vs_measured",
    "profile_plan_grid",
    "render_text",
    "resolve_profile",
    "roofline",
    "validate_report",
    "worst_ratio",
]
