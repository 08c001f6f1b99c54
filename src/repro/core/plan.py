"""Convert-once inference engine: the ``InferencePlan`` artifact.

The paper's deployment story (§4.1 "the map can be precomputed to speed up
inference", §6 sparsity) lands here as a single object.  Building a plan:

* **fuses inference-mode batch norm** into the adjacent conv's Ξ operator
  (``core.batchnorm.fold_batchnorm``): the scale multiplies Ξ's
  output-channel rows at precompute time and the β/μ constant rides on the
  operator as a DC shift — the per-step ``dispatch.batchnorm`` calls
  disappear from the precomputed path entirely;
* **autotunes ``bands`` per layer**: the quantization table already crushed
  high-frequency energy, so an energy budget over ``1/q²`` picks each
  layer's truncation (``bands_for_budget``), optionally refined by a parity
  sweep against the reference full-band path (``autotune_bands``).  The
  global ``DispatchConfig.bands`` knob remains as an override;
* is **serializable** through ``checkpoint.manager.CheckpointManager``
  (``save_plan``/``load_plan``): numeric leaves go into the checksummed
  array store, static structure into the manifest ``extra`` JSON, so a
  serving process restores the plan and never re-explodes at trace time.

``resnet.precompute_operators`` / ``resnet.jpeg_apply_precomputed`` are
thin wrappers over :func:`build_operators` / :func:`apply_operators` (the
unfused, per-step-batchnorm walk kept for training-state parity checks and
as the perf baseline); :func:`build_plan` / :func:`apply_plan` are the
serving path.

A plan can additionally be **compiled** (:func:`compile_plan`): the
per-layer dispatch walk is lowered into a static schedule whose steps are
fused residual-block megakernels (``kernels.fused_block``) over
**tile-packed** banded operators (``kernels.tiling``) — band-truncated Ξ
slices padded to sublane-aligned per-channel widths and concatenated into
one contiguous buffer per layer at compile time, batch-norm DC shifts
baked into broadcast rows, ASM matrices packed to the same widths.  The
compiled runtime path (:func:`apply_compiled`) therefore does zero band
slicing/padding between ops: activations stay at their packed widths from
the stem to the classifier head, and each residual block is one fused step
(conv → ASM → conv → residual add → ASM with no HBM round trips between
them on the Pallas path).  Blocks whose operators are not materialised or
whose VMEM estimate exceeds the budget fall back to the per-layer walk —
recorded per block in ``CompiledPlan.meta``.  Compiled schedules serialize
through the same ``CheckpointManager`` (:func:`save_compiled_plan` /
:func:`load_compiled_plan`) with bit-identical restored logits.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import batchnorm as bnlib
from repro.core import dct as dctlib
from repro.core import dispatch as dispatchlib
from repro.core import pooling as poollib
from repro.core import resnet as resnetlib
from repro.parallel.sharding import shard

__all__ = [
    "InferencePlan",
    "qtable_band_energy",
    "bands_for_budget",
    "bands_for_profile",
    "autotune_bands",
    "operator_keys",
    "build_operators",
    "apply_operators",
    "build_plan",
    "apply_plan",
    "save_plan",
    "load_plan",
    "CompiledStem",
    "CompiledBlock",
    "CompiledPlan",
    "compile_plan",
    "apply_compiled",
    "apply_compiled_packed",
    "capture_compiled",
    "jit_over",
    "step_executor",
    "save_compiled_plan",
    "load_compiled_plan",
]

#: candidate band counts the autotuner moves along (multiples of 8 keep the
#: coefficient axis lane-aligned for the Pallas kernels).
BAND_LADDER = (8, 16, 24, 32, 40, 48, 56, 64)


# --------------------------------------------------------------------------
# Per-layer band autotuning (ROADMAP "Band autotuning")
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def qtable_band_energy(quality: int = 50) -> np.ndarray:
    """Cumulative retained-energy fraction per zigzag prefix length.

    The quantization table divides coefficient ``k`` by ``q[k]``; for a
    flat spectral prior the signal energy surviving quantization scales as
    ``1/q[k]²`` — exactly the "high-frequency energy the qtable already
    crushes".  ``out[b-1]`` is the fraction of that retained energy covered
    by keeping the first ``b`` zigzag coefficients; it is non-decreasing.
    """
    q = dctlib.quantization_table(quality)
    w = 1.0 / (q * q)
    return np.cumsum(w) / np.sum(w)


def _bands_from_cum(cum: np.ndarray, budget: float) -> int:
    if not 0.0 < budget <= 1.0:
        raise ValueError(f"budget must be in (0, 1], got {budget}")
    b = int(np.searchsorted(cum, budget - 1e-12) + 1)
    return min(dctlib.NFREQ, ((b + 7) // 8) * 8)


def bands_for_budget(quality: int, budget: float) -> int:
    """Smallest band count whose cumulative qtable energy ≥ ``budget``.

    Rounded up to a multiple of 8 (lane alignment).  Monotone in
    ``budget``: a tighter (smaller) budget never yields *more* bands.
    """
    return _bands_from_cum(qtable_band_energy(quality), budget)


def _profile_cum(profile: np.ndarray) -> np.ndarray:
    p = np.asarray(profile, np.float64).reshape(dctlib.NFREQ)
    if np.any(p < 0):
        raise ValueError("energy profile must be non-negative")
    total = p.sum()
    if total <= 0:
        raise ValueError("energy profile is all zero")
    return np.cumsum(p) / total


def bands_for_profile(profile: np.ndarray, budget: float) -> int:
    """:func:`bands_for_budget` over an *empirical* per-zigzag energy
    profile (e.g. ``codec.ingest.IngestStats.energy`` measured on real
    traffic) instead of the flat-spectrum ``1/q²`` qtable prior.
    Monotone in ``budget`` for a fixed profile.
    """
    return _bands_from_cum(_profile_cum(profile), budget)


def operator_keys(params: Any, spec: resnetlib.ResNetSpec) -> list[str]:
    """Flat conv-operator keys in forward order: ``stem``, then per block
    its projection (if any) and its slots, ``s0b0/conv1``…"""
    keys = ["stem"]
    for blk in resnetlib._stages(spec):
        if "proj" in params[blk.name]:
            keys.append(f"{blk.name}/proj")
        keys += [f"{blk.name}/{sl.name}" for sl in blk.slots]
    return keys


def autotune_bands(
    params: Any,
    state: Any,
    spec: resnetlib.ResNetSpec,
    *,
    budget: float = 0.95,
    probe_coef: jnp.ndarray | None = None,
    tol: float = 5e-2,
    ladder: tuple[int, ...] = BAND_LADDER,
    phi: int | None = None,
    profile: np.ndarray | None = None,
    occupancy: np.ndarray | None = None,
) -> dict[str, int]:
    """Per-layer band assignment from qtable energy + optional parity sweep.

    Every conv operator starts at :func:`bands_for_budget` (the qtable
    energy heuristic — monotone in ``budget``); with ``profile`` (a
    per-zigzag empirical energy vector, e.g. measured by
    ``codec.ingest``) the start point is :func:`bands_for_profile` over
    the *observed* traffic instead of the flat-spectrum prior.  With
    ``probe_coef`` (a small ``(N, bh, bw, C, 64)`` coefficient batch) the
    assignment is refined against the *reference path at full bands*:

    1. escalate all layers one ladder step while the probe logits disagree
       (top-1) or deviate by more than ``tol`` — the heuristic may be too
       aggressive for a particular network;
    2. one greedy tightening pass, last layer to first: lower each layer
       individually while parity still holds — layers differ in
       sensitivity, which is what makes the result genuinely per-layer.

    When a profile is given, the chosen per-layer bands are logged against
    its energy coverage (and ``occupancy`` — the fraction of nonzero
    input coefficients a cutoff drops — when provided), so silent
    over-truncation is visible in the build output.
    """
    base = (bands_for_profile(profile, budget) if profile is not None
            else bands_for_budget(spec.quality, budget))
    keys = operator_keys(params, spec)
    bands = {k: base for k in keys}
    if probe_coef is None:
        _log_band_choice(bands, keys, profile, occupancy)
        return bands

    # The sweep probes many assignments that differ in a single layer, so
    # operators are exploded once per distinct (layer, band) pair and
    # trial plans are assembled from that cache — not rebuilt per probe.
    phi = spec.phi if phi is None else phi
    ref_cfg = dispatchlib.DispatchConfig(path="reference",
                                         bands=dctlib.NFREQ)
    folds = _fold_all(params, state, spec)
    ops_at: dict[int, dict[str, Any]] = {}

    def ops_for(level: int) -> dict[str, Any]:
        if level not in ops_at:
            ops_at[level] = build_operators(params, spec, ref_cfg,
                                            folds=folds, bands=level)
        return ops_at[level]

    def plan_for(assign: dict[str, int]) -> InferencePlan:
        operators: dict[str, Any] = {"stem": ops_for(assign["stem"])["stem"]}
        for blk in resnetlib._stages(spec):
            name = blk.name
            entry = {}
            for slot in ops_for(assign[f"{name}/conv1"])[name]:
                entry[slot] = ops_for(assign[f"{name}/{slot}"])[name][slot]
            operators[name] = entry
        return InferencePlan(operators, params["head"]["w"],
                             params["head"]["b"], spec, phi, ref_cfg,
                             dict(assign))

    ref = np.asarray(apply_plan(plan_for({k: dctlib.NFREQ for k in keys}),
                                probe_coef))
    ref_top1 = ref.argmax(-1)

    def parity(assign: dict[str, int]) -> bool:
        got = np.asarray(apply_plan(plan_for(assign), probe_coef))
        return (float(np.abs(got - ref).max()) <= tol
                and bool((got.argmax(-1) == ref_top1).all()))

    def bump(b: int) -> int:
        nxt = [l for l in ladder if l > b]
        return nxt[0] if nxt else dctlib.NFREQ

    while not parity(bands) and any(v < dctlib.NFREQ for v in bands.values()):
        bands = {k: bump(v) for k, v in bands.items()}

    for k in reversed(keys):
        while True:
            lower = [l for l in ladder if l < bands[k]]
            if not lower:
                break
            trial = dict(bands)
            trial[k] = lower[-1]
            if not parity(trial):
                break
            bands = trial
    _log_band_choice(bands, keys, profile, occupancy)
    return bands


def _log_band_choice(bands: dict[str, int], keys: list[str],
                     profile: np.ndarray | None,
                     occupancy: np.ndarray | None) -> None:
    """Make over-truncation visible: per layer, the empirical energy the
    cutoff keeps and the nonzero-coefficient mass it drops."""
    if profile is None:
        return
    cum = _profile_cum(profile)
    occ_total = float(np.sum(occupancy)) if occupancy is not None else 0.0
    for k in keys:
        b = bands[k]
        line = f"[autotune] {k}: bands={b} energy_kept={cum[b - 1]:.4f}"
        if occupancy is not None and occ_total > 0:
            dropped = float(np.sum(occupancy[b:])) / occ_total
            line += f" occupancy_dropped={dropped:.2%}"
        print(line, flush=True)


# --------------------------------------------------------------------------
# Operator construction + the two forward walks
# --------------------------------------------------------------------------


def _resolve_bands(bands: Any, key: str,
                   cfg: dispatchlib.DispatchConfig) -> int:
    if bands is None:
        return cfg.bands
    if isinstance(bands, int):
        return bands
    return int(bands.get(key, cfg.bands))


def build_operators(params: Any, spec: resnetlib.ResNetSpec,
                    cfg: dispatchlib.DispatchConfig, *,
                    folds: dict[str, tuple] | None = None,
                    bands: Any = None) -> dict[str, Any]:
    """Explode every convolution once; returns the operator pytree.

    ``folds`` maps operator keys to ``(scale, shift)`` pairs from
    ``batchnorm.fold_batchnorm`` (fused-BN plans); ``bands`` is None
    (global ``cfg.bands``), an int, or a per-key dict.  Each leaf is a
    :class:`repro.core.dispatch.ConvOperator` with its apply path resolved
    here — apply is a pure table lookup per step.
    """
    folds = folds or {}

    def pc(key, kernel, stride, **kw):
        scale, shift = folds.get(key, (None, None))
        return dispatchlib.precompute_conv(
            kernel, stride, bands=_resolve_bands(bands, key, cfg),
            scale=scale, shift=shift, cfg=cfg, **kw)

    stem_stride = resnetlib.stem_layout(spec)[1]
    ops: dict[str, Any] = {"stem": pc("stem", params["stem"]["kernel"],
                                      stem_stride, in_scaled=True,
                                      quality=spec.quality)}
    for blk in resnetlib._stages(spec):
        p = params[blk.name]
        entry = {sl.name: pc(f"{blk.name}/{sl.name}", p[sl.name], sl.stride)
                 for sl in blk.slots}
        if "proj" in p:
            entry["proj"] = pc(f"{blk.name}/proj", p["proj"], blk.stride)
        ops[blk.name] = entry
    return ops


def apply_operators(params: Any, state: Any, ops: dict[str, Any],
                    coef: jnp.ndarray, *, spec: resnetlib.ResNetSpec,
                    phi: int | None = None,
                    cfg: dispatchlib.DispatchConfig | None = None
                    ) -> jnp.ndarray:
    """Precomputed-operator inference with *per-step* batch norm.

    The unfused walk — kept as the parity baseline against ``jpeg_apply``
    (it consumes the live ``state``) and as the perf baseline the fused
    :func:`apply_plan` is measured against.  Rejects operators that carry
    a fused batch norm: applying ``state`` on top of them would run BN
    twice and silently corrupt the logits — use :func:`apply_plan`.
    """
    resnetlib._require_basic(spec, "plan.apply_operators")
    phi = spec.phi if phi is None else phi
    cfg = dispatchlib.resolve_config(cfg)
    stem = ops["stem"]
    if stem.shift is not None or stem.scale is not None:
        raise ValueError(
            "operators carry a fused batch norm (built by build_plan); "
            "applying per-step batch norm on top would run BN twice — "
            "serve them through plan.apply_plan, or build unfused "
            "operators with resnet.precompute_operators")

    def bn(name, h):
        p = bnlib.BatchNormParams(params[name]["gamma"], params[name]["beta"])
        s = bnlib.BatchNormState(state[name]["mean"], state[name]["var"])
        h, _ = dispatchlib.batchnorm(h, p, s, training=False, cfg=cfg)
        return h

    def relu(h):
        return dispatchlib.asm_relu(h, phi, cfg=cfg)

    h = dispatchlib.apply_conv(coef, ops["stem"], cfg=cfg)
    h = relu(bn("stem_bn", h))
    for layout in resnetlib._stages(spec):
        name = layout.name
        blk, op = params[name], ops[name]
        short = h
        if "proj" in blk:
            short = dispatchlib.apply_conv(h, op["proj"], cfg=cfg)
        h = dispatchlib.apply_conv(h, op["conv1"], cfg=cfg)
        h = relu(bn(name + "_bn1", h))
        h = dispatchlib.apply_conv(h, op["conv2"], cfg=cfg)
        h = bn(name + "_bn2", h)
        h = relu(poollib.residual_add(h, short))
    pooled = poollib.global_avg_pool_jpeg(h)
    return pooled @ params["head"]["w"] + params["head"]["b"]


# --------------------------------------------------------------------------
# The plan artifact
# --------------------------------------------------------------------------


class InferencePlan(NamedTuple):
    """Everything JPEG-domain serving needs, precomputed once.

    ``operators`` carry the fused batch norms (scale folded into Ξ, DC
    shift on the operator) at their per-layer band truncations; batch-norm
    parameters and running statistics are *gone* — only the head weights
    remain as raw parameters.  Closure-only (static metadata is not a
    pytree leaf): close over the plan in a jitted lambda rather than
    passing it as a jit argument.
    """

    operators: dict[str, Any]
    head_w: jnp.ndarray
    head_b: jnp.ndarray
    spec: resnetlib.ResNetSpec
    phi: int
    cfg: dispatchlib.DispatchConfig
    bands: dict[str, int]
    #: how the band assignment was produced ({"bands_mode": "auto" |
    #: "global" | "explicit", ...}) — serving uses it to decide whether a
    #: restored plan satisfies an --autotune-bands request.
    provenance: Any = None

    def __call__(self, coef: jnp.ndarray) -> jnp.ndarray:
        return apply_plan(self, coef)


def build_plan(
    params: Any,
    state: Any,
    spec: resnetlib.ResNetSpec,
    *,
    phi: int | None = None,
    dispatch: dispatchlib.DispatchConfig | None = None,
    bands: Any = None,
    budget: float | None = None,
    probe_coef: jnp.ndarray | None = None,
    profile: np.ndarray | None = None,
    occupancy: np.ndarray | None = None,
    eps: float = 1e-5,
) -> InferencePlan:
    """Fuse, autotune, and explode a trained model into an ``InferencePlan``.

    ``bands``: None → the frozen dispatch config's global knob (the
    override path); an int or per-key dict → explicit assignment; the
    string ``"auto"`` (or a ``budget``) → :func:`autotune_bands` from the
    quantization table — or from an empirical coefficient-energy
    ``profile`` (``codec.ingest`` stats) when given — refined by a parity
    sweep when ``probe_coef`` is given.
    """
    phi = spec.phi if phi is None else phi
    cfg = dispatchlib.resolve_config(dispatch)
    autotuned = bands == "auto" or budget is not None
    if autotuned:
        bands = autotune_bands(params, state, spec,
                               budget=0.95 if budget is None else budget,
                               probe_coef=probe_coef, phi=phi,
                               profile=profile, occupancy=occupancy)
    provenance = {
        # the backend every operator's apply path was resolved on
        "platform": dispatchlib.platform(),
        "bands_mode": ("auto" if autotuned
                       else "global" if bands is None
                       else "explicit"),
        "budget": budget,
        "probe": probe_coef is not None,
        "energy": ("empirical" if profile is not None else "qtable")
        if autotuned else None,
    }
    folds = _fold_all(params, state, spec, eps=eps)
    ops = build_operators(params, spec, cfg, folds=folds, bands=bands)
    resolved = {k: _resolve_bands(bands, k, cfg)
                for k in operator_keys(params, spec)}
    return InferencePlan(ops, params["head"]["w"], params["head"]["b"],
                         spec, phi, cfg, resolved, provenance)


def _fold_all(params: Any, state: Any, spec: resnetlib.ResNetSpec,
              eps: float = 1e-5) -> dict[str, tuple]:
    """(scale, shift) folds for every batch-normed conv, keyed like
    :func:`operator_keys` (a basic block's proj has no BN and gets no
    entry)."""

    def fold(bn_name):
        p = bnlib.BatchNormParams(params[bn_name]["gamma"],
                                  params[bn_name]["beta"])
        s = bnlib.BatchNormState(state[bn_name]["mean"],
                                 state[bn_name]["var"])
        return bnlib.fold_batchnorm(p, s, eps=eps)

    folds = {"stem": fold("stem_bn")}
    for blk in resnetlib._stages(spec):
        slots = blk.slots + ((blk.proj,) if blk.proj is not None else ())
        for sl in slots:
            if sl.bn is not None:
                folds[f"{blk.name}/{sl.name}"] = fold(blk.name + sl.bn)
    return folds


def apply_plan(plan: InferencePlan, coef: jnp.ndarray,
               cfg: dispatchlib.DispatchConfig | None = None) -> jnp.ndarray:
    """Serve from a plan: matmuls + ASM only — no batch norm, no explode.

    Each activation runs ASM at its producing layer's band truncation (the
    residual join runs at the wider of its two contributors, since the
    shortcut may carry coefficients the main branch truncated away).
    """
    cfg = plan.cfg if cfg is None else cfg
    ops = plan.operators

    def relu(h, b):
        return dispatchlib.asm_relu(h, plan.phi, cfg=cfg, bands=b)

    h = dispatchlib.apply_conv(coef, ops["stem"], cfg=cfg)
    cur = ops["stem"].bands
    h = relu(h, cur)
    if resnetlib.stem_layout(plan.spec)[2]:
        h = poollib.max_pool_jpeg(h, cur)
    h = shard(h, "batch", None, None, None, None)
    for blk in resnetlib._stages(plan.spec):
        op = ops[blk.name]
        short, short_bands = h, cur
        if "proj" in op:
            short = dispatchlib.apply_conv(h, op["proj"], cfg=cfg)
            short_bands = op["proj"].bands
        for sl in blk.slots:
            h = dispatchlib.apply_conv(h, op[sl.name], cfg=cfg)
            if sl.relu_after:
                h = relu(h, op[sl.name].bands)
        cur = max(op[blk.slots[-1].name].bands, short_bands)
        h = relu(poollib.residual_add(h, short), cur)
        h = shard(h, "batch", None, None, None, None)
    pooled = poollib.global_avg_pool_jpeg(h)
    return pooled @ plan.head_w + plan.head_b


# --------------------------------------------------------------------------
# Compiled plan execution: fused megakernels over tile-packed operators
# --------------------------------------------------------------------------

#: default per-instance VMEM allowance for a fused block (of the ~16 MB/core
#: budget; the rest is headroom for Mosaic's own spills and double buffering).
VMEM_BUDGET = 12 << 20

#: the rule that keeps the fused-block megakernel off a TPU, recorded in
#: ``CompiledPlan.meta["rules"]`` of every plan it applies to.
MEGAKERNEL_OFF_TPU = (
    "megakernel off on tpu: Mosaic rejects kernels/fused_block.py "
    "(_asm_tile lane-splitting reshape: unsupported shape cast; stride-2 "
    "_conv_tile slice: only 2D gather) - fused steps run the packed-GEMM "
    "twin fused_block_reference")


def _r8(bands: int) -> int:
    """Packed per-channel width for a band count (sublane-aligned)."""
    from repro.kernels import tiling

    return min(dctlib.NFREQ, tiling.round_up(bands, tiling.SUBLANE))


class CompiledStem(NamedTuple):
    """The compiled stem step: one packed conv + ASM (no residual)."""

    kind: str                  # "packed" | "layers"
    conv: Any                  # tiling.PackedConv | None
    asm: Any                   # tiling.PackedAsm | None
    op: Any                    # ConvOperator (fallback walk) | None
    cin: int
    cout: int
    w_in: int                  # zigzag prefix sliced from the raw coefficients
    w_out: int
    bands_out: int             # true band count of the stem activation


class CompiledBlock(NamedTuple):
    """One residual block in the compiled schedule.

    ``kind == "fused"`` executes through ``dispatch.fused_block`` (the
    megakernel / its XLA twin) over packed operators; ``kind == "layers"``
    keeps the per-layer dispatch walk (operators not materialised, or the
    VMEM estimate exceeded the budget — ``CompiledPlan.meta`` records why).
    ``w_in``/``w_out`` are packed per-channel widths; ``bands_in`` /
    ``bands_out`` the true band counts (``bands_out`` is the residual-join
    width: ``max(conv2.bands, shortcut bands)``).
    """

    kind: str
    name: str
    cin: int
    cout: int
    w_in: int
    w_out: int
    bands_in: int
    bands_out: int
    path: str                  # resolved execution path for fused steps
    conv1: Any = None
    asm_mid: Any = None
    conv2: Any = None
    proj: Any = None
    asm_out: Any = None
    ops: Any = None            # ConvOperator dict for the fallback walk
    vmem_bytes: int = 0


class CompiledPlan(NamedTuple):
    """A static schedule of fused steps lowered from an ``InferencePlan``.

    Closure-only, like the plan: close over it in a jitted lambda.  The
    activations between steps live in the packed ``(N, bh, bw, C·w)``
    layout — no 64-wide padding anywhere on the runtime path.
    """

    stem: CompiledStem
    blocks: tuple
    head_w: jnp.ndarray
    head_b: jnp.ndarray
    spec: resnetlib.ResNetSpec
    phi: int
    cfg: dispatchlib.DispatchConfig
    bands: dict[str, int]
    meta: Any = None

    def __call__(self, coef: jnp.ndarray) -> jnp.ndarray:
        return apply_compiled(self, coef)


def compile_plan(plan: InferencePlan, *, vmem_budget: int = VMEM_BUDGET,
                 image_size: int | None = None) -> CompiledPlan:
    """Lower a plan into the fused static schedule.

    Per basic residual block: pack conv1/conv2 (and the projection
    shortcut) at their own sublane-aligned per-channel band widths; the
    executors fit the activation between stages with elementwise lane
    slices/pads.  Blocks whose operators are factored (never materialised
    Ξ) or — on the pallas path — whose VMEM estimate exceeds
    ``vmem_budget`` stay on the per-layer walk, and so does every
    bottleneck block (the packed-GEMM fused path takes basic blocks
    only).  ``meta["routes"]`` counts the convolutions by route
    (``pointwise``, ``factored``, ``materialised``) and
    ``meta["pointwise"]`` names what runs the pointwise ones.  On a TPU
    the fused path resolves to ``"gemm"``
    (the packed-GEMM twin, ``MEGAKERNEL_OFF_TPU``); ``meta["platform"]``
    records the backend the paths were resolved on.

    ``image_size`` sizes the block grid the VMEM estimate assumes (the
    megakernel holds one image's whole feature map per grid instance);
    None falls back to the paper-canonical ``8·2^(stages-1)`` input that
    ends at a single block.  Pass the real serving resolution when it
    differs — an underestimated grid would admit Mosaic kernels that do
    not fit.
    """
    from repro.kernels import fused_block as fblib
    from repro.kernels import tiling

    spec, phi, cfg = plan.spec, plan.phi, plan.cfg
    path = dispatchlib.choose_path("fused_block", cfg)
    if path not in dispatchlib.available_paths("fused_block"):
        path = "reference"
    platform = dispatchlib.platform()
    rules: dict[str, str] = {}
    if path == "pallas" and platform == "tpu":
        path = "gemm"
        rules["megakernel"] = MEGAKERNEL_OFF_TPU
    meta: dict[str, Any] = {"fused": [], "layers": {}, "vmem": {},
                            "budget": int(vmem_budget), "path": path,
                            "platform": platform, "rules": rules,
                            "routes": _route_counts(plan)}
    if meta["routes"]["pointwise"]:
        meta["pointwise"] = ("einsum" if dispatchlib._pallas_delegates(cfg)
                             else "pointwise_conv_pallas")

    st = plan.operators["stem"]
    cout0 = st.kernel.shape[0]
    cin0 = st.kernel.shape[1]
    w0 = _r8(st.bands)
    if st.xi is not None:
        stem = CompiledStem(
            "packed",
            tiling.pack_conv(st.xi, st.shift, st.stride, w_in=w0, w_out=w0),
            tiling.pack_asm(phi, st.bands, w0),
            st, cin0, cout0, w0, w0, st.bands)
    else:
        stem = CompiledStem("layers", None, None, st, cin0, cout0,
                            dctlib.NFREQ, w0, st.bands)
        meta["layers"]["stem"] = "factored operator"

    # block grid for the VMEM estimate: one block per 8 px at the stem,
    # halving at each stride-2 stage
    if image_size is None:
        image_size = dctlib.BLOCK * 2 ** (len(spec.widths) - 1)
    _r, stem_stride, pool = resnetlib.stem_layout(spec)
    bh = max(1, image_size // dctlib.BLOCK // stem_stride // (2 if pool
                                                                else 1))
    cur_b, cur_w = stem.bands_out, stem.w_out
    blocks = []
    for layout in resnetlib._stages(spec):
        name, s, cin, w = layout.name, layout.stride, layout.cin, layout.cout
        entry = plan.operators[name]
        last = entry[layout.slots[-1].name]
        pr = entry.get("proj")
        short_b = pr.bands if pr is not None else cur_b
        j_true = max(last.bands, short_b)
        blk = None
        if spec.block != "basic":
            meta["layers"][name] = ("bottleneck block: the packed-GEMM fused "
                                    "path takes basic blocks only")
        elif all(op.xi is not None for op in entry.values()):
            c1, c2 = entry["conv1"], entry["conv2"]
            # every operand at its *own* true (sublane-rounded) band width
            # — the fused executor fits the activation between stages with
            # elementwise lane slices/pads, so a wide residual join never
            # inflates a GEMM dimension.
            w_in = cur_w
            w_j = _r8(j_true)
            w_mid = _r8(c1.bands)
            p1 = tiling.pack_conv(c1.xi, c1.shift, c1.stride,
                                  w_in=_r8(min(c1.bands, cur_b)),
                                  w_out=w_mid)
            a1 = tiling.pack_asm(phi, c1.bands, w_mid)
            p2 = tiling.pack_conv(c2.xi, c2.shift, c2.stride,
                                  w_in=_r8(min(c2.bands, c1.bands)),
                                  w_out=_r8(c2.bands))
            pp = None
            if pr is not None:
                pp = tiling.pack_conv(pr.xi, pr.shift, pr.stride,
                                      w_in=_r8(min(pr.bands, cur_b)),
                                      w_out=_r8(pr.bands))
            a2 = tiling.pack_asm(phi, j_true, w_j)
            vmem = fblib.fused_vmem_bytes(bh, bh, p1, a1, p2, a2, pp)
            meta["vmem"][name] = int(vmem)
            # The budget only gates the Mosaic kernel, whose operands must
            # be VMEM-resident per instance; the XLA reference executor
            # (also the off-TPU serving path) has no such limit.
            if path != "pallas" or vmem <= vmem_budget:
                blk = CompiledBlock("fused", name, cin, w, w_in, w_j,
                                    cur_b, j_true, path, p1, a1, p2, pp, a2,
                                    dict(entry), int(vmem))
                meta["fused"].append(name)
            else:
                meta["layers"][name] = f"vmem {vmem} > budget {vmem_budget}"
        else:
            meta["layers"][name] = "factored operator"
        if blk is None:
            blk = CompiledBlock("layers", name, cin, w, cur_w, _r8(j_true),
                                cur_b, j_true, path, ops=dict(entry))
        blocks.append(blk)
        cur_b, cur_w = blk.bands_out, blk.w_out
        bh = max(1, bh // s)
    return CompiledPlan(stem, tuple(blocks), plan.head_w, plan.head_b,
                        spec, phi, cfg, dict(plan.bands), meta)


def _route_counts(plan: InferencePlan) -> dict[str, int]:
    """Convolutions of ``plan`` by route: ``pointwise`` (the channel
    GEMM), ``factored`` (decode, spatial conv, encode) or
    ``materialised`` (a Ξ applied by the reference or Pallas path)."""
    counts = {"pointwise": 0, "factored": 0, "materialised": 0}
    for op in _flat_ops(plan).values():
        route = op.path if op.path in ("pointwise", "factored") \
            else "materialised"
        counts[route] += 1
    return counts


def _repack_width(h: jnp.ndarray, c: int, w_to: int) -> jnp.ndarray:
    """Move a packed activation between per-channel widths (block
    boundaries only — the compiler chains widths so this is rare)."""
    from repro.kernels.tiling import fit_width

    return fit_width(h, c, w_to)


def _stem_runs_gemm(stem: CompiledStem, path: str,
                    cfg: dispatchlib.DispatchConfig,
                    executor: str | None) -> bool:
    """Whether a packed stem runs its packed GEMM (else the spatial
    lowering): forced by ``executor="gemm"``, or resolved to a GEMM path."""
    return stem.kind == "packed" and (
        executor == "gemm" or path == "gemm"
        or (path == "pallas" and not dispatchlib._pallas_delegates(cfg)))


def step_executor(cp: CompiledPlan, step_name: str,
                  executor: str | None = None) -> tuple[str, str]:
    """``(kind, executor)`` of one schedule step as :func:`compiled_steps`
    runs it.  ``kind`` is ``stem`` / ``fused`` / ``layers`` / ``head``;
    ``executor`` is ``gemm`` (tile-packed GEMM over Ξ: a packed stem, or a
    fused block's twin ``fused_block_reference``), ``pallas`` (the fused
    megakernel), ``spatial`` (the spatial-resident XLA lowering),
    ``layers`` (the per-layer walk; each conv runs its operator's own
    ``path``) or ``xla`` (the head)."""
    path = (cp.meta or {}).get("path", "reference")
    if step_name == "stem":
        if cp.stem.kind != "packed":
            return "stem", "layers"
        return "stem", ("gemm" if _stem_runs_gemm(cp.stem, path, cp.cfg,
                                                  executor) else "spatial")
    if step_name == "head":
        return "head", "xla"
    blk = next(b for b in cp.blocks if b.name == step_name)
    if blk.kind != "fused":
        return "layers", "layers"
    if executor == "gemm" or blk.path == "gemm":
        return "fused", "gemm"
    if blk.path == "pallas" and not dispatchlib._pallas_delegates(cp.cfg):
        return "fused", "pallas"
    return "fused", "spatial"


def _apply_stem(stem: CompiledStem, coef: jnp.ndarray, phi: int, path: str,
                cfg: dispatchlib.DispatchConfig,
                executor: str | None = None) -> jnp.ndarray:
    from repro.kernels import fused_block as fblib
    from repro.kernels import tiling

    n, bh, bw = coef.shape[:3]
    if stem.kind == "packed":
        if _stem_runs_gemm(stem, path, cfg, executor):
            h = coef[..., : stem.w_in].reshape(n, bh, bw,
                                               stem.cin * stem.w_in)
            h = tiling.packed_conv_apply(h, stem.conv)
            return tiling.packed_asm_apply(h, stem.asm)
        return fblib.fused_stem_spatial(coef, stem.op, phi, stem.w_out)
    h = dispatchlib.apply_conv(coef, stem.op, cfg=cfg)
    h = dispatchlib.asm_relu(h, phi, cfg=cfg, bands=stem.bands_out)
    return h[..., : stem.w_out].reshape(*h.shape[:3],
                                        stem.cout * stem.w_out)


def _stem_pool(h: jnp.ndarray, stem: CompiledStem) -> jnp.ndarray:
    """The stem's max-pool on its packed output (``7x7s2-maxpool``)."""
    n, bh, bw, _ = h.shape
    x = poollib.max_pool_jpeg(h.reshape(n, bh, bw, stem.cout, stem.w_out),
                              stem.bands_out)
    return x.reshape(n, bh // 2, bw // 2, stem.cout * stem.w_out)


def _apply_layers_block(blk: CompiledBlock, layout: resnetlib.BlockLayout,
                        h: jnp.ndarray, phi: int,
                        cfg: dispatchlib.DispatchConfig) -> jnp.ndarray:
    """Per-layer fallback: unpack to the 64-wide layout, run the exact
    ``apply_plan`` block body (each convolution under its slot's name),
    repack to the scheduled output width."""
    from repro.core.conv import pad_bands

    n, bh, bw, _ = h.shape
    ops = blk.ops
    s = layout.stride
    h64 = pad_bands(h.reshape(n, bh, bw, blk.cin, blk.w_in))
    short, short_b = h64, blk.bands_in
    if "proj" in ops:
        with jax.named_scope("proj"):
            short = dispatchlib.apply_conv(h64, ops["proj"], cfg=cfg)
        short_b = ops["proj"].bands
    x = h64
    for sl in layout.slots:
        with jax.named_scope(sl.name):
            x = dispatchlib.apply_conv(x, ops[sl.name], cfg=cfg)
            if sl.relu_after:
                x = dispatchlib.asm_relu(x, phi, cfg=cfg,
                                         bands=ops[sl.name].bands)
    x = poollib.residual_add(x, short)
    x = dispatchlib.asm_relu(
        x, phi, cfg=cfg, bands=max(ops[layout.slots[-1].name].bands, short_b))
    return x[..., : blk.w_out].reshape(n, bh // s, bw // s,
                                       blk.cout * blk.w_out)


def apply_compiled(cp: CompiledPlan, coef: jnp.ndarray,
                   cfg: dispatchlib.DispatchConfig | None = None, *,
                   executor: str | None = None,
                   profile: "StepProfile | None" = None) -> jnp.ndarray:
    """Execute the compiled schedule: packed stem, then one fused (or
    fallback) step per residual block, then the DC-read head.

    Mathematically identical to :func:`apply_plan` on the source plan
    (coefficients beyond each layer's band cutoff are zero in both
    layouts); differs only in float summation order.

    ``executor=None`` honors each step's compile-time path resolution
    (the Mosaic megakernel on TPU, the spatial-resident XLA lowering
    elsewhere).  ``executor="gemm"`` forces the **transform-domain
    tile-packed GEMM lowering** (``kernels.fused_block.
    fused_block_reference`` — the megakernel's operand-identical XLA
    twin) on every fused step: unlike the spatial lowering, whose conv
    cost is independent of the band budget, its FLOPs scale with the
    packed widths — this is the executor whose latency the §6 band knob
    actually moves, hence what the band-elastic serving ladder runs
    off-TPU.

    ``profile`` (a :class:`StepProfile`) switches to the profiling
    execution mode: the identical schedule runs step by step with
    device synchronization around each step, per-step walls accumulate
    on the profile object, and the returned logits are bit-identical to
    the unprofiled walk (same step closures, same order).
    """
    cfg = cp.cfg if cfg is None else cfg
    if profile is not None:
        return _apply_profiled(cp, coef, cfg, executor, profile,
                               packed=False)
    return _fold(compiled_steps(cp, cfg, executor=executor), coef)


def apply_compiled_packed(cp: CompiledPlan, packed: jnp.ndarray,
                          cfg: dispatchlib.DispatchConfig | None = None, *,
                          executor: str | None = None,
                          profile: "StepProfile | None" = None
                          ) -> jnp.ndarray:
    """Execute the compiled schedule from a **tile-packed** stem input.

    ``packed`` is ``(N, bh, bw, Cin·w_in)`` with ``w_in =
    CompiledPlan.stem.w_in`` — the layout ``codec.ingest.ingest_batch``
    emits with ``pack_width=cp.stem.w_in``, i.e. band truncation already
    happened at ingest and the 64-wide batch was never materialised.
    Identical logits to :func:`apply_compiled` on the corresponding
    full-width batch: every stem executor reads at most ``w_in ≥
    stem.bands`` zigzag lanes per channel, so the packing drops nothing.

    ``profile`` behaves as on :func:`apply_compiled`.
    """
    cfg = cp.cfg if cfg is None else cfg
    if profile is not None:
        return _apply_profiled(cp, packed, cfg, executor, profile,
                               packed=True)
    return _fold(compiled_steps(cp, cfg, executor=executor, packed=True),
                 packed)


def capture_compiled(cp: CompiledPlan, shape, *, packed: bool = False,
                     executor: str | None = None, donate: bool = True,
                     dtype=jnp.float32, on_trace=None):
    """Capture a **static-shape** jitted entry point over the compiled
    schedule, with the input buffer donated to the executable.

    ``shape`` is the full batch shape — ``(N, bh, bw, C, 64)`` for the
    coefficient entry, ``(N, bh, bw, C·w_in)`` with ``packed=True`` for
    the tile-packed stem entry.  The returned callable traces (and
    compiles) exactly once: any call at a different shape raises
    ``ValueError`` at trace time instead of silently retracing, which is
    the invariant the serving plan grid is built on — after warmup the
    set of compiled shapes is closed.

    ``donate=True`` passes the input through ``donate_argnums`` so XLA
    may reuse its device buffer for intermediates (steady-state serving
    allocates nothing per batch beyond the staged input itself).  Both
    :func:`apply_compiled` and :func:`apply_compiled_packed` are safe
    under donation: neither aliases the input into the output, so the
    caller only loses the donated array — pass a fresh copy per call
    (``jnp.array`` of a host staging buffer).

    ``on_trace`` (no-arg callable) fires from inside the traced body —
    i.e. exactly once per compile — giving callers honest compile
    accounting without reaching into jax internals.

    The schedule's arrays enter as arguments (:func:`jit_over`), not as
    program constants; ``call.lower()`` lowers the captured entry for
    inspection (``.compile().as_text()``).
    """
    shape = tuple(int(s) for s in shape)
    apply_fn = apply_compiled_packed if packed else apply_compiled

    def fwd(cp, x):
        if tuple(x.shape) != shape:
            raise ValueError(
                f"captured executable is pinned to shape {shape}, "
                f"got {tuple(x.shape)} — route through the grid cell "
                f"for this shape instead of retracing")
        if on_trace is not None:
            on_trace()
        return apply_fn(cp, x, executor=executor)

    fn = jit_over(cp, fwd, donate_argnums=(0,) if donate else ())

    def call(x):
        if not traced:
            # donation is best-effort: when XLA finds no intermediate to
            # fold into the donated buffer it warns at lowering time —
            # harmless (the array is still consumed), and one line per
            # grid cell would drown the serving log
            import warnings

            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable")
                out = fn(jnp.asarray(x, dtype))
            traced.append(True)
            return out
        return fn(jnp.asarray(x, dtype))

    traced: list[bool] = []
    call.captured_shape = shape
    call.lower = lambda: fn.lower(jax.ShapeDtypeStruct(shape, dtype))
    return call


def jit_over(tree: Any, fn, **jit_kw):
    """``jax.jit`` of ``fn(tree, x)`` as a function of ``x`` alone, with
    ``tree``'s arrays (a plan's or schedule's weights) passed to the
    executable as arguments instead of being baked into the program as
    constants — at published widths those constants are tens of MB that
    would slow every compile and key the compile cache on the weights.
    ``donate_argnums`` counts ``x`` as argument 0.  The returned callable
    has ``.lower(x)``."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    idx = [i for i, leaf in enumerate(leaves)
           if isinstance(leaf, (jax.Array, np.ndarray))]
    arrays = [leaves[i] for i in idx]

    def inner(x, arrs):
        full = list(leaves)
        for i, a in zip(idx, arrs):
            full[i] = a
        return fn(jax.tree_util.tree_unflatten(treedef, full), x)

    jitted = jax.jit(inner, **jit_kw)

    def call(x):
        return jitted(x, arrays)

    call.lower = lambda x: jitted.lower(x, arrays)
    return call


def _make_block_fn(blk: CompiledBlock, layout: resnetlib.BlockLayout,
                   w_prev: int, phi: int, cfg: dispatchlib.DispatchConfig,
                   executor: str | None):
    """One schedule step: (optional width repack into the block, then)
    the fused/fallback block body, then the batch-axis shard hint."""
    from repro.kernels import fused_block as fblib

    def fn(h):
        if blk.w_in != w_prev:
            h = _repack_width(h, blk.cin, blk.w_in)
        if blk.kind == "fused":
            if executor == "gemm" or blk.path == "gemm":
                h = fblib.fused_block_reference(h, blk.conv1, blk.asm_mid,
                                                blk.conv2, blk.asm_out,
                                                blk.proj)
            else:
                h = dispatchlib.fused_block(h, blk, phi, path=blk.path,
                                            cfg=cfg)
        else:
            h = _apply_layers_block(blk, layout, h, phi, cfg)
        return shard(h, "batch", None, None, None)

    return fn


def _make_head_fn(cp: CompiledPlan, w: int):
    def fn(h):
        dc = h[..., 0::w]  # per-channel DC lanes of the packed layout
        pooled = jnp.mean(dc, axis=(1, 2)) / bnlib.DC_GAIN
        return pooled @ cp.head_w + cp.head_b

    return fn


def _named(name: str, fn):
    """``fn`` traced under ``jax.named_scope(name)``: every HLO
    instruction the step issues carries ``name`` in its ``op_name``
    metadata (``jit(inner)/s2b0/...``), which is how a device profile's
    ops map back to schedule steps.  Metadata only: the compiled program
    is otherwise the same."""

    def step(h):
        with jax.named_scope(name):
            return fn(h)

    return step


def _fold(steps, x: jnp.ndarray) -> jnp.ndarray:
    for _name, fn in steps:
        x = fn(x)
    return x


def compiled_steps(cp: CompiledPlan,
                   cfg: dispatchlib.DispatchConfig | None = None, *,
                   executor: str | None = None, packed: bool = False):
    """The full compiled schedule as an explicit ``(name, fn)`` step
    list: ``stem`` (coefficients — or the tile-packed stem layout with
    ``packed=True`` — to packed activations), one step per residual
    block, and ``head`` (packed activations to logits).

    Folding the list is exactly :func:`apply_compiled` /
    :func:`apply_compiled_packed` — the steps are the *same closures*
    the whole-schedule walk executes, so per-step introspection (HLO
    attribution, profiled timing) observes the production schedule, not
    a re-implementation of it.  Each step runs under
    ``jax.named_scope(<name>)`` (:func:`_named`), so the served program
    and the per-step walk carry the same step names in their metadata.
    """
    cfg = cp.cfg if cfg is None else cfg
    path = (cp.meta or {}).get("path", "reference")
    st = cp.stem
    pool = resnetlib.stem_layout(cp.spec)[2]

    def stem_fn(x):
        if packed:
            n, bh, bw, k = x.shape
            if k != st.cin * st.w_in:
                raise ValueError(
                    f"packed input has per-channel width {k / st.cin:g}, "
                    f"stem expects w_in={st.w_in} (cin={st.cin})")
            if _stem_runs_gemm(st, path, cfg, executor):
                from repro.kernels import tiling

                h = tiling.packed_conv_apply(x, st.conv)
                h = tiling.packed_asm_apply(h, st.asm)
            else:
                # the spatial / per-layer stem executors consume the
                # 64-wide layout; unpacking is an elementwise zero-pad
                # (exact — lanes beyond w_in ≥ stem.bands are dropped by
                # the stem conv anyway)
                from repro.core.conv import pad_bands

                coef = pad_bands(x.reshape(n, bh, bw, st.cin, st.w_in))
                h = _apply_stem(st, coef, cp.phi, path, cfg, executor)
        else:
            h = _apply_stem(st, x, cp.phi, path, cfg, executor)
        if pool:
            with jax.named_scope("maxpool"):
                h = _stem_pool(h, st)
        return shard(h, "batch", None, None, None)

    layouts = {b.name: b for b in resnetlib._stages(cp.spec)}
    steps = [("stem", stem_fn)]
    cur_w = st.w_out
    for blk in cp.blocks:
        steps.append((blk.name, _make_block_fn(blk, layouts[blk.name], cur_w,
                                               cp.phi, cfg, executor)))
        cur_w = blk.w_out
    steps.append(("head", _make_head_fn(cp, cur_w)))
    return [(name, _named(name, fn)) for name, fn in steps]


class StepProfile:
    """Collector for per-step device walls of a profiled compiled run.

    Pass an instance as ``apply_compiled(..., profile=prof)`` (or the
    packed twin): the schedule executes step by step — each step jitted
    on its own, with ``jax.block_until_ready`` fencing both sides of the
    wall — and one sample per step is appended per call.  Logits are
    produced by the same step closures the unprofiled walk folds, so
    the profiled output is bit-identical to the unprofiled one.

    The first call through a given ``(plan, executor, packing)`` pays
    per-step compilation inside the recorded walls; call once to warm,
    then :meth:`reset` (keeps the jitted steps, drops the samples)
    before the measuring calls.  :meth:`summary` reduces samples to
    per-step medians.
    """

    def __init__(self) -> None:
        self.order: list[str] = []
        self.samples: dict[str, list[float]] = {}
        self.calls = 0
        self._fns: dict[tuple, list] = {}

    def steps_for(self, cp: CompiledPlan,
                  cfg: dispatchlib.DispatchConfig,
                  executor: str | None, packed: bool):
        key = (id(cp), id(cfg), executor, bool(packed))
        fns = self._fns.get(key)
        if fns is None:
            fns = [(name, jax.jit(fn)) for name, fn in
                   compiled_steps(cp, cfg, executor=executor, packed=packed)]
            self._fns[key] = fns
        return fns

    def record(self, name: str, seconds: float) -> None:
        if name not in self.samples:
            self.order.append(name)
            self.samples[name] = []
        self.samples[name].append(seconds)

    def reset(self) -> None:
        """Drop recorded samples; keep the compiled per-step entries."""
        self.order.clear()
        self.samples.clear()
        self.calls = 0

    def summary(self) -> dict[str, float]:
        """Per-step median wall (seconds), in schedule order."""
        import statistics

        return {name: statistics.median(self.samples[name])
                for name in self.order}

    def total_s(self) -> float:
        return sum(self.summary().values())


def _apply_profiled(cp: CompiledPlan, x: jnp.ndarray,
                    cfg: dispatchlib.DispatchConfig,
                    executor: str | None, profile: StepProfile,
                    packed: bool) -> jnp.ndarray:
    import time

    h = jnp.asarray(x)
    jax.block_until_ready(h)
    for name, fn in profile.steps_for(cp, cfg, executor, packed):
        t0 = time.perf_counter()
        h = fn(h)
        jax.block_until_ready(h)
        profile.record(name, time.perf_counter() - t0)
    profile.calls += 1
    return h


# --------------------------------------------------------------------------
# Serialization through the checkpoint manager
# --------------------------------------------------------------------------

_OP_ARRAYS = ("xi", "kernel", "scale", "shift", "bn_scale")
_OP_STATIC = ("stride", "bands", "quality", "in_scaled", "out_scaled", "path")
# format 2: operators additionally carry ``bn_scale`` (the retained BN fold
# compile_plan re-lowers from) — format-1 artifacts predate compiled plans.
_PLAN_FORMAT = 2


def _flat_ops(plan: InferencePlan) -> dict[str, dispatchlib.ConvOperator]:
    out = {}
    for name, entry in plan.operators.items():
        if isinstance(entry, dict):
            out.update({f"{name}/{slot}": op for slot, op in entry.items()})
        else:
            out[name] = entry
    return out


def _leaf_path(key: str) -> str:
    """The path string CheckpointManager records for flat-dict key ``key``
    (derived through jax itself so renames in DictKey.__str__ can't skew
    the format)."""
    (path, _), = jax.tree_util.tree_flatten_with_path({key: 0})[0]
    return "/".join(str(p) for p in path)


def _op_save(key: str, op: dispatchlib.ConvOperator,
             arrays: dict[str, np.ndarray]) -> dict[str, Any]:
    meta: dict[str, Any] = {f: getattr(op, f) for f in _OP_STATIC}
    for f in _OP_ARRAYS:
        val = getattr(op, f)
        meta[f"has_{f}"] = val is not None
        if val is not None:
            arrays[f"{key}.{f}"] = np.asarray(val)
    return meta


def _op_load(key: str, meta: dict[str, Any],
             arr: Any) -> dispatchlib.ConvOperator:
    fields = {f: meta[f] for f in _OP_STATIC}
    for f in _OP_ARRAYS:
        fields[f] = arr(f"{key}.{f}") if meta[f"has_{f}"] else None
    return dispatchlib.ConvOperator(**fields)


def save_plan(plan: InferencePlan, directory: str, step: int = 0,
              keep: int = 3) -> None:
    """Persist a plan: arrays through the checksummed/atomic checkpoint
    store, static structure in the manifest ``extra`` JSON."""
    from repro.checkpoint import CheckpointManager

    arrays: dict[str, np.ndarray] = {"head.w": np.asarray(plan.head_w),
                                     "head.b": np.asarray(plan.head_b)}
    meta_ops: dict[str, dict[str, Any]] = {}
    for key, op in _flat_ops(plan).items():
        meta_ops[key] = _op_save(key, op, arrays)
    extra = {
        "kind": "jpeg_inference_plan",
        "format": _PLAN_FORMAT,
        "spec": dict(plan.spec._asdict(), widths=list(plan.spec.widths)),
        "phi": plan.phi,
        "cfg": dataclasses.asdict(plan.cfg),
        "bands": plan.bands,
        "provenance": plan.provenance,
        "ops": meta_ops,
    }
    CheckpointManager(directory, keep=keep).save(step, arrays, extra=extra)


def load_plan(directory: str, step: int | None = None) -> InferencePlan:
    """Restore an :class:`InferencePlan` saved by :func:`save_plan`.

    Bit-exact: restored logits equal the pre-save plan's (tests assert
    array equality across all three dispatch paths).
    """
    from repro.checkpoint import CheckpointManager

    _, by_path, extra = CheckpointManager(directory).restore_tree(step)
    if extra.get("kind") != "jpeg_inference_plan":
        raise ValueError(f"{directory} does not hold an inference plan")
    if extra.get("format") != _PLAN_FORMAT:
        raise ValueError(f"unsupported plan format {extra.get('format')!r}")

    def arr(key):
        return jnp.asarray(by_path[_leaf_path(key)])

    spec = resnetlib.spec_from_dict(extra["spec"])
    cfg = dispatchlib.DispatchConfig(**extra["cfg"])
    operators: dict[str, Any] = {}
    for key, meta in extra["ops"].items():
        op = _op_load(key, meta, arr)
        if "/" in key:
            name, slot = key.split("/", 1)
            operators.setdefault(name, {})[slot] = op
        else:
            operators[key] = op
    return InferencePlan(operators, arr("head.w"), arr("head.b"), spec,
                         int(extra["phi"]), cfg,
                         {k: int(v) for k, v in extra["bands"].items()},
                         extra.get("provenance"))


# --------------------------------------------------------------------------
# Compiled-schedule serialization (packed-operator pytree)
# --------------------------------------------------------------------------

_COMPILED_FORMAT = 1
_PC_STATIC = ("stride", "ndy", "ndx", "cin", "w_in", "cout", "w_out")
_PA_STATIC = ("w", "bands", "phi")


def save_compiled_plan(cp: CompiledPlan, directory: str, step: int = 0,
                       keep: int = 3) -> None:
    """Persist a compiled schedule: the packed buffers go through the
    checksummed array store, the static schedule into ``extra`` — a
    restore re-serves the exact buffers (bit-identical logits) with no
    recompile."""
    from repro.checkpoint import CheckpointManager

    arrays: dict[str, np.ndarray] = {"head.w": np.asarray(cp.head_w),
                                     "head.b": np.asarray(cp.head_b)}

    def pc_save(prefix, pc):
        arrays[f"{prefix}.xi"] = np.asarray(pc.xi)
        arrays[f"{prefix}.shift"] = np.asarray(pc.shift)
        return {f: int(getattr(pc, f)) for f in _PC_STATIC}

    def pa_save(prefix, pa):
        arrays[f"{prefix}.cat"] = np.asarray(pa.cat)
        arrays[f"{prefix}.recon_t"] = np.asarray(pa.recon_t)
        return {f: int(getattr(pa, f)) for f in _PA_STATIC}

    stem = cp.stem
    stem_meta: dict[str, Any] = {
        "kind": stem.kind, "cin": stem.cin, "cout": stem.cout,
        "w_in": stem.w_in, "w_out": stem.w_out, "bands_out": stem.bands_out}
    stem_meta["op"] = _op_save("stem.op", stem.op, arrays)
    if stem.kind == "packed":
        stem_meta["conv"] = pc_save("stem.conv", stem.conv)
        stem_meta["asm"] = pa_save("stem.asm", stem.asm)
    blocks_meta = []
    for blk in cp.blocks:
        m: dict[str, Any] = {
            "kind": blk.kind, "name": blk.name, "cin": blk.cin,
            "cout": blk.cout, "w_in": blk.w_in, "w_out": blk.w_out,
            "bands_in": blk.bands_in, "bands_out": blk.bands_out,
            "path": blk.path, "vmem_bytes": blk.vmem_bytes}
        m["ops"] = {slot: _op_save(f"{blk.name}.ops.{slot}", op, arrays)
                    for slot, op in blk.ops.items()}
        if blk.kind == "fused":
            m["conv1"] = pc_save(f"{blk.name}.conv1", blk.conv1)
            m["asm_mid"] = pa_save(f"{blk.name}.asm_mid", blk.asm_mid)
            m["conv2"] = pc_save(f"{blk.name}.conv2", blk.conv2)
            if blk.proj is not None:
                m["proj"] = pc_save(f"{blk.name}.proj", blk.proj)
            m["asm_out"] = pa_save(f"{blk.name}.asm_out", blk.asm_out)
        blocks_meta.append(m)
    extra = {
        "kind": "jpeg_compiled_plan",
        "format": _COMPILED_FORMAT,
        "spec": dict(cp.spec._asdict(), widths=list(cp.spec.widths)),
        "phi": cp.phi,
        "cfg": dataclasses.asdict(cp.cfg),
        "bands": cp.bands,
        "meta": cp.meta,
        "stem": stem_meta,
        "blocks": blocks_meta,
    }
    CheckpointManager(directory, keep=keep).save(step, arrays, extra=extra)


def load_compiled_plan(directory: str, step: int | None = None
                       ) -> CompiledPlan:
    """Restore a :class:`CompiledPlan` saved by :func:`save_compiled_plan`
    (bit-exact: the packed buffers round-trip through the array store)."""
    from repro.checkpoint import CheckpointManager
    from repro.kernels.tiling import PackedAsm, PackedConv

    _, by_path, extra = CheckpointManager(directory).restore_tree(step)
    if extra.get("kind") != "jpeg_compiled_plan":
        raise ValueError(f"{directory} does not hold a compiled plan")
    if extra.get("format") != _COMPILED_FORMAT:
        raise ValueError(
            f"unsupported compiled-plan format {extra.get('format')!r}")

    def arr(key):
        return jnp.asarray(by_path[_leaf_path(key)])

    def pc_load(prefix, meta):
        return PackedConv(arr(f"{prefix}.xi"), arr(f"{prefix}.shift"),
                          **{f: int(meta[f]) for f in _PC_STATIC})

    def pa_load(prefix, meta):
        return PackedAsm(arr(f"{prefix}.cat"), arr(f"{prefix}.recon_t"),
                         **{f: int(meta[f]) for f in _PA_STATIC})

    sm = extra["stem"]
    stem_op = _op_load("stem.op", sm["op"], arr)
    if sm["kind"] == "packed":
        stem = CompiledStem("packed", pc_load("stem.conv", sm["conv"]),
                            pa_load("stem.asm", sm["asm"]), stem_op,
                            int(sm["cin"]), int(sm["cout"]),
                            int(sm["w_in"]), int(sm["w_out"]),
                            int(sm["bands_out"]))
    else:
        stem = CompiledStem("layers", None, None, stem_op,
                            int(sm["cin"]), int(sm["cout"]),
                            int(sm["w_in"]), int(sm["w_out"]),
                            int(sm["bands_out"]))
    blocks = []
    for m in extra["blocks"]:
        common = (m["kind"], m["name"], int(m["cin"]), int(m["cout"]),
                  int(m["w_in"]), int(m["w_out"]), int(m["bands_in"]),
                  int(m["bands_out"]), m["path"])
        ops = {slot: _op_load(f"{m['name']}.ops.{slot}", om, arr)
               for slot, om in m["ops"].items()}
        if m["kind"] == "fused":
            name = m["name"]
            proj = pc_load(f"{name}.proj", m["proj"]) if "proj" in m else None
            blocks.append(CompiledBlock(
                *common, pc_load(f"{name}.conv1", m["conv1"]),
                pa_load(f"{name}.asm_mid", m["asm_mid"]),
                pc_load(f"{name}.conv2", m["conv2"]), proj,
                pa_load(f"{name}.asm_out", m["asm_out"]), ops,
                int(m["vmem_bytes"])))
        else:
            blocks.append(CompiledBlock(*common, ops=ops))
    return CompiledPlan(stem, tuple(blocks), arr("head.w"), arr("head.b"),
                        resnetlib.spec_from_dict(extra["spec"]),
                        int(extra["phi"]),
                        dispatchlib.DispatchConfig(**extra["cfg"]),
                        {k: int(v) for k, v in extra["bands"].items()},
                        extra.get("meta"))
