"""Residual networks in the spatial and JPEG transform domains (paper §4).

One parameter pytree drives *two* mathematically-equivalent apply functions:

* :func:`spatial_apply` — ordinary NCHW ResNet (the oracle / source model);
* :func:`jpeg_apply` — the same network evaluated entirely on JPEG
  coefficients: exploded convolutions (§4.1), ASM ReLU (§4.2), coefficient
  batch-norm (§4.3), free residual adds (§4.4), DC-read global pooling
  (§4.5).

Model conversion (§4.6) is therefore *structural*: a spatial checkpoint is a
JPEG checkpoint.  ``precompute_operators`` bakes the exploded Ξ operators
for inference so each step is matmuls only (the paper's "the map can be
precomputed to speed up inference").

Architecture (paper Fig. 3, generalised): a stem, then ``len(widths)``
stages of ``blocks_per_stage`` residual blocks; every stage after the
first downsamples by 2 so a 32×32 input with 3 stages ends at a single JPEG
block; global average pool; linear classifier.  Two block kinds:

* ``"basic"`` (the paper's): 3×3/s → ReLU → 3×3, ReLU after the add;
* ``"bottleneck"`` (ResNet-50 v1.5, arXiv:1512.03385 Table 1 with the
  stride on the 3×3 as torchvision's ``resnet50``): 1×1 → ReLU → 3×3/s →
  ReLU → 1×1 to ``4·width`` channels, ReLU after the add; the projection
  shortcut carries a batch norm.

and two stems: ``"3x3"`` (the paper's 3×3 stride-1 convolution) and
``"7x7s2-maxpool"`` (ResNet's 7×7 stride-2 convolution, then a 3×3/2
max-pool — not in the paper).  :func:`_stages` describes every block as
an ordered list of convolution slots; the parameter pytree, the spatial
oracle and the plan code (``core.plan``) all walk those slots.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import asm as asmlib
from repro.core import batchnorm as bnlib
from repro.core import conv as convlib
from repro.core import dispatch as dispatchlib
from repro.core import jpeg as jpeglib
from repro.core import pooling as poollib
from repro.parallel.sharding import shard

__all__ = [
    "ResNetSpec",
    "init_resnet",
    "spatial_apply",
    "jpeg_apply",
    "precompute_operators",
    "jpeg_apply_precomputed",
    "compile_for_inference",
]


BLOCKS = ("basic", "bottleneck")
STEMS = ("3x3", "7x7s2-maxpool")
#: output channels of a bottleneck block per unit of its inner width
EXPANSION = 4


class ResNetSpec(NamedTuple):
    in_channels: int = 3
    widths: tuple[int, ...] = (16, 32, 64)
    #: one count for every stage, or one count per stage
    blocks_per_stage: int | tuple[int, ...] = 1
    num_classes: int = 10
    quality: int = 50  # quantization table the input coefficients use
    phi: int = asmlib.EXACT_PHI  # ASM ReLU spatial frequencies
    block: str = "basic"
    stem: str = "3x3"


class ConvSlot(NamedTuple):
    """One convolution of a block, in forward order.  ``relu_after``: an
    ASM ReLU follows it directly (the last slot's ReLU follows the
    residual add instead); ``bn``: the suffix of its batch norm's
    parameter name (``<block><bn>``), None where it has none."""

    name: str
    kernel: int
    stride: int
    cin: int
    cout: int
    relu_after: bool
    bn: str | None


class BlockLayout(NamedTuple):
    """One residual block: its convolution ``slots`` in order and the
    ``proj`` shortcut (a 1×1 convolution at the block's stride) where the
    shape changes, else None."""

    name: str
    stride: int
    cin: int
    cout: int
    slots: tuple[ConvSlot, ...]
    proj: ConvSlot | None


def stage_counts(spec: ResNetSpec) -> tuple[int, ...]:
    """Blocks in each stage."""
    bps = spec.blocks_per_stage
    if isinstance(bps, int):
        return (bps,) * len(spec.widths)
    if len(bps) != len(spec.widths):
        raise ValueError(f"blocks_per_stage {tuple(bps)} does not give one "
                         f"count per stage of widths {spec.widths}")
    return tuple(int(b) for b in bps)


def stem_layout(spec: ResNetSpec) -> tuple[int, int, bool]:
    """``(kernel, stride, max_pool)`` of the stem."""
    if spec.stem == "3x3":
        return 3, 1, False
    if spec.stem == "7x7s2-maxpool":
        return 7, 2, True
    raise ValueError(f"unknown stem {spec.stem!r} (have {STEMS})")


def spec_from_dict(d: dict) -> ResNetSpec:
    """A spec from its ``_asdict()`` after a JSON round trip (lists back
    to tuples)."""
    d = dict(d, widths=tuple(d["widths"]))
    if isinstance(d.get("blocks_per_stage"), list):
        d["blocks_per_stage"] = tuple(d["blocks_per_stage"])
    return ResNetSpec(**d)


def _require_basic(spec: ResNetSpec, route: str) -> None:
    """The unserved routes implement the paper's network only; they must
    not compute something else for any other."""
    if spec.block != "basic" or spec.stem != "3x3":
        raise NotImplementedError(
            f"{route} runs basic blocks with the 3x3 stem only; serve a "
            f"{spec.block!r} / {spec.stem!r} spec through plan.build_plan "
            f"-> plan.compile_plan")


def _conv_init(key, cout, cin, r, dtype):
    fan_in = cin * r * r
    return jax.random.normal(key, (cout, cin, r, r), dtype) * np.sqrt(2.0 / fan_in)


def init_resnet(key: jax.Array, spec: ResNetSpec, dtype=jnp.float32):
    """Returns ``(params, state)`` pytrees shared by both domains."""
    keys = iter(jax.random.split(key, 4 + 4 * sum(stage_counts(spec))))
    params: dict[str, Any] = {}
    state: dict[str, Any] = {}

    def bn(name, c):
        p, s = bnlib.init_batchnorm(c, dtype)
        params[name] = {"gamma": p.gamma, "beta": p.beta}
        state[name] = {"mean": s.running_mean, "var": s.running_var}

    def conv(slot):
        return _conv_init(next(keys), slot.cout, slot.cin, slot.kernel, dtype)

    r = stem_layout(spec)[0]
    params["stem"] = {"kernel": _conv_init(next(keys), spec.widths[0], spec.in_channels, r, dtype)}
    bn("stem_bn", spec.widths[0])
    cin = spec.widths[0]
    for blk in _stages(spec):
        params[blk.name] = {sl.name: conv(sl) for sl in blk.slots}
        for sl in blk.slots:
            bn(blk.name + sl.bn, sl.cout)
        if blk.proj is not None:
            params[blk.name]["proj"] = conv(blk.proj)
            if blk.proj.bn is not None:
                bn(blk.name + blk.proj.bn, blk.cout)
        cin = blk.cout
    params["head"] = {
        "w": jax.random.normal(next(keys), (cin, spec.num_classes), dtype)
        * np.sqrt(1.0 / cin),
        "b": jnp.zeros((spec.num_classes,), dtype),
    }
    return params, state


def _stages(spec: ResNetSpec):
    """Every residual block as a :class:`BlockLayout`, in forward order."""
    if spec.block not in BLOCKS:
        raise ValueError(f"unknown block {spec.block!r} (have {BLOCKS})")
    cin = spec.widths[0]
    for si, (w, count) in enumerate(zip(spec.widths, stage_counts(spec))):
        for bi in range(count):
            s = 2 if si and not bi else 1
            if spec.block == "basic":
                cout, proj_bn = w, None
                slots = (ConvSlot("conv1", 3, s, cin, w, True, "_bn1"),
                         ConvSlot("conv2", 3, 1, w, w, False, "_bn2"))
            else:
                cout, proj_bn = EXPANSION * w, "_bnp"
                slots = (ConvSlot("conv1", 1, 1, cin, w, True, "_bn1"),
                         ConvSlot("conv2", 3, s, w, w, True, "_bn2"),
                         ConvSlot("conv3", 1, 1, w, cout, False, "_bn3"))
            proj = (ConvSlot("proj", 1, s, cin, cout, False, proj_bn)
                    if s != 1 or cin != cout else None)
            yield BlockLayout(f"s{si}b{bi}", s, cin, cout, slots, proj)
            cin = cout


# --------------------------------------------------------------------------
# Spatial-domain apply (oracle)
# --------------------------------------------------------------------------


def spatial_apply(params, state, x, *, training: bool, spec: ResNetSpec):
    """``x``: (N, C, H, W) pixels -> (logits, new_state)."""
    new_state = {}

    def bn(name, h):
        p = bnlib.BatchNormParams(params[name]["gamma"], params[name]["beta"])
        s = bnlib.BatchNormState(state[name]["mean"], state[name]["var"])
        h, s2 = bnlib.batchnorm_spatial(h, p, s, training=training)
        new_state[name] = {"mean": s2.running_mean, "var": s2.running_var}
        return h

    _r, stem_stride, pool = stem_layout(spec)
    h = convlib.spatial_conv(x, params["stem"]["kernel"], stem_stride)
    h = jax.nn.relu(bn("stem_bn", h))
    if pool:
        h = poollib.max_pool_spatial(h)
    for blk in _stages(spec):
        p = params[blk.name]
        short = h
        if blk.proj is not None:
            short = convlib.spatial_conv(h, p["proj"], blk.stride)
            if blk.proj.bn is not None:
                short = bn(blk.name + blk.proj.bn, short)
        for sl in blk.slots:
            h = bn(blk.name + sl.bn, convlib.spatial_conv(h, p[sl.name],
                                                          sl.stride))
            if sl.relu_after:
                h = jax.nn.relu(h)
        h = jax.nn.relu(h + short)
    pooled = poollib.global_avg_pool_spatial(h)
    logits = pooled @ params["head"]["w"] + params["head"]["b"]
    return logits, new_state


# --------------------------------------------------------------------------
# JPEG-domain apply (the paper's network)
# --------------------------------------------------------------------------


def jpeg_apply(params, state, coef, *, training: bool, spec: ResNetSpec,
               phi: int | None = None, remat: bool = False,
               dispatch: dispatchlib.DispatchConfig | None = None):
    """``coef``: (N, bh, bw, C, 64) step-4 JPEG coefficients -> logits.

    Input coefficients are quantization-scaled (true JPEG); the stem conv
    folds de-quantization (Eq. 20 collapsed across the network); all
    internal activations use the orthonormal-DCT convention.

    ``remat``: checkpoint each residual block (recompute the ASM/conv
    intermediates in backward — they are several× the activation size).

    ``dispatch``: per-op backend/band policy (None = the global config,
    see ``core.dispatch``).  Resolved at trace time.
    """
    _require_basic(spec, "jpeg_apply")
    phi = spec.phi if phi is None else phi
    cfg = dispatchlib.resolve_config(dispatch)
    new_state = {}

    def bn_apply(pdict, sdict, h):
        p = bnlib.BatchNormParams(pdict["gamma"], pdict["beta"])
        s = bnlib.BatchNormState(sdict["mean"], sdict["var"])
        return dispatchlib.batchnorm(h, p, s, training=training, cfg=cfg)

    def bn(name, h):
        h, s2 = bn_apply(params[name], state[name], h)
        new_state[name] = {"mean": s2.running_mean, "var": s2.running_var}
        return h

    def relu(h):
        return dispatchlib.asm_relu(h, phi, cfg=cfg)

    h = dispatchlib.conv(coef, params["stem"]["kernel"], 1,
                         in_scaled=True, quality=spec.quality, cfg=cfg)
    h = relu(bn("stem_bn", h))
    h = shard(h, "batch", None, None, None, None)
    for layout in _stages(spec):
        name, s = layout.name, layout.stride

        def block_fn(h, blk, bn1p, bn1s, bn2p, bn2s):
            short = h
            if "proj" in blk:
                short = dispatchlib.conv(h, blk["proj"], s, cfg=cfg)
            h = dispatchlib.conv(h, blk["conv1"], s, cfg=cfg)
            h1, st1 = bn_apply(bn1p, bn1s, h)
            h = relu(h1)
            h = dispatchlib.conv(h, blk["conv2"], 1, cfg=cfg)
            h2, st2 = bn_apply(bn2p, bn2s, h)
            h = relu(poollib.residual_add(h2, short))
            h = shard(h, "batch", None, None, None, None)
            return h, st1, st2

        if remat:
            block_fn = jax.checkpoint(block_fn)
        h, st1, st2 = block_fn(h, params[name], params[name + "_bn1"],
                               state[name + "_bn1"], params[name + "_bn2"],
                               state[name + "_bn2"])
        new_state[name + "_bn1"] = {"mean": st1.running_mean, "var": st1.running_var}
        new_state[name + "_bn2"] = {"mean": st2.running_mean, "var": st2.running_var}
    pooled = poollib.global_avg_pool_jpeg(h)
    logits = pooled @ params["head"]["w"] + params["head"]["b"]
    return logits, new_state


# --------------------------------------------------------------------------
# Precomputed-operator inference (paper §4.1: "can be precomputed")
# --------------------------------------------------------------------------


def precompute_operators(params, spec: ResNetSpec,
                         dispatch: dispatchlib.DispatchConfig | None = None):
    """Explode every convolution once; returns an operator pytree.

    Thin wrapper over :func:`repro.core.plan.build_operators` (the
    convert-once engine) — unfused, so batch norm still runs per step from
    the live ``state``.  Each leaf is a
    :class:`repro.core.dispatch.ConvOperator` whose apply path (reference /
    pallas / factored) and band truncation were resolved at precompute time
    from ``dispatch`` (None = global config).  For fused-BN, per-layer-band
    serving build an :class:`repro.core.plan.InferencePlan` instead.
    """
    from repro.core import plan as planlib

    return planlib.build_operators(params, spec,
                                   dispatchlib.resolve_config(dispatch))


def jpeg_apply_precomputed(params, state, ops, coef, *, spec: ResNetSpec,
                           phi: int | None = None,
                           dispatch: dispatchlib.DispatchConfig | None = None):
    """Inference-only apply using precomputed exploded operators.

    Thin wrapper over :func:`repro.core.plan.apply_operators` — the
    per-step-batchnorm walk, kept as the parity/perf baseline against the
    fused :func:`repro.core.plan.apply_plan`.
    """
    from repro.core import plan as planlib

    _require_basic(spec, "jpeg_apply_precomputed")
    return planlib.apply_operators(params, state, ops, coef, spec=spec,
                                   phi=phi, cfg=dispatch)


def compile_for_inference(params, state, spec: ResNetSpec, *,
                          dispatch: dispatchlib.DispatchConfig | None = None,
                          bands=None, probe_coef=None, **compile_kw):
    """One call from trained parameters to the compiled serving schedule:
    ``plan.build_plan`` (fused BN, per-layer bands) followed by
    ``plan.compile_plan`` (fused residual-block megakernels over
    tile-packed operators).  Returns the :class:`repro.core.plan.
    CompiledPlan`; close over it in a jitted lambda to serve."""
    from repro.core import plan as planlib

    plan = planlib.build_plan(params, state, spec, dispatch=dispatch,
                              bands=bands, probe_coef=probe_coef)
    return planlib.compile_plan(plan, **compile_kw)
