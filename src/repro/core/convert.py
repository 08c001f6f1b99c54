"""Model conversion: spatial-domain checkpoints → JPEG-domain networks (§4.6).

Because ``repro.core.resnet`` evaluates both domains from one parameter
pytree, conversion is the identity on parameters plus a *verification*
contract: at φ = 14 (exact ReLU) the two networks must agree to float
error (paper Table 1).  ``convert_and_verify`` enforces that contract and
returns the precomputed-operator bundle for fast inference.

For models trained elsewhere, ``from_torch_layout`` maps common layouts
(OIHW conv kernels, BN (γ, β, μ, σ²)) into our pytree.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import asm as asmlib
from repro.core import jpeg as jpeglib
from repro.core import resnet as resnetlib

__all__ = ["ConvertedModel", "convert", "convert_and_verify", "from_torch_layout"]


class ConvertedModel(NamedTuple):
    """``operators`` mirrors ``plan.operators`` when a plan is carried —
    i.e. BN-*fused*; feeding them to ``jpeg_apply_precomputed`` raises
    (it would apply batch norm twice).  Convert with ``fuse_bn=False``
    for unfused per-step-batchnorm operators."""

    params: Any
    state: Any
    operators: Any
    spec: resnetlib.ResNetSpec
    phi: int
    dispatch: Any = None  # DispatchConfig resolved at convert time
    plan: Any = None      # InferencePlan (fused BN) when converted with one

    def __call__(self, coef: jnp.ndarray) -> jnp.ndarray:
        if self.plan is not None:
            from repro.core import plan as planlib

            return planlib.apply_plan(self.plan, coef)
        return resnetlib.jpeg_apply_precomputed(
            self.params, self.state, self.operators, coef,
            spec=self.spec, phi=self.phi, dispatch=self.dispatch,
        )


def convert(params, state, spec: resnetlib.ResNetSpec,
            phi: int = asmlib.EXACT_PHI,
            dispatch=None, *, fuse_bn: bool = True, bands=None,
            probe_coef=None) -> ConvertedModel:
    """Convert a (trained) spatial model for JPEG-domain inference.

    ``dispatch``: a ``core.dispatch.DispatchConfig`` resolving the apply
    path and band truncation of every precomputed operator (None = the
    global config *frozen here*, so later env/config changes cannot skew
    an already-converted model's ASM/batchnorm away from its operators).

    By default the result carries an :class:`repro.core.plan.InferencePlan`
    — inference-mode batch norm fused into the operators at convert time —
    and ``__call__`` serves from it.  ``fuse_bn=False`` keeps the PR-1
    behaviour (unfused operators, per-step batch norm).  ``bands`` is
    forwarded to :func:`repro.core.plan.build_plan` (``"auto"`` autotunes
    per layer from the quantization table; ``probe_coef`` enables the
    parity sweep).
    """
    from repro.core import dispatch as dispatchlib
    from repro.core import plan as planlib

    cfg = dispatchlib.resolve_config(dispatch)
    if not fuse_bn:
        ops = resnetlib.precompute_operators(params, spec, dispatch=cfg)
        return ConvertedModel(params, state, ops, spec, phi, cfg)
    plan = planlib.build_plan(params, state, spec, phi=phi, dispatch=cfg,
                              bands=bands, probe_coef=probe_coef)
    return ConvertedModel(params, state, plan.operators, spec, phi, cfg, plan)


def convert_and_verify(
    params, state, spec: resnetlib.ResNetSpec, sample_images: jnp.ndarray,
    phi: int = asmlib.EXACT_PHI, atol: float = 1e-4,
) -> tuple[ConvertedModel, float]:
    """Convert + assert spatial/JPEG logit agreement on sample images.

    ``sample_images``: (N, C, H, W) pixels.  Returns (model, max_abs_dev).
    At φ = 14 the deviation is float-accumulation only (paper Table 1:
    ~1e-6 in accuracy).
    """
    model = convert(params, state, spec, phi)
    logits_sp, _ = resnetlib.spatial_apply(
        params, state, sample_images, training=False, spec=spec
    )
    coef = jpeglib.jpeg_encode(sample_images, quality=spec.quality, scaled=True)
    coef = jnp.moveaxis(coef, 1, 3)  # (N, bh, bw, C, 64)
    logits_jp = model(coef)
    dev = float(jnp.max(jnp.abs(logits_sp - logits_jp)))
    if phi >= asmlib.EXACT_PHI and dev > atol:
        raise ValueError(
            f"conversion verification failed: max logit deviation {dev} > {atol}"
        )
    return model, dev


def from_torch_layout(tensors: dict[str, Any], spec: resnetlib.ResNetSpec):
    """Map a {name: array} dict in torch ResNet layout onto our pytree.

    Expected names per block: ``<pre>.conv1.weight`` (OIHW), ``<pre>.bn1.
    {weight,bias,running_mean,running_var}``, etc.  Purely a relayout —
    no numerics.  Basic blocks only (raises ``NotImplementedError``
    otherwise).
    """
    resnetlib._require_basic(spec, "from_torch_layout")
    params: dict[str, Any] = {}
    state: dict[str, Any] = {}

    def grab_bn(src: str, dst: str):
        params[dst] = {
            "gamma": jnp.asarray(tensors[f"{src}.weight"]),
            "beta": jnp.asarray(tensors[f"{src}.bias"]),
        }
        state[dst] = {
            "mean": jnp.asarray(tensors[f"{src}.running_mean"]),
            "var": jnp.asarray(tensors[f"{src}.running_var"]),
        }

    params["stem"] = {"kernel": jnp.asarray(tensors["stem.weight"])}
    grab_bn("stem_bn", "stem_bn")
    for blk in resnetlib._stages(spec):
        name = blk.name
        entry = {
            "conv1": jnp.asarray(tensors[f"{name}.conv1.weight"]),
            "conv2": jnp.asarray(tensors[f"{name}.conv2.weight"]),
        }
        if f"{name}.proj.weight" in tensors:
            entry["proj"] = jnp.asarray(tensors[f"{name}.proj.weight"])
        params[name] = entry
        grab_bn(f"{name}.bn1", f"{name}_bn1")
        grab_bn(f"{name}.bn2", f"{name}_bn2")
    params["head"] = {
        "w": jnp.asarray(tensors["head.weight"]).T,
        "b": jnp.asarray(tensors["head.bias"]),
    }
    return params, state
