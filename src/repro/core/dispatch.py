"""Backend-aware operator dispatch for the JPEG-domain network.

Every JPEG-domain op the model forward needs — convolution, ASM ReLU,
block DCT/IDCT, batch norm — has a registry entry mapping *path* names to
implementations:

* ``reference`` — the pure-jnp ``core.*`` code (XLA, runs everywhere);
* ``pallas``    — the kernels in ``repro.kernels`` (Mosaic-compiled on
  TPU; on other backends the Pallas interpreter is a correctness harness,
  not a perf path, so the pallas entry *delegates to reference* unless
  ``interpret=True`` forces the interpreter — tests do);
* ``factored``  — the never-materialise path (J ∘ C ∘ J̃ applied as its
  factors; O(1) extra memory for arbitrarily wide layers).

Selection per call-site is (1) an explicit override — the ``JPEG_DISPATCH``
env var or :func:`configure`/:func:`override` — then (2) operator size
(above ``MATERIALIZE_LIMIT`` elements the conv goes factored), then (3)
backend (pallas on TPU, reference elsewhere).

The ``bands`` knob (paper §6: "the sparsity of the JPEG format allows for
faster processing") keeps only the first ``bands`` zigzag coefficients.
It threads down into ``explosion_basis`` / ``apply_exploded`` /
``jpeg_conv_pallas`` / ASM so dropped coefficients shrink the matmuls by
``(bands/64)²`` instead of being multiplied as zeros; activations stay
64-wide at op boundaries (zero-padded above the cutoff) so every layer
stays shape-compatible.  ``bands=64`` is bit-exact with the seed code.

Note: dispatch decisions are read at *trace* time.  Configure the path
and bands before ``jax.jit`` compiles a forward; changing the global
config does not retrace already-compiled functions.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import asm as asmlib
from repro.core import batchnorm as bnlib
from repro.core import conv as convlib
from repro.core import dct as dctlib

__all__ = [
    "PATHS", "DispatchConfig", "get_config", "configure", "override",
    "resolve_config", "register", "lookup", "available_paths", "choose_path",
    "ConvOperator", "conv", "precompute_conv", "apply_conv", "asm_relu",
    "batchnorm", "block_dct", "block_idct", "fused_block",
]

PATHS = ("reference", "pallas", "factored")


# --------------------------------------------------------------------------
# Configuration (env defaults + programmatic overrides)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DispatchConfig:
    """Per-forward dispatch policy.

    ``path``: 'auto' or a forced path name for every op.
    ``bands``: zigzag coefficients kept (1..64); 64 = exact.
    ``materialize_limit``: Ξ element count above which conv goes factored
        (None = ``core.conv.MATERIALIZE_LIMIT``).
    ``interpret``: force the Pallas interpreter off-TPU (tests/validation);
        None = delegate the pallas path to reference off-TPU.  On a TPU
        the kernels always compile with Mosaic; the interpreter never runs.
    """

    path: str = "auto"
    bands: int = dctlib.NFREQ
    materialize_limit: int | None = None
    interpret: bool | None = None

    def __post_init__(self):
        if self.path not in ("auto",) + PATHS:
            raise ValueError(f"unknown dispatch path {self.path!r}")
        if not 1 <= self.bands <= dctlib.NFREQ:
            raise ValueError(f"bands must be in [1, {dctlib.NFREQ}]")

    @property
    def limit(self) -> int:
        if self.materialize_limit is not None:
            return self.materialize_limit
        return convlib.MATERIALIZE_LIMIT


def _from_env() -> DispatchConfig:
    return DispatchConfig(
        path=os.environ.get("JPEG_DISPATCH", "auto").strip().lower() or "auto",
        bands=int(os.environ.get("JPEG_BANDS", dctlib.NFREQ)),
    )


# Parsed lazily on first use so a malformed JPEG_DISPATCH/JPEG_BANDS fails
# at the first dispatch call (with the validating ValueError) instead of
# crashing every import of the core package.
_CONFIG: DispatchConfig | None = None


def get_config() -> DispatchConfig:
    global _CONFIG
    if _CONFIG is None:
        _CONFIG = _from_env()
    return _CONFIG


def configure(**changes) -> DispatchConfig:
    """Permanently replace fields of the global config (serve/CLI entry)."""
    global _CONFIG
    _CONFIG = dataclasses.replace(get_config(), **changes)
    return _CONFIG


@contextlib.contextmanager
def override(**changes):
    """Scoped config override (benchmarks / tests)."""
    global _CONFIG
    prev = get_config()
    _CONFIG = dataclasses.replace(prev, **changes)
    try:
        yield _CONFIG
    finally:
        _CONFIG = prev


def resolve_config(cfg: DispatchConfig | None) -> DispatchConfig:
    return get_config() if cfg is None else cfg


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

_REGISTRY: dict[str, dict[str, Callable[..., Any]]] = {}


def register(op: str, path: str, fn: Callable[..., Any]) -> None:
    if path not in PATHS:
        raise ValueError(f"unknown path {path!r}")
    _REGISTRY.setdefault(op, {})[path] = fn


def available_paths(op: str) -> tuple[str, ...]:
    return tuple(p for p in PATHS if p in _REGISTRY.get(op, {}))


def lookup(op: str, path: str) -> Callable[..., Any]:
    """Implementation for ``op`` on ``path``; missing paths fall back to
    ``reference`` (e.g. batch norm is bandwidth-bound elementwise work XLA
    already emits optimally — it has no dedicated kernel yet)."""
    impls = _REGISTRY[op]
    return impls.get(path, impls["reference"])


def platform() -> str:
    """The backend operator paths resolve against (``cpu``/``gpu``/``tpu``);
    plans record it so one built elsewhere is never served here."""
    return jax.default_backend()


def _on_tpu() -> bool:
    return platform() == "tpu"


def choose_path(op: str, cfg: DispatchConfig, *,
                op_elems: int | None = None) -> str:
    """Resolve 'auto' to a concrete path for one call-site."""
    if cfg.path != "auto":
        if cfg.path == "pallas" and op == "conv" and op_elems is not None \
                and op_elems > cfg.limit:
            # A forced-pallas Ξ that cannot be materialised must go factored.
            return "factored"
        return cfg.path
    if op == "conv" and op_elems is not None and op_elems > cfg.limit:
        return "factored"
    if _on_tpu():
        return "pallas"
    return "reference"


def _pallas_delegates(cfg: DispatchConfig) -> bool:
    """Off-TPU, the pallas path runs reference XLA unless interpret forced."""
    return not _on_tpu() and cfg.interpret is not True


# --------------------------------------------------------------------------
# Convolution
# --------------------------------------------------------------------------


class ConvOperator(NamedTuple):
    """A precomputed layer operator with its resolved apply path.

    ``xi`` is the (possibly band-truncated) materialised Ξ; ``kernel`` is
    retained for the factored path (which never forms Ξ).  Closure-only:
    hold it outside jit arguments (``path``/metadata are not pytree leaves).

    ``scale``/``shift`` carry a fused inference-mode batch norm (see
    ``core.batchnorm.fold_batchnorm``): on materialised paths the scale is
    already folded into Ξ's output-channel rows at precompute time (so the
    field is None); the factored path, which never forms Ξ, keeps it and
    applies it per step.  ``shift`` is the DC-coefficient bias added after
    the conv.  ``bands`` is *per-operator* — the plan autotuner may assign
    each layer its own truncation instead of the global knob.

    ``bn_scale`` retains the *original* folded scale even when it was
    already multiplied into Ξ: plan compilation (``core.plan.compile_plan``)
    re-lowers the layer from ``kernel`` for backends where Ξ matmuls are
    not the fastest form, and needs the fold to reproduce the same math.
    :func:`apply_conv` never applies it.
    """

    xi: jnp.ndarray | None
    kernel: jnp.ndarray
    stride: int
    bands: int
    quality: int
    in_scaled: bool
    out_scaled: bool
    path: str
    scale: jnp.ndarray | None = None
    shift: jnp.ndarray | None = None
    bn_scale: jnp.ndarray | None = None


def _conv_reference(coef, kernel, stride, cfg, *, in_scaled, out_scaled,
                    quality):
    xi = convlib.explode(kernel, stride, quality=quality, in_scaled=in_scaled,
                         out_scaled=out_scaled, bands=cfg.bands)
    return convlib.pad_bands(convlib.apply_exploded(coef, xi, stride))


def _conv_pallas(coef, kernel, stride, cfg, *, in_scaled, out_scaled,
                 quality):
    if _pallas_delegates(cfg):
        return _conv_reference(coef, kernel, stride, cfg, in_scaled=in_scaled,
                               out_scaled=out_scaled, quality=quality)
    from repro.kernels import ops as kops

    xi = convlib.explode(kernel, stride, quality=quality, in_scaled=in_scaled,
                         out_scaled=out_scaled, bands=cfg.bands)
    return convlib.pad_bands(kops.jpeg_conv_apply(coef, xi, stride))


def _conv_factored(coef, kernel, stride, cfg, *, in_scaled, out_scaled,
                   quality):
    return convlib._jpeg_conv_factored(
        coef, kernel, stride, quality=quality, in_scaled=in_scaled,
        out_scaled=out_scaled, bands=cfg.bands)


def conv(coef: jnp.ndarray, kernel: jnp.ndarray, stride: int = 1,
         bias: jnp.ndarray | None = None, *, in_scaled: bool = False,
         out_scaled: bool = False, quality: int = 50,
         cfg: DispatchConfig | None = None) -> jnp.ndarray:
    """JPEG-domain convolution through the registry.

    Drop-in for ``core.conv.jpeg_conv``; returns 64-wide coefficients
    (zero above the band cutoff when ``cfg.bands < 64``).
    """
    cfg = resolve_config(cfg)
    path = choose_path("conv", cfg, op_elems=convlib.operator_elems(
        kernel.shape, stride, cfg.bands))
    out = lookup("conv", path)(coef, kernel, stride, cfg,
                               in_scaled=in_scaled, out_scaled=out_scaled,
                               quality=quality)
    return convlib.add_dc_bias(out, bias, out_scaled)


def precompute_conv(kernel: jnp.ndarray, stride: int = 1, *,
                    in_scaled: bool = False, out_scaled: bool = False,
                    quality: int = 50, bands: int | None = None,
                    scale: jnp.ndarray | None = None,
                    shift: jnp.ndarray | None = None,
                    cfg: DispatchConfig | None = None) -> ConvOperator:
    """Explode a layer once for inference (paper §4.1 "can be precomputed").

    The apply path is resolved here — by size, backend, and override — so
    :func:`apply_conv` is a pure table lookup per step.

    ``bands`` overrides ``cfg.bands`` for this operator (per-layer
    autotuning); ``scale``/``shift`` fuse a folded inference batch norm:
    the scale multiplies Ξ's output-channel rows here (materialised paths)
    or is retained for per-step application (factored path); the DC shift
    is always carried on the operator and added by :func:`apply_conv`.
    """
    cfg = resolve_config(cfg)
    bands = cfg.bands if bands is None else bands
    path = choose_path("conv", cfg, op_elems=convlib.operator_elems(
        kernel.shape, stride, bands))
    xi = None
    bn_scale = scale
    if path != "factored":
        xi = convlib.explode(kernel, stride, quality=quality,
                             in_scaled=in_scaled, out_scaled=out_scaled,
                             bands=bands)
        if scale is not None:
            # BN scale folds into the output-channel axis of Ξ
            # (ndy, ndx, Cin, bands, Cout, bands).
            xi = xi * jnp.asarray(scale, xi.dtype)[None, None, None, None, :,
                                                   None]
            scale = None
    return ConvOperator(xi, kernel, stride, bands, quality,
                        in_scaled, out_scaled, path, scale, shift, bn_scale)


def _apply_reference(coef, op: ConvOperator, cfg):
    return convlib.pad_bands(convlib.apply_exploded(coef, op.xi, op.stride))


def _apply_pallas(coef, op: ConvOperator, cfg):
    if _pallas_delegates(cfg):
        return _apply_reference(coef, op, cfg)
    from repro.kernels import ops as kops

    return convlib.pad_bands(kops.jpeg_conv_apply(coef, op.xi, op.stride))


def _apply_factored(coef, op: ConvOperator, cfg):
    return convlib._jpeg_conv_factored(
        coef, op.kernel, op.stride, quality=op.quality,
        in_scaled=op.in_scaled, out_scaled=op.out_scaled, bands=op.bands)


def apply_conv(coef: jnp.ndarray, op: ConvOperator,
               cfg: DispatchConfig | None = None) -> jnp.ndarray:
    """Apply a precomputed operator along its resolved path.

    Honors the operator's fused batch norm: ``scale`` (only present on the
    factored path — materialised Ξ already absorbed it) multiplies every
    output coefficient per channel, ``shift`` adds to DC.
    """
    cfg = resolve_config(cfg)
    out = lookup("conv_apply", op.path)(coef, op, cfg)
    if op.scale is not None:
        out = out * op.scale[None, None, None, :, None]
    if op.shift is not None:
        out = out.at[..., 0].add(op.shift[None, None, None, :])
    return out


# --------------------------------------------------------------------------
# ASM ReLU
# --------------------------------------------------------------------------


def _asm_reference(coef, phi, cfg):
    return asmlib.asm_relu(coef, phi, bands=cfg.bands)


def _asm_pallas(coef, phi, cfg):
    if _pallas_delegates(cfg):
        return _asm_reference(coef, phi, cfg)
    from repro.kernels import ops as kops

    return kops.asm_relu(coef, phi, bands=cfg.bands)


def asm_relu(coef: jnp.ndarray, phi: int = asmlib.EXACT_PHI,
             cfg: DispatchConfig | None = None, *,
             bands: int | None = None) -> jnp.ndarray:
    """``bands`` overrides ``cfg.bands`` for this call (per-layer plans
    run each activation at its layer's autotuned truncation)."""
    cfg = resolve_config(cfg)
    if bands is not None and bands != cfg.bands:
        cfg = dataclasses.replace(cfg, bands=bands)
    path = choose_path("asm_relu", cfg)
    return lookup("asm_relu", path)(coef, phi, cfg)


# --------------------------------------------------------------------------
# Fused residual block (compiled plans — ``core.plan.compile_plan``)
# --------------------------------------------------------------------------


def _fused_reference(x, block, phi, cfg):
    # XLA backends: the block-fused math in its FLOP-optimal lowering —
    # spatial-resident between the block-edge transforms.
    from repro.kernels.fused_block import fused_block_spatial

    return fused_block_spatial(x, block, phi)


def _fused_pallas(x, block, phi, cfg):
    if _pallas_delegates(cfg):
        return _fused_reference(x, block, phi, cfg)
    from repro.kernels import ops as kops

    return kops.fused_block(x, block.conv1, block.asm_mid, block.conv2,
                            block.asm_out, block.proj)


def fused_block(x: jnp.ndarray, block, phi: int, *, path: str | None = None,
                cfg: DispatchConfig | None = None) -> jnp.ndarray:
    """One whole residual block of a compiled plan
    (``core.plan.CompiledBlock``): the Pallas megakernel over the packed
    banded operators on TPU, the spatial-resident XLA lowering elsewhere.
    ``path`` is normally the block's compile-time resolution; None
    re-resolves from ``cfg`` (there is no factored fused kernel — a
    forced-factored config falls back to the reference executor).
    """
    cfg = resolve_config(cfg)
    path = choose_path("fused_block", cfg) if path is None else path
    return lookup("fused_block", path)(x, block, phi, cfg)


# --------------------------------------------------------------------------
# Batch norm (coefficient domain)
# --------------------------------------------------------------------------


def _bn_reference(coef, params, state, cfg, *, training, momentum, eps):
    return bnlib.batchnorm_jpeg(coef, params, state, training=training,
                                momentum=momentum, eps=eps)


def batchnorm(coef: jnp.ndarray, params: bnlib.BatchNormParams,
              state: bnlib.BatchNormState, *, training: bool,
              momentum: float = 0.1, eps: float = 1e-5,
              cfg: DispatchConfig | None = None):
    cfg = resolve_config(cfg)
    path = choose_path("batchnorm", cfg)
    return lookup("batchnorm", path)(coef, params, state, cfg,
                                     training=training, momentum=momentum,
                                     eps=eps)


# --------------------------------------------------------------------------
# Block DCT / IDCT (codec boundary)
# --------------------------------------------------------------------------


def _dct_reference(blocks, quality, cfg):
    from repro.kernels.block_dct import _fwd_operator

    lead = blocks.shape[:-2]
    flat = blocks.reshape(-1, dctlib.NFREQ)
    op = jnp.asarray(_fwd_operator(quality), blocks.dtype)
    return (flat @ op).reshape(*lead, dctlib.NFREQ)


def _dct_pallas(blocks, quality, cfg):
    if _pallas_delegates(cfg):
        return _dct_reference(blocks, quality, cfg)
    from repro.kernels import ops as kops

    return kops.block_dct(blocks, quality)


def _idct_reference(coef, quality, cfg):
    from repro.kernels.block_dct import _inv_operator

    lead = coef.shape[:-1]
    op = jnp.asarray(_inv_operator(quality), coef.dtype)
    out = coef.reshape(-1, dctlib.NFREQ) @ op
    return out.reshape(*lead, dctlib.BLOCK, dctlib.BLOCK)


def _idct_pallas(coef, quality, cfg):
    if _pallas_delegates(cfg):
        return _idct_reference(coef, quality, cfg)
    from repro.kernels import ops as kops

    return kops.block_idct(coef, quality)


def block_dct(blocks: jnp.ndarray, quality: int | None = None,
              cfg: DispatchConfig | None = None) -> jnp.ndarray:
    """(..., 8, 8) pixel blocks -> (..., 64) zigzag coefficients."""
    cfg = resolve_config(cfg)
    return lookup("block_dct", choose_path("block_dct", cfg))(
        blocks, quality, cfg)


def block_idct(coef: jnp.ndarray, quality: int | None = None,
               cfg: DispatchConfig | None = None) -> jnp.ndarray:
    """(..., 64) zigzag coefficients -> (..., 8, 8) pixel blocks."""
    cfg = resolve_config(cfg)
    return lookup("block_idct", choose_path("block_idct", cfg))(
        coef, quality, cfg)


# --------------------------------------------------------------------------
# Registry population.  Missing (op, path) pairs fall back to reference —
# the factored column only differs for conv (the other ops have no
# materialise/factor distinction), and batch norm has no kernel yet.
# --------------------------------------------------------------------------

register("conv", "reference", _conv_reference)
register("conv", "pallas", _conv_pallas)
register("conv", "factored", _conv_factored)

register("conv_apply", "reference", _apply_reference)
register("conv_apply", "pallas", _apply_pallas)
register("conv_apply", "factored", _apply_factored)

register("asm_relu", "reference", _asm_reference)
register("asm_relu", "pallas", _asm_pallas)

register("fused_block", "reference", _fused_reference)
register("fused_block", "pallas", _fused_pallas)

register("batchnorm", "reference", _bn_reference)

register("block_dct", "reference", _dct_reference)
register("block_dct", "pallas", _dct_pallas)

register("block_idct", "reference", _idct_reference)
register("block_idct", "pallas", _idct_pallas)
