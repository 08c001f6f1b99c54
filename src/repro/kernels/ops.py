"""Jit'd public wrappers over the Pallas kernels.

On CPU (this container) the kernels execute in ``interpret`` mode — the
kernel body runs through the Pallas interpreter for correctness validation;
on TPU (``jax.default_backend() == 'tpu'``) they compile to Mosaic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.conv import pad_bands
from repro.kernels.asm_relu import asm_relu_pallas
from repro.kernels.block_dct import block_dct_pallas, block_idct_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_block import fused_block_pallas
from repro.kernels.jpeg_conv import jpeg_conv_pallas
from repro.kernels.pointwise_conv import pointwise_conv_pallas

__all__ = ["interpret_default", "asm_relu", "block_dct", "block_idct",
           "jpeg_conv_apply", "pointwise_conv", "fused_block",
           "flash_attention"]


def interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def asm_relu(coef: jnp.ndarray, phi: int = 14,
             bands: int | None = None) -> jnp.ndarray:
    """ASM ReLU over (..., 64) coefficient tensors (orthonormal units).

    ``bands`` slices the input to the kept zigzag coefficients before the
    kernel's matmuls and zero-pads the result back to the caller's width.
    """
    lead, nf = coef.shape[:-1], coef.shape[-1]
    flat = coef.reshape(-1, nf)
    if bands is not None and bands < nf:
        flat = flat[:, :bands]
    out = asm_relu_pallas(flat, phi, interpret=interpret_default())
    return pad_bands(out, nf).reshape(*lead, nf)


def block_dct(blocks: jnp.ndarray, quality: int | None = None) -> jnp.ndarray:
    lead = blocks.shape[:-2]
    flat = blocks.reshape(-1, 8, 8)
    out = block_dct_pallas(flat, quality=quality,
                           interpret=interpret_default())
    return out.reshape(*lead, 64)


def block_idct(coef: jnp.ndarray, quality: int | None = None) -> jnp.ndarray:
    lead = coef.shape[:-1]
    flat = coef.reshape(-1, 64)
    out = block_idct_pallas(flat, quality=quality,
                            interpret=interpret_default())
    return out.reshape(*lead, 8, 8)


def jpeg_conv_apply(coef: jnp.ndarray, xi: jnp.ndarray,
                    stride: int = 1) -> jnp.ndarray:
    """Pallas twin of ``core.conv.apply_exploded``."""
    return jpeg_conv_pallas(coef, xi, stride, interpret=interpret_default())


def pointwise_conv(coef: jnp.ndarray, w: jnp.ndarray,
                   shift: jnp.ndarray | None, bands: int) -> jnp.ndarray:
    """Pallas twin of the ``pointwise`` route over ``(..., Cin, nf)``
    coefficients: the leading axes flatten to blocks (a free reshape of
    the row-major layout)."""
    *lead, cin, nf = coef.shape
    out = pointwise_conv_pallas(coef.reshape(-1, cin, nf), w, shift,
                                bands=min(bands, nf),
                                interpret=interpret_default())
    return out.reshape(*lead, w.shape[0], nf)


def fused_block(x: jnp.ndarray, conv1, asm_mid, conv2, asm_out,
                proj=None) -> jnp.ndarray:
    """One fused residual block over tile-packed operators
    (``kernels.fused_block``); ``x`` is ``(N, bh, bw, Cin·w_in)``."""
    return fused_block_pallas(x, conv1, asm_mid, conv2, asm_out, proj,
                              interpret=interpret_default())


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True,
                    window: int | None = None) -> jnp.ndarray:
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  interpret=interpret_default())
