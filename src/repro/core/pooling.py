"""JPEG-domain pooling and residual addition (paper §4.4, §4.5).

* Component-wise (residual) addition is identity-cost by linearity.
* Global average pooling reads DC coefficients: the mean over the image is
  the mean of per-block means, and when the feature map is a single block
  it is one unconditional read per channel (paper Fig. 2).
* Max pooling (ResNet's stem; not in the paper) is not linear, so it has
  no transform-domain form: decode, pool, encode.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.core import jpeg as jpeglib
from repro.core.batchnorm import DC_GAIN

__all__ = ["residual_add", "global_avg_pool_jpeg", "global_avg_pool_spatial",
           "max_pool_spatial", "max_pool_jpeg"]


def residual_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """J(F + G) = J(F) + J(G) — Eq. 25."""
    return a + b


def global_avg_pool_jpeg(coef: jnp.ndarray, *, dc_gain: float = DC_GAIN) -> jnp.ndarray:
    """``(N, bh, bw, C, 64) -> (N, C)``: channel-wise mean via DC reads."""
    return jnp.mean(coef[..., 0], axis=(1, 2)) / dc_gain


def global_avg_pool_spatial(x: jnp.ndarray) -> jnp.ndarray:
    """``(N, C, H, W) -> (N, C)`` — the spatial oracle."""
    return jnp.mean(x, axis=(2, 3))


def max_pool_spatial(x: jnp.ndarray) -> jnp.ndarray:
    """``(N, C, H, W) -> (N, C, H/2, W/2)``: 3×3 max-pool at stride 2 with
    padding 1, the padding at −∞ (torchvision's ``MaxPool2d(3, 2, 1)``)."""
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                             (1, 1, 2, 2), ((0, 0), (0, 0), (1, 1), (1, 1)))


def max_pool_jpeg(coef: jnp.ndarray, bands: int | None = None) -> jnp.ndarray:
    """:func:`max_pool_spatial` on ``(N, bh, bw, C, w)`` orthonormal
    coefficients -> ``(N, bh/2, bw/2, C, w)``.

    Decode (one dense 64×64 transform per block, the first ``w`` rows),
    ``reduce_window`` max, encode; the output keeps the input's ``w``
    lanes, zero from ``bands`` on.  Exact at 64 bands.  ResNet pools the
    stem's ReLU output, which is ≥ 0, so its −∞ padding equals zero
    padding there; below 64 bands the ASM ReLU's output is a band-limited
    projection that can dip below zero near an edge, and the −∞ padding
    keeps the pool torchvision's.
    """
    nf = coef.shape[-1]
    img = jpeglib.jpeg_decode(jnp.moveaxis(coef, 3, 1), scaled=False)
    enc = jpeglib.jpeg_encode(max_pool_spatial(img), scaled=False)
    enc = jnp.moveaxis(enc, 1, 3)[..., :nf]
    if bands is not None and bands < nf:
        enc = jnp.where(jnp.arange(nf) < bands, enc, 0.0)
    return enc
