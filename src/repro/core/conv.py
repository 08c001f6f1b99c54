"""Convolution explosion — the paper's §4.1 / Algorithm 1, TPU-adapted.

Two implementations of the JPEG-domain convolution operator Ξ = J ∘ C ∘ J̃:

1. ``explode_full`` / ``apply_full`` — the paper's Algorithm 1 verbatim:
   convolve the filter against the decompression tensor J̃ reshaped as a
   batch of images (Eq. 12), re-encode, and keep the full position-dependent
   operator.  O((#blocks)²·64²·Cin·Cout) memory — used as the faithful
   reference and for paper-scale images.

2. ``explosion_basis`` / ``explode`` / ``apply_exploded`` — the production
   path (DESIGN.md §3).  Exploits translation invariance: away from borders
   the operator depends only on the *relative* block offset, and with SAME
   zero-padding the border cases are exactly the interior operator with
   missing neighbours contributing zero.  The operator is assembled from a
   precomputed separable basis

       basis[u, v, dy, dx, k, k']

   (kernel tap (u,v) → block-offset (dy,dx) coefficient mixing), so that for
   filters K of shape (Cout, Cin, r, r):

       Ξ[dy, dx, i, k, o, k'] = Σ_uv K[o, i, u, v] · basis[u, v, dy, dx, k, k']

   This contraction is linear in K — gradients for JPEG-domain *training*
   flow through it with no custom VJP — and ``apply_exploded`` is a sum of
   ``ndy·ndx`` dense (64·Cin → 64·Cout) matmuls per block: MXU-shaped.

Layout: coefficient activations are ``(N, bh, bw, C, 64)`` (channels-last
blocks); filters are ``(Cout, Cin, r, r)``; only odd ``r`` is supported.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core import dct as dctlib
from repro.core import jpeg as jpeglib

__all__ = [
    "block_offsets",
    "explosion_basis",
    "explode",
    "apply_exploded",
    "pad_bands",
    "operator_elems",
    "add_dc_bias",
    "jpeg_conv",
    "explode_full",
    "apply_full",
    "spatial_conv",
]


def block_offsets(stride: int, r: int, block: int = dctlib.BLOCK) -> tuple[int, int]:
    """Range ``[d_min, d_max]`` of relative input-block offsets per axis."""
    if r % 2 != 1:
        raise ValueError("only odd receptive fields supported")
    pad = (r - 1) // 2
    d_min = (0 * stride - pad) // block  # floor division
    d_max = ((block - 1) * stride + pad) // block
    return d_min, d_max


@functools.lru_cache(maxsize=None)
def _basis_1d(stride: int, r: int, block: int = dctlib.BLOCK) -> np.ndarray:
    """1-D explosion basis ``(r, ndy, block, block)``.

    ``basis[u, d, a, a']`` maps input frequency ``a`` of the block at
    relative offset ``d + d_min`` to output frequency ``a'``, for the 1-D
    single-tap filter at tap ``u`` (translation ``t = u - pad``):

        out[m'] = in[stride * m' + t]      (zero outside)

    so ``basis[u, d, a, a'] = Σ_{m': blk(m')==d} D[a, pos(m')] D[a', m']``.
    """
    d = dctlib.dct_matrix(block)
    pad = (r - 1) // 2
    d_min, d_max = block_offsets(stride, r, block)
    nd = d_max - d_min + 1
    out = np.zeros((r, nd, block, block))
    for u in range(r):
        t = u - pad
        for mp in range(block):
            src = stride * mp + t
            blk, pos = src // block, src % block
            out[u, blk - d_min] += np.einsum("a,b->ab", d[:, pos], d[:, mp])
    return out


@functools.lru_cache(maxsize=None)
def explosion_basis(
    stride: int,
    r: int,
    quality: int = 50,
    in_scaled: bool = False,
    out_scaled: bool = False,
    bands: int = dctlib.NFREQ,
) -> np.ndarray:
    """2-D explosion basis ``(r, r, ndy, ndx, bands, bands)`` in zigzag order.

    ``in_scaled`` folds the de-quantization diagonal S̃ on the input side;
    ``out_scaled`` folds the re-quantization diagonal S on the output side
    (paper Eq. 20).  Both ``False`` is the orthonormal-DCT internal
    convention (quantization already folded into the first layer).

    ``bands`` (paper §6 sparsity) keeps only the first ``bands`` zigzag
    coefficients on *both* sides of the operator: high-frequency inputs are
    never read and high-frequency outputs never computed, so the downstream
    matmuls shrink by ``(bands/64)²`` instead of multiplying zeros.
    ``bands=64`` is exact.
    """
    if not 1 <= bands <= dctlib.NFREQ:
        raise ValueError(f"bands must be in [1, {dctlib.NFREQ}], got {bands}")
    b1 = _basis_1d(stride, r)
    b = dctlib.BLOCK
    # (u, v, dy, dx, a, a', c, c') -> zigzag (k = (a,c) in, k' = (a',c') out)
    full = np.einsum("udaA,vxcC->uvdxacAC", b1, b1)
    r_, nd = b1.shape[0], b1.shape[1]
    full = full.reshape(r_, r_, nd, nd, b * b, b * b)
    zz = dctlib.zigzag_permutation()
    full = full[..., zz, :][..., zz]
    full = full[..., :bands, :bands]
    q = dctlib.quantization_table(quality)
    if in_scaled:
        full = full * q[:bands, None]
    if out_scaled:
        full = full / q[None, :bands]
    return np.ascontiguousarray(full)


def explode(
    kernel: jnp.ndarray,
    stride: int = 1,
    *,
    quality: int = 50,
    in_scaled: bool = False,
    out_scaled: bool = False,
    bands: int = dctlib.NFREQ,
) -> jnp.ndarray:
    """Exploded JPEG-domain operator ``(ndy, ndx, Cin, bands, Cout, bands)``.

    Linear in ``kernel`` (Cout, Cin, r, r) — differentiable for JPEG-domain
    training (the paper's "more complex gradient" is just this einsum's
    transpose).
    """
    r = kernel.shape[-1]
    basis = jnp.asarray(
        explosion_basis(stride, r, quality, in_scaled, out_scaled, bands),
        kernel.dtype,
    )
    return jnp.einsum("oiuv,uvyxkl->yxikol", kernel, basis)


def pad_bands(coef: jnp.ndarray, nf: int = dctlib.NFREQ) -> jnp.ndarray:
    """Zero-pad the trailing coefficient axis back up to ``nf`` entries."""
    have = coef.shape[-1]
    if have == nf:
        return coef
    pad = [(0, 0)] * (coef.ndim - 1) + [(0, nf - have)]
    return jnp.pad(coef, pad)


def operator_elems(kernel_shape, stride: int, bands: int = dctlib.NFREQ) -> int:
    """Element count of the materialised Ξ for a (Cout, Cin, r, r) kernel —
    the quantity compared against ``MATERIALIZE_LIMIT``."""
    cout, cin, r = kernel_shape[0], kernel_shape[1], kernel_shape[-1]
    d_min, d_max = block_offsets(stride, r)
    nd = d_max - d_min + 1
    return nd * nd * cin * cout * bands * bands


def add_dc_bias(out: jnp.ndarray, bias: jnp.ndarray | None,
                out_scaled: bool = False) -> jnp.ndarray:
    """Per-channel bias ``b`` adds a constant to every pixel, i.e. ``8·b``
    on the orthonormal DC coefficient (``b`` directly when re-quantization
    with q₀ = 8 is folded on the output side)."""
    if bias is None:
        return out
    dc_gain = 1.0 if out_scaled else float(dctlib.BLOCK)
    return out.at[..., 0].add(dc_gain * bias)


def apply_exploded(coef: jnp.ndarray, xi: jnp.ndarray, stride: int = 1) -> jnp.ndarray:
    """Apply an exploded operator to ``(N, bh, bw, Cin, ≥bands)`` coefficients.

    ``out[n, x', y', o, k'] = Σ_{dy,dx,i,k} coef[n, s·x'+dy, s·y'+dx, i, k]
    · xi[dy, dx, i, k, o, k']`` with zero padding outside the block grid —
    exactly the border behaviour of SAME zero-padded spatial convolution.

    If ``xi`` was built with ``bands < 64`` the input is sliced to the kept
    coefficients before the matmuls and the output has ``bands`` trailing
    entries (use :func:`pad_bands` to restore the 64-wide layout).
    """
    ndy, ndx = xi.shape[0], xi.shape[1]
    nf_in = xi.shape[3]
    if coef.shape[-1] > nf_in:
        coef = coef[..., :nf_in]
    n, bh, bw, cin, nf = coef.shape
    d_min_y, _ = _offsets_from(ndy, stride)
    d_min_x, _ = _offsets_from(ndx, stride)
    bh_out, bw_out = bh // stride, bw // stride
    pad_lo_y, pad_hi_y = -d_min_y, (ndy - 1 + d_min_y)
    pad_lo_x, pad_hi_x = -d_min_x, (ndx - 1 + d_min_x)
    padded = jnp.pad(
        coef, ((0, 0), (pad_lo_y, pad_hi_y), (pad_lo_x, pad_hi_x), (0, 0), (0, 0))
    )
    out = None
    for iy in range(ndy):
        for ix in range(ndx):
            # input block index = stride*x' + (iy + d_min_y); shift by pad_lo.
            y0 = iy + d_min_y + pad_lo_y
            x0 = ix + d_min_x + pad_lo_x
            sl = padded[
                :,
                y0 : y0 + stride * bh_out : stride,
                x0 : x0 + stride * bw_out : stride,
            ]
            term = jnp.einsum("nxyik,ikol->nxyol", sl, xi[iy, ix])
            out = term if out is None else out + term
    return out


def _offsets_from(nd: int, stride: int) -> tuple[int, int]:
    """Recover ``(d_min, d_max)`` from the basis offset count.

    Per :func:`block_offsets` with odd r < 16: ``d_min = -1`` iff pad > 0.
    The only supported nd > 1 case with pad == 0 is (r=1, stride=2), where
    offsets are {0, 1}.
    """
    if nd == 1:
        return 0, 0
    if stride == 2 and nd == 2:
        return 0, 1
    return -1, nd - 2


# Above this operator size (elements of Ξ), materialising the exploded
# operator is worse than the factored (transform) application — the paper's
# §6 "efficiency of representation" limit.  3·3·(64·C_in)·(64·C_out) crosses
# it around C_in·C_out ≈ 3.6k (e.g. 64×64 channels).
# Env override JPEG_CONV_MATERIALIZE_LIMIT forces a path for perf A/B runs
# (EXPERIMENTS.md §Perf: set huge for the paper-faithful baseline, 0 for
# the always-factored variant).
import os as _os

MATERIALIZE_LIMIT = int(_os.environ.get("JPEG_CONV_MATERIALIZE_LIMIT",
                                        64 * 1024 * 1024))


def jpeg_conv(
    coef: jnp.ndarray,
    kernel: jnp.ndarray,
    stride: int = 1,
    bias: jnp.ndarray | None = None,
    *,
    in_scaled: bool = False,
    out_scaled: bool = False,
    quality: int = 50,
    bands: int = dctlib.NFREQ,
) -> jnp.ndarray:
    """JPEG-domain convolution: explode + apply, or factored for wide nets.

    The *materialised* path (paper Alg. 1) precomputes Ξ — best for small
    channel counts and the inference-precompute story.  For wide layers the
    operator itself is O(9·64²·C_in·C_out) (38 GB at 512×512 channels!), so
    the *factored* path applies J̃ → C → J without ever forming Ξ:
    mathematically identical (Ξ is exactly that composition), O(1) extra
    memory, and 64× fewer FLOPs.  On TPU the factored form lives in VMEM
    tiles (``repro.kernels.jpeg_conv``); here the paths are selected by
    operator size.  Recorded as the beyond-paper optimisation in
    EXPERIMENTS.md §Perf.

    Bias ``b`` per output channel adds a constant to every pixel, i.e. adds
    ``8·b`` to the orthonormal DC coefficient (``b`` directly in the scaled
    convention with q₀ = 8).
    """
    if operator_elems(kernel.shape, stride, bands) <= MATERIALIZE_LIMIT:
        xi = explode(kernel, stride, quality=quality, in_scaled=in_scaled,
                     out_scaled=out_scaled, bands=bands)
        out = pad_bands(apply_exploded(coef, xi, stride))
    else:
        out = _jpeg_conv_factored(coef, kernel, stride, quality=quality,
                                  in_scaled=in_scaled, out_scaled=out_scaled,
                                  bands=bands)
    return add_dc_bias(out, bias, out_scaled)


def _jpeg_conv_factored(coef, kernel, stride, *, quality, in_scaled,
                        out_scaled, bands=dctlib.NFREQ):
    """Ξ = J ∘ C ∘ J̃ applied as its factors (exact, never forms Ξ).

    coef: (N, bh, bw, Cin, 64) -> (N, bh/s, bw/s, Cout, 64).

    J̃ and J are each one dense 64×64 matmul per block (zigzag order, 2-D
    DCT and quantization folded into one constant matrix, see
    :mod:`repro.core.jpeg`); C is a spatial convolution.

    ``bands`` truncates the input and output coefficient sets so the result
    matches the band-truncated materialised operator: the decode meets only
    the first ``bands`` rows of its matrix, and the encoded output is zeroed
    past ``bands`` (this path's win is memory, not the §6 sparsity FLOPs).
    """
    if bands < coef.shape[-1]:
        coef = coef[..., :bands]
    img = jpeglib.jpeg_decode(jnp.moveaxis(coef, 3, 1), scaled=in_scaled,
                              quality=quality)
    out = spatial_conv(img, kernel, stride)
    enc = jpeglib.jpeg_encode(out, scaled=out_scaled, quality=quality)
    enc = jnp.moveaxis(enc, 1, 3)
    if bands < enc.shape[-1]:
        enc = pad_bands(enc[..., :bands])
    return enc


# --------------------------------------------------------------------------
# Faithful full-operator path (paper Algorithm 1) — reference & tests
# --------------------------------------------------------------------------


def spatial_conv(
    img: jnp.ndarray, kernel: jnp.ndarray, stride: int = 1,
    bias: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Centered zero-padded spatial conv (PyTorch ``padding=r//2``), NCHW/OIHW.

    Note: XLA's ``"SAME"`` pads asymmetrically for even strides; the
    explosion basis assumes *centered* padding, so we pad explicitly.
    """
    pad = (kernel.shape[-1] - 1) // 2
    out = lax.conv_general_dilated(
        img, kernel, window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    if bias is not None:
        out = out + bias[None, :, None, None]
    return out


def explode_full(
    kernel: jnp.ndarray, bh: int, bw: int, stride: int = 1,
    *, quality: int = 50, scaled: bool = False,
) -> jnp.ndarray:
    """Paper Algorithm 1: full operator ``(bh, bw, 64, Cin, Cout, bh', bw', 64)``.

    Convolves each J̃ "image" (Eq. 12) with every (o, i) filter slice and
    re-encodes the result.  Memory grows with the block grid squared — use
    only at paper scale (tests, CIFAR-sized images).
    """
    b = dctlib.BLOCK
    h, w = bh * b, bw * b
    cout, cin, r, _ = kernel.shape
    jt = np.asarray(
        jpeglib.ijpeg_tensor(h, w, quality=quality, scaled=scaled), np.float32
    )
    imgs = jnp.asarray(jt.reshape(bh * bw * b * b, 1, h, w), kernel.dtype)
    k2 = kernel.reshape(cout * cin, 1, r, r)
    pad = (r - 1) // 2
    conv = lax.conv_general_dilated(
        imgs, k2, window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )  # (bh*bw*64, cout*cin, h/s, w/s)
    enc = jpeglib.jpeg_encode(conv, quality=quality, scaled=scaled)
    enc = enc.reshape(bh, bw, b * b, cout, cin, bh // stride, bw // stride, b * b)
    return jnp.moveaxis(enc, 4, 3)  # (bh, bw, 64, cin, cout, bh', bw', 64)


def apply_full(coef: jnp.ndarray, op: jnp.ndarray) -> jnp.ndarray:
    """Apply a full operator to ``(N, bh, bw, Cin, 64)`` coefficients."""
    return jnp.einsum("nxyik,xykioXYK->nXYoK", coef, op)
