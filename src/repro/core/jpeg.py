"""The JPEG transform as a linear map (paper §3.2).

The *JPEG transform domain* is the output of step 4 of JPEG encoding:
blocked, DCT-transformed, zigzag-ordered, quantization-scaled coefficients
(real-valued — rounding/entropy coding are outside the transform domain).

Layouts
-------
Spatial images are ``(..., H, W)``; their transform-domain representation is
``(..., H/8, W/8, 64)`` — block-row, block-col, zigzag coefficient.  The
leading axes (batch, channels) are untouched.

Coefficient conventions (DESIGN.md §7; the first two are this module's
``scaled`` flag, the third is produced by the codec subsystem):

===========================  ==============================================
convention                   meaning
===========================  ==============================================
``scaled=True``              true step-4 JPEG coefficients (divided by
                             ``q``) for pixels in the network's ~[-1, 1)
                             range — the network input convention
``scaled=False``             plain orthonormal DCT coefficients ("DCT
                             domain"); quantization diagonals folded into
                             the adjacent operators
canonical-qtable-normalized  a *file's* quantized integers rescaled by
                             ``codec.normalize`` into ``scaled=True`` form
                             under THIS repo's canonical table
                             (``dct.quantization_table(quality)``, DC
                             forced to 8): ``v·q_file/(128·q_canon)``.
                             Exact and linear, so one compiled plan serves
                             files with arbitrary quantization tables
===========================  ==============================================

Each block's transform is one constant linear map: ``jpeg_decode`` is
zigzag coefficients ``@ diag(q) · R`` and ``jpeg_encode`` flat pixels
``@ Rᵀ · diag(1/q)``, with ``R = dct.reconstruction_matrix()`` (64×64:
the separable 2-D DCT and the zigzag order in one matrix).  So both lower
to one dense matmul, never to a gather over the coefficient axis.

Note the orthonormal 8×8 DCT here coincides with the JPEG standard's DCT
definition, and steps 5+ (rounding, entropy coding) live in
``repro.codec`` (``bitstream``/``encode``) — this module stays the
real-valued transform-domain core.

``jpeg_tensor``/``ijpeg_tensor`` materialise the paper's ``J``/``J̃``
tensors explicitly; they are O((HW)²) and exist for tests and for the
faithful operator-explosion path on small images.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import dct as dctlib

__all__ = [
    "block_image",
    "unblock_image",
    "jpeg_encode",
    "jpeg_decode",
    "jpeg_round_trip_lossy",
    "jpeg_tensor",
    "ijpeg_tensor",
]


def block_image(img: jnp.ndarray, block: int = dctlib.BLOCK) -> jnp.ndarray:
    """``(..., H, W) -> (..., H/b, W/b, b, b)`` — the paper's B tensor."""
    *lead, h, w = img.shape
    if h % block or w % block:
        raise ValueError(f"image ({h}x{w}) not divisible into {block}x{block} blocks")
    img = img.reshape(*lead, h // block, block, w // block, block)
    return jnp.moveaxis(img, -3, -2)


def unblock_image(blocks: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`block_image`."""
    *lead, bh, bw, b1, b2 = blocks.shape
    blocks = jnp.moveaxis(blocks, -2, -3)
    return blocks.reshape(*lead, bh * b1, bw * b2)


def _quant_steps(quality: int, scaled: bool,
                 qtable: np.ndarray | None) -> np.ndarray:
    """Zigzag-ordered quantization steps ``q`` (all ones when not ``scaled``)."""
    if not scaled:
        return np.ones(dctlib.NFREQ)
    q = qtable if qtable is not None else dctlib.quantization_table(quality)
    return np.asarray(q, np.float64).reshape(dctlib.NFREQ)


def jpeg_encode(
    img: jnp.ndarray,
    *,
    quality: int = 50,
    scaled: bool = True,
    qtable: np.ndarray | None = None,
) -> jnp.ndarray:
    """Steps 1–4 of JPEG encoding: ``(..., H, W) -> (..., H/8, W/8, 64)``.

    One dense matmul per block: flat pixels ``@ Rᵀ · diag(1/q)``, where
    ``R`` is :func:`repro.core.dct.reconstruction_matrix`.  The 2-D DCT,
    the zigzag order and the division by ``q`` (when ``scaled``) are one
    constant 64×64 matrix.
    """
    q = _quant_steps(quality, scaled, qtable)
    fwd = jnp.asarray(dctlib.reconstruction_matrix().T / q, img.dtype)
    blocks = block_image(img)
    return blocks.reshape(*blocks.shape[:-2], dctlib.NFREQ) @ fwd


def jpeg_decode(
    coef: jnp.ndarray,
    *,
    quality: int = 50,
    scaled: bool = True,
    qtable: np.ndarray | None = None,
) -> jnp.ndarray:
    """Inverse of :func:`jpeg_encode` (no rounding — exact inverse).

    One dense matmul per block: zigzag coefficients ``@ diag(q) · R`` give
    the block's flat pixels.  A trailing axis shorter than 64 holds the
    first zigzag coefficients (the rest are zero) and meets only the
    matching rows of the matrix.
    """
    nf = coef.shape[-1]
    if nf > dctlib.NFREQ:
        raise ValueError(f"{nf} coefficients per block, at most {dctlib.NFREQ}")
    q = _quant_steps(quality, scaled, qtable)
    rec = q[:, None] * dctlib.reconstruction_matrix()
    flat = jnp.matmul(coef, jnp.asarray(rec[:nf], coef.dtype))
    return unblock_image(flat.reshape(*flat.shape[:-1], dctlib.BLOCK,
                                      dctlib.BLOCK))


def jpeg_round_trip_lossy(img: jnp.ndarray, *, quality: int = 50) -> jnp.ndarray:
    """Lossy JPEG round trip (with step-5 rounding) — for data simulation."""
    coef = jpeg_encode(img, quality=quality, scaled=True)
    coef = jnp.round(coef)
    return jpeg_decode(coef, quality=quality, scaled=True)


# --------------------------------------------------------------------------
# Explicit J / J~ tensors (tests + faithful explosion path; numpy, small images)
# --------------------------------------------------------------------------


def jpeg_tensor(
    h: int, w: int, *, quality: int = 50, scaled: bool = True
) -> np.ndarray:
    """The paper's ``J`` (Eq. 8) as ``(h, w, h/8, w/8, 64)``: pixels->coeffs."""
    b = dctlib.BLOCK
    # (pixel, coef): forward DCT in zigzag order, ÷ q when scaled
    fwd = dctlib.reconstruction_matrix().T / _quant_steps(quality, scaled, None)
    j = np.zeros((h, w, h // b, w // b, b * b))
    for x in range(h // b):
        for y in range(w // b):
            for m in range(b):
                for n in range(b):
                    j[x * b + m, y * b + n, x, y, :] = fwd[m * b + n]
    return j


def ijpeg_tensor(
    h: int, w: int, *, quality: int = 50, scaled: bool = True
) -> np.ndarray:
    """The paper's ``J̃`` (Eq. 10) as ``(h/8, w/8, 64, h, w)``: coeffs->pixels."""
    b = dctlib.BLOCK
    # (coef, pixel), × q when scaled
    rec = (_quant_steps(quality, scaled, None)[:, None]
           * dctlib.reconstruction_matrix())
    jt = np.zeros((h // b, w // b, b * b, h, w))
    for x in range(h // b):
        for y in range(w // b):
            blk = rec.reshape(b * b, b, b)
            jt[x, y, :, x * b : (x + 1) * b, y * b : (y + 1) * b] = blk
    return jt
