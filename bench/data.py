"""Inputs made from the seed: images and their JPEG integers.

Images are frequency-shaped Gaussian fields with a class template, the
statistics of the program's own synthetic corpus (power-law spectra, so
the DCT energy compacts as in natural images), made on the device in one
jitted call.  Each plane is then taken through JPEG steps 1-5 at an IJG
quality: blocked, orthonormal 8x8 DCT, zigzag, divided by the table and
rounded.  Those integers are the ground truth of the comparison: the
reference decodes pixels from them, and the cells hand them to the
program in its input convention.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

BLOCK = 8
NFREQ = 64
PIXEL_SCALE = 128.0  # JPEG level-shifted samples over network pixels

_IJG_LUMA = np.array([
    [16, 11, 10, 16, 24, 40, 51, 61],
    [12, 12, 14, 19, 26, 58, 60, 55],
    [14, 13, 16, 24, 40, 57, 69, 56],
    [14, 17, 22, 29, 51, 87, 80, 62],
    [18, 22, 37, 56, 68, 109, 103, 77],
    [24, 35, 55, 64, 81, 104, 113, 92],
    [49, 64, 78, 87, 103, 121, 120, 101],
    [72, 92, 95, 98, 112, 100, 103, 99]], np.float64)


@functools.lru_cache(maxsize=None)
def zigzag() -> np.ndarray:
    """``(64,)`` flat row-major index of each zigzag position."""
    order = []
    for band in range(2 * BLOCK - 1):
        rc = [(a, band - a) for a in range(BLOCK) if 0 <= band - a < BLOCK]
        rc.sort(key=lambda p: p[0], reverse=band % 2 == 0)
        order += [a * BLOCK + b for a, b in rc]
    return np.array(order)


@functools.lru_cache(maxsize=None)
def dct_matrix() -> np.ndarray:
    """Orthonormal DCT-II matrix ``D`` (``D @ D.T == I``)."""
    a = np.arange(BLOCK)[:, None]
    m = np.arange(BLOCK)[None, :]
    d = np.cos((2 * m + 1) * a * np.pi / (2 * BLOCK)) * np.sqrt(2 / BLOCK)
    d[0] *= np.sqrt(0.5)
    return d


@functools.lru_cache(maxsize=None)
def basis() -> np.ndarray:
    """``(64 zigzag coefficients, 64 row-major pixels)`` orthonormal basis:
    a block's pixels are ``coef @ basis()``, its coefficients
    ``pixels @ basis().T``."""
    d = dct_matrix()
    return np.einsum("am,bn->abmn", d, d).reshape(NFREQ, NFREQ)[zigzag()]


def ijg_table(quality: int, *, dc_is_mean: bool = False) -> np.ndarray:
    """Zigzag IJG luminance table at ``quality``; ``dc_is_mean`` forces the
    DC step to 8, the convention of the network's input coefficients."""
    scale = 5000.0 / quality if quality < 50 else 200.0 - 2.0 * quality
    q = np.clip(np.floor((_IJG_LUMA * scale + 50.0) / 100.0), 1.0, 255.0)
    if dc_is_mean:
        q[0, 0] = 8.0
    return q.reshape(-1)[zigzag()]


@functools.partial(jax.jit, static_argnames=("n", "size", "channels",
                                             "classes"))
def images(key, *, n: int, size: int, channels: int, classes: int):
    """``(n, C, size, size)`` float32 pixels in [-1, 127/128]."""
    k_lab, k_re, k_im = jax.random.split(key, 3)
    labels = jax.random.randint(k_lab, (n,), 0, classes)
    f = jnp.fft.fftfreq(size)
    rad = jnp.sqrt(f[:, None] ** 2 + f[None, :] ** 2) + 1.0 / size
    expo = 1.0 + labels / classes
    shape = (n, channels, size, size)
    spec = jax.random.normal(k_re, shape) + 1j * jax.random.normal(k_im,
                                                                 shape)
    spec = spec * rad[None, None] ** (-expo[:, None, None, None])
    img = jnp.real(jnp.fft.ifft2(spec, axes=(-2, -1)))
    img = img / (jnp.abs(img).max(axis=(-1, -2), keepdims=True) + 1e-8)
    # class templates are a constant of the corpus, not of the seed
    tpl = jax.random.normal(jax.random.PRNGKey(7777),
                            (classes, channels, 4, 4))
    tpl = jnp.repeat(jnp.repeat(tpl, size // 4, axis=-2), size // 4, axis=-1)
    out = 0.6 * img + 0.4 * jnp.tanh(tpl[labels])
    return jnp.clip(out, -1.0, 127.0 / 128.0).astype(jnp.float32)


def to_blocks(planes):
    """``(..., H, W) -> (..., H/8, W/8, 64)`` row-major pixels per block."""
    *lead, h, w = planes.shape
    x = planes.reshape(*lead, h // BLOCK, BLOCK, w // BLOCK, BLOCK)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, h // BLOCK, w // BLOCK,
                                            NFREQ)


def from_blocks(blocks):
    """Inverse of :func:`to_blocks`."""
    *lead, bh, bw, _ = blocks.shape
    x = blocks.reshape(*lead, bh, bw, BLOCK, BLOCK)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, bh * BLOCK, bw * BLOCK)


@jax.jit
def quantize(imgs, qtable):
    """JPEG steps 1-5 on every plane, 4:4:4: integers
    ``round(DCT(x)·128/q)``.

    Returns ``(luma, chroma)``: ``(N, H/8, W/8, 64)`` and
    ``(N, 2, H/8, W/8, 64)`` int32.
    """
    r = jnp.asarray(basis(), jnp.float32)
    q = jnp.asarray(qtable, jnp.float32)

    def plane(x):
        with jax.default_matmul_precision("highest"):
            c = to_blocks(x) @ r.T
        return jnp.round(c * PIXEL_SCALE / q).astype(jnp.int32)

    return plane(imgs[:, 0]), plane(imgs[:, 1:])


@jax.jit
def pixels(luma, chroma, qtable):
    """Network pixels ``(N, 3, H, W)`` decoded from the integers:
    dequantize, inverse DCT, 1/128."""
    r = jnp.asarray(basis(), jnp.float32)
    q = jnp.asarray(qtable, jnp.float32)

    def plane(v):
        with jax.default_matmul_precision("highest"):
            blocks = (v.astype(jnp.float32) * q) @ r
            return from_blocks(blocks) / PIXEL_SCALE

    return jnp.concatenate([plane(luma)[:, None], plane(chroma)], axis=1)


def coefficients(luma: np.ndarray, chroma: np.ndarray, qtable: np.ndarray,
                 quality: int) -> np.ndarray:
    """4:4:4 integers in the program's input convention: ``(N, bh, bw, 3,
    64)`` float32 ``v·q_file / (128·q_canon)``, with ``q_canon`` the IJG
    table at the network's quality with its DC step forced to 8."""
    v = np.concatenate([luma[:, None], chroma], axis=1).astype(np.float64)
    gain = (np.asarray(qtable, np.float64)
            / (PIXEL_SCALE * ijg_table(quality, dc_is_mean=True)))
    return np.ascontiguousarray(np.moveaxis(v * gain, 1, 3), np.float32)
