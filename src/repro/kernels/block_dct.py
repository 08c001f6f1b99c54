"""Pallas TPU kernel: batched 8×8 forward/inverse DCT (+ zigzag, + quant).

The JPEG encode hot loop (data pipeline / first-layer folding).  A tile of
``TILE`` blocks is laid out as ``(TILE, 64)`` flat pixels in VMEM; the 2-D
DCT is one ``(64, 64)`` matmul with the precomputed separable operator
``K[pq, ab] = D[a,p]·D[b,q]`` (zigzag and quantization folded in), keeping
everything in a single MXU pass — no 8-wide matmuls, no transposes.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import dct as dctlib

__all__ = ["block_dct_pallas", "block_idct_pallas"]

TILE = 1024


def _matmul_kernel(x_ref, op_ref, out_ref):
    out_ref[...] = jnp.dot(x_ref[...], op_ref[...],
                           preferred_element_type=jnp.float32
                           ).astype(out_ref.dtype)


def _run(x: jnp.ndarray, op: np.ndarray, interpret: bool) -> jnp.ndarray:
    n = x.shape[0]
    tile = min(TILE, n)
    if n % tile:
        x = jnp.pad(x, ((0, tile - n % tile), (0, 0)))
    grid = (x.shape[0] // tile,)
    out = pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, 64), lambda i: (i, 0)),
            pl.BlockSpec((64, 64), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, 64), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, jnp.asarray(op, x.dtype))
    return out[:n]


@functools.lru_cache(maxsize=None)
def _fwd_operator(quality: int | None) -> np.ndarray:
    """(64 flat-pixel, 64 zigzag-coef) forward DCT operator."""
    r = dctlib.reconstruction_matrix()  # (coef, pixel); forward = transpose
    op = r.T.copy()
    if quality is not None:
        op = op / dctlib.quantization_table(quality)[None, :]
    return op


@functools.lru_cache(maxsize=None)
def _inv_operator(quality: int | None) -> np.ndarray:
    r = dctlib.reconstruction_matrix().copy()
    if quality is not None:
        r = dctlib.quantization_table(quality)[:, None] * r
    return r


@functools.partial(jax.jit, static_argnames=("quality", "interpret"))
def block_dct_pallas(blocks: jnp.ndarray, *, quality: int | None = None,
                     interpret: bool) -> jnp.ndarray:
    """(N, 8, 8) pixel blocks -> (N, 64) zigzag coefficients."""
    n = blocks.shape[0]
    return _run(blocks.reshape(n, 64), _fwd_operator(quality), interpret)


@functools.partial(jax.jit, static_argnames=("quality", "interpret"))
def block_idct_pallas(coef: jnp.ndarray, *, quality: int | None = None,
                      interpret: bool) -> jnp.ndarray:
    """(N, 64) zigzag coefficients -> (N, 8, 8) pixel blocks."""
    out = _run(coef, _inv_operator(quality), interpret)
    return out.reshape(coef.shape[0], 8, 8)
