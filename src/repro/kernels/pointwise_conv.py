"""Pallas TPU kernel: a 1×1 stride-1 convolution on JPEG coefficients.

A 1×1 stride-1 convolution maps every pixel's channel vector through one
matrix ``W (Cout, Cin)``.  The blockwise DCT is linear and acts on each
channel alone, so in the transform domain the convolution is the same
matrix applied to each coefficient of each block:

    out[m, o, k] = Σ_c W[o, c] · in[m, c, k]        (+ shift_o on k = 0)

with ``m`` running over the ``N·bh·bw`` blocks.  No Ξ is formed and no
coefficient mixes with another, so a band-truncated input gives a
band-truncated output and the operator is exact at every band count.

Grid: ``(cout_tile, block_tile)``, blocks innermost, so one tile of
``W`` stays resident in VMEM while every tile of blocks streams past
it.  The activation is read in the packed ``(N·bh·bw, Cin, w)`` layout
the compiled schedule already holds (no transpose); each block of the
tile is one ``(tco, Cin) @ (Cin, w)`` MXU product.  Operands are rounded
to bfloat16 and accumulated in float32 (the TPU's default precision for
float32 products); the epilogue adds the folded batch-norm shift to the
DC lane and zeroes the lanes at and past ``bands``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

__all__ = ["pointwise_conv_pallas", "pointwise_tiles"]

#: bytes one VMEM buffer of the kernel may hold (each is double-buffered)
BUFFER_BYTES = 2 << 20
#: most blocks one grid step unrolls into MXU products
MAX_TILE_BLOCKS = 64


def _largest_divisor(n: int, limit: int, align: int = 1) -> int | None:
    for d in range(min(n, limit), 0, -1):
        if n % d == 0 and d % align == 0:
            return d
    return None


def pointwise_tiles(m: int, cin: int, cout: int) -> tuple[int, int]:
    """``(blocks, cout)`` tile of one grid step: each buffer (a ``W``
    tile in bfloat16, an input or output tile in float32 with its lanes
    padded to 128) within ``BUFFER_BYTES``; tiles divide the axes."""
    tco = cout
    if cout * cin * 2 > BUFFER_BYTES:
        tco = _largest_divisor(cout, BUFFER_BYTES // (cin * 2), 8) or cout
    row = 128 * 4
    tm_max = max(1, min(MAX_TILE_BLOCKS, BUFFER_BYTES // (cin * row),
                        BUFFER_BYTES // (tco * row)))
    return _largest_divisor(m, tm_max), tco


def _make_kernel(tm: int, bands: int, width: int):
    def kernel(x_ref, w_ref, s_ref, o_ref):
        w = w_ref[...]
        lane = lax.broadcasted_iota(jnp.int32, (w.shape[0], width), 1)
        shift = jnp.where(lane == 0, s_ref[...], 0.0)
        keep = lane < bands

        def body(m, carry):
            acc = jnp.dot(w, x_ref[m].astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
            out = acc + shift
            if bands < width:
                out = jnp.where(keep, out, 0.0)
            o_ref[m] = out.astype(o_ref.dtype)
            return carry

        lax.fori_loop(0, tm, body, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=("bands", "interpret"))
def pointwise_conv_pallas(x: jnp.ndarray, w: jnp.ndarray,
                          shift: jnp.ndarray | None = None, *,
                          bands: int, interpret: bool) -> jnp.ndarray:
    """``x`` ``(M, Cin, w)`` coefficients, ``w`` ``(Cout, Cin)`` (batch
    norm scale folded in), ``shift`` ``(Cout,)`` DC shift or None ->
    ``(M, Cout, w)``, zero at lanes ``>= bands``.  ``interpret`` runs the
    body through the Pallas interpreter (off-TPU validation); on a TPU
    pass False."""
    m, cin, width = x.shape
    cout = w.shape[0]
    if shift is None:
        shift = jnp.zeros((cout,), x.dtype)
    tm, tco = pointwise_tiles(m, cin, cout)
    return pl.pallas_call(
        _make_kernel(tm, bands, width),
        grid=(cout // tco, m // tm),
        in_specs=[
            pl.BlockSpec((tm, cin, width), lambda o, i: (i, 0, 0)),
            pl.BlockSpec((tco, cin), lambda o, i: (o, 0)),
            pl.BlockSpec((tco, 1), lambda o, i: (o, 0)),
        ],
        out_specs=pl.BlockSpec((tm, tco, width), lambda o, i: (i, o, 0)),
        out_shape=jax.ShapeDtypeStruct((m, cout, width), x.dtype),
        interpret=interpret,
    )(x, w.astype(jnp.bfloat16), shift.reshape(cout, 1).astype(x.dtype))
