"""Pallas TPU kernel: exploded JPEG-domain convolution (paper §4.1 / Alg. 1).

Applies the block-banded operator Ξ (built by ``core.conv.explode``) as
dense MXU matmuls.  Grid: ``(image, out_block_row, cout_tile, cin_tile)``;
one instance computes one output block-row tile:

    out[n, i, :, co] += Σ_{dy, dx} in[n, s·i+dy, dx::s, ci] @ Ξ[dy, dx, ci, co]

The input row is passed once per ``dy`` offset (same buffer, shifted
index map — overlapping windows are not expressible with one BlockSpec);
``ci`` is the accumulation grid dim (output block constant across it, so
revisiting is legal).  A strided conv reads its columns through the
wrapper's ``stride`` column phases (``x[..., p::stride, :]``), so the
kernel body only takes unit-stride sublane slices — Mosaic lowers no
strided vector slice.  Channel tiles keep the Ξ slices inside VMEM:
(ndx, 256, 256) fp32 per dy ≈ 0.8 MB.

This kernel is why the paper's "sparse einsum" complaint (§6) does not
apply on TPU: every matmul is a dense (bw, 256)x(256, 256) MXU op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.conv import _offsets_from
from repro.kernels.tiling import round_up

__all__ = ["jpeg_conv_pallas", "CH_TILE"]

CH_TILE = 256


def _make_kernel(ndy: int, ndx: int, stride: int, bw_out: int):
    n_in = ndy * stride

    def kernel(*refs):
        in_refs = refs[:n_in]           # [dy * stride + phase]
        xi_refs = refs[n_in: n_in + ndy]
        out_ref = refs[n_in + ndy]
        ci = pl.program_id(3)

        @pl.when(ci == 0)
        def _init():
            out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

        acc = jnp.zeros(out_ref.shape[2:], jnp.float32)
        for dy in range(ndy):
            xi_dy = xi_refs[dy]      # (1, ndx, ci_tile, co_tile)
            for dx in range(ndx):
                # padded column dx + stride·j lives in phase dx % stride
                # at position dx // stride + j
                row = in_refs[dy * stride + dx % stride][0, 0]
                q = dx // stride
                sl = row[q: q + bw_out]  # (bw_out, ci_tile)
                acc = acc + jnp.dot(sl, xi_dy[0, dx],
                                    preferred_element_type=jnp.float32)
        out_ref[0, 0] = (out_ref[0, 0] + acc.astype(out_ref.dtype))

    return kernel


@functools.partial(jax.jit, static_argnames=("stride", "interpret"))
def jpeg_conv_pallas(coef: jnp.ndarray, xi: jnp.ndarray, stride: int = 1, *,
                     interpret: bool) -> jnp.ndarray:
    """Apply an exploded operator.

    ``coef``: (N, bh, bw, Cin, nf); ``xi``: (ndy, ndx, Cin, nf, Cout, nf').
    Returns (N, bh/stride, bw/stride, Cout, nf').  Matches
    ``core.conv.apply_exploded`` exactly (tests sweep shapes); band-truncated
    operators (``nf = nf' = bands < 64``) shrink the matmuls accordingly.
    ``interpret`` runs the body through the Pallas interpreter (off-TPU
    validation); on TPU pass False.
    """
    ndy, ndx = xi.shape[0], xi.shape[1]
    nf_in, cout, nf_out = xi.shape[3], xi.shape[4], xi.shape[5]
    if coef.shape[-1] > nf_in:
        coef = coef[..., :nf_in]
    n, bh, bw, cin, _ = coef.shape
    d_min_y, _ = _offsets_from(ndy, stride)
    d_min_x, _ = _offsets_from(ndx, stride)
    bh_out, bw_out = bh // stride, bw // stride

    x = coef.reshape(n, bh, bw, cin * nf_in)
    pad_lo_y, pad_hi_y = -d_min_y, ndy - 1 + d_min_y
    pad_lo_x, pad_hi_x = -d_min_x, ndx - 1 + d_min_x
    x = jnp.pad(x, ((0, 0), (pad_lo_y, pad_hi_y), (pad_lo_x, pad_hi_x),
                    (0, 0)))
    w = xi.reshape(ndy, ndx, cin * nf_in, cout * nf_out)

    ci_full, co_full = cin * nf_in, cout * nf_out
    tci = min(CH_TILE, ci_full)
    tco = min(CH_TILE, co_full)
    if ci_full % tci:
        p = round_up(ci_full, tci) - ci_full
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, p)))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, p), (0, 0)))
        ci_full += p
    if co_full % tco:
        p = round_up(co_full, tco) - co_full
        w = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, p)))
    co_pad = w.shape[-1]
    # column phases: phase p holds padded columns p, p+s, p+2s, ...
    bw_pad = round_up(x.shape[2], stride)
    if bw_pad != x.shape[2]:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, bw_pad - x.shape[2]), (0, 0)))
    phases = [x[:, :, p::stride] for p in range(stride)]
    bw_ph = bw_pad // stride

    grid = (n, bh_out, co_pad // tco, ci_full // tci)
    in_specs = []
    for dy in range(ndy):
        for _p in range(stride):
            in_specs.append(pl.BlockSpec(
                (1, 1, bw_ph, tci),
                functools.partial(
                    lambda b, i, co, ci, dy=dy: (b, stride * i + dy, 0, ci))))
    for dy in range(ndy):
        in_specs.append(pl.BlockSpec(
            (1, ndx, tci, tco),
            functools.partial(
                lambda b, i, co, ci, dy=dy: (dy, 0, ci, co))))
    out_spec = pl.BlockSpec((1, 1, bw_out, tco),
                            lambda b, i, co, ci: (b, i, 0, co))
    out = pl.pallas_call(
        _make_kernel(ndy, ndx, stride, bw_out),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n, bh_out, bw_out, co_pad),
                                       coef.dtype),
        interpret=interpret,
    )(*(phases * ndy + [w] * ndy))
    out = out[..., : cout * nf_out]
    return out.reshape(n, bh_out, bw_out, cout, nf_out)
