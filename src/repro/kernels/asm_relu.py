"""Pallas TPU kernel: fused ASM ReLU (paper §4.2) over tiles of JPEG blocks.

One grid step processes ``TILE_BLOCKS`` 8×8 blocks resident in VMEM:

    approx  = tile @ R_phi      # (T, 64)·(64, 64) MXU
    mask    = approx > 0        # VPU
    spatial = tile @ R          # MXU
    out     = (mask ? spatial : 0) @ Rᵀ   # VPU select + MXU

Three small matmuls per tile, no HBM round-trip for the spatial
intermediate — this is the TPU-native replacement for the paper's sparse
harmonic-mixing einsum (DESIGN.md §3).  The 64-wide contraction is padded
to 128 lanes by Mosaic; tiles are 8·128 rows to keep the MXU busy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import dct as dctlib
from repro.kernels.tiling import pick_tile

__all__ = ["asm_relu_pallas", "TILE_BLOCKS"]

TILE_BLOCKS = 1024


def _asm_relu_kernel(coef_ref, recon_phi_ref, recon_ref, recon_t_ref, out_ref):
    tile = coef_ref[...]
    approx = jnp.dot(tile, recon_phi_ref[...],
                     preferred_element_type=jnp.float32)
    spatial = jnp.dot(tile, recon_ref[...],
                      preferred_element_type=jnp.float32)
    masked = jnp.where(approx > 0, spatial, 0.0)
    out_ref[...] = jnp.dot(masked, recon_t_ref[...],
                           preferred_element_type=jnp.float32
                           ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("phi", "interpret"))
def asm_relu_pallas(coef: jnp.ndarray, phi: int = 14, *,
                    interpret: bool) -> jnp.ndarray:
    """ASM ReLU over ``(N, nf)`` zigzag coefficients (orthonormal units).

    ``nf`` may be < 64 for band-truncated activations (paper §6 sparsity):
    the reconstruction operands shrink to ``(nf, 64)`` / ``(64, nf)`` so the
    dropped coefficients never enter the MXU.  ``interpret=True`` runs the
    kernel body through the Pallas interpreter (off-TPU validation); on
    TPU pass ``interpret=False``.
    """
    n, nf = coef.shape
    # Tile picked *from n* (balanced, sublane-aligned — kernels.tiling):
    # a serve-time single-image request runs one right-sized tile instead
    # of padding up to TILE_BLOCKS and wasting the VPU on zeros.
    tile = pick_tile(n, TILE_BLOCKS)
    if n % tile:
        pad = tile - n % tile
        coef = jnp.pad(coef, ((0, pad), (0, 0)))
    grid = (coef.shape[0] // tile,)
    recon = jnp.asarray(dctlib.reconstruction_matrix()[:nf], coef.dtype)
    recon_phi = jnp.asarray(dctlib.truncated_reconstruction_matrix(phi)[:nf],
                            coef.dtype)
    out = pl.pallas_call(
        _asm_relu_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, nf), lambda i: (i, 0)),
            pl.BlockSpec((nf, 64), lambda i: (0, 0)),
            pl.BlockSpec((nf, 64), lambda i: (0, 0)),
            pl.BlockSpec((64, nf), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, nf), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(coef.shape, coef.dtype),
        interpret=interpret,
    )(coef, recon_phi, recon, recon.T)
    return out[:n]
