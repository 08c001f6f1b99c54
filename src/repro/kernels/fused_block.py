"""Pallas megakernel: one fused JPEG-domain residual block per grid step.

``InferencePlan`` execution (PR 2) still paid one dispatch call per layer:
every intermediate — conv1 output, ASM mask input, conv2 output, residual
sum — made an HBM round trip.  This kernel executes an **entire residual
block** on a VMEM-resident activation tile:

    h   = conv1(x)·Ξ₁ + shift₁          # banded GEMMs, BN scale already in Ξ
    h   = ASM(h)                         # mask from the same VMEM tile
    y   = conv2(h)·Ξ₂ + shift₂
    y  += shortcut                       # identity, or proj conv of x
    out = ASM(y)                         # epilogue at the residual join bands

Grid: ``(image,)`` — one instance owns one image's full block grid, which
is what the paper's scale makes natural: after the stem a 32×32 input is a
4×4 block grid, so whole feature maps are a few hundred KB.  All operands
are the **tile-packed** banded operators from ``kernels.tiling``
(``PackedConv`` / ``PackedAsm``): band padding and batch-norm folds were
baked at plan-compile time, so the kernel body is nothing but dense 2-D
MXU dots, a compare, and adds — zero reshapes of HBM-resident data.

VMEM budget per block tile (float32 bytes, per grid instance):

    x tile        bh·bw·Cin·w_in·4           (+ halo-padded copy, same order)
    h tile        (bh/s)·(bw/s)·C·w_mid·4    (+ halo-padded copy for conv2)
    y/out tiles   (bh/s)·(bw/s)·C·w_out·4
    Ξ₁, Ξ₂, proj  ndy·ndx·(Cin·w_in)·(Cout·w_out)·4 each
    ASM operands  w·(2·64)·4 + 64·w·4 per stage

``core.plan.compile_plan`` evaluates this sum against its ``vmem_budget``
(default 12 MB of the ~16 MB/core budget) and falls back to per-layer
execution for blocks that do not fit.

The body is interpreter-validated only: Mosaic (the TPU compiler) rejects
two of its in-kernel ops — the lane-splitting reshape in ``_asm_tile``
(``infer-vector-layout: unsupported shape cast``) and the strided slice
of a stride-2 ``_conv_tile`` (``Only 2D gather is supported``).  So
``core.plan.compile_plan`` never schedules it on a TPU
(``MEGAKERNEL_OFF_TPU``); the fused step there runs the operand-identical
XLA twin :func:`fused_block_reference`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import conv as convlib
from repro.core import dct as dctlib
from repro.core.conv import _offsets_from
from repro.kernels.tiling import PackedAsm, PackedConv, fit_width, \
    packed_asm_apply, packed_conv_apply

__all__ = ["fused_block_pallas", "fused_block_reference",
           "fused_block_spatial", "fused_stem_spatial", "fused_vmem_bytes"]


def _conv_tile(x, xi_ref, shift_ref, stride: int, ndy: int, ndx: int):
    """Banded conv over one image's VMEM tile: Σ_offsets slice·Ξ + shift."""
    bh, bw, k = x.shape
    m = xi_ref.shape[2]
    d_min_y, _ = _offsets_from(ndy, stride)
    d_min_x, _ = _offsets_from(ndx, stride)
    bh_o, bw_o = bh // stride, bw // stride
    xp = jnp.pad(x, ((-d_min_y, ndy - 1 + d_min_y),
                     (-d_min_x, ndx - 1 + d_min_x), (0, 0)))
    acc = jnp.zeros((bh_o * bw_o, m), jnp.float32)
    for o in range(ndy * ndx):
        iy, ix = o // ndx, o % ndx
        sl = xp[iy: iy + stride * bh_o: stride,
                ix: ix + stride * bw_o: stride]
        acc = acc + jnp.dot(sl.reshape(bh_o * bw_o, k), xi_ref[o],
                            preferred_element_type=jnp.float32)
    return acc.reshape(bh_o, bw_o, m) + shift_ref[0]


def _asm_tile(h, cat_ref, rt_ref, w: int):
    """ASM ReLU on a resident tile: mask and value from one lane-wide dot."""
    nf = dctlib.NFREQ
    shape = h.shape
    t = h.reshape(-1, w)
    both = jnp.dot(t, cat_ref[...], preferred_element_type=jnp.float32)
    masked = jnp.where(both[:, :nf] > 0, both[:, nf:], 0.0)
    out = jnp.dot(masked, rt_ref[...], preferred_element_type=jnp.float32)
    return out.reshape(shape)


def _make_kernel(conv1: PackedConv, asm_mid: PackedAsm, conv2: PackedConv,
                 asm_out: PackedAsm, proj: PackedConv | None, out_dtype):
    def kernel(*refs):
        (x_ref, xi1, sh1, cat1, rt1, xi2, sh2, cat2, rt2, *rest) = refs
        out_ref = rest[-1]
        x = x_ref[0]
        h = fit_width(x, conv1.cin, conv1.w_in)
        h = _conv_tile(h, xi1, sh1, conv1.stride, conv1.ndy, conv1.ndx)
        h = _asm_tile(h, cat1, rt1, asm_mid.w)
        h = fit_width(h, conv2.cin, conv2.w_in)
        y = _conv_tile(h, xi2, sh2, conv2.stride, conv2.ndy, conv2.ndx)
        y = fit_width(y, conv2.cout, asm_out.w)
        if proj is not None:
            pxi, psh = rest[0], rest[1]
            short = fit_width(x, proj.cin, proj.w_in)
            short = _conv_tile(short, pxi, psh, proj.stride, proj.ndy,
                               proj.ndx)
            short = fit_width(short, proj.cout, asm_out.w)
        else:
            short = fit_width(x, conv1.cin, asm_out.w)
        y = y + short
        out_ref[0] = _asm_tile(y, cat2, rt2, asm_out.w).astype(out_dtype)

    return kernel


def fused_block_pallas(x: jnp.ndarray, conv1: PackedConv, asm_mid: PackedAsm,
                       conv2: PackedConv, asm_out: PackedAsm,
                       proj: PackedConv | None = None, *,
                       interpret: bool) -> jnp.ndarray:
    """Run one residual block fused; ``x`` is ``(N, bh, bw, Cin·w)``.

    Each operand is applied at its own packed band width; the activation
    is width-fitted on the VMEM tile between stages (slice / zero lanes —
    never a GEMM-dimension inflation).  Matches
    :func:`fused_block_reference` on every shape the compiler emits
    (tests sweep strides, shortcuts, bands, and φ).
    """
    n, bh, bw, k_in = x.shape
    if k_in % conv1.cin:
        raise ValueError(f"input width {k_in} not a multiple of "
                         f"Cin={conv1.cin}")
    s = conv1.stride
    bh_o, bw_o = bh // s, bw // s
    m_out = conv2.cout * conv2.w_out

    def whole(shape):
        nd = len(shape)
        return pl.BlockSpec(shape, lambda b, nd=nd: (0,) * nd)

    in_specs = [pl.BlockSpec((1, bh, bw, k_in), lambda b: (b, 0, 0, 0)),
                whole(conv1.xi.shape), whole(conv1.shift.shape),
                whole(asm_mid.cat.shape), whole(asm_mid.recon_t.shape),
                whole(conv2.xi.shape), whole(conv2.shift.shape),
                whole(asm_out.cat.shape), whole(asm_out.recon_t.shape)]
    operands = [x, conv1.xi, conv1.shift, asm_mid.cat, asm_mid.recon_t,
                conv2.xi, conv2.shift, asm_out.cat, asm_out.recon_t]
    if proj is not None:
        in_specs += [whole(proj.xi.shape), whole(proj.shift.shape)]
        operands += [proj.xi, proj.shift]
    out = pl.pallas_call(
        _make_kernel(conv1, asm_mid, conv2, asm_out, proj, x.dtype),
        grid=(n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh_o, bw_o, m_out),
                               lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, bh_o, bw_o, m_out), x.dtype),
        interpret=interpret,
    )(*operands)
    return out


def fused_block_reference(x: jnp.ndarray, conv1: PackedConv,
                          asm_mid: PackedAsm, conv2: PackedConv,
                          asm_out: PackedAsm,
                          proj: PackedConv | None = None) -> jnp.ndarray:
    """XLA twin of the megakernel over the same packed operators — the
    parity oracle the interpreted kernel is tested against.  (Off-TPU
    *serving* uses :func:`fused_block_spatial` instead, which is the
    FLOP-optimal lowering of the same block.)
    """
    h = packed_conv_apply(fit_width(x, conv1.cin, conv1.w_in), conv1)
    h = packed_asm_apply(h, asm_mid)
    y = packed_conv_apply(fit_width(h, conv2.cin, conv2.w_in), conv2)
    y = fit_width(y, conv2.cout, asm_out.w)
    if proj is None:
        short = fit_width(x, conv1.cin, asm_out.w)
    else:
        short = packed_conv_apply(fit_width(x, proj.cin, proj.w_in), proj)
        short = fit_width(short, proj.cout, asm_out.w)
    return packed_asm_apply(y + short, asm_out)


# --------------------------------------------------------------------------
# Spatial-resident fused block: the XLA (off-TPU) serving path
# --------------------------------------------------------------------------
#
# On the MXU the banded Ξ matmuls above are the right shape.  On XLA
# backends the FLOP count rules instead, and Ξ application costs
# ``ndy·ndx·Cin·Cout·b²`` per block versus ``64·r²·Cin·Cout`` for the
# spatial convolution it factors through — ~(b/8)² more work.  Per-layer
# execution cannot exploit this (each op must return to the coefficient
# domain to stay composable), but a *fused block* can: decode once at
# block entry, run both convolutions on the spatial tile, take the ASM
# masks directly from it (ASM ≡ project-to-bands → threshold), and encode
# once at the join.  Mathematically identical to the Ξ walk — every band
# truncation of the plan is reproduced as a subspace projection — and
# parity-tested against it.


def _blocks_to_image(px: jnp.ndarray) -> jnp.ndarray:
    """``(N, bh, bw, C, 64)`` raster-ordered block pixels → ``(N, C, H, W)``."""
    n, bh, bw, c, _ = px.shape
    b = dctlib.BLOCK
    t = px.reshape(n, bh, bw, c, b, b).transpose(0, 3, 1, 4, 2, 5)
    return t.reshape(n, c, bh * b, bw * b)


def _image_to_blocks(img: jnp.ndarray) -> jnp.ndarray:
    """``(N, C, H, W)`` → ``(N, bh, bw, C, 64)`` raster-ordered pixels."""
    n, c, h, w = img.shape
    b = dctlib.BLOCK
    t = img.reshape(n, c, h // b, b, w // b, b).transpose(0, 2, 4, 1, 3, 5)
    return t.reshape(n, h // b, w // b, c, b * b)


def _recon(dtype) -> jnp.ndarray:
    return jnp.asarray(dctlib.reconstruction_matrix(), dtype)


def _recon_phi(phi: int, dtype) -> jnp.ndarray:
    return jnp.asarray(dctlib.truncated_reconstruction_matrix(phi), dtype)


def _spatial_op(img: jnp.ndarray, op) -> jnp.ndarray:
    """One conv layer in pixel space: BN-scaled kernel, stride, DC shift
    (a coefficient-DC shift ``s`` is a per-pixel bias ``s/8`` — the
    orthonormal DC basis value)."""
    k = op.kernel
    if op.bn_scale is not None:
        k = k * op.bn_scale[:, None, None, None]
    img = convlib.spatial_conv(img, k, op.stride)
    if op.shift is not None:
        img = img + (op.shift / dctlib.BLOCK)[None, :, None, None]
    return img


def _pad_last(t: jnp.ndarray, w: int) -> jnp.ndarray:
    if t.shape[-1] == w:
        return t
    return jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, w - t.shape[-1])])


def fused_block_spatial(x: jnp.ndarray, blk, phi: int) -> jnp.ndarray:
    """Whole-block execution on a spatial-resident activation.

    ``blk`` is a ``core.plan.CompiledBlock`` (its ``ops`` carry the raw
    kernels plus the retained BN folds); ``x`` is the packed
    ``(N, bh, bw, Cin·w_in)`` coefficient activation with true content in
    the first ``blk.bands_in`` lanes per channel.
    """
    ops = blk.ops
    c1, c2 = ops["conv1"], ops["conv2"]
    pr = ops.get("proj")
    n, bh, bw, k_in = x.shape
    w_in = k_in // blk.cin
    r = _recon(x.dtype)
    rphi = _recon_phi(phi, x.dtype)
    coef = x.reshape(n, bh, bw, blk.cin, w_in)
    b1, b2 = c1.bands, c2.bands

    # conv1 (input truncated to its band cutoff, decoded once)
    bin1 = min(b1, blk.bands_in, w_in)
    img = _blocks_to_image(coef[..., :bin1] @ r[:bin1])
    px = _image_to_blocks(_spatial_op(img, c1))
    # mid ASM at b1: project onto the kept bands, threshold, keep pixels
    t = px @ r[:b1].T
    px = jnp.where(t @ rphi[:b1] > 0, t @ r[:b1], 0.0)
    # conv2 input truncation (nested projections collapse: P_a∘P_b = P_min)
    bin2 = min(b2, b1)
    px = (px @ r[:bin2].T) @ r[:bin2]
    img = _spatial_op(_blocks_to_image(px), c2)
    y = _image_to_blocks(img) @ r[:b2].T  # encode + truncate, once per block
    # shortcut: identity stays coefficients (never decoded); projection
    # shortcut runs its own spatial conv
    if pr is not None:
        binp = min(pr.bands, blk.bands_in, w_in)
        simg = _spatial_op(_blocks_to_image(coef[..., :binp] @ r[:binp]), pr)
        s_coef = _image_to_blocks(simg) @ r[:pr.bands].T
    else:
        s_coef = coef[..., : min(blk.bands_in, w_in)]
    j = blk.bands_out
    yj = _pad_last(y, j) + _pad_last(s_coef, j)
    # join ASM at the residual-join bands, back to packed coefficients
    out = jnp.where(yj @ rphi[:j] > 0, yj @ r[:j], 0.0) @ r[:j].T
    s = c1.stride
    return _pad_last(out, blk.w_out).reshape(n, bh // s, bw // s,
                                             blk.cout * blk.w_out)


def fused_stem_spatial(coef: jnp.ndarray, op, phi: int,
                       w_out: int) -> jnp.ndarray:
    """Spatial-resident stem: de-quantize + decode the kept bands, one
    spatial conv, encode, ASM at the stem bands.  ``coef`` is the raw
    ``(N, bh, bw, C, 64)`` quantization-scaled input."""
    n, bh, bw = coef.shape[:3]
    r = _recon(coef.dtype)
    rphi = _recon_phi(phi, coef.dtype)
    b = op.bands
    t = coef[..., :b]
    if op.in_scaled:
        q = jnp.asarray(dctlib.quantization_table(op.quality), coef.dtype)
        t = t * q[:b]
    img = _spatial_op(_blocks_to_image(t @ r[:b]), op)
    y = _image_to_blocks(img) @ r[:b].T
    out = jnp.where(y @ rphi[:b] > 0, y @ r[:b], 0.0) @ r[:b].T
    cout = op.kernel.shape[0]
    s = op.stride
    return _pad_last(out, w_out).reshape(n, bh // s, bw // s, cout * w_out)


def fused_vmem_bytes(bh: int, bw: int, conv1: PackedConv, asm_mid: PackedAsm,
                     conv2: PackedConv, asm_out: PackedAsm,
                     proj: PackedConv | None = None) -> int:
    """Estimated per-instance VMEM footprint (see module docstring)."""
    f = 4  # float32
    s = conv1.stride
    bh_o, bw_o = bh // s, bw // s
    x_t = bh * bw * conv1.cin * conv1.w_in * f
    h_t = bh_o * bw_o * conv1.cout * conv1.w_out * f
    y_t = bh_o * bw_o * conv2.cout * conv2.w_out * f
    ops = conv1.nbytes + conv2.nbytes + asm_mid.nbytes + asm_out.nbytes
    if proj is not None:
        ops += proj.nbytes
    # x and h each exist twice (raw + halo-padded copy); y + out once each.
    return 2 * x_t + 2 * h_t + 2 * y_t + ops
