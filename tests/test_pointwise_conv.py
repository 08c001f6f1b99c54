"""The 1x1 stride-1 convolution as a channel GEMM per coefficient.

A 1x1 stride-1 convolution multiplies every pixel's channel vector by one
matrix, and the blockwise DCT acts on each channel alone, so on
coefficients it is the same matrix applied to each coefficient of each
block.  The ``pointwise`` route (one einsum off the TPU, the Pallas
kernel ``pointwise_conv_pallas`` on it or in interpret mode) must equal
the factored convolution of the same kernel, the spatial 1x1 convolution
of the decoded input, and the materialised Ξ with a folded batch norm.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import conv as C
from repro.core import dispatch as DSP
from repro.core import jpeg as J
from repro.kernels.pointwise_conv import pointwise_conv_pallas, pointwise_tiles
from repro.serving.ladder import cap_operator


def _bf16(x):
    return jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)


def _inputs(rng, n=2, c=6, o=10, hw=16):
    x = jnp.asarray(rng.normal(size=(n, c, hw, hw)), jnp.float32)
    coef = jnp.moveaxis(J.jpeg_encode(x, scaled=False), 1, 3)
    k = jnp.asarray(rng.normal(size=(o, c, 1, 1)) * 0.4, jnp.float32)
    return x, coef, k


@pytest.mark.parametrize("bands", [64, 32])
def test_kernel_matches_einsum_with_dc_shift(rng, bands):
    """Interpret mode against the einsum on bfloat16-rounded operands
    (what the kernel feeds the MXU), float32 accumulation: equal to
    float32 rounding.  The shift lands on coefficient 0 only and every
    lane from ``bands`` on is zero."""
    x = jnp.asarray(rng.normal(size=(12, 16, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
    shift = jnp.asarray(rng.normal(size=(24,)), jnp.float32)
    got = pointwise_conv_pallas(x, w, shift, bands=bands, interpret=True)
    want = jnp.einsum("oc,mck->mok", _bf16(w), _bf16(x))
    want = want.at[:, :, 0].add(shift).at[:, :, bands:].set(0.0)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    plain = pointwise_conv_pallas(x, w, None, bands=bands, interpret=True)
    np.testing.assert_allclose(got[:, :, 0] - plain[:, :, 0],
                               np.broadcast_to(shift, (12, 24)), atol=1e-5)
    np.testing.assert_array_equal(got[:, :, 1:], plain[:, :, 1:])


@pytest.mark.parametrize("bands", [64, 32])
def test_route_matches_factored_and_spatial(rng, bands):
    """``auto`` sends a 1x1 stride-1 kernel to the pointwise route whatever
    its size; float32 off the TPU, so it equals the factored route and the
    band-projected spatial 1x1 convolution to float32 rounding."""
    x, coef, k = _inputs(rng)
    cfg = DSP.DispatchConfig(bands=bands)
    assert DSP.choose_path("conv", cfg, op_elems=10 ** 12,
                           pointwise=DSP.is_pointwise(k.shape, 1)) \
        == "pointwise"
    got = DSP.conv(coef, k, 1, cfg=cfg)
    factored = DSP.conv(coef, k, 1, cfg=DSP.DispatchConfig(
        path="factored", bands=bands))
    np.testing.assert_allclose(got, factored, atol=1e-4)
    spatial = jnp.moveaxis(J.jpeg_encode(C.spatial_conv(x, k), scaled=False),
                           1, 3)
    spatial = spatial.at[..., bands:].set(0.0)
    np.testing.assert_allclose(got, spatial, atol=1e-4)


@pytest.mark.parametrize("bands", [64, 32])
def test_kernel_route_matches_spatial(rng, bands):
    """The Pallas kernel through the route (interpret mode) against the
    spatial 1x1 convolution: within bfloat16 operand rounding (a relative
    2**-8 on each operand, summed over 6 channels)."""
    x, coef, k = _inputs(rng)
    got = DSP.conv(coef, k, 1, cfg=DSP.DispatchConfig(bands=bands,
                                                      interpret=True))
    spatial = jnp.moveaxis(J.jpeg_encode(C.spatial_conv(x, k), scaled=False),
                           1, 3).at[..., bands:].set(0.0)
    scale = float(jnp.abs(spatial).max())
    np.testing.assert_allclose(got, spatial, atol=2e-2 * scale)


def test_precomputed_operator_folds_batch_norm(rng):
    """``precompute_conv`` folds the batch-norm scale into the channel
    matrix and carries the DC shift; applied, it equals the materialised
    Ξ with the same fold (a forced path keeps its meaning)."""
    _x, coef, k = _inputs(rng)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, size=10), jnp.float32)
    shift = jnp.asarray(rng.normal(size=10), jnp.float32)
    op = DSP.precompute_conv(k, 1, scale=scale, shift=shift,
                             cfg=DSP.DispatchConfig())
    assert op.path == "pointwise" and op.xi.shape == (10, 6)
    assert op.scale is None and op.bn_scale is scale
    ref = DSP.precompute_conv(k, 1, scale=scale, shift=shift,
                              cfg=DSP.DispatchConfig(path="reference"))
    assert ref.path == "reference"
    np.testing.assert_allclose(DSP.apply_conv(coef, op),
                               DSP.apply_conv(coef, ref), atol=1e-4)


def test_not_pointwise():
    """Only a 1x1 kernel at stride 1 between orthonormal coefficients."""
    assert DSP.is_pointwise((4, 3, 1, 1), 1)
    assert not DSP.is_pointwise((4, 3, 1, 1), 2)
    assert not DSP.is_pointwise((4, 3, 3, 3), 1)
    assert not DSP.is_pointwise((4, 3, 1, 1), 1, in_scaled=True)


def test_ladder_caps_pointwise_operator(rng):
    """Capping lowers the band count and keeps the channel matrix: equal
    to the operator built at the capped bands."""
    _x, coef, k = _inputs(rng)
    shift = jnp.asarray(rng.normal(size=10), jnp.float32)
    top = DSP.precompute_conv(k, 1, shift=shift, cfg=DSP.DispatchConfig())
    capped = cap_operator(top, 24)
    assert capped.bands == 24 and capped.xi is top.xi
    direct = DSP.precompute_conv(k, 1, shift=shift, bands=24,
                                 cfg=DSP.DispatchConfig())
    np.testing.assert_array_equal(DSP.apply_conv(coef, capped),
                                  DSP.apply_conv(coef, direct))


@pytest.mark.parametrize("m,cin,cout", [(2048, 64, 256), (32, 2048, 512),
                                        (7, 24, 40)])
def test_tiles_divide_and_fit(m, cin, cout):
    tm, tco = pointwise_tiles(m, cin, cout)
    assert m % tm == 0 and cout % tco == 0
    assert tco * cin * 2 <= 2 << 20 or tco == cout
