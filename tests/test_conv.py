"""Convolution explosion: exact equivalence with spatial convolution."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import conv as C
from repro.core import jpeg as J


def _to_jpeg_layout(x):
    return jnp.moveaxis(J.jpeg_encode(x, scaled=False), 1, 3)


def _from_jpeg_layout(c):
    return J.jpeg_decode(jnp.moveaxis(c, 3, 1), scaled=False)


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("r", [1, 3, 5])
def test_explosion_matches_spatial(rng, stride, r):
    k = jnp.asarray(rng.normal(size=(4, 3, r, r)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 3, 16, 16)), jnp.float32)
    spatial = C.spatial_conv(x, k, stride)
    out = C.jpeg_conv(_to_jpeg_layout(x), k, stride)
    assert np.allclose(_from_jpeg_layout(out), spatial, atol=1e-4)


def test_scaled_input_convention(rng):
    """Input layer: de-quantization folded into the operator (Eq. 20)."""
    k = jnp.asarray(rng.normal(size=(2, 3, 3, 3)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 3, 24, 24)), jnp.float32)
    coef_scaled = jnp.moveaxis(J.jpeg_encode(x, scaled=True), 1, 3)
    out = C.jpeg_conv(coef_scaled, k, 1, in_scaled=True)
    spatial = C.spatial_conv(x, k, 1)
    assert np.allclose(_from_jpeg_layout(out), spatial, atol=1e-4)


def test_bias_on_dc(rng):
    k = jnp.asarray(rng.normal(size=(2, 3, 3, 3)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(2,)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 3, 16, 16)), jnp.float32)
    out = C.jpeg_conv(_to_jpeg_layout(x), k, 1, bias=b)
    spatial = C.spatial_conv(x, k, 1, bias=b)
    assert np.allclose(_from_jpeg_layout(out), spatial, atol=1e-4)


def test_full_operator_matches_basis(rng):
    """Paper Algorithm 1 (full position-dependent operator) == basis path."""
    k = jnp.asarray(rng.normal(size=(2, 3, 3, 3)) * 0.3, jnp.float32)
    x = _to_jpeg_layout(jnp.asarray(rng.normal(size=(2, 3, 16, 16)), jnp.float32))
    for stride in (1, 2):
        op = C.explode_full(k, 2, 2, stride, scaled=False)
        a = C.apply_full(x, op)
        b = C.jpeg_conv(x, k, stride)
        assert np.allclose(a, b, atol=1e-4), stride


def test_gradient_equivalence(rng):
    """The conversion is exact for *training* too: dL/dK agrees across
    domains (the paper's 'more complex gradient' is the same gradient)."""
    k = jnp.asarray(rng.normal(size=(2, 3, 3, 3)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 3, 16, 16)), jnp.float32)
    coef = _to_jpeg_layout(x)

    def loss_spatial(kk):
        return jnp.sum(C.spatial_conv(x, kk, 1) ** 2)

    def loss_jpeg(kk):
        return jnp.sum(C.jpeg_conv(coef, kk, 1) ** 2)

    # Parseval: sum of squares is preserved by the orthonormal transform,
    # so the losses and their gradients must agree.
    g1 = jax.grad(loss_spatial)(k)
    g2 = jax.grad(loss_jpeg)(k)
    assert np.allclose(g1, g2, atol=1e-2, rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_linearity_in_kernel(seed):
    """explode(aK1 + bK2) == a explode(K1) + b explode(K2)."""
    r = np.random.default_rng(seed)
    k1 = jnp.asarray(r.normal(size=(2, 2, 3, 3)), jnp.float32)
    k2 = jnp.asarray(r.normal(size=(2, 2, 3, 3)), jnp.float32)
    lhs = C.explode(2.0 * k1 - 0.5 * k2, 1)
    rhs = 2.0 * C.explode(k1, 1) - 0.5 * C.explode(k2, 1)
    assert np.allclose(lhs, rhs, atol=1e-5)


def test_block_offsets():
    assert C.block_offsets(1, 3) == (-1, 1)
    assert C.block_offsets(2, 3) == (-1, 1)
    assert C.block_offsets(1, 1) == (0, 0)
    assert C.block_offsets(2, 1) == (0, 1)
    with pytest.raises(ValueError):
        C.block_offsets(1, 4)


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("bands", [8, 24, 48])
@pytest.mark.parametrize("r", [1, 3])
def test_factored_matches_exploded_at_bands(rng, stride, bands, r):
    """The factored path (two dense 64×64 transforms around a spatial conv)
    equals the band-truncated materialised operator, both conventions."""
    k = jnp.asarray(rng.normal(size=(4, 3, r, r)) * 0.3, jnp.float32)
    coef = jnp.asarray(rng.normal(size=(2, 4, 4, 3, 64)), jnp.float32)
    kw = dict(quality=50, in_scaled=True, out_scaled=False)
    xi = C.explode(k, stride, bands=bands, **kw)
    want = C.pad_bands(C.apply_exploded(coef, xi, stride))
    got = C._jpeg_conv_factored(coef, k, stride, bands=bands, **kw)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())
