"""The JPEG linear map: roundtrips, explicit J/J~ tensors, linearity."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import dct as D
from repro.core import jpeg as J


@pytest.mark.parametrize("scaled", [True, False])
@pytest.mark.parametrize("shape", [(8, 8), (16, 24), (2, 3, 32, 16)])
def test_roundtrip(rng, scaled, shape):
    img = rng.normal(size=shape)
    co = J.jpeg_encode(jnp.asarray(img), scaled=scaled)
    back = J.jpeg_decode(co, scaled=scaled)
    assert np.allclose(back, img, atol=1e-5)


def test_dc_coefficient_is_block_mean(rng):
    img = rng.normal(size=(16, 16))
    co = J.jpeg_encode(jnp.asarray(img), scaled=True)
    means = np.asarray(img).reshape(2, 8, 2, 8).transpose(0, 2, 1, 3).mean((-1, -2))
    assert np.allclose(np.asarray(co)[..., 0], means, atol=1e-6)
    co_u = J.jpeg_encode(jnp.asarray(img), scaled=False)
    assert np.allclose(np.asarray(co_u)[..., 0], 8 * means, atol=1e-5)


def test_explicit_j_tensor_matches_encode(rng):
    x = rng.normal(size=(16, 16))
    jt = J.jpeg_tensor(16, 16)
    c_tensor = np.einsum("hwxyk,hw->xyk", jt, x)
    c_fn = np.asarray(J.jpeg_encode(jnp.asarray(x)))
    assert np.allclose(c_tensor, c_fn, atol=1e-6)


def test_explicit_ijpeg_tensor_inverts(rng):
    x = rng.normal(size=(16, 16))
    c = np.asarray(J.jpeg_encode(jnp.asarray(x)))
    ijt = J.ijpeg_tensor(16, 16)
    assert np.allclose(np.einsum("xykhw,xyk->hw", ijt, c), x, atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**32 - 1), st.floats(-3, 3), st.floats(-3, 3))
def test_linearity_property(seed, a, b):
    """J(aF + bG) == a J(F) + b J(G) — the foundation of the whole paper."""
    r = np.random.default_rng(seed)
    f, g = r.normal(size=(2, 16, 16))
    lhs = J.jpeg_encode(jnp.asarray(a * f + b * g))
    rhs = a * J.jpeg_encode(jnp.asarray(f)) + b * J.jpeg_encode(jnp.asarray(g))
    assert np.allclose(lhs, rhs, atol=1e-4)


def test_lossy_roundtrip_reduces_energy(rng):
    img = rng.normal(size=(32, 32))
    out = J.jpeg_round_trip_lossy(jnp.asarray(img), quality=10)
    # quantization must change the image but keep it bounded
    assert not np.allclose(out, img, atol=1e-3)
    assert np.abs(np.asarray(out)).max() < 10 * np.abs(img).max() + 1


def test_block_unblock_inverse(rng):
    img = rng.normal(size=(3, 24, 16))
    assert np.allclose(J.unblock_image(J.block_image(jnp.asarray(img))), img)


_QT = np.linspace(1.0, 40.0, D.NFREQ)  # an explicit, non-standard q-table


def _numpy_encode(img, q):
    """Blocks -> separable 2-D DCT -> zigzag -> ÷ q, in float64 numpy."""
    *lead, h, w = img.shape
    blocks = img.reshape(*lead, h // 8, 8, w // 8, 8).swapaxes(-3, -2)
    flat = D.dct2(blocks).reshape(*blocks.shape[:-2], D.NFREQ)
    return flat[..., D.zigzag_permutation()] / q


def _numpy_decode(coef, q):
    """× q -> un-zigzag -> separable inverse DCT -> unblock, float64 numpy."""
    flat = np.empty_like(coef)
    flat[..., D.zigzag_permutation()] = coef * q
    blocks = D.idct2(flat.reshape(*flat.shape[:-1], 8, 8))
    *lead, bh, bw, _, _ = blocks.shape
    return blocks.swapaxes(-3, -2).reshape(*lead, bh * 8, bw * 8)


_CONVENTIONS = [
    pytest.param(dict(scaled=True), D.quantization_table(50), id="scaled"),
    pytest.param(dict(scaled=True, quality=90), D.quantization_table(90),
                 id="scaled-q90"),
    pytest.param(dict(scaled=False), np.ones(D.NFREQ), id="unscaled"),
    pytest.param(dict(scaled=True, qtable=_QT), _QT, id="qtable"),
]


def _rel_err(got, want):
    return np.abs(np.asarray(got, np.float64) - want).max() / np.abs(want).max()


@pytest.mark.parametrize("kw,q", _CONVENTIONS)
def test_encode_matches_numpy_definition(rng, kw, q):
    img = rng.normal(size=(2, 3, 16, 24))
    got = J.jpeg_encode(jnp.asarray(img, jnp.float32), **kw)
    assert _rel_err(got, _numpy_encode(img, q)) < 1e-5


@pytest.mark.parametrize("kw,q", _CONVENTIONS)
def test_decode_matches_numpy_definition(rng, kw, q):
    coef = rng.normal(size=(2, 3, 2, 3, D.NFREQ))
    got = J.jpeg_decode(jnp.asarray(coef, jnp.float32), **kw)
    assert _rel_err(got, _numpy_decode(coef, q)) < 1e-5


@pytest.mark.parametrize("nf", [1, 24, 48])
def test_decode_of_leading_coefficients_zero_fills(rng, nf):
    """Fewer than 64 coefficients decode as if the rest were zero."""
    coef = rng.normal(size=(3, 2, 2, nf)).astype(np.float32)
    full = np.pad(coef, [(0, 0)] * 3 + [(0, D.NFREQ - nf)])
    np.testing.assert_allclose(J.jpeg_decode(jnp.asarray(coef)),
                               J.jpeg_decode(jnp.asarray(full)), atol=1e-6)


@pytest.mark.parametrize("fn,shape", [
    (J.jpeg_encode, (2, 3, 16, 24)),
    (J.jpeg_decode, (2, 3, 2, 3, D.NFREQ)),
], ids=["encode", "decode"])
@pytest.mark.parametrize("kw,q", _CONVENTIONS)
def test_transform_lowers_without_gather(fn, shape, kw, q):
    """The zigzag order is folded into a dense matrix: no gather is emitted
    (on a TPU a gather over the coefficient axis lowers to a loop)."""
    hlo = jax.jit(lambda x: fn(x, **kw)).lower(
        jax.ShapeDtypeStruct(shape, jnp.float32)).as_text()
    assert "stablehlo.gather" not in hlo
    assert "dot_general" in hlo
