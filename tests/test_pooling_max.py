"""The JPEG-domain 3x3/2 max-pool of ResNet's stem (not in the paper).

``max_pool_jpeg`` decodes, pools and encodes; it must equal exactly that
composition, keep the input's lane width with zeros from ``bands`` on,
and pool as torchvision does: padding at -inf, which for the stem's ReLU
output (>= 0) is the same as zero padding.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from jax import lax

from repro.core import jpeg as J
from repro.core import pooling as P


def _coef(x):
    return jnp.moveaxis(J.jpeg_encode(x, scaled=False), 1, 3)


def _zero_padded_pool(x):
    return lax.reduce_window(x, 0.0, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                             ((0, 0), (0, 0), (1, 1), (1, 1)))


def test_equals_decode_pool_encode(rng):
    x = jnp.asarray(rng.normal(size=(2, 3, 32, 32)), jnp.float32)
    got = P.max_pool_jpeg(_coef(x))
    assert got.shape == (2, 2, 2, 3, 64)
    np.testing.assert_allclose(got, _coef(P.max_pool_spatial(x)), atol=1e-5)


def test_nonnegative_input_zero_padding_is_torchvision(rng):
    """The stem pools a ReLU output: on input >= 0 the -inf padding and
    zero padding agree, so the transform-domain pool of the exact-ReLU
    stem is torchvision's at any padding convention."""
    x = jnp.maximum(jnp.asarray(rng.normal(size=(2, 4, 16, 16)),
                                jnp.float32), 0.0)
    assert float(x.min()) >= 0.0
    np.testing.assert_array_equal(P.max_pool_spatial(x), _zero_padded_pool(x))
    np.testing.assert_allclose(P.max_pool_jpeg(_coef(x)),
                               _coef(_zero_padded_pool(x)), atol=1e-5)


def test_negative_input_pools_with_inf_padding():
    """Negative input (a band-limited ReLU output can dip below zero at an
    edge) pools as torchvision does: the border maximum is the largest
    real pixel in the window, not the zero of the padding."""
    x = -jnp.ones((1, 1, 16, 16), jnp.float32)
    np.testing.assert_array_equal(P.max_pool_spatial(x),
                                  -jnp.ones((1, 1, 8, 8)))
    got = P.max_pool_jpeg(_coef(x))
    np.testing.assert_allclose(J.jpeg_decode(jnp.moveaxis(got, 3, 1),
                                             scaled=False),
                               -jnp.ones((1, 1, 8, 8)), atol=1e-5)


@pytest.mark.parametrize("bands", [32, 8])
def test_truncated_input_keeps_width_and_bands(rng, bands):
    """A packed input of ``w`` lanes (here ``bands``) decodes from those
    lanes; the output keeps the width and is zero from ``bands`` on."""
    x = jnp.asarray(rng.normal(size=(1, 2, 32, 32)), jnp.float32)
    coef = _coef(x)[..., :bands]
    got = P.max_pool_jpeg(coef, bands)
    assert got.shape == (1, 2, 2, 2, bands)
    img = J.jpeg_decode(jnp.moveaxis(coef, 3, 1), scaled=False)
    np.testing.assert_allclose(got, _coef(P.max_pool_spatial(img))[
        ..., :bands], atol=1e-5)
    wide = P.max_pool_jpeg(J.jpeg_encode(img, scaled=False).transpose(
        0, 2, 3, 1, 4), bands)
    np.testing.assert_array_equal(wide[..., bands:], 0.0)
