"""Encoder → decoder round-trip property tests (bit-exact entropy coding)
and the exactness of the normalization stage's linear maps."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import dct as dctlib
from repro.core import jpeg as J
from repro.codec import bitstream as bs
from repro.codec import encode as enc
from repro.codec import normalize as nm

from _hypothesis_compat import given, settings, st


def _random_coefficients(rng, by, bx, density=0.3, lim=1023):
    c = np.zeros((by, bx, dctlib.NFREQ), np.int32)
    mask = rng.random((by, bx, dctlib.NFREQ)) < density
    c[mask] = rng.integers(-lim, lim + 1, int(mask.sum()))
    c[..., 0] = rng.integers(-1024, 1017, (by, bx))
    return c


@settings(max_examples=12)
@given(st.integers(1, 5), st.integers(1, 5), st.integers(0, 10_000),
       st.booleans())
def test_roundtrip_single_component(by, bx, seed, use_restart):
    rng = np.random.default_rng(seed)
    c = _random_coefficients(rng, by, bx,
                             density=float(rng.uniform(0.02, 0.6)))
    q = np.rint(dctlib.quantization_table(50)).astype(np.int64)
    ri = int(rng.integers(1, by * bx + 1)) if use_restart else 0
    data = enc.encode_baseline([c], [q], restart_interval=ri)
    dec = bs.decode_jpeg(data)
    assert np.array_equal(dec.coefficients[0], c)
    assert np.array_equal(dec.qtables[dec.components[0].tq], q)
    assert dec.restart_interval == ri


@settings(max_examples=8)
@given(st.integers(0, 10_000), st.booleans())
def test_roundtrip_three_components(seed, subsampled):
    rng = np.random.default_rng(seed)
    if subsampled:
        comps = [_random_coefficients(rng, 4, 4, 0.25)] + \
                [_random_coefficients(rng, 2, 2, 0.25) for _ in range(2)]
        sampling = [(2, 2), (1, 1), (1, 1)]
    else:
        comps = [_random_coefficients(rng, 3, 2, 0.25) for _ in range(3)]
        sampling = [(1, 1)] * 3
    qs = [np.rint(dctlib.quantization_table(q)).astype(np.int64)
          for q in (50, 75, 75)]
    data = enc.encode_baseline(comps, qs, sampling=sampling)
    dec = bs.decode_jpeg(data)
    for i in range(3):
        assert np.array_equal(dec.coefficients[i], comps[i]), i
        assert np.array_equal(dec.qtable(i), qs[i]), i
        assert (dec.components[i].h, dec.components[i].v) == sampling[i]


def test_roundtrip_16bit_qtable():
    rng = np.random.default_rng(7)
    c = _random_coefficients(rng, 2, 2, 0.3, lim=100)
    q = np.full(dctlib.NFREQ, 300, np.int64)  # needs 16-bit DQT precision
    data = enc.encode_baseline([c], [q])
    dec = bs.decode_jpeg(data)
    assert np.array_equal(dec.coefficients[0], c)
    assert np.array_equal(dec.qtable(0), q)


def test_roundtrip_extreme_runs():
    """ZRL chains, EOB-less blocks, all-zero blocks."""
    c = np.zeros((2, 2, dctlib.NFREQ), np.int32)
    c[0, 0, 63] = 5          # 62 zeros -> 3 ZRLs + run
    c[0, 1, :] = 0           # all-zero block (EOB immediately)
    c[1, 0, 1:] = 1          # dense block, no EOB
    c[1, 1, 0] = -1024       # extreme DC swing after 0
    q = np.rint(dctlib.quantization_table(50)).astype(np.int64)
    data = enc.encode_baseline([c], [q])
    assert np.array_equal(bs.decode_jpeg(data).coefficients[0], c)


def test_encoder_rejects_out_of_range():
    q = np.rint(dctlib.quantization_table(50)).astype(np.int64)
    c = np.zeros((1, 1, dctlib.NFREQ), np.int32)
    c[0, 0, 3] = 2000  # AC size category 11 — not codable in baseline
    with pytest.raises(ValueError):
        enc.encode_baseline([c], [q])
    c = np.zeros((1, 2, dctlib.NFREQ), np.int32)
    c[0, 0, 0], c[0, 1, 0] = -2000, 2000  # DC diff 4000 -> category 12
    with pytest.raises(ValueError):
        enc.encode_baseline([c], [q])


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([(2, 2), (2, 1), (1, 2)]))
def test_upsample_matches_spatial_replication(seed, f):
    """Coefficient-domain chroma upsampling == decode, replicate pixels,
    re-encode — exactly (replication is linear, R is orthonormal)."""
    fy, fx = f
    rng = np.random.default_rng(seed)
    coef = rng.normal(size=(2, 3, dctlib.NFREQ))
    up = nm.upsample_coefficients(coef, fy, fx)
    spat = np.asarray(J.jpeg_decode(jnp.asarray(coef[None]), scaled=False))[0]
    rep = np.repeat(np.repeat(spat, fy, 0), fx, 1)
    ref = np.asarray(J.jpeg_encode(jnp.asarray(rep[None]), scaled=False))[0]
    assert np.abs(up - ref).max() < 1e-5


def test_rescale_is_the_exact_linear_map():
    rng = np.random.default_rng(3)
    v = rng.integers(-500, 500, (2, 2, dctlib.NFREQ))
    q_file = np.rint(dctlib.quantization_table(85, dc_is_mean=False))
    out = nm.rescale_component(v, q_file, quality=50)
    expect = (v * q_file / (128.0 * dctlib.quantization_table(50)))
    assert np.abs(out - expect).max() < 1e-6


def test_mixed_quality_normalizes_to_one_convention():
    """The same image encoded at different qualities lands near the same
    canonical coefficients after normalization (within quantization
    error) — the property that lets one plan serve mixed traffic."""
    rng = np.random.default_rng(11)
    img = np.clip(rng.normal(size=(32, 32)) * 0.3, -1, 127 / 128.0)
    exact = np.asarray(J.jpeg_encode(jnp.asarray(img[None]), quality=50,
                                     scaled=True))[0]
    for q in (35, 60, 90):
        qt = np.rint(dctlib.quantization_table(
            q, dc_is_mean=False)).astype(np.int64)
        data = enc.encode_pixels(img, qtable=qt)
        dec = bs.decode_jpeg(data)
        got = nm.normalize_image(dec, quality=50)[:, :, 0]
        # per-coefficient quantization error bound: half a file step,
        # mapped through the same linear rescale
        bound = 0.5 * qt / (128.0 * dctlib.quantization_table(50)) + 1e-6
        assert (np.abs(got - exact) <= bound).all(), q


def test_fit_grid_pad_and_crop():
    coef = np.arange(3 * 5 * 64, dtype=np.float32).reshape(3, 5, 64)
    padded = nm.fit_grid(coef, 4, 6)
    assert padded.shape == (4, 6, 64)
    assert np.array_equal(padded[:3, :5], coef)
    assert not padded[3].any() and not padded[:, 5].any()
    cropped = nm.fit_grid(coef, 2, 3)  # center crop
    assert np.array_equal(cropped, coef[0:2, 1:4])


def test_420_normalization_exact_in_dct_basis():
    """Regression: the canonical per-index rescale must come AFTER the
    chroma upsample (the upsample map mixes zigzag indices).  Ground
    truth: de-quantize chroma, IDCT to pixels, replicate 2×2, re-encode
    under the canonical convention."""
    rng = np.random.default_rng(17)
    y = _random_coefficients(rng, 4, 4, 0.2, lim=200)
    cb = _random_coefficients(rng, 2, 2, 0.2, lim=200)
    cr = _random_coefficients(rng, 2, 2, 0.2, lim=200)
    qt = np.rint(dctlib.quantization_table(
        70, dc_is_mean=False)).astype(np.int64)
    data = enc.encode_baseline([y, cb, cr], [qt] * 3,
                               sampling=[(2, 2), (1, 1), (1, 1)])
    got = nm.normalize_image(bs.decode_jpeg(data), quality=50)
    for ci, comp in ((1, cb), (2, cr)):
        deq = comp * qt.astype(np.float64)
        px = np.asarray(J.jpeg_decode(jnp.asarray(deq[None]),
                                      scaled=False))[0]
        rep = np.repeat(np.repeat(px, 2, 0), 2, 1) / 128.0
        ref = np.asarray(J.jpeg_encode(jnp.asarray(rep[None]), quality=50,
                                       scaled=True))[0]
        assert np.abs(got[:, :, ci] - ref).max() < 1e-5, ci


def test_grayscale_file_into_3channel_network():
    rng = np.random.default_rng(5)
    img = np.clip(rng.normal(size=(16, 16)) * 0.3, -1, 127 / 128.0)
    data = enc.encode_pixels(img, quality=50)
    out = nm.normalize_image(bs.decode_jpeg(data), quality=50, channels=3)
    assert out.shape == (2, 2, 3, 64)
    assert np.array_equal(out[:, :, 0], out[:, :, 1])
