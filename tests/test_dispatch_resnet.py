"""End-to-end forward equivalence through the dispatch layer.

At φ = EXACT_PHI the three ways of evaluating the network —
``spatial_apply`` (oracle), ``jpeg_apply`` (per-layer dispatch), and
``jpeg_apply_precomputed`` (baked operators) — must agree to float error
on every dispatch path, and the fixed-seed logits must match the stored
golden values (guards silent re-wiring of the forward).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import asm as A
from repro.core import dispatch as DSP
from repro.core import jpeg as J
from repro.core import resnet as R

# Logits of spatial_apply for (spec, PRNGKey(0) params, PRNGKey(1) inputs)
# below, recorded on the CPU float32 build under jax 0.9.0 (its default
# threefry-partitionable PRNG).  Loose tolerance absorbs BLAS/platform
# variation; parity assertions below are the tight contract.
GOLDEN_LOGITS = np.array(
    [[2.978531, 1.273964, -0.622319, -0.800257, -2.462926, 0.495881,
      -1.071896, -1.820461, -0.340299, 0.086774],
     [2.980937, 1.31003, -0.587563, -0.729128, -2.481851, 0.439167,
      -1.034054, -2.019651, -0.298335, 0.291198]], np.float32)


@pytest.fixture(scope="module")
def setup():
    spec = R.ResNetSpec(widths=(8, 16, 24), num_classes=10)
    params, state = R.init_resnet(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 32, 32)) * 0.5
    coef = jnp.moveaxis(J.jpeg_encode(x, quality=spec.quality, scaled=True),
                        1, 3)
    spatial, _ = R.spatial_apply(params, state, x, training=False, spec=spec)
    return spec, params, state, coef, spatial


def test_spatial_matches_golden(setup):
    *_, spatial = setup
    np.testing.assert_allclose(np.asarray(spatial), GOLDEN_LOGITS, atol=2e-3)


@pytest.mark.parametrize("path", DSP.PATHS)
def test_jpeg_apply_matches_spatial(setup, path):
    spec, params, state, coef, spatial = setup
    cfg = DSP.DispatchConfig(path=path, interpret=True)
    logits, _ = R.jpeg_apply(params, state, coef, training=False, spec=spec,
                             phi=A.EXACT_PHI, dispatch=cfg)
    np.testing.assert_allclose(logits, spatial, atol=1e-4)


@pytest.mark.parametrize("path", DSP.PATHS)
def test_precomputed_matches_spatial(setup, path):
    spec, params, state, coef, spatial = setup
    cfg = DSP.DispatchConfig(path=path, interpret=True)
    ops = R.precompute_operators(params, spec, dispatch=cfg)
    for entry in ops.values():
        leaves = entry.values() if isinstance(entry, dict) else [entry]
        assert all(op.path == path for op in leaves)
    logits = R.jpeg_apply_precomputed(params, state, ops, coef, spec=spec,
                                      phi=A.EXACT_PHI, dispatch=cfg)
    np.testing.assert_allclose(logits, spatial, atol=1e-4)


def test_precomputed_matches_per_layer_banded(setup):
    """Banded inference: precomputed and per-layer agree with each other
    (both are the same truncated network, just different plumbing)."""
    spec, params, state, coef, _ = setup
    cfg = DSP.DispatchConfig(path="reference", bands=32)
    ops = R.precompute_operators(params, spec, dispatch=cfg)
    a = R.jpeg_apply_precomputed(params, state, ops, coef, spec=spec,
                                 dispatch=cfg)
    b, _ = R.jpeg_apply(params, state, coef, training=False, spec=spec,
                        dispatch=cfg)
    np.testing.assert_allclose(a, b, atol=1e-4)


def test_converted_model_keeps_its_dispatch(setup):
    """A ConvertedModel freezes the dispatch config it was converted with:
    its ASM must run banded to match its banded fused operators, even when
    the global config says otherwise."""
    from repro.core import convert as CV
    from repro.core import plan as PL

    spec, params, state, coef, _ = setup
    cfg = DSP.DispatchConfig(path="reference", bands=32)
    model = CV.convert(params, state, spec, dispatch=cfg)
    assert model.dispatch == cfg
    assert model.plan is not None and model.plan.cfg == cfg
    want = PL.apply_plan(model.plan, coef)
    with DSP.override(path="reference", bands=64):
        got = model(coef)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_converted_model_unfused_matches_per_step(setup):
    """fuse_bn=False keeps the PR-1 per-step-batchnorm contract exactly."""
    from repro.core import convert as CV

    spec, params, state, coef, _ = setup
    cfg = DSP.DispatchConfig(path="reference", bands=32)
    model = CV.convert(params, state, spec, dispatch=cfg, fuse_bn=False)
    assert model.plan is None
    want = R.jpeg_apply_precomputed(params, state, model.operators, coef,
                                    spec=spec, dispatch=cfg)
    np.testing.assert_array_equal(np.asarray(model(coef)), np.asarray(want))


def test_banded_accuracy_degrades_gracefully(setup):
    """Fig. 4b analogue for the bands knob: logit deviation from the exact
    network grows smoothly (never jumps) as bands decrease."""
    spec, params, state, coef, spatial = setup
    devs = []
    for bands in (64, 48, 32):
        cfg = DSP.DispatchConfig(path="reference", bands=bands)
        logits, _ = R.jpeg_apply(params, state, coef, training=False,
                                 spec=spec, dispatch=cfg)
        devs.append(float(jnp.abs(logits - spatial).max()))
    assert devs[0] < 1e-4
    assert devs[0] <= devs[1] + 1e-6 <= devs[2] + 2e-6, devs
    # top-1 prediction survives moderate truncation on this batch
    cfg = DSP.DispatchConfig(path="reference", bands=32)
    logits, _ = R.jpeg_apply(params, state, coef, training=False, spec=spec,
                             dispatch=cfg)
    assert (jnp.argmax(logits, -1) == jnp.argmax(spatial, -1)).all()
