"""Convert-once inference engine (``core.plan``): fused BN·Ξ operator
plans, per-layer band autotuning, and plan serialization.

Contracts:

* a fused-BN ``InferencePlan`` matches ``jpeg_apply`` (training=False) at
  φ = EXACT_PHI to ≤1e-4 on every dispatch path — including strided /
  projection blocks and *non-trivial* batch-norm parameters and running
  statistics (the fixture randomises them; identity BN would make the fold
  vacuous);
* save → restore through ``CheckpointManager`` is bit-identical;
* band autotuning is monotone in the energy budget (tighter budget ⇒
  fewer bands, never more);
* the precomputed path's residual join uses ``poollib.residual_add`` and
  agrees with the per-layer path through the projection shortcut.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import asm as A
from repro.core import batchnorm as BN
from repro.core import dct as dctlib
from repro.core import dispatch as DSP
from repro.core import jpeg as J
from repro.core import plan as PL
from repro.core import resnet as R


@pytest.fixture(scope="module")
def setup():
    # widths force a stride-2 + projection block in stages 1 and 2.
    spec = R.ResNetSpec(widths=(8, 16, 24), num_classes=10)
    params, state = R.init_resnet(jax.random.PRNGKey(0), spec)
    # randomise every BN so the fold carries real scales and shifts
    key = jax.random.PRNGKey(7)
    for name in params:
        if "_bn" in name or name.endswith("bn"):
            k1, k2, k3, k4, key = jax.random.split(key, 5)
            c = params[name]["gamma"].shape[0]
            params[name]["gamma"] = 1.0 + 0.2 * jax.random.normal(k1, (c,))
            params[name]["beta"] = 0.1 * jax.random.normal(k2, (c,))
            state[name]["mean"] = 0.1 * jax.random.normal(k3, (c,))
            state[name]["var"] = 1.0 + 0.3 * jax.random.uniform(k4, (c,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 32, 32)) * 0.5
    coef = jnp.moveaxis(J.jpeg_encode(x, quality=spec.quality, scaled=True),
                        1, 3)
    ref, _ = R.jpeg_apply(params, state, coef, training=False, spec=spec,
                          phi=A.EXACT_PHI)
    return spec, params, state, coef, np.asarray(ref)


def test_fold_batchnorm_is_inference_bn():
    """fold_batchnorm's (scale, shift) reproduce batchnorm_jpeg exactly."""
    c = 5
    p = BN.BatchNormParams(jnp.asarray([1.2, 0.8, 1.0, 0.5, 2.0]),
                           jnp.asarray([0.1, -0.2, 0.0, 0.3, -0.1]))
    s = BN.BatchNormState(jnp.asarray([0.4, -0.3, 0.0, 0.2, 0.1]),
                          jnp.asarray([1.5, 0.7, 1.0, 2.0, 0.9]))
    coef = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 3, c, 64))
    want, _ = BN.batchnorm_jpeg(coef, p, s, training=False)
    scale, shift = BN.fold_batchnorm(p, s)
    got = coef * scale[None, None, None, :, None]
    got = got.at[..., 0].add(shift[None, None, None, :])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("path", DSP.PATHS)
def test_fused_plan_matches_jpeg_apply(setup, path):
    """Fused-BN plan ≡ per-step network at φ=14 on every dispatch path,
    through strided and projection blocks."""
    spec, params, state, coef, ref = setup
    cfg = DSP.DispatchConfig(path=path, interpret=True)
    plan = PL.build_plan(params, state, spec, dispatch=cfg)
    # batch norm is gone from the plan: fused operators carry the shift
    assert plan.operators["stem"].shift is not None
    strided = plan.operators["s1b0"]
    assert strided["conv1"].stride == 2 and "proj" in strided
    got = np.asarray(PL.apply_plan(plan, coef))
    np.testing.assert_allclose(got, ref, atol=1e-4)


def test_fused_scale_location_per_path(setup):
    """Materialised paths fold the BN scale into Ξ (field cleared); the
    factored path never forms Ξ and must keep it for per-step apply."""
    spec, params, state, coef, _ = setup
    mat = PL.build_plan(params, state, spec,
                        dispatch=DSP.DispatchConfig(path="reference"))
    assert mat.operators["stem"].xi is not None
    assert mat.operators["stem"].scale is None
    fac = PL.build_plan(params, state, spec,
                        dispatch=DSP.DispatchConfig(path="factored"))
    assert fac.operators["stem"].xi is None
    assert fac.operators["stem"].scale is not None


@pytest.mark.parametrize("path", DSP.PATHS)
def test_plan_serialization_roundtrip(setup, path, tmp_path):
    """save_plan → CheckpointManager → load_plan is bit-identical."""
    spec, params, state, coef, _ = setup
    cfg = DSP.DispatchConfig(path=path, bands=32, interpret=True)
    plan = PL.build_plan(params, state, spec, dispatch=cfg)
    before = np.asarray(PL.apply_plan(plan, coef))
    PL.save_plan(plan, str(tmp_path))
    restored = PL.load_plan(str(tmp_path))
    assert restored.cfg == cfg
    assert restored.spec == spec
    assert restored.bands == plan.bands
    assert restored.provenance == plan.provenance
    assert plan.provenance["bands_mode"] == "global"
    after = np.asarray(PL.apply_plan(restored, coef))
    np.testing.assert_array_equal(before, after)


def test_plan_roundtrip_keeps_per_layer_bands(setup, tmp_path):
    spec, params, state, coef, _ = setup
    bands = {k: b for k, b in zip(PL.operator_keys(params, spec),
                                  (64, 56, 48, 40, 32, 48, 56, 40, 64))}
    plan = PL.build_plan(params, state, spec, bands=bands,
                         dispatch=DSP.DispatchConfig(path="reference"))
    PL.save_plan(plan, str(tmp_path))
    restored = PL.load_plan(str(tmp_path))
    assert restored.bands == bands
    np.testing.assert_array_equal(np.asarray(PL.apply_plan(plan, coef)),
                                  np.asarray(PL.apply_plan(restored, coef)))


def test_apply_operators_rejects_fused_ops(setup):
    """Feeding BN-fused plan operators to the per-step walk must fail
    loudly — silently it would apply batch norm twice."""
    spec, params, state, coef, _ = setup
    plan = PL.build_plan(params, state, spec,
                         dispatch=DSP.DispatchConfig(path="reference"))
    with pytest.raises(ValueError, match="fused batch norm"):
        R.jpeg_apply_precomputed(params, state, plan.operators, coef,
                                 spec=spec)


def test_load_plan_rejects_foreign_checkpoint(tmp_path):
    from repro.checkpoint import CheckpointManager

    CheckpointManager(str(tmp_path)).save(0, {"w": np.ones(3)})
    with pytest.raises(ValueError, match="inference plan"):
        PL.load_plan(str(tmp_path))


def test_band_budget_monotone():
    """Tighter energy budget ⇒ fewer bands, never more (per quality)."""
    for quality in (30, 50, 75):
        picks = [PL.bands_for_budget(quality, b)
                 for b in (0.5, 0.8, 0.9, 0.95, 0.99, 0.999, 1.0)]
        assert picks == sorted(picks), (quality, picks)
        assert picks[-1] == dctlib.NFREQ


def test_autotune_monotone_in_budget(setup):
    """Autotuned per-layer assignment is monotone in the budget too."""
    spec, params, state, *_ = setup
    prev = None
    for budget in (0.6, 0.9, 0.99, 1.0):
        bands = PL.autotune_bands(params, state, spec, budget=budget)
        if prev is not None:
            assert all(prev[k] <= bands[k] for k in bands), (prev, bands)
        prev = bands


def test_autotune_parity_sweep(setup):
    """The probe sweep returns an assignment that actually holds parity
    (top-1 agreement + bounded deviation) against the full-band plan."""
    spec, params, state, coef, _ = setup
    tol = 0.5
    bands = PL.autotune_bands(params, state, spec, budget=0.9,
                              probe_coef=coef, tol=tol)
    ref_cfg = DSP.DispatchConfig(path="reference")
    full = PL.build_plan(params, state, spec, dispatch=ref_cfg)
    tuned = PL.build_plan(params, state, spec, dispatch=ref_cfg, bands=bands)
    a = np.asarray(PL.apply_plan(full, coef))
    b = np.asarray(PL.apply_plan(tuned, coef))
    assert np.abs(a - b).max() <= tol
    assert (a.argmax(-1) == b.argmax(-1)).all()
    # something was actually truncated
    assert min(bands.values()) < dctlib.NFREQ


def test_precomputed_residual_uses_residual_add(setup):
    """Regression for the ``h + short`` vs ``residual_add`` split: the
    precomputed walk goes through ``poollib.residual_add`` like
    ``jpeg_apply``, and the two agree through the projection shortcut."""
    from unittest import mock

    from repro.core import plan as planlib
    from repro.core import pooling as poollib

    spec, params, state, coef, _ = setup
    cfg = DSP.DispatchConfig(path="reference", bands=32)
    ops = R.precompute_operators(params, spec, dispatch=cfg)
    calls = []
    real = poollib.residual_add

    def spy(a, b):
        calls.append(a.shape)
        return real(a, b)

    with mock.patch.object(planlib.poollib, "residual_add", spy):
        pre = R.jpeg_apply_precomputed(params, state, ops, coef, spec=spec,
                                       dispatch=cfg)
    # one residual join per block, including the projection-shortcut ones
    assert len(calls) == len(spec.widths) * spec.blocks_per_stage
    per_layer, _ = R.jpeg_apply(params, state, coef, training=False,
                                spec=spec, dispatch=cfg)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(per_layer),
                               atol=1e-4)


def test_plan_restore_tree_generic(tmp_path):
    """CheckpointManager.restore_tree round-trips a flat dict without a
    template and verifies checksums."""
    from repro.checkpoint import CheckpointManager

    m = CheckpointManager(str(tmp_path))
    arrays = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
              "b": np.ones((4,), np.int32)}
    m.save(3, arrays, extra={"tag": "x"})
    step, by_path, extra = m.restore_tree()
    assert step == 3 and extra == {"tag": "x"}
    assert len(by_path) == 2
    vals = sorted(by_path.items())
    np.testing.assert_array_equal(vals[0][1], arrays["a"])
    np.testing.assert_array_equal(vals[1][1], arrays["b"])
    with pytest.raises(FileNotFoundError):
        m.restore_tree(99)


def test_prepare_plan_rebuilds_plan_resolved_on_another_platform(tmp_path):
    """A restored plan or compiled schedule whose apply paths were resolved
    on another backend (here: recorded as a TPU's) is rebuilt, never
    served; one from this backend is restored."""
    import argparse

    from repro.configs.base import reduced_config
    from repro.launch import serve as S

    ns = argparse.Namespace(seed=0, dispatch=None, bands=None,
                            plan_dir=str(tmp_path), autotune_bands=False,
                            compiled=None, ingest="coefficients")
    cfg = reduced_config("jpeg-resnet")
    dcfg = DSP.DispatchConfig()
    here = DSP.platform()
    plan, cp, info = S.prepare_plan(ns, cfg, dcfg)
    assert info["built"] and info["platform"] == here
    assert plan.provenance["platform"] == here
    assert cp.meta["platform"] == here
    assert not S.prepare_plan(ns, cfg, dcfg)[2]["built"]

    # the schedule alone claims another platform: recompiled, plan kept
    PL.save_compiled_plan(cp._replace(meta=dict(cp.meta, platform="tpu")),
                          str(tmp_path / "compiled"))
    _, cp2, info2 = S.prepare_plan(ns, cfg, dcfg)
    assert not info2["built"] and cp2.meta["platform"] == here

    # the plan claims another platform: rebuilt from the seed
    PL.save_plan(plan._replace(provenance=dict(plan.provenance,
                                               platform="tpu")),
                 str(tmp_path))
    assert PL.load_plan(str(tmp_path)).provenance["platform"] == "tpu"
    plan3, _, info3 = S.prepare_plan(ns, cfg, dcfg)
    assert info3["built"] and plan3.provenance["platform"] == here
