"""The plain reference against the program's own plan walk, on the
CPU at the paper network's published size, at full and truncated
bands."""
import json
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bench import data, reference, spec, system

ROOT = Path(__file__).resolve().parents[2]
CFG = json.loads((ROOT / "bench/configs/paper-cifar32.json").read_text())
ARCH = spec.arch(ROOT / "bench/archs", CFG["arch"])


@pytest.fixture(scope="module")
def model():
    from repro.core import dispatch as dl
    from repro.core import plan as planlib

    params, state = system.weights(2 ** 33 + 5, CFG, ARCH)
    plan = planlib.build_plan(params, state, ARCH.program_spec(CFG),
                              dispatch=dl.DispatchConfig(path="reference"))
    return params, state, plan


@pytest.mark.parametrize("quality", [50, 90])
@pytest.mark.parametrize("bands", [64, 48, 24])
def test_reference_matches_plan_walk(model, quality, bands):
    from repro.core import plan as planlib
    from repro.serving.ladder import cap_plan

    params, state, plan = model
    q = np.rint(data.ijg_table(quality))
    imgs = data.images(system.seed_key(11, "images"), n=6, size=32,
                       channels=3, classes=10)
    luma, chroma = (np.asarray(a) for a in data.quantize(imgs, q))
    coef = data.coefficients(luma, chroma, q, CFG["quality"])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(planlib.apply_plan(
            cap_plan(plan, None if bands == 64 else bands),
            jnp.asarray(coef)))
    ref = reference.logits(params, state, CFG, ARCH, luma, chroma, q,
                           bands=bands, block=4)
    scale = max(1.0, float(np.abs(ref).max()))
    assert np.abs(got - ref).max() / scale < 1e-4
