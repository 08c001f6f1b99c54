"""ResNet-50's block and stem through the served path, at a small size.

A bottleneck spec (widths (8, 16), blocks (1, 2), 7x7/2 stem and 3x3/2
max-pool, 64 px) goes ``build_plan`` -> ``build_ladder`` (top tier and a
32-band tier) -> ``compile_plan``'s schedule, folded step by step as the
grid cells run it, and is compared with the plain pixel-domain
reference of the benchmark (``bench/archs/resnet_bottleneck.py``, loaded
from its file: it imports nothing of the program) on the same seeded
weights, and with ``resnet.spatial_apply``.  Everything here is float32
on the CPU, where the pointwise route is one einsum, so the only
difference from the reference is float32 summation order (transform
matmuls against direct convolutions): the tolerance is 1e-4 of the
logit scale, about 100 float32 ulps of it.
"""
import importlib.util
import json
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import serving
from repro.core import dispatch as D
from repro.core import jpeg as J
from repro.core import plan as PL
from repro.core import resnet as R

ROOT = Path(__file__).resolve().parents[1]
CFG = {"arch": "resnet_bottleneck", "image_size": 64, "in_channels": 3,
       "widths": [8, 16], "blocks_per_stage": [1, 2], "num_classes": 10,
       "asm_phi": 14, "quality": 50, "bands": 64}
TOL = 1e-4


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"_arch_{name}", ROOT / "bench/archs" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    arch = _load("resnet_bottleneck")
    params, state = arch.weights(jax.random.PRNGKey(3), CFG)
    spec = arch.program_spec(CFG)
    key = jax.random.PRNGKey(4)
    small = jax.random.uniform(key, (2, 3, 16, 16), minval=-1, maxval=1)
    x = jax.image.resize(small, (2, 3, 64, 64), "linear")
    coef = jnp.moveaxis(J.jpeg_encode(x, quality=spec.quality, scaled=True),
                        1, 3)
    plan = PL.build_plan(params, state, spec,
                         dispatch=D.DispatchConfig(bands=64))
    ladder = serving.build_ladder(plan, caps=(None, 32), image_size=64)
    return arch, params, state, spec, x, coef, ladder


def _projector(bands):
    if bands >= 64:
        return lambda h: h
    return lambda h: J.jpeg_decode(
        J.jpeg_encode(h, scaled=False)[..., :bands], scaled=False)


def _gap(got, want):
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()) / max(
        1.0, float(np.abs(np.asarray(want)).max()))


@pytest.mark.parametrize("executor", [None, "gemm"])
@pytest.mark.parametrize("tier,bands", [(0, 64), (1, 32)])
def test_served_schedule_matches_reference(model, tier, bands, executor):
    arch, params, state, spec, x, coef, ladder = model
    cp = ladder.tiers[tier].compiled
    assert set(cp.bands.values()) == {bands}
    got = coef
    for _name, step in PL.compiled_steps(cp, executor=executor):
        got = step(got)
    want = arch.forward(params, state, x, _projector(bands), lambda a: a,
                        CFG)
    assert _gap(got, want) <= TOL


def test_plan_walk_and_spatial_oracle_agree(model):
    arch, params, state, spec, x, coef, ladder = model
    spatial, _ = R.spatial_apply(params, state, x, training=False, spec=spec)
    want = arch.forward(params, state, x, _projector(64), lambda a: a, CFG)
    assert _gap(spatial, want) <= TOL
    assert _gap(PL.apply_plan(ladder.base, coef), want) <= TOL


def test_schedule_layout(model):
    """Every bottleneck block on the per-layer walk, with its reason; the
    1x1 stride-1 convolutions (conv1, conv3 and s0b0's projection) on the
    pointwise route, the stride-2 projection and 3x3s on the others."""
    *_, ladder = model
    cp = ladder.top.compiled
    assert [b.kind for b in cp.blocks] == ["layers"] * 3
    assert all("bottleneck" in cp.meta["layers"][b.name] for b in cp.blocks)
    assert cp.meta["routes"] == {"pointwise": 7, "factored": 0,
                                 "materialised": 5}
    assert cp.meta["pointwise"] == "einsum"
    paths = {k: op.path for k, op in PL._flat_ops(ladder.base).items()}
    assert {k for k, p in paths.items() if p == "pointwise"} == {
        "s0b0/conv1", "s0b0/conv3", "s0b0/proj", "s1b0/conv1", "s1b0/conv3",
        "s1b1/conv1", "s1b1/conv3"}
    names = [n for n, _ in PL.compiled_steps(cp)]
    assert names == ["stem", "s0b0", "s1b0", "s1b1", "head"]


def test_resnet50_route_counts():
    """ResNet-50 v1.5 at its published widths: 33 pointwise convolutions,
    19 factored (16 3x3 and 3 stride-2 projections) and the materialised
    7x7/2 stem.  Counted while building the plan on abstract weights, so
    nothing of the published size is computed."""
    arch = _load("resnet_bottleneck")
    cfg = json.loads((ROOT / "bench/configs/resnet50-256.json").read_text())
    shapes = jax.eval_shape(lambda k: arch.weights(k, cfg),
                            jax.random.PRNGKey(0))
    counts = {}

    def build(params, state):
        plan = PL.build_plan(params, state, arch.program_spec(cfg),
                             dispatch=D.DispatchConfig(bands=64))
        counts.update(PL._route_counts(plan))
        return jnp.zeros(())

    jax.eval_shape(build, *shapes)
    assert counts == {"pointwise": 33, "factored": 19, "materialised": 1}


def test_compiled_bottleneck_plan_round_trips(model, tmp_path):
    """The spec's new fields (block, stem, per-stage counts) survive the
    compiled plan's save and load, and the restored schedule serves the
    same logits."""
    *_, coef, ladder = model
    cp = ladder.top.compiled
    PL.save_compiled_plan(cp, str(tmp_path))
    back = PL.load_compiled_plan(str(tmp_path))
    assert back.spec == cp.spec
    np.testing.assert_array_equal(PL.apply_compiled(back, coef),
                                  PL.apply_compiled(cp, coef))


@pytest.mark.parametrize("route", ["jpeg_apply", "jpeg_apply_precomputed",
                                   "apply_operators"])
def test_unserved_routes_refuse_bottleneck(model, route):
    """The routes the serving path does not use implement basic blocks
    only; given a bottleneck spec they raise rather than compute
    something else."""
    arch, params, state, spec, x, coef, ladder = model
    with pytest.raises(NotImplementedError):
        if route == "jpeg_apply":
            R.jpeg_apply(params, state, coef, training=False, spec=spec)
        elif route == "jpeg_apply_precomputed":
            R.jpeg_apply_precomputed(params, state, {}, coef, spec=spec)
        else:
            PL.apply_operators(params, state, {}, coef, spec=spec)


def test_spec_counts_and_layout():
    """Per-stage counts, a bad count, and ResNet-50's block layout."""
    spec = R.ResNetSpec(widths=(64, 128, 256, 512),
                        blocks_per_stage=(3, 4, 6, 3), block="bottleneck",
                        stem="7x7s2-maxpool", num_classes=1000)
    blocks = list(R._stages(spec))
    assert len(blocks) == 16
    assert [sl.kernel for sl in blocks[0].slots] == [1, 3, 1]
    assert [sl.stride for sl in blocks[3].slots] == [1, 2, 1]
    assert blocks[0].proj.stride == 1 and blocks[0].cout == 256
    assert [b.name for b in blocks if b.proj] == ["s0b0", "s1b0", "s2b0",
                                                  "s3b0"]
    assert R.stem_layout(spec) == (7, 2, True)
    with pytest.raises(ValueError):
        list(R._stages(spec._replace(blocks_per_stage=(3, 4))))
