"""The control fails the comparison: with the control's logits (the
reference with float8 operands) in the served ones' place, the
harness's own comparison reads above the configuration's limit on every
seed, at the paper network's own size and at ResNet-18 widths on 32 px
images (a size a test run can hold); and a whole run with the control
comes out not correct."""
import json
import os
import time
from pathlib import Path

import pytest

from bench import harness, spec, system

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


@pytest.mark.parametrize("config,traffic,size", [
    ("paper-cifar32", "coef-closed-c128-b64", None),
    ("resnet18-256", "coef-closed-c64-b32", 32)])
def test_control_fails_the_limit(config, traffic, size):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    if size is not None:
        cfg["image_size"] = size
    mix.update(pool=64, check_sample=64, check_block=32)
    limit = cfg["limits"]["logit_gap"]
    arch = spec.arch(BENCH / "archs", cfg["arch"])
    answers = [(i, "top", None) for i in range(64)]
    for seed in (3, 2 ** 31 + 17, 4000000007):
        inputs = harness.make_inputs(seed, cfg, mix, {})
        params, state = system.weights(seed, cfg, arch)
        got = harness.check(answers, inputs, params, state, cfg, arch, mix,
                            seed, control=True)
        assert got["sample"] == 64
        assert got["logit_gap"] > limit, (config, seed, got, limit)


def test_control_run_is_not_correct(tmp_path):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = harness.run(ROOT, "paper-cifar32.coef.closed", 2 ** 32 + 5, 1.0,
                      False, t_process=time.monotonic(),
                      require_chips=False, control=True)
    gap = out["checks"]["logit_gap"]
    assert not out["correct"] and gap["value"] > gap["limit"]
    assert out["failed"] == 0
