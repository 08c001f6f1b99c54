"""The bottleneck ResNet's module (``archs/resnet_bottleneck.py``): its
weights are a function of the seed alone, ResNet-50 v1.5 has its
published 25,557,032 parameters, ``model_flops`` gives the published
multiply-adds (torchvision's 4,089,184,256 at 224 px), and the cell
``resnet50-256.coef.closed`` resolves to this module."""
import json
from pathlib import Path

import numpy as np
import jax

from bench import spec, system

ROOT = Path(__file__).resolve().parents[2]
CFG = json.loads((ROOT / "bench/configs/resnet50-256.json").read_text())
ARCH = spec.arch(ROOT / "bench/archs", "resnet_bottleneck")
SMALL = dict(CFG, widths=[4, 8], blocks_per_stage=[1, 2], num_classes=5,
             image_size=32)


def test_weights_deterministic_per_seed():
    a = system.weights(2 ** 33 + 5, SMALL, ARCH)
    b = system.weights(2 ** 33 + 5, SMALL, ARCH)
    c = system.weights(2 ** 33 + 6, SMALL, ARCH)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(x, y) for x, y in
               zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(c)))
    params, state = a
    assert set(params["s0b0"]) == {"conv1", "conv2", "conv3", "proj"}
    assert "proj" not in params["s1b1"] and "s0b0_bnp" in state


def test_resnet50_parameter_count():
    params, _ = jax.eval_shape(lambda k: ARCH.weights(k, CFG),
                               jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(params)) == 25_557_032


def test_model_flops_exact():
    assert ARCH.model_flops(CFG) == 2 * 5_340_348_416
    assert ARCH.model_flops(dict(CFG, image_size=224)) == 2 * 4_089_184_256


def test_cell_resolves():
    cell = spec.cell(ROOT, "resnet50-256.coef.closed")
    assert cell["arch"].__name__ == "bench_arch_resnet_bottleneck"
    assert cell["config"]["blocks_per_stage"] == [3, 4, 6, 3]
    assert cell["traffic"]["max_batch"] == 32
    assert [m["name"] for m in cell["per_layer"]] == [
        "pointwise_conv_roofline"]


def test_pointwise_roofline_cost():
    """``2·M·O·C·w`` FLOPs; bytes of the HBM operands and output."""
    from bench.metrics import pointwise_conv_roofline as m

    ops = ["f32[2048,64,64]{2,1,0}", "bf16[256,64]{1,0}",
           "f32[256,1]{1,0}"]
    flops, nbytes = m.cost(ops, "f32[2048,256,64]{2,1,0}")
    assert flops == 2.0 * 2048 * 256 * 64 * 64
    assert nbytes == (2048 * 64 * 64 * 4 + 256 * 64 * 2 + 256 * 4
                      + 2048 * 256 * 64 * 4)
