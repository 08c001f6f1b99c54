"""Operation counts against XLA's own cost analysis, and the parser of
compiled programs' kernels."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import flops

ROOT = Path(__file__).resolve().parents[2]


def _in_bounds_taps(n: int, stride: int, r: int) -> int:
    """Taps of an ``r``-wide centered window, over one axis of ``n``
    inputs at ``stride``, that land inside the input."""
    pad = (r - 1) // 2
    return sum(0 <= o * stride + t - pad < n
               for o in range(n // stride) for t in range(r))


@pytest.mark.parametrize("name", ["paper-cifar32", "resnet18-256"])
def test_model_flops_matches_xla_cost_analysis(name):
    """``model_flops`` against XLA's count for the program's own spatial
    network (``core.resnet.spatial_apply``) at the published sizes.  XLA
    counts only the taps that land inside the image, and the elementwise
    batch norm, ReLU and adds besides; ``model_flops`` counts every tap,
    zero padding included (the usual convention).  So XLA's count lies
    between the in-bounds multiply-adds and 2% above them."""
    from repro.core import resnet as R
    from bench.system import stages

    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    spec = R.ResNetSpec(in_channels=3, widths=tuple(cfg["widths"]),
                        blocks_per_stage=cfg["blocks_per_stage"],
                        num_classes=cfg["num_classes"])
    params, state = jax.eval_shape(
        lambda k: R.init_resnet(k, spec), jax.random.PRNGKey(0))
    size = cfg["image_size"]
    x = jax.ShapeDtypeStruct((1, 3, size, size), jnp.float32)
    cost = jax.jit(lambda p, s, x: R.spatial_apply(
        p, s, x, training=False, spec=spec)[0]).lower(
            params, state, x).cost_analysis()
    macs = _in_bounds_taps(size, 1, 3) ** 2 * cfg["widths"][0] * 3
    n = size
    for _name, s, cin, w in stages(cfg):
        macs += _in_bounds_taps(n, s, 3) ** 2 * w * cin
        macs += _in_bounds_taps(n // s, 1, 3) ** 2 * w * w
        if s != 1 or cin != w:
            macs += _in_bounds_taps(n, s, 1) ** 2 * w * cin
        n //= s
    macs += cfg["widths"][-1] * cfg["num_classes"]
    assert 2 * macs <= cost["flops"] <= 2 * macs * 1.02
    assert 2 * macs < flops.model_flops(cfg) < 2 * macs * 1.1


def test_published_model_flops():
    cfg = json.loads((ROOT / "bench/configs/resnet18-256.json").read_text())
    assert flops.model_flops(cfg) == pytest.approx(71.09e9, rel=1e-3)
    cfg = json.loads((ROOT / "bench/configs/paper-cifar32.json").read_text())
    assert flops.model_flops(cfg) == pytest.approx(25.0e6, rel=1e-3)


HLO = """HloModule jit_inner, is_scheduled=true
  %copy-done.103 = f32[64,64]{1,0:T(8,128)S(1)} copy-done(%copy-start.103)
  %copy-done.119 = f32[64,64]{1,0:T(8,128)S(1)} copy-done(%copy-start.119)
  %bitcast.17 = f32[2097152,64]{1,0:T(8,128)} bitcast(%copy.188)
  %asm_relu_pallas.16 = f32[2097152,64]{1,0:T(8,128)} custom-call(\
%bitcast.17, %copy-done.103, %copy-done.103, %copy-done.119), \
custom_call_target="tpu_custom_call", operand_layout_constraints=\
{f32[2097152,64]{1,0}, f32[64,64]{1,0}, f32[64,64]{1,0}, f32[64,64]{1,0}}, \
metadata={op_name="jit(inner)/jit(asm_relu_pallas)/pallas_call"}
  %bitcast.212 = f32[4096,64]{1,0:T(8,128)S(1)} bitcast(%copy.77)
  %asm_relu_pallas.2 = f32[4096,64]{1,0:T(8,128)S(1)} custom-call(\
%bitcast.212, %copy-done.103, %copy-done.103, %copy-done.119), \
custom_call_target="tpu_custom_call", \
metadata={op_name="jit(inner)/jit(asm_relu_pallas)/pallas_call"}
"""
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_custom_calls_and_asm_relu_cost():
    """Shapes as compiled, memory space included: rows held in HBM cost
    their bytes (a memory-bound least time), rows the program placed in
    VMEM cost none (a compute-bound one)."""
    assert flops.module_name(HLO) == "jit_inner"
    big, small = flops.custom_calls(HLO)
    assert big["name"] == "asm_relu_pallas.16"
    assert "asm_relu_pallas" in big["op_name"]
    assert big["operands"][0] == "f32[2097152,64]{1,0:T(8,128)}"
    f, b = flops.asm_relu_cost(big["operands"], big["output"])
    assert f == 6 * 2097152 * 64 * 64
    assert b == 4 * 2 * 2097152 * 64
    t, term = flops.least_time(f, b, PEAK)
    assert term == "memory" and t == pytest.approx(b / 819e9)
    f, b = flops.asm_relu_cost(small["operands"], small["output"])
    assert b == 0
    t, term = flops.least_time(f, b, PEAK)
    assert term == "compute" and t == pytest.approx(f / 197e12)
