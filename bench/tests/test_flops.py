"""Operation counts against XLA's own cost analysis, and the parser of
compiled programs' kernels."""
import json
from pathlib import Path

import numpy as np
import jax
import jax.extend.core as jcore
import jax.numpy as jnp
import pytest

from bench import flops, spec

ROOT = Path(__file__).resolve().parents[2]


def _in_bounds_macs(jaxpr) -> int:
    """Multiply-adds of every convolution and matrix product in
    ``jaxpr`` and the jaxprs inside it, counting only the taps of a
    convolution that land inside its input."""
    macs = 0
    for eqn in jaxpr.eqns:
        shapes = [v.aval.shape for v in eqn.invars]
        if eqn.primitive.name == "conv_general_dilated":
            prm = eqn.params
            lhs_spec, rhs_spec, out_spec = prm["dimension_numbers"]
            (lhs, rhs), out = shapes, eqn.outvars[0].aval.shape
            taps = 1
            for i, (s, (lo, _hi), d) in enumerate(zip(
                    prm["window_strides"], prm["padding"],
                    prm["rhs_dilation"])):
                n, r = lhs[lhs_spec[2 + i]], rhs[rhs_spec[2 + i]]
                taps *= sum(0 <= o * s + t * d - lo < n
                            for o in range(out[out_spec[2 + i]])
                            for t in range(r))
            macs += (out[out_spec[0]] * out[out_spec[1]]
                     * rhs[rhs_spec[1]] * taps)
        elif eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            macs += int(np.prod(eqn.outvars[0].aval.shape)
                        * np.prod([shapes[0][d] for d in contract]))
        for sub in jax.tree_util.tree_leaves(
                list(eqn.params.values()),
                is_leaf=lambda x: isinstance(x, (jcore.Jaxpr,
                                                 jcore.ClosedJaxpr))):
            if isinstance(sub, jcore.ClosedJaxpr):
                macs += _in_bounds_macs(sub.jaxpr)
            elif isinstance(sub, jcore.Jaxpr):
                macs += _in_bounds_macs(sub)
    return macs


@pytest.mark.parametrize("name", [c["name"] for c in
                                  spec.load_benchmark(ROOT)["configs"]])
def test_model_flops_matches_xla_cost_analysis(name):
    """The architecture module's ``model_flops`` against XLA's count for
    the program's own spatial network (``core.resnet.spatial_apply`` of
    the module's ``program_spec``) at the configuration's size.  XLA
    counts only the taps that land inside the image, and the elementwise
    batch norm, ReLU and adds besides; ``model_flops`` counts every tap,
    zero padding included (the usual convention).  So XLA's count lies
    between the in-bounds multiply-adds, read from the network's own
    convolutions and products, and 2% above them."""
    from repro.core import resnet as R

    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    arch = spec.arch(ROOT / "bench/archs", cfg["arch"])
    program_spec = arch.program_spec(cfg)
    params, state = jax.eval_shape(
        lambda k: R.init_resnet(k, program_spec), jax.random.PRNGKey(0))
    size = cfg["image_size"]
    x = jax.ShapeDtypeStruct((1, cfg["in_channels"], size, size),
                             jnp.float32)

    def fn(p, s, x):
        return R.spatial_apply(p, s, x, training=False, spec=program_spec)[0]

    cost = jax.jit(fn).lower(params, state, x).cost_analysis()
    macs = _in_bounds_macs(jax.make_jaxpr(fn)(params, state, x).jaxpr)
    assert 2 * macs <= cost["flops"] <= 2 * macs * 1.02
    assert 2 * macs < arch.model_flops(cfg) < 2 * macs * 1.1


@pytest.mark.parametrize("name,want", [("resnet18-256", 71_094_476_800),
                                       ("paper-cifar32", 25_003_264)])
def test_published_model_flops(name, want):
    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    assert spec.arch(ROOT / "bench/archs", cfg["arch"]).model_flops(
        cfg) == want


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
