"""The Pallas kernels of the serving path compile for a TPU v5e.

Each test compiles one kernel ahead of time for one chip of a *described*
``v5e:2x2`` topology (the TPU compiler ships with jaxlib; no chip is
attached) at the operator shapes the served plans run, and asserts that
the program holds the Mosaic kernel (``tpu_custom_call``).  Mosaic refuses
what the Pallas interpreter accepts — strided vector slices, lane-splitting
reshapes, VMEM overcommit — so these guard the chip path at no chip time.
The factored convolution (XLA, no Pallas) is compiled the same way at
published widths and must hold no loop and no gather: a gather over the
coefficient axis lowers to a ``while`` over its 64 lanes on a TPU.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.  The fused-block megakernel is absent on purpose: the
plan compiler never schedules it on a TPU (``plan.MEGAKERNEL_OFF_TPU``).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config, reduced_config
from repro.core import conv as C
from repro.kernels.asm_relu import asm_relu_pallas
from repro.kernels.jpeg_conv import jpeg_conv_pallas
from repro.kernels.pointwise_conv import pointwise_conv_pallas


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _conv_shapes():
    """(name, batch, grid, cin, cout, r, stride) of every conv the reduced
    plan materialises (stem, s0b0, s1b0 and the s2b0 projection; s2b0's
    3x3 convs are over the materialise limit and go factored) plus the
    published-width stem."""
    red, full = reduced_config("jpeg-resnet"), get_config("jpeg-resnet")
    w = red.widths
    g = red.image_size // 8
    return [
        ("reduced/stem", 4, g, red.in_channels, w[0], 3, 1),
        ("reduced/s0b0", 4, g, w[0], w[0], 3, 1),
        ("reduced/s1b0/conv1", 4, g, w[0], w[1], 3, 2),
        ("reduced/s1b0/proj", 4, g, w[0], w[1], 1, 2),
        ("reduced/s1b0/conv2", 4, g // 2, w[1], w[1], 3, 1),
        ("reduced/s2b0/proj", 4, g // 2, w[1], w[2], 1, 2),
        ("full/stem", 8, full.image_size // 8, full.in_channels,
         full.widths[0], 3, 1),
    ]


@pytest.mark.parametrize("name,n,grid,cin,cout,r,stride", _conv_shapes(),
                         ids=[c[0] for c in _conv_shapes()])
def test_jpeg_conv_compiles_for_v5e(one_chip, name, n, grid, cin, cout, r,
                                    stride):
    xi_shape = jax.eval_shape(
        lambda k: C.explode(k, stride),
        jax.ShapeDtypeStruct((cout, cin, r, r), jnp.float32)).shape
    compiled = jpeg_conv_pallas.lower(
        _spec((n, grid, grid, cin, 64), one_chip), _spec(xi_shape, one_chip),
        stride=stride, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows,nf", [
    (4 * 4 * 4 * 16, 64),      # reduced s0 activation, full bands
    (4 * 2 * 2 * 32, 32),      # reduced s1 activation at the b32 tier
    (4 * 1 * 1 * 64, 24),      # reduced s2 activation at the b24 tier
    (8 * 32 * 32 * 64, 64),    # published-width s0 activation, batch 8
    (8 * 4 * 4 * 512, 64),     # published-width s3 activation
], ids=["reduced-s0-64", "reduced-s1-32", "reduced-s2-24", "full-s0-64",
        "full-s3-64"])
def test_asm_relu_compiles_for_v5e(one_chip, rows, nf):
    compiled = asm_relu_pallas.lower(_spec((rows, nf), one_chip), 14,
                                     interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _factored_shapes():
    """(name, grid, cin, cout, r, stride) of published-width factored convs:
    stage 0's 3x3, and s1b0's stride-2 3x3 and 1x1 projection."""
    full = get_config("jpeg-resnet")
    w, g = full.widths, full.image_size // 8
    return [
        ("full/s0b0", g, w[0], w[0], 3, 1),
        ("full/s1b0/conv1", g, w[0], w[1], 3, 2),
        ("full/s1b0/proj", g, w[0], w[1], 1, 2),
    ]


@pytest.mark.parametrize("name,grid,cin,cout,r,stride", _factored_shapes(),
                         ids=[c[0] for c in _factored_shapes()])
def test_factored_conv_compiles_without_loops(one_chip, name, grid, cin,
                                              cout, r, stride):
    """Batch 8: below it the TPU compiler lowers a gather differently."""
    conv = jax.jit(lambda c, k: C._jpeg_conv_factored(
        c, k, stride, quality=50, in_scaled=False, out_scaled=False))
    text = conv.lower(_spec((8, grid, grid, cin, 64), one_chip),
                      _spec((cout, cin, r, r), one_chip)).compile().as_text()
    assert "while(" not in text
    assert "gather(" not in text


@pytest.mark.parametrize("blocks,cin,cout,bands", [
    (32 * 8 * 8, 64, 256, 64),     # ResNet-50 s0 conv3 / s0b0 proj, batch 32
    (32 * 8 * 8, 256, 64, 64),     # s0 conv1
    (32 * 4 * 4, 512, 128, 32),    # s1 conv1 at the b32 tier
    (32 * 1 * 1, 2048, 512, 64),   # s3 conv1
    (32 * 1 * 1, 512, 2048, 64),   # s3 conv3
], ids=["r50-s0-conv3", "r50-s0-conv1", "r50-s1-conv1-b32", "r50-s3-conv1",
        "r50-s3-conv3"])
def test_pointwise_conv_compiles_for_v5e(one_chip, blocks, cin, cout, bands):
    """ResNet-50's 1x1 stride-1 convolutions at its published widths, on
    the packed ``(N·bh·bw, C, 64)`` activation of a batch of 32."""
    compiled = pointwise_conv_pallas.lower(
        _spec((blocks, cin, 64), one_chip), _spec((cout, cin), one_chip),
        _spec((cout,), one_chip), bands=bands, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
