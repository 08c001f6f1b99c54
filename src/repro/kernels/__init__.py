"""Pallas TPU kernels (pl.pallas_call + explicit BlockSpec VMEM tiling).

The paper's compute hot-spots, TPU-adapted (DESIGN.md §3): ``asm_relu``
(fused harmonic-mixing ReLU), ``jpeg_conv`` (block-banded exploded conv),
``pointwise_conv`` (a 1×1 stride-1 conv as a channel GEMM per
coefficient), ``block_dct`` (batched 8×8 codec transform), plus
``flash_attention`` for the assigned LM architectures.

``ops`` — jit'd wrappers (interpret-mode on CPU, Mosaic on TPU);
``ref`` — pure-jnp oracles the tests assert against.
"""
from repro.kernels import ops, ref  # noqa: F401
