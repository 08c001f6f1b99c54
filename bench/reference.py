"""The plain reference: the configuration's network in the pixel domain.

The JPEG-domain ResNet of arXiv:1812.11690 is, layer for layer, the
spatial ResNet seen through a blockwise DCT: a convolution in the
transform domain is decode -> spatial convolution -> encode (Ξ = J∘C∘J̃),
an ASM ReLU with all 15 frequencies is exact ReLU, batch norm acts
per channel, and global pooling reads the DC terms, i.e. the mean pixel.
What the transform domain adds is band truncation: an operator at ``b``
bands keeps only the first ``b`` zigzag coefficients of each 8x8 block,
on its input and on its output, and so does ReLU.  Here that is a
64x64 projection ``P_b`` on each block's pixels.  So the reference is a
spatial network with ``P_b`` wherever the program truncates; the
configuration's architecture module (``archs/<arch>.py``, ``forward``)
writes out the layers, and this module runs it in float32 at
``"highest"`` matmul precision.  ``P`` is the identity at 64 bands.  It
imports nothing of the program and uses only the weights and the JPEG
integers that the benchmark itself made.

The control (``cast=fp8``) rounds every operand of every convolution and
of the head to float8 e4m3, scaled per tensor, as an fp8 matrix unit
would take them: one step below the bfloat16 operands of the
configuration's stated precision.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from bench import data


def projector(bands: int) -> np.ndarray:
    """``P_b`` on a block's 64 row-major pixels."""
    r = data.basis()
    keep = (np.arange(data.NFREQ) < bands).astype(np.float64)
    return (r.T * keep) @ r


def fp8(x):
    """Round to float8 e4m3 under a per-tensor scale, back to float32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q / scale


def _identity(x):
    return x


def _frozen(value):
    """A configuration as a hashable static argument of ``jit``."""
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    if isinstance(value, list):
        return tuple(_frozen(v) for v in value)
    return value


@functools.partial(jax.jit, static_argnames=("arch", "layout", "control",
                                             "truncate"))
def _forward(params, state, x, p_b, *, arch, layout, control: bool,
             truncate: bool):
    cast = fp8 if control else _identity

    def p(h):
        if not truncate:
            return h
        return data.from_blocks(data.to_blocks(h) @ p_b)

    return arch.forward(params, state, x, p, cast, dict(layout))


def logits(params, state, cfg: dict, arch, luma, chroma, qtable, *,
           bands: int, control: bool = False, block: int = 32
           ) -> np.ndarray:
    """Reference logits of ``arch`` (the configuration's architecture
    module) on the images whose JPEG integers are ``luma``
    ``(N, bh, bw, 64)`` and ``chroma`` ``(N, 2, bh, bw, 64)`` under
    ``qtable``, at ``bands``, ``block`` images at a time (the last block
    padded, so that one program serves every block)."""
    layout = _frozen(cfg)
    p_b = jnp.asarray(projector(bands), jnp.float32)
    out = []
    n = luma.shape[0]
    with jax.default_matmul_precision("highest"):
        for i in range(0, n, block):
            y, c = luma[i:i + block], chroma[i:i + block]
            if y.shape[0] < block:
                pad = block - y.shape[0]
                y = np.concatenate([y, np.zeros((pad,) + y.shape[1:],
                                                y.dtype)])
                c = np.concatenate([c, np.zeros((pad,) + c.shape[1:],
                                                c.dtype)])
            x = data.pixels(y, c, qtable)
            z = _forward(params, state, x, p_b, arch=arch, layout=layout,
                         control=control, truncate=bands < data.NFREQ)
            out.append(np.asarray(z)[: min(block, n - i)])
    return np.concatenate(out)
