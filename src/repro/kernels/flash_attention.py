"""Pallas TPU kernel: flash attention with GQA + causal/sliding-window masks.

Grid ``(batch, kv_head, q_group, q_tile, kv_tile)`` with the kv-tile as the
innermost (accumulation) dimension; running max / denominator / weighted
accumulator live in VMEM scratch across kv tiles (the online-softmax
recurrence).  Working set per instance: q tile (Tq, hd) + kv tiles
(Tk, hd)×2 + (Tq, Tk) scores — all VMEM.  The pure-JAX twin used by the
models is ``repro.models.layers.attention``; tests assert they agree with
``ref.flash_attention_ref`` across shape/dtype sweeps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas", "Q_TILE", "KV_TILE"]

Q_TILE = 256
KV_TILE = 256
NEG_INF = -1e30


def _make_kernel(causal: bool, window: int | None, qt: int, kt: int,
                 scale: float, n_kv: int, t_valid: int):
    def kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        i = pl.program_id(3)
        j = pl.program_id(4)

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        q = q_ref[0, 0, 0].astype(jnp.float32) * scale  # (qt, hd)
        k = k_ref[0, 0].astype(jnp.float32)             # (kt, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        qpos = i * qt + jax.lax.broadcasted_iota(jnp.int32, (qt, kt), 0)
        kpos = j * kt + jax.lax.broadcasted_iota(jnp.int32, (qt, kt), 1)
        mask = kpos < t_valid  # key padding
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

        @pl.when(j == n_kv - 1)
        def _finish():
            denom = jnp.maximum(l_scr[...], 1e-30)
            o_ref[0, 0, 0] = (acc_scr[...] / denom[:, None]
                              ).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window: int | None = None,
                           interpret: bool) -> jnp.ndarray:
    """``q``: (B, S, H, hd); ``k``/``v``: (B, T, KVH, hd) -> (B, S, H, hd)."""
    b, s, h, hd = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qt = min(Q_TILE, s)
    kt = min(KV_TILE, t)
    s_pad = -(-s // qt) * qt
    t_pad = -(-t // kt) * kt
    qx = q.reshape(b, s, kvh, g, hd).transpose(0, 2, 3, 1, 4)  # (B,KVH,G,S,hd)
    if s_pad != s:
        qx = jnp.pad(qx, ((0, 0), (0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    kx = k.transpose(0, 2, 1, 3)  # (B, KVH, T, hd)
    vx = v.transpose(0, 2, 1, 3)
    if t_pad != t:
        # padding keys sit at positions >= t; mask them out via window/causal
        # or explicit validity below
        kx = jnp.pad(kx, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
        vx = jnp.pad(vx, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
    n_q, n_kv = s_pad // qt, t_pad // kt
    grid = (b, kvh, g, n_q, n_kv)
    kernel = _make_kernel(causal, window, qt, kt, hd ** -0.5, n_kv, t)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, qt, hd), lambda b_, n, g_, i, j: (b_, n, g_, i, 0)),
            pl.BlockSpec((1, 1, kt, hd), lambda b_, n, g_, i, j: (b_, n, j, 0)),
            pl.BlockSpec((1, 1, kt, hd), lambda b_, n, g_, i, j: (b_, n, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, qt, hd),
                               lambda b_, n, g_, i, j: (b_, n, g_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, s_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qt,), jnp.float32),
            pltpu.VMEM((qt,), jnp.float32),
            pltpu.VMEM((qt, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qx, kx, vx)
    out = out[:, :, :, :s]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)
