"""The basic-block ResNet of arXiv:1812.11690 Fig. 3: a 3x3 stride-1
stem, ``blocks_per_stage`` residual blocks of two 3x3 convolutions per
stage of ``widths``, the first block of every later stage at stride 2
with a 1x1 projection, global pooling and a linear head.  Parameters
carry ``core.resnet.init_resnet``'s names.

In the pixel domain, with ``P`` the band projector wherever the
JPEG-domain program truncates:

    stem:   h = P(relu(P(bn(conv(P(x))))))
    block:  s = h, or P(conv_1x1(h)) with a projection
            a = P(relu(P(bn1(conv1(h)))))
            h = P(relu(P(bn2(conv2(a))) + s))
    head:   mean pixel of each channel @ W + b

with centered zero padding.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5


def stages(cfg: dict):
    """``(name, stride, cin, cout)`` of every residual block, in order."""
    cin = cfg["widths"][0]
    for si, w in enumerate(cfg["widths"]):
        for bi in range(cfg["blocks_per_stage"]):
            yield f"s{si}b{bi}", (2 if si and not bi else 1), cin, w
            cin = w


def program_spec(cfg: dict):
    from repro.core import resnet as R

    return R.ResNetSpec(in_channels=cfg["in_channels"],
                        widths=tuple(cfg["widths"]),
                        blocks_per_stage=cfg["blocks_per_stage"],
                        num_classes=cfg["num_classes"],
                        quality=cfg["quality"], phi=cfg["asm_phi"])


@functools.partial(jax.jit, static_argnames=("layout",))
def _weights(key, layout):
    cfg = dict(layout)
    keys = iter(jax.random.split(key, 8 + 8 * len(list(stages(cfg)))))
    params, state = {}, {}

    def conv(cout, cin, r):
        std = np.sqrt(2.0 / (cin * r * r))
        return jax.random.normal(next(keys), (cout, cin, r, r)) * std

    def bn(name, c):
        k1, k2, k3, k4 = jax.random.split(next(keys), 4)
        params[name] = {"gamma": 1.0 + 0.1 * jax.random.normal(k1, (c,)),
                        "beta": 0.1 * jax.random.normal(k2, (c,))}
        state[name] = {"mean": 0.1 * jax.random.normal(k3, (c,)),
                       "var": jax.random.uniform(k4, (c,), minval=0.5,
                                                 maxval=1.5)}

    widths = cfg["widths"]
    params["stem"] = {"kernel": conv(widths[0], cfg["in_channels"], 3)}
    bn("stem_bn", widths[0])
    for name, s, cin, w in stages(cfg):
        params[name] = {"conv1": conv(w, cin, 3), "conv2": conv(w, w, 3)}
        if s != 1 or cin != w:
            params[name]["proj"] = conv(w, cin, 1)
        bn(name + "_bn1", w)
        bn(name + "_bn2", w)
    k1, k2 = jax.random.split(next(keys))
    params["head"] = {
        "w": jax.random.normal(k1, (widths[-1], cfg["num_classes"]))
        * np.sqrt(1.0 / widths[-1]),
        "b": 0.1 * jax.random.normal(k2, (cfg["num_classes"],))}
    return params, state


def weights(key, cfg: dict):
    """He-normal convolutions and head, batch norm with random running
    statistics so that its fold into the operators is exercised."""
    layout = (("widths", tuple(cfg["widths"])),
              ("blocks_per_stage", cfg["blocks_per_stage"]),
              ("in_channels", cfg["in_channels"]),
              ("num_classes", cfg["num_classes"]))
    return _weights(key, layout)


def forward(params, state, x, p, cast, cfg: dict):
    def conv(h, k, s):
        pad = (k.shape[-1] - 1) // 2
        return lax.conv_general_dilated(
            cast(h), cast(k), (s, s), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    def bn(h, name):
        inv = params[name]["gamma"] / jnp.sqrt(state[name]["var"] + EPS)
        shift = params[name]["beta"] - state[name]["mean"] * inv
        return h * inv[None, :, None, None] + shift[None, :, None, None]

    def relu(h):
        return p(jnp.maximum(h, 0.0))

    h = relu(p(bn(conv(p(x), params["stem"]["kernel"], 1), "stem_bn")))
    for name, s, _cin, _w in stages(cfg):
        blk = params[name]
        short = p(conv(h, blk["proj"], s)) if "proj" in blk else h
        a = relu(p(bn(conv(h, blk["conv1"], s), name + "_bn1")))
        c = p(bn(conv(a, blk["conv2"], 1), name + "_bn2"))
        h = relu(c + short)
    pooled = jnp.mean(h, axis=(2, 3))
    return cast(pooled) @ cast(params["head"]["w"]) + params["head"]["b"]


def model_flops(cfg: dict) -> float:
    size = cfg["image_size"]
    macs = size * size * cfg["widths"][0] * cfg["in_channels"] * 9
    for _name, s, cin, w in stages(cfg):
        size //= s
        macs += size * size * w * (cin * 9 + w * 9 + (cin if s != 1 or
                                                      cin != w else 0))
    macs += cfg["widths"][-1] * cfg["num_classes"]
    return 2.0 * macs
