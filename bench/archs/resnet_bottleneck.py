"""ResNet-50 v1.5 (arXiv:1512.03385 Table 1, "50-layer"; v1.5 as
torchvision's ``resnet50``, with the stride on the 3x3): a 7x7 stride-2
stem and a 3x3 stride-2 max-pool, then ``blocks_per_stage[i]``
bottleneck blocks per stage of ``widths``, each 1x1 -> 3x3 -> 1x1 to
four times the stage's width, the first block of every later stage at
stride 2; a 1x1 projection with batch norm where the shape changes;
global pooling and a linear head.  Parameters carry
``core.resnet.init_resnet``'s names (``conv1``-``conv3`` and ``proj``,
batch norms ``<block>_bn1``-``_bn3`` and ``<block>_bnp``).

In the pixel domain, with ``P`` the band projector wherever the
JPEG-domain program truncates:

    stem:   h = P(maxpool(P(relu(P(bn(conv7x7/2(P(x))))))))
    block:  s = h, or P(bnp(conv_1x1/s(h))) with a projection
            a = P(relu(P(bn1(conv1_1x1(h)))))
            b = P(relu(P(bn2(conv2_3x3/s(a)))))
            h = P(relu(P(bn3(conv3_1x1(b))) + s))
    head:   mean pixel of each channel @ W + b

with centered zero padding for the convolutions and -inf padding for
the max-pool, as torchvision.  The max-pool is not in arXiv:1812.11690;
the program evaluates it by decode, pool and encode.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5
EXPANSION = 4


def stages(cfg: dict):
    """``(name, stride, cin, width, cout)`` of every bottleneck block."""
    cin = cfg["widths"][0]
    for si, (w, count) in enumerate(zip(cfg["widths"],
                                        cfg["blocks_per_stage"])):
        for bi in range(count):
            cout = EXPANSION * w
            yield f"s{si}b{bi}", (2 if si and not bi else 1), cin, w, cout
            cin = cout


def program_spec(cfg: dict):
    from repro.core import resnet as R

    return R.ResNetSpec(in_channels=cfg["in_channels"],
                        widths=tuple(cfg["widths"]),
                        blocks_per_stage=tuple(cfg["blocks_per_stage"]),
                        num_classes=cfg["num_classes"],
                        quality=cfg["quality"], phi=cfg["asm_phi"],
                        block="bottleneck", stem="7x7s2-maxpool")


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
    params["stem"] = {"kernel": conv(widths[0], cfg["in_channels"], 7)}
    bn("stem_bn", widths[0])
    cout = widths[0]
    for name, s, cin, w, cout in stages(cfg):
        params[name] = {"conv1": conv(w, cin, 1), "conv2": conv(w, w, 3),
                        "conv3": conv(cout, w, 1)}
        bn(name + "_bn1", w)
        bn(name + "_bn2", w)
        bn(name + "_bn3", cout)
        if s != 1 or cin != cout:
            params[name]["proj"] = conv(cout, cin, 1)
            bn(name + "_bnp", cout)
    k1, k2 = jax.random.split(next(keys))
    params["head"] = {
        "w": jax.random.normal(k1, (cout, cfg["num_classes"]))
        * np.sqrt(1.0 / cout),
        "b": 0.1 * jax.random.normal(k2, (cfg["num_classes"],))}
    return params, state


def weights(key, cfg: dict):
    """He-normal convolutions and head, batch norm with random running
    statistics so that its fold into the operators is exercised."""
    layout = (("widths", tuple(cfg["widths"])),
              ("blocks_per_stage", tuple(cfg["blocks_per_stage"])),
              ("in_channels", cfg["in_channels"]),
              ("num_classes", cfg["num_classes"]))
    return _weights(key, layout)


def _max_pool(h):
    return lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3),
                             (1, 1, 2, 2), ((0, 0), (0, 0), (1, 1), (1, 1)))


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

    h = relu(p(bn(conv(p(x), params["stem"]["kernel"], 2), "stem_bn")))
    h = p(_max_pool(h))
    for name, s, _cin, _w, _cout in stages(cfg):
        blk = params[name]
        short = (p(bn(conv(h, blk["proj"], s), name + "_bnp"))
                 if "proj" in blk else h)
        a = relu(p(bn(conv(h, blk["conv1"], 1), name + "_bn1")))
        b = relu(p(bn(conv(a, blk["conv2"], s), name + "_bn2")))
        c = p(bn(conv(b, blk["conv3"], 1), name + "_bn3"))
        h = relu(c + short)
    pooled = jnp.mean(h, axis=(2, 3))
    return cast(pooled) @ cast(params["head"]["w"]) + params["head"]["b"]


def model_flops(cfg: dict) -> float:
    size = cfg["image_size"] // 2
    macs = size * size * cfg["widths"][0] * cfg["in_channels"] * 49
    size //= 2
    for _name, s, cin, w, cout in stages(cfg):
        out = size // s
        macs += size * size * cin * w + out * out * (
            9 * w * w + w * cout + (cin * cout if s != 1 or cin != cout
                                    else 0))
        size = out
    macs += EXPANSION * cfg["widths"][-1] * cfg["num_classes"]
    return 2.0 * macs
