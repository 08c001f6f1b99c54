"""The system under test, built the way a deployment builds it.

Weights come from the seed, on the device, in one jitted call, in the
program's parameter layout (``core.resnet.init_resnet``'s names) and in
float32, the type they are served in; batch norm gets random running
statistics so that its fold into the operators is exercised.  From them
the program builds its serving stack: ``plan.build_plan`` (fused batch
norm, operators at the configuration's 64 bands) -> ``serving.
build_ladder`` (one compiled schedule per band tier) ->
``serving.BandElasticScheduler`` over the plan grid, warmed for the
cell's own ingest kind only.  Nothing is read from or written to disk
but JAX's compile cache.
"""
from __future__ import annotations

import functools

import numpy as np
import jax


def stages(cfg: dict):
    """``(name, stride, cin, cout)`` of every residual block, in order."""
    cin = cfg["widths"][0]
    for si, w in enumerate(cfg["widths"]):
        for bi in range(cfg["blocks_per_stage"]):
            yield f"s{si}b{bi}", (2 if si and not bi else 1), cin, w
            cin = w


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


def seed_key(seed: int, purpose: str):
    """A PRNG key for ``purpose`` from a seed of any size up to 2**62."""
    key = jax.random.PRNGKey(sum(map(ord, purpose)))
    key = jax.random.fold_in(key, seed % (2 ** 31))
    return jax.random.fold_in(key, seed // (2 ** 31))


def weights(seed: int, cfg: dict):
    """``(params, state)`` float32 pytrees from ``seed``, made on the
    device in one call."""
    layout = (("widths", tuple(cfg["widths"])),
              ("blocks_per_stage", cfg["blocks_per_stage"]),
              ("in_channels", cfg["in_channels"]),
              ("num_classes", cfg["num_classes"]))
    out = _weights(seed_key(seed, "weights"), layout)
    jax.block_until_ready(out)
    return out


def tier_caps(traffic: dict) -> tuple:
    """Ladder caps of the cell: ``"top"`` is the plan's own bands."""
    return tuple(None if t == "top" else int(t) for t in traffic["tiers"])


def build(cfg: dict, traffic: dict, params, state, *, tracer=None,
          timings: dict):
    """Plan, ladder and warmed scheduler for one cell; ``timings`` gets
    each step's seconds."""
    import time

    from repro import serving
    from repro.core import dispatch as dispatchlib
    from repro.core import plan as planlib
    from repro.core import resnet as R

    spec = R.ResNetSpec(in_channels=cfg["in_channels"],
                        widths=tuple(cfg["widths"]),
                        blocks_per_stage=cfg["blocks_per_stage"],
                        num_classes=cfg["num_classes"],
                        quality=cfg["quality"], phi=cfg["asm_phi"])
    t = time.monotonic()
    plan = planlib.build_plan(
        params, state, spec,
        dispatch=dispatchlib.DispatchConfig(bands=cfg["bands"]))
    jax.block_until_ready(jax.tree_util.tree_leaves(plan.operators))
    timings["plan_s"] = time.monotonic() - t
    buckets = (None if traffic["buckets"] == "auto"
               else tuple(traffic["buckets"]))
    t = time.monotonic()
    ladder = serving.build_ladder(plan, caps=tier_caps(traffic),
                                  image_size=cfg["image_size"],
                                  buckets=buckets)
    timings["ladder_s"] = time.monotonic() - t
    nb = cfg["image_size"] // 8
    sched = serving.BandElasticScheduler(
        ladder, batch=traffic["max_batch"], buckets=buckets,
        max_pending=traffic["max_pending"], grid=(nb, nb),
        channels=cfg["in_channels"], tracer=tracer)
    t = time.monotonic()
    sched.warmup(kinds=(traffic["kind"],))
    timings["warmup_s"] = time.monotonic() - t
    return sched
