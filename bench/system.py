"""The system under test, built the way a deployment builds it.

Weights come from the seed, on the device, in one jitted call, in the
program's parameter layout and in float32, the type they are served in,
as the configuration's architecture module (``archs/<arch>.py``) makes
them.  From them and the module's ``program_spec`` the program builds
its serving stack: ``plan.build_plan`` (fused batch norm, operators at
the configuration's 64 bands) -> ``serving.build_ladder`` (one compiled
schedule per band tier) -> ``serving.BandElasticScheduler`` over the
plan grid, warmed for the cell's own ingest kind only.  Nothing is read
from or written to disk but JAX's compile cache.
"""
from __future__ import annotations

import jax


def seed_key(seed: int, purpose: str):
    """A PRNG key for ``purpose`` from a seed of any size up to 2**62."""
    key = jax.random.PRNGKey(sum(map(ord, purpose)))
    key = jax.random.fold_in(key, seed % (2 ** 31))
    return jax.random.fold_in(key, seed // (2 ** 31))


def weights(seed: int, cfg: dict, arch):
    """``(params, state)`` float32 pytrees of ``arch`` (the
    configuration's architecture module) from ``seed``, made on the
    device in one call."""
    out = arch.weights(seed_key(seed, "weights"), cfg)
    jax.block_until_ready(out)
    return out


def tier_caps(traffic: dict) -> tuple:
    """Ladder caps of the cell: ``"top"`` is the plan's own bands."""
    return tuple(None if t == "top" else int(t) for t in traffic["tiers"])


def build(cfg: dict, traffic: dict, arch, params, state, *, tracer=None,
          timings: dict):
    """Plan, ladder and warmed scheduler for one cell; ``timings`` gets
    each step's seconds."""
    import time

    from repro import serving
    from repro.core import dispatch as dispatchlib
    from repro.core import plan as planlib

    t = time.monotonic()
    plan = planlib.build_plan(
        params, state, arch.program_spec(cfg),
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
