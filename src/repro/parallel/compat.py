"""The jax spellings this repo uses for ``shard_map``, meshes and axis sizes.

One import site for the three, so call sites stay short; the installed jax
(0.9) provides all of them directly.
"""
from __future__ import annotations

import jax
from jax import shard_map


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis typed ``Auto`` (the implicit
    sharding-propagation mode the sharding rules are written for)."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def axis_size(axis) -> int:
    """Size of a named mesh axis inside shard_map'd code."""
    return jax.lax.axis_size(axis)


__all__ = ["shard_map", "make_mesh", "axis_size"]
