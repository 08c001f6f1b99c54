"""JAX's persistent compilation cache for this repo's entry points.

A cache is only found again at the same path, so the entry points
(``launch.serve``, ``benchmarks.run``, ``chip_smoke.py``) call
:func:`enable_compile_cache` before their first compile: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing here
overrides it; otherwise the cache lives at the fixed ``.jax_cache/`` of
this checkout.  Importing this module sets nothing, so tests and library
callers keep JAX's defaults.

JAX persists only programs that took at least
``jax_persistent_cache_min_compile_time_secs`` (1 s by default) to
compile.  A reduced plan's grid cells each compile faster than that, so
with the default a warm start recompiles every one of them.  The entry
points lower the threshold to :data:`MIN_COMPILE_SECS`, unless
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise: low
enough to keep every grid cell, high enough to skip the hundreds of
one-op programs that eager array code compiles (a threshold of 0 wrote
them all and made a cold start slower).
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "MIN_COMPILE_SECS", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

#: programs that compile for at least this many seconds are persisted
MIN_COMPILE_SECS = 0.1


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    lower its persist threshold; returns the directory in use."""
    import jax

    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          MIN_COMPILE_SECS)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
