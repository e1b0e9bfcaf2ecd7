"""Where the program's entry points (``python -m repro.launch.serve``,
``chip_smoke.py``, ``benchmarks/run.py``) keep JAX's persistent
compilation cache.

A run on a fresh machine compiles every program from cold; with the cache
on, a later process in the same checkout (or on a machine that mounts the
same cache directory) reads them back instead. The cache directory is part
of each entry's key, so it must not move between runs: no temp, pid or
time-derived paths.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE", "configure"]

# <checkout>/.jax_cache (gitignored): this file is src/repro/utils/*.py
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it at
    start-up and no other directory is set here. Otherwise the cache goes
    to the fixed :data:`CHECKOUT_CACHE`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
