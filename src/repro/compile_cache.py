"""Where a process keeps JAX's persistent compilation cache.

A cold run of the fused slot step pays every XLA compile (the multi-region
scan alone takes seconds at fleet scale); the persistent cache lets a
later process on the same machine load them instead.  The cache key
includes the directory, so the directory must never move between runs.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here.  Otherwise the cache goes to the fixed,
    git-ignored ``.jax_cache`` at the root of the checkout.  Call it from
    an entry point (a script's ``main``), never on import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
