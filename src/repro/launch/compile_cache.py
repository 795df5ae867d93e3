"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the directory, so a path that moves between runs
(a temporary name, a pid, a time) never hits.  Call
``enable_compile_cache()`` first thing in an entry point, before anything
compiles.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone.  Otherwise the cache lives at ``.jax_cache/`` in the
    checkout (gitignored)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
