"""JAX's persistent compilation cache for the launch entry points."""
from __future__ import annotations

import os
import pathlib

import jax

# src/repro/launch/compile_cache.py -> the checkout root.
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep compiled programs across processes and return the cache dir.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of what a cache entry
    is found by, so it must not move between runs.  Call it from entry
    points, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
