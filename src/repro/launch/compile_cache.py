"""JAX's persistent compilation cache for the launchers.

Call :func:`enable_compile_cache` before the first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no other
directory is set here.  Otherwise the cache lives at a fixed path inside
the checkout, so every run of this checkout finds the programs the last
one compiled; a path derived from a temporary name, a pid or the time
would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
