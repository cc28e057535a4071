"""JAX's persistent compilation cache for every entry point of the repo.

A cold start compiles the whole serving stack — minutes at 28 layers — so
``chip_smoke.py``, the launchers and the benchmarks keep compiled programs
on disk.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing here overrides it.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache``: the directory is part of what a later run must
find again, so it is never derived from a temporary name, a pid or the
time.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["use_compile_cache", "DEFAULT_DIR"]

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
