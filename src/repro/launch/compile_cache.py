"""JAX's persistent compilation cache for the entry-point scripts.

The cache key includes the cache directory, so the directory must not
move between runs: it is `JAX_COMPILATION_CACHE_DIR` when that is set
(JAX reads it itself), else `.jax_cache/` at the root of the checkout.
Entry points call `enable_compile_cache()` under their `__main__` check;
importing a library module never turns the cache on.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
