"""JAX's persistent compilation cache, in one place for every entry point.

`chip_smoke.py`, the benchmark scripts and the examples call
`enable_compile_cache()` before they compile anything:

  - where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
    sets nothing;
  - otherwise the cache goes to `.jax_cache/` at the checkout root
    (git-ignored). The path is part of the cache key, so it is fixed: never
    a temporary name, a pid or a time.

Library code and the tests never call it.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root (this file is <root>/src/repro/compile_cache.py)
CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
