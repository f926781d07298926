"""Persistent XLA compile cache for the launchers and `chip_smoke.py`.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache goes to `<checkout>/.jax_cache`:
a fixed path, because the directory is part of the cache key and a path
that moves never hits.  Call `enable_compile_cache()` before the first
compile; tests and imports never call it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
