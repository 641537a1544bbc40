"""JAX's persistent compilation cache for the entry points.

Every bucket of the batching ladder is its own program, and a full-width
step takes seconds to compile, so an entry point turns the cache on before
its first compile: ``launch/serve.py``, ``launch/train.py`` and
``chip_smoke.py`` call :func:`enable_compilation_cache` from their
``main()``. Importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# The checkout root (src/repro/launch/ → three levels up). The cache path is
# part of what JAX keys on, so it must not move between runs: never a temp
# name, a pid or a timestamp.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other directory. Otherwise the cache goes to
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``). Call it before
    the first compile: JAX decides whether the cache is on at that point.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
