"""JAX's persistent compilation cache for the command-line entry points.

``enable_compile_cache()`` is called by ``launch.serve``, ``launch.train``
and ``chip_smoke.py`` before their first compile — never at import, so
library users and the test suite stay cache-free.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
changed; otherwise the cache lives at ``<checkout>/.jax_cache``, a fixed
path (the directory is part of the cache key, so it must not move between
runs).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

# src/repro/launch/cache.py -> the checkout root
_DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
