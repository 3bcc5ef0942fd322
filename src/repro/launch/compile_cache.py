"""JAX's persistent compilation cache for the entry points.

A chip run compiles a 30-layer prefill, a decode segment, a refill and a
page scatter; the cache lets the next run on the same machine skip them.
Entry points call :func:`enable_compile_cache` once, before their first
compile.  Library code and tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout root: src/repro/launch/compile_cache.py -> parents[3]
CHECKOUT = Path(__file__).resolve().parents[3]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``
    — a fixed path, because the path is part of the cache key."""
    return os.environ.get(CACHE_ENV) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir` (and
    nowhere else) and return that directory."""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
