"""Persistent XLA compilation cache that can be placed from outside.

Call :func:`enable_compile_cache` before any JAX work. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing here
changes it. Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
path, since the directory is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
