"""JAX's persistent compilation cache, kept at one fixed place.

A cold run of the fleet sweep spends minutes compiling (the elasticity
scan's sorts over f64 scores dominate), so every entry point turns
the persistent cache on before its first compile. The cache key
includes the directory, so the directory never moves: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, and nothing here overrides it), and ``<repo>/.jax_cache``
otherwise.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
