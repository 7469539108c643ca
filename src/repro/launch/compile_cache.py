"""Persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``benchmarks/smoke.py``) call :func:`enable_compile_cache` once at start-up;
importing the library never does.  A deployment places the cache from
outside with ``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself.  Without
it the cache lives at a fixed path inside the checkout, ``<repo>/.jax_cache``
(git-ignored): the directory is part of what a later run must find again,
so it never comes from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

#: ``<repo>/.jax_cache`` — this file is ``<repo>/src/repro/launch/compile_cache.py``.
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed  # JAX already took the directory from the environment
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
