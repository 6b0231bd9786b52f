"""Persistent XLA compilation cache for the entry points.

Scripts (``chip_smoke.py``, the examples, the benchmarks) call
:func:`enable_compile_cache` once at start-up; importing the package never
turns the cache on, so tests and library users keep JAX's defaults.

The cache key includes the directory, so the directory never moves: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable on
its own) and otherwise the fixed ``.jax_cache/`` at the root of the
checkout, which ``.gitignore`` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
