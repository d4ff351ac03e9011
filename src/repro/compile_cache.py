"""Where the program's entry points keep JAX's persistent compilation cache.

A compile of the scale-20 serving step takes tens of seconds on the chip,
so every entry point (``chip_smoke.py``, the examples, the benchmarks)
calls :func:`enable_compile_cache` before its first compile. The cache key
includes the directory, so the directory never moves: it is the one that
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself and no
other cache is set in code), or else the fixed ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
