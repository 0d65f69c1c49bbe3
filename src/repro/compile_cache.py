"""JAX's persistent compilation cache, switched on by the entry points.

A VGG16-224 forward bakes its weights into the executable, so its XLA
compile takes minutes; the persistent cache turns the second run's
compile into a load from disk.  The cache path is part of the cache
key, so it is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads the variable itself, and nothing here overrides it),
else ``<repo>/.jax_cache``.

Each entry point (``chip_smoke.py``, ``examples/*.py``,
``benchmarks/run.py``, ``benchmarks/bench_engine.py``) calls
:func:`enable_compile_cache` before its first compile.  Importing
``repro`` never does.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
