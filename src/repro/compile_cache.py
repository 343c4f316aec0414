"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``python -m repro.deploy``, the
``benchmarks/*.py`` CLIs) call :func:`enable_compile_cache` once at start;
nothing calls it on import or in tests.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: a fixed path, because the path is part of what
# makes a later run find an entry (listed in .gitignore).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other path is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]
