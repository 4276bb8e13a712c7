"""Where JAX keeps its persistent compilation cache.

The directory is part of what makes a cache hit, so it is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names (which JAX reads itself), else
``<checkout>/.jax_cache``.  Entry points call :func:`use_compile_cache`
before their first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/utils/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the cache on at its fixed directory and return that directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken it from
    the environment and nothing is set here."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
