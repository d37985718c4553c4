"""JAX's persistent compilation cache for the entry points.

The directory is part of the cache key, so it must not move between runs:
``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads that
variable itself, and nothing here overrides it), otherwise the fixed,
git-ignored ``<checkout>/.jax_cache``.  Entry points call
:func:`enable_compile_cache` at start-up; importing this module changes
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def compile_cache_dir() -> str:
    """Where the cache lives: the environment's directory, else the
    checkout's fixed one."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
