"""JAX's persistent compilation cache for the repo's entry points.

Scripts call :func:`enable_compile_cache` once, before their first
compile; the library never turns it on at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
else is set here.  Otherwise the cache lives at a fixed directory inside
the checkout (``<repo>/.jax_cache``, git-ignored): the directory is part
of what a later process must find again, so it never carries a temp
name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (``src/repro/launch/cache.py`` → three levels up)
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
