"""JAX's persistent compilation cache for the entry points.

Each entry point calls :func:`use_compile_cache` before it compiles; nothing
sets the cache on import, so tests and library callers stay untouched.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

# a fixed path inside the checkout: the cache key includes the directory,
# so a path that moved between runs would never hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Cache compiled programs across runs; return the directory in use.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it and
    no other directory is set; otherwise the cache goes to
    ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
