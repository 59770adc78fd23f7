"""JAX's persistent compilation cache, placed from outside or kept at one
fixed directory of the repository."""
from __future__ import annotations

import os
import pathlib

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

# <repo>/.jax_cache (gitignored).  Fixed, never derived from a temporary
# name, a pid or the time: a later run must find what an earlier one wrote.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and it
    is left alone.  Otherwise the cache goes to ``REPO_CACHE_DIR``.  Call it
    from an entry point before the first compile, never at import time.
    """
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
