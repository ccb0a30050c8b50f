"""Where JAX keeps its persistent compilation cache.

The cache key includes the cache directory, so a directory that moves
between runs never hits: the path is fixed, never built from a temporary
name, a process id or the time.
"""
from __future__ import annotations

import os

# inside the checkout (listed in .gitignore)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``CACHE_DIR``.  Call
    from an entry point, never at import."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
