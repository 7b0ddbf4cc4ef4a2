"""JAX's persistent compilation cache, kept at one fixed place.

A cache at a path that moves between runs is never found again, so no
temporary directory, process id or time goes into the path.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it; otherwise the cache lives at ``<checkout>/.jax_cache``
(listed in ``.gitignore``).  Call ``enable_compile_cache()`` before the
first compile of a process.
"""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
