"""JAX persistent compilation cache for the entry points.

Called by the launch and benchmark entry points and by ``chip_smoke.py``
before their first compile — never when a module is imported, so library
users and tests keep whatever cache setting they chose.
"""
from __future__ import annotations

import os

import jax

__all__ = ["configure_compile_cache"]


def configure_compile_cache(checkout: str) -> str:
    """Turn on the persistent cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set in code. Otherwise the cache is the fixed
    ``<checkout>/.jax_cache``: the directory is part of every entry's key,
    so a path built from a temp name, a PID or a time would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
