"""JAX's persistent compilation cache, at one place.

Entry points that compile real-size programs (``chip_smoke.py``,
``__graft_entry__.py``) call ``enable_compile_cache()``
once before their first compile; tests do not.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
directory is set in code.  Where it is not, the cache lives at ONE fixed
path inside the checkout — the path is part of the cache key, so a
directory that moves (a temporary name, a pid, a time) never hits.
Nothing else in the repo sets a cache directory.
"""
from __future__ import annotations

import os

__all__ = ["CACHE_DIR_NAME", "enable_compile_cache"]

CACHE_DIR_NAME = ".jax_cache"      # listed in .gitignore / .chiprunignore


def enable_compile_cache() -> str:
    """Point the persistent cache at its directory and return it.
    Touches ``jax.config`` only — no backend is initialised."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.path.join(root, CACHE_DIR_NAME)
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX's defaults do the rest: every program that took a second or
    # more to compile is kept, whatever its size
    return path
