"""JAX's persistent compilation cache, placed from outside the program.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins.
Otherwise the cache lives at a fixed ``.jax_cache/`` in the root of the
checkout: a cache directory that moves between runs never hits, so the
path is never built from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent cache on before anything compiles; returns its
    directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
