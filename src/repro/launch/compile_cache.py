"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called from each driver's ``main()`` (never at
import, so tests and library users keep JAX's defaults). A set
``JAX_COMPILATION_CACHE_DIR`` wins: JAX already reads it, and no other
directory is set. Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``: a path built from a temp name, a pid or the time
would change from run to run, and no run would find what the last one
cached.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
