"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` before they compile anything;
importing the package never does. The path is part of each cache key's
lookup, so it is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the caller
set it (JAX reads that variable itself, and nothing here overrides it),
otherwise ``.jax_cache`` at the root of this checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
