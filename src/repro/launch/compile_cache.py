"""JAX's persistent compilation cache for this repo's entry points.

A cache hits only if its directory stays put (the path is part of each
entry's key), so it lives at one fixed place: the directory that
``JAX_COMPILATION_CACHE_DIR`` names when it is set, otherwise
``<checkout>/.jax_cache``. Entry points call :func:`enable_compile_cache`
from ``main``; importing the library never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout root: src/repro/launch/compile_cache.py -> parents[3]
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
