"""JAX persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, the benchmark CLIs) call
:func:`enable_compile_cache` once before their first compile. Nothing
turns the cache on at import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no directory is
  set in code, so whoever runs the program decides where compiles persist.
* unset: ``<checkout>/.jax_cache`` (git-ignored). The path is fixed because
  it is part of the cache key: a temp-, pid- or time-derived directory
  would never hit.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
