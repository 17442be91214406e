"""JAX's persistent compilation cache, in one fixed place.

Entry points (``chip_smoke.py``, ``launch/serve.py``, the examples) call
:func:`enable_compile_cache` before their first compile, never at import.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
there and nothing else is set. Otherwise the cache goes to ``.jax_cache``
at the root of the checkout: a fixed path, because the path is part of
the cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
