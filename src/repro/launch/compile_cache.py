"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``launch.serve.main``,
``benchmarks.run.main``) call :func:`enable_compile_cache` once before
they compile anything; importing a library module never does.  The
cache directory is part of each entry's key, so it is a fixed path:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself, and nothing here overrides it), otherwise
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
