"""Where jax keeps its persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, :func:`repro.launch.train.main`, the bench
scripts) call :func:`enable_compile_cache` once at start-up; importing any
library module leaves the cache as jax configured it.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: a fixed path, since the cache key includes it and a
# directory that moves never hits.  Git-ignored.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it and
    that directory stands; otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
