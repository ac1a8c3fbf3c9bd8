"""Where JAX keeps its persistent compile cache.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``,
``launch/train.py``, ``launch/serve.py``) calls ``use_compile_cache`` before
it compiles anything.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it by itself and nothing here sets another cache.  Otherwise the cache goes
to ``.jax_cache/`` at the root of the checkout — a fixed path, because the
path is part of what a cached entry is found by.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Points JAX's persistent compile cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
