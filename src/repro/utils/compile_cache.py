"""Where JAX keeps its persistent compilation cache for this repository.

Entry points (``chip_smoke.py``, the launchers' and benchmarks' ``main``)
call :func:`enable_compile_cache` once, before their first compile. Library
code and tests never do: importing a module changes no JAX setting.
"""
from __future__ import annotations

import os

import jax

# A fixed path inside the checkout (gitignored): the cache key includes the
# directory, so a path built from a temp dir, a pid or the time never hits.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing else is set here. Otherwise the cache goes to ``REPO_CACHE_DIR``.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
