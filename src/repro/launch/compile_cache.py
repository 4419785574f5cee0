"""JAX's persistent compilation cache, set up the same way by every entry
point (the serving launcher, ``chip_smoke.py``, the benchmarks).

The cache key includes the directory, so the directory must not move
between runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads the variable itself, and nothing else is set here), and otherwise
the fixed ``<repo>/.jax_cache`` — never a name made from a temporary
directory, a process id or the time.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return REPO_CACHE
