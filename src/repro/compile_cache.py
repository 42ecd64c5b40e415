"""Where JAX keeps its persistent compilation cache.

The entry points (``chip_smoke.py``, ``python -m repro.workloads`` and
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before they
compile anything.  Importing the library sets nothing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache goes to :data:`DEFAULT_DIR`, one
fixed directory at the root of the checkout, which git ignores.  The path
must not move between runs: a cache whose directory moves is never hit.
"""
from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
