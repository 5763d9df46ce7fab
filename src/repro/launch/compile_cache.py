"""Persistent XLA compile cache for the program's entry points.

A cold start compiles every engine and model program again; the
persistent cache keeps the compiled executables on disk so the next run
of the same program on the same device reads them back.  JAX keys its
entries by the cache directory among other things, so the directory
must not move between runs.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples and
``repro.launch.serve``) call :func:`enable_compile_cache` once, before
their first compile.  Importing ``repro`` never does: the tests run
without a persistent cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    DEFAULT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
