"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``python -m repro.launch.train``) call ``enable_compile_cache()`` before
their first compile. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and this sets nothing. Otherwise the cache goes to one fixed
directory inside the checkout, ``.jax_cache`` (git-ignored): the path
is part of every cache key, so a directory that moved between runs
would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
