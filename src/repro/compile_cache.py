"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``python -m repro.launch.train``) call ``enable_compile_cache()`` before
their first compile. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and this sets no directory. Otherwise the cache goes to one
fixed directory inside the checkout, ``.jax_cache`` (git-ignored): the
path is part of every cache key, so a directory that moved between runs
would never hit.

Either way the cache key keeps the programs' debug metadata
(``jax_compilation_cache_include_metadata_in_key``). By default JAX
strips it before hashing, so a program that differs from a cached one
only in its ``jax.named_scope`` stage names (``repro.obs.scopes``) would
load the cached executable, whose ops carry the old names, and a device
profile would attribute its time to the wrong stages or to none.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
