"""Persistent XLA compilation cache for the program's entry points.

`chip_smoke.py`, `benchmarks/run.py` and `repro.launch.topo_plan` call
`enable_compile_cache()` once, before their first compile, so a second run
loads every DES bucket and kernel instead of compiling it again.  Library
code and tests never call it.

Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this module
sets no other path.  Otherwise the cache lives in ``.jax_cache/`` at the
root of the checkout (git-ignored): a fixed path, because the directory is
part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # the fused DES step compiles in about a second and the kernels in well
    # under one: keep them all, not only compiles above JAX's 1 s default
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
