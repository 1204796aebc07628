"""Persistent XLA compilation cache for the repo's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at start-up and
keeps its cache there, and this module sets nothing. Otherwise the cache
lives in ``.jax_cache/`` at the root of the checkout: a fixed path, because
the path is part of what a later run has to find again. Entry points
(``chip_smoke.py``, ``benchmarks/``, ``examples/``) call
``enable_compile_cache()`` before they compile anything; the tests never
do, so a test run compiles from scratch.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
