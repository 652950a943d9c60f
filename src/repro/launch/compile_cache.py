"""Where JAX's persistent compilation cache lives — the one place that
says so.

Entry points that compile for the chip (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`use_compile_cache` once, before their
first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this changes nothing; otherwise the cache goes to
``<repo>/.jax_cache`` — a fixed path, because the directory is part of
the cache key and a cache that moves never hits.  Nothing else in the
repo sets ``jax_compilation_cache_dir``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
