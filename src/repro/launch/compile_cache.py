"""Where JAX's persistent compilation cache lives — the one place that
says so.

Entry points that compile for the chip (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`use_compile_cache` once, before their
first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this changes nothing; otherwise the cache goes to
``<repo>/.jax_cache`` — a fixed path, because the directory is part of
the cache key and a cache that moves never hits.  Nothing else in the
repo sets ``jax_compilation_cache_dir``.

By default JAX hashes a program with its debug info stripped, so an
executable whose named scopes (the ``op_name`` metadata a profile
attributes device time by) have since moved would load from the cache
with the old ones.  The key therefore includes the metadata, with this
checkout's root cut from every source path so that the same program
hits from any checkout.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
REPO_CACHE_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(REPO_ROOT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
