"""Where JAX keeps its persistent compile cache.

A process that compiles the served path calls `use_compile_cache()` before
its first compile.  `JAX_COMPILATION_CACHE_DIR`, when set, wins: JAX reads it
itself and nothing here overrides it.  Otherwise the cache lives at a fixed
path inside the checkout, `<repo>/.jax_cache` (gitignored): the directory is
part of each entry's key, so a path derived from a temporary name, a PID or
the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; return its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
