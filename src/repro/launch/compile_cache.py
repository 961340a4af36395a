"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move
between runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads the variable itself, and nothing here overrides it), and otherwise
the fixed ``.jax_cache/`` at the repository root.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
