"""Where JAX keeps its persistent compilation cache.

A run on an accelerator pays seconds to minutes of compilation per program
shape.  A later run finds those entries only in the same directory, so the
directory must not move between runs: it is either the one the environment
names in ``JAX_COMPILATION_CACHE_DIR`` (which JAX reads on its own) or the
fixed, git-ignored ``<repo>/.jax_cache`` next to this checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``src/repro/launch/compile_cache.py`` -> the repository root
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and no
    other directory is configured here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
