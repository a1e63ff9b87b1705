"""Where JAX keeps its persistent compilation cache.

A 48-layer scanned train step takes tens of seconds to compile, and every
cold process pays it again unless the compiled program is on disk.  JAX
reads ``JAX_COMPILATION_CACHE_DIR`` itself: when the environment sets it,
this module leaves the cache alone.  Otherwise the cache goes to the fixed
``<checkout>/.jax_cache`` (git-ignored), so every later process started
from the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the repository checkout this module was imported from
CHECKOUT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Make sure JAX caches compiled programs on disk; returns the cache
    directory in effect.  Call before the first compilation."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
