"""Where the launchers keep JAX's persistent compilation cache.

A fixed directory matters: the cache is keyed by what was compiled, and
a run only finds entries where an earlier run put them.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; call before the first
    compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache``. Returns the directory in use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
