"""JAX's persistent compilation cache at one fixed place.

Entry points call `enable_compile_cache()` before they compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
is changed here. Otherwise the cache goes to ``<checkout>/.jax_cache``
(git-ignored): a fixed path, because the directory is part of what a
later process must find again — a temporary or per-process path would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
