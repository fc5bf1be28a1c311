"""JAX's persistent compilation cache for this checkout's entry points.

Entry points call ``enable_compile_cache()`` before their first compile;
library modules never touch JAX config.  Where ``JAX_COMPILATION_CACHE_DIR``
is set JAX reads it itself and this sets nothing.  Otherwise the cache lives
at ``<checkout>/.jax_cache``, a fixed path: the directory is part of what a
later process must find again, so it never carries a temp name, pid or time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache``, resolved from this file (src/repro/launch/).
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
