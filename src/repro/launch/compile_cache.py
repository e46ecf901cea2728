"""JAX's persistent compilation cache, at one fixed place.

The cache key includes the directory, so a path that moves (a temporary
name, a pid, the time) never hits. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing here overrides it; otherwise
the cache lives at ``<checkout>/.jax_cache`` (listed in .gitignore).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
