"""One place that decides where JAX's persistent compilation cache lives.

``JAX_COMPILATION_CACHE_DIR`` set in the environment wins: JAX reads it
itself and this module sets nothing.  Otherwise the cache goes to the
fixed ``<checkout>/.jax_cache`` (listed in ``.gitignore``) — never a temp,
pid- or time-derived path, because the path is part of what makes a later
process find the entry.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    # <checkout>/src/repro/launch/compile_cache.py → <checkout>/.jax_cache
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
