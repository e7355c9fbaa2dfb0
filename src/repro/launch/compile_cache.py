"""Persistent XLA compilation cache for the entry points that run on a chip.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax reads it itself and
nothing is set here. Otherwise the cache lives at ``<checkout>/.jax_cache``
— a fixed path, because the path is part of the cache key, so a directory
named after a temp dir, pid or time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
