"""JAX's persistent compilation cache for the entry points.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing, so tests never see a cache.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
other directory is set here. Otherwise the cache lives at one fixed path in
the checkout (``.jax_cache/``, git-ignored): the path is part of the
cache's key, so it never carries a temporary name, a process id or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: src/repro/launch/compile_cache.py → the checkout root's .jax_cache/
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
