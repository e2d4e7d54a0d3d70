"""Persistent XLA compilation cache for the entry points.

Every ``(B, T, V)`` round-buffer bucket of the affinity auction compiles
once per process; the persistent cache lets a later process on the same
machine skip those compiles.  Entry points call :func:`use_compile_cache`
before their first compile — importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path, so that a later run finds what an earlier one wrote.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing here overrides it.  Otherwise the cache lives in ``.jax_cache/``
    at the repository root.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
