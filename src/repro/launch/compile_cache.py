"""Persistent compilation cache location for the entry points.

A 32-layer engine step takes minutes to compile, and each process
starts cold without a persistent cache. The cache lives in one fixed
place so a later run finds what an earlier one compiled: either what
``JAX_COMPILATION_CACHE_DIR`` says (JAX reads that variable itself) or
``<repo>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"

__all__ = ["enable_compile_cache", "DEFAULT_DIR"]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Call before the first compile. Leaves ``JAX_COMPILATION_CACHE_DIR``
    alone when it is set; otherwise points the cache at
    ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
