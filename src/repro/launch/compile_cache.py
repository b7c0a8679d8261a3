"""Where JAX keeps its persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when it is set nothing
is set here.  Otherwise the cache lives at one fixed path inside the
checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is part
of the cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIRNAME", "enable_compile_cache"]

CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[3] / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
