"""Where JAX's persistent compilation cache lives for chip runs.

``chip_smoke.py`` calls ``enable_compile_cache`` once, before its first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache goes to the fixed
    ``<repo>/.jax_cache`` (git-ignored): the directory is part of what a
    later run looks up, so it is never derived from a temporary name, a
    process id or the time."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
