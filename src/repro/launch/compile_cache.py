"""Persistent XLA compilation cache, placed from outside the program.

Every entry point that compiles calls :func:`enable_compile_cache` before
its first compile.  The cache directory is part of each entry's key, so
it is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(and no other), else ``<checkout>/.jax-cache`` — never a temporary, pid-
or time-derived path, which would never hit again.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: the checkout's own cache directory (listed in .gitignore)
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax-cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory.

    Stores every compiled program, however quick its compile: the round
    programs and their kernels are what a later run must find again.
    JAX opens the cache at its first compile; a process that already
    compiled is re-pointed too.  Returns the directory in use.
    """
    from jax.experimental.compilation_cache import compilation_cache

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    return path
