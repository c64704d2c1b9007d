"""JAX's persistent compilation cache, kept at one fixed place.

Every entry point that compiles (``launch.serve``, ``launch.train``, the
HTTP worker processes, ``chip_smoke.py``) calls :func:`enable_compile_cache`
before its first jax operation, so a second run of the same program
loads its executables instead of compiling them again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory on
its own and nothing here names another one.  Otherwise the cache lives in
``<repo>/.jax_cache`` (git-ignored): a fixed path, because the path is
part of what the cache is keyed on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fallback cache directory: the repository root's ``.jax_cache``
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its
    directory.  Caches every executable, however quick its compile, so
    the small top-k and merge programs are loaded too."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        cache_dir = env_dir
    else:
        cache_dir = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
