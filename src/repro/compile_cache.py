"""JAX's persistent compilation cache for the repo's entry points.

A cold run on a chip compiles every kernel shape it meets; the cache keeps
those executables on disk so the next run of the same program finds them.
The cache directory is part of what makes an entry findable, so it is a
fixed path, never one built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: Where the cache lives unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other path is set here; otherwise the cache is ``<repo>/.jax_cache``.
    Every compile is kept: a Pallas kernel compiles in about a second, under
    JAX's default minimum compile time for a cache entry."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
