"""JAX's persistent compilation cache for the launchers.

Compiling a full-width pipeline step takes longer than running a few of
them, so every entry point that compiles one turns this cache on first.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: The cache when ``JAX_COMPILATION_CACHE_DIR`` is unset: one fixed path
#: at the repository root, so a later run finds what an earlier run wrote.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and nothing here overrides it. Otherwise the cache is
    ``REPO_CACHE_DIR``.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
