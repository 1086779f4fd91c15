"""JAX's persistent compilation cache for the repo's entry points.

A run on a fresh machine compiles every program again; a cache at a
fixed path lets the processes of one command share what the first one
compiled.  The path is part of the cache's key, so it never carries a
temp name, a pid or a time.  The test suite does not call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fallback cache directory, inside the checkout (git-ignored)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself),
    else ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
