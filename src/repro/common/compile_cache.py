"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles the served model calls
:func:`use_compile_cache` once, before its first compile. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at start-up and this
helper sets nothing. Otherwise the cache goes to ``<checkout>/.jax_cache``:
a fixed path, because the directory is part of the cache's key — a path that
moved with a temporary directory, a pid or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
