"""Where JAX's persistent compilation cache lives.

A cache directory is part of each entry's key, so it must not move between
runs. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here; otherwise the cache goes to ``<checkout>/.jax_cache``
(listed in ``.gitignore``). Call before the first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def place_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
