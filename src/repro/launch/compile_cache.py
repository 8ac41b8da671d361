"""Where the persistent XLA compile cache lives.

Every entry point calls :func:`enable_compile_cache` before it compiles
anything.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on
its own and this sets nothing.  Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path, because the directory is part
of what makes a later run find the entries again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one place; returns
    the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
