"""Where the launchers keep JAX's persistent compilation cache.

A compiled step is looked up by its program and by the cache directory, so
the directory has to stay put from one run to the next: a temporary or
per-process name never hits.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it on its own and nothing here overrides it; otherwise the cache
lives at ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

Entry points call :func:`enable_compile_cache` once, before their first
compile.  Tests do not: they leave the cache as JAX configures it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
