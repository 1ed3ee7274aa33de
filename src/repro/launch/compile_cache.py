"""JAX's persistent compilation cache for the entry points.

Compiling one serve or train step of a 32-layer model takes tens of
seconds; the cache lets a later run in the same place skip it.  The
cache key includes the directory, so the directory must not move
between runs: it is ``$JAX_COMPILATION_CACHE_DIR`` where that is set
(JAX reads the variable itself), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the fixed in-checkout cache directory (git ignores it)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; call before the first
    compile.  Returns the cache directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
