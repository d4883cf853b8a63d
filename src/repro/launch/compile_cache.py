"""Where JAX's persistent compilation cache lives.

Entry points (``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` from their ``main``; importing a
library module never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed on purpose: the directory is part of what a later run must find
# again, so it is never built from a temp name, a pid or the time
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is where the cache goes;
    otherwise it is ``<repo>/.jax_cache``.  Every executable is cached,
    however quickly it compiled: a serving process pays each compile
    before its first answer.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
