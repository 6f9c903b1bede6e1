"""JAX's persistent compilation cache, at a path a later run finds again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it.  Otherwise the cache lives at
``<checkout>/.jax_cache``.  The path is part of what a later run must
match, so it is fixed: never built from a temporary name, a process id
or the time.  Programs call ``enable_compile_cache()`` first thing in
``main`` (chip_smoke.py, launch/train.py, launch/serve.py); importing
this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]  # src/repro/launch/ -> checkout


def compile_cache_dir() -> str:
    return os.environ.get(ENV_VAR) or str(REPO_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at ``compile_cache_dir()``; returns it."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
