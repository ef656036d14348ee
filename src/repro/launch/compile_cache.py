"""JAX's persistent compilation cache for the entry points.

Call :func:`enable_compile_cache` from a program's ``main`` (never at
import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and this sets nothing, so the cache goes where the environment says.
Otherwise the cache lives at a fixed ``.jax_cache/`` in the repository
root: the directory is part of what a later run looks up, so it must not
move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
