"""JAX's persistent compilation cache for the entry points.

The cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else at
``<repo>/.jax_cache`` (gitignored).  The path is fixed on purpose: it is
part of what a cached entry is found by, so a directory named after a PID,
a time or a temporary name would never be hit by the next run.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
