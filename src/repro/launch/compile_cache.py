"""JAX persistent compilation cache location for the repo's entry points.

Scripts (``chip_smoke.py``, ``examples/``, ``benchmarks/``) call
``enable_compile_cache()`` once at start; importing this module changes
nothing. The cache key includes the directory, so the default is a fixed
path inside the checkout — never a temp, pid- or time-derived one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself); otherwise the cache lives at ``<repo>/.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
