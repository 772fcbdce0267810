"""JAX's persistent compilation cache for the entry points.

Called first by ``chip_smoke.py``, ``repro.launch.serve`` and
``repro.launch.quantize``, before anything compiles. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache lives at the fixed path
``<repo>/.jax_cache``: the directory is part of what a later process must
find again, so it never moves.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
