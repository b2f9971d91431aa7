"""JAX persistent compilation cache, one definition for every entry point
(``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.train``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module names no other directory.  Otherwise the cache lives at a fixed
path inside the checkout, ``<repo>/.jax_cache`` (listed in .gitignore):
the path is part of what a later run must find again, so it is never
built from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use.
    Every compile is cached, however short: a kernel compiles in about a
    second, under JAX's default threshold."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
