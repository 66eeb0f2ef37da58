"""JAX's persistent compilation cache, for the entry points that use it.

A later run finds its programs only in the same directory, so the cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says (jax reads that variable
itself) or else at one fixed directory inside the checkout:
``.jax_cache/``, never a temporary or per-process name. Entry
points call :func:`enable` once at start-up; importing this module
changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    # small kernels compile in well under jax's default one-second floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
