"""JAX's persistent compilation cache, for the entry points.

Entry points call :func:`enable_compile_cache` once, before they compile
anything; library code and tests never do.  The directory is
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself, so
nothing is set in code), else ``.jax_cache/`` at the checkout root,
resolved from this file's location.  The path is fixed, never a
temporary name, a process id or a time, so the next run finds what this
one cached.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
