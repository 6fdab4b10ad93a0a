"""JAX's persistent compilation cache, turned on by the entry points.

Importing ``repro`` leaves the cache as JAX configured it.  Scripts that
compile the solvers at real sizes (``chip_smoke.py``, ``benchmarks/run.py``,
the examples) call :func:`enable_compile_cache` first, so a second run on
the same machine loads its executables instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

# One fixed directory in the checkout (listed in .gitignore): the cache
# directory is part of what a cached entry is found by, so it never moves.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` where that is set, else
    :data:`CHECKOUT_CACHE_DIR`."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
