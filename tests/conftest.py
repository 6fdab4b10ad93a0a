"""Shared test configuration.

float64 is enabled globally: the estimation-theory tests need it, and all
model code is dtype-explicit (bf16/f32 literals) so it is unaffected.
NOTE: tests intentionally see the single real CPU device -- only
launch/dryrun.py forces 512 host platform devices (and only in its own
process).  Multi-device tests spawn subprocesses.
"""
import gc
import os

# Keep any ambient dry-run flags out of the test process.
os.environ.pop("XLA_FLAGS", None)

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _release_executables():
    """Free each test file's compiled programs when the file ends.  Every
    XLA:CPU executable holds hundreds to thousands of memory maps, and a
    test worker that kept all of them reached the kernel's limit
    (vm.max_map_count, 65 530 by default) and crashed in a later compile."""
    yield
    from repro.core import clear_cache

    clear_cache()
    jax.clear_caches()
    gc.collect()
