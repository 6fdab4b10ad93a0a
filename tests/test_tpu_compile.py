"""Ahead-of-time compiles for a described TPU v5e: what Mosaic and the TPU
compiler accept, at real sizes, without a chip.

Nothing here runs: each test compiles one program of the main path in
float32 for one chip of a ``v5e:2x2`` topology.  The topology is described
inside a fixture (never at import), so every test worker collects the same
tests and only the worker that runs this file loads the TPU compiler.
"""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.wiener_velocity import WienerVelocityConfig
from repro.core import KernelOptions, ParallelOptions, grid_lqt_from_linear
from repro.core.registry import get_method
from repro.core.types import LQTElement
from repro.kernels.lqt_combine import kernel_suffix_scan
from repro.kernels.lqt_combine.kernel import lqt_combine_lanes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def float32_no_cache():
    """float32 (conftest turns x64 on) and no persistent compilation cache:
    an executable compiled for a described chip cannot be read back here,
    so a cache would only warn on the next compile."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile(fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


@pytest.mark.parametrize("nx", [2, 4, 5])
def test_combine_kernel_compiles(one_chip, nx):
    B = 1024
    mat, vec = _spec((nx, nx, B), one_chip), _spec((nx, B), one_chip)
    ops = (mat, vec, mat, vec, mat)
    compiled, _ = _compile(
        lambda a, b: lqt_combine_lanes(a, b, block_b=512), ops, ops)
    assert "tpu_custom_call" in compiled.as_text()


def test_suffix_scan_kernel_compiles(one_chip):
    T, nx = 2_560, 4
    mat, vec = _spec((T, nx, nx), one_chip), _spec((T, nx), one_chip)
    elems = LQTElement(mat, vec, mat, vec, mat)
    compiled, _ = _compile(lambda e: kernel_suffix_scan(e), elems)
    assert "tpu_custom_call" in compiled.as_text()


def _solve_fn(method, options):
    model = WienerVelocityConfig().model()
    solver = get_method(method).solver
    return lambda ts, y: solver(grid_lqt_from_linear(model, ts, y),
                                options).x


def test_parallel_kernel_solve_compiles(one_chip):
    N = 2_560
    fn = _solve_fn("parallel_kernel", KernelOptions(
        nsub=10, mode="discrete", interpret=False))
    compiled, _ = _compile(fn, _spec((N + 1,), one_chip),
                           _spec((N, 2), one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def parallel_rts_10240(one_chip):
    with jax.enable_x64(False):
        N = 10_240
        fn = _solve_fn("parallel_rts",
                       ParallelOptions(nsub=10, mode="discrete"))
        return _compile(fn, _spec((N + 1,), one_chip),
                        _spec((N, 2), one_chip))


def _time_batched_convolutions(text, N):
    conv_shapes = re.findall(r"= f32\[([\d,]*)\]\S* convolution\(", text)
    return [s for s in conv_shapes
            if any(int(d) >= N // 10 for d in s.split(",") if d)]


def test_parallel_rts_long_horizon_compiles(parallel_rts_10240):
    """The plain-XLA smoother compiles well inside two minutes at a horizon
    where the batched dots and pivoted LU of ``jnp`` took minutes and grew
    with N (core/linalg.py)."""
    compiled, seconds = parallel_rts_10240
    # No dot batched over the time axis: the TPU emits those as
    # convolutions over the batch.  Single small dots may remain.
    assert not _time_batched_convolutions(compiled.as_text(), 10_240)
    assert seconds < 120, seconds
    assert np.isfinite(compiled.memory_analysis().temp_size_in_bytes)


def test_parallel_rts_folds_blocks_in_the_kernel(parallel_rts_10240):
    """On a TPU the in-block fold of ``smoother.elements`` is one Pallas
    kernel: a ``tpu_custom_call`` in that scope, which the benchmark's
    ``block_ms`` reads, and no ``while`` over the substeps left in it."""
    text = parallel_rts_10240[0].as_text()
    in_scope = [line for line in text.splitlines()
                if "smoother.elements" in line and " = " in line]
    assert any('custom_call_target="tpu_custom_call"' in line
               for line in in_scope)
    assert not [line for line in in_scope if re.search(r" while\(", line)]
    assert not _time_batched_convolutions(text, 10_240)


def test_stacked_solve_folds_blocks_in_the_kernel(one_chip):
    """Stacked records, as the engines solve them: the kernel batches."""
    B, N = 8, 2_560
    fn = jax.vmap(_solve_fn("parallel_rts",
                            ParallelOptions(nsub=10, mode="discrete")))
    compiled, _ = _compile(fn, _spec((B, N + 1), one_chip),
                           _spec((B, N, 2), one_chip))
    assert any("tpu_custom_call" in line and "smoother.elements" in line
               for line in compiled.as_text().splitlines())


def test_distributed_solve_compiles_on_four_chips(topo):
    """XLA cannot partition a Mosaic kernel, so a solve over a mesh of
    several chips keeps the XLA fold."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core.options import DistributedOptions
    from repro.distributed.sharding import mesh_context

    mesh = Mesh(np.array(topo.devices).reshape(4), ("time",))
    replicated = NamedSharding(mesh, PartitionSpec())
    N = 2_560
    with mesh_context(mesh):
        compiled, _ = _compile(
            _solve_fn("distributed", DistributedOptions(nsub=10,
                                                        mode="discrete")),
            _spec((N + 1,), replicated), _spec((N, 2), replicated))
    assert "tpu_custom_call" not in compiled.as_text()
