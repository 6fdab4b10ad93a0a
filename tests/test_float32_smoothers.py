"""Every smoother in float32, the chip's dtype, against float64.

The rest of the suite runs with x64 on, where ``core.linalg`` solves by
LAPACK; in float32 it solves by the pivoting Gauss-Jordan elimination the
chip runs.  These tests run that form end to end on the CPU, through
``Estimator.solve``, and compare each trajectory with a float64 one:
the numpy Kalman/RTS smoother for the linear model, the library's own
float64 solve for the nonlinear one.  Errors are relative to the largest
state magnitude, like ``chip_smoke.py``'s.
"""
import jax
import numpy as np
import pytest

from repro.configs.coordinated_turn import CoordinatedTurnConfig
from repro.configs.wiener_velocity import WienerVelocityConfig
from repro.core import (
    Estimator,
    IteratedOptions,
    KernelOptions,
    ParallelOptions,
    Problem,
    SequentialOptions,
    TwoFilterOptions,
    simulate_linear,
    simulate_nonlinear,
    time_grid,
)
from repro.core.oracle import rts_map_host

EPS32 = 2.0 ** -24
N = 640
PAR = dict(nsub=10, mode="discrete")


def _f32_values(a):
    """float64 array of float32-representable values, so both dtypes solve
    the same problem."""
    return np.asarray(a, np.float32).astype(np.float64)


def _rel_err(x, ref):
    x = np.asarray(x, np.float64)
    return float(np.max(np.abs(x - ref)) / np.max(np.abs(ref)))


@pytest.fixture(scope="module")
def wiener_data():
    cfg = WienerVelocityConfig()
    model = cfg.model()
    ts = _f32_values(time_grid(cfg.t0, cfg.tf, N))
    _, y = simulate_linear(model, ts, jax.random.PRNGKey(0))
    y = _f32_values(y)
    a = lambda v: np.asarray(v, np.float64)
    ref = rts_map_host(a(model.F), a(model.c), a(model.H), a(model.r),
                       a(model.Q), a(model.R), y, np.diff(ts), a(model.m0),
                       a(model.P0))
    return cfg, ts, y, ref


# Tolerances in float32 rounding units (eps), about four times the error
# observed at N = 640 on the CPU: 18 eps for parallel_rts and the kernel,
# 49 eps for the sequential recursion, 124 eps for the two-filter, whose
# pointwise (I + C S) solve is the worst-conditioned step.
@pytest.mark.parametrize("method,options,tol", [
    ("sequential_rts", SequentialOptions(mode="discrete"), 200 * EPS32),
    ("parallel_rts", ParallelOptions(**PAR), 80 * EPS32),
    ("parallel_two_filter", TwoFilterOptions(**PAR), 500 * EPS32),
    ("parallel_kernel", KernelOptions(**PAR), 80 * EPS32),
])
def test_linear_smoother_float32_matches_float64(wiener_data, method,
                                                 options, tol):
    cfg, ts, y, ref = wiener_data
    with jax.enable_x64(False):
        model = cfg.model()
        sol = Estimator(model, method=method, options=options).solve(
            Problem.single(model, ts.astype(np.float32),
                           y.astype(np.float32)))
        assert sol.x.dtype == np.float32
        assert np.isfinite(float(sol.cost))
    err = _rel_err(sol.x, ref)
    assert err < tol, (method, err, tol)


def test_iterated_smoother_float32_matches_float64():
    """Coordinated turn, five Gauss-Newton passes of parallel_rts: 5 x 5
    solves in every combine; 70 eps observed."""
    cfg = CoordinatedTurnConfig(q_jitter=float(np.finfo(np.float32).eps))
    opts = IteratedOptions(iterations=cfg.iterations,
                           inner=ParallelOptions(**PAR))
    model64 = cfg.model()
    ts = _f32_values(time_grid(cfg.t0, cfg.tf, N))
    _, y = simulate_nonlinear(model64, ts, jax.random.PRNGKey(1))
    y = _f32_values(y)
    ref = Estimator(model64, method="parallel_rts", options=opts).solve(
        Problem.single(model64, ts, y))
    with jax.enable_x64(False):
        model = cfg.model()
        sol = Estimator(model, method="parallel_rts", options=opts).solve(
            Problem.single(model, ts.astype(np.float32),
                           y.astype(np.float32)))
        assert sol.x.dtype == np.float32
    err = _rel_err(sol.x, np.asarray(ref.x))
    assert err < 300 * EPS32, err
