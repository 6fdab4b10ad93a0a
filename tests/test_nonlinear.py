"""Iterated (parallel) MAP estimation on the coordinated-turn model (5.2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Estimator,
    IteratedOptions,
    ParallelOptions,
    Problem,
    SequentialOptions,
    TwoFilterOptions,
    om_cost_nonlinear,
    simulate_nonlinear,
    time_grid,
)

from helpers import coordinated_turn


@pytest.fixture(scope="module")
def ct_problem():
    model = coordinated_turn()
    N = 640
    ts = time_grid(0.0, 5.0, N)
    xs, y = simulate_nonlinear(model, ts, jax.random.PRNGKey(2))
    return model, ts, xs, y


def _ieks(model, method, inner, **outer):
    return Estimator(model, method=method,
                     options=IteratedOptions(inner=inner, **outer))


def test_parallel_equals_sequential_ieks(ct_problem):
    model, ts, _, y = ct_problem
    problem = Problem.single(model, ts, y)
    par = _ieks(model, "parallel_rts",
                ParallelOptions(nsub=10, mode="discrete"),
                iterations=5).solve(problem)
    seq = _ieks(model, "sequential_rts",
                SequentialOptions(mode="discrete"),
                iterations=5).solve(problem)
    np.testing.assert_allclose(par.x, seq.x, rtol=1e-8, atol=1e-8)
    np.testing.assert_allclose(par.cost_trace, seq.cost_trace,
                               rtol=1e-8, atol=1e-8)


def test_cost_trace_is_gauss_newton_descent(ct_problem):
    """Solution.cost_trace: one entry per linearise+solve pass, matching
    the true nonlinear OM cost of each iterate, and with
    cost == cost_trace[-1].  Gauss-Newton is not guaranteed monotone on
    the first pass (the prior-mean linearisation point is far off), so we
    require descent overall and from iteration 2 on."""
    model, ts, _, y = ct_problem
    sol = _ieks(model, "parallel_rts",
                ParallelOptions(nsub=10, mode="discrete"),
                iterations=5).solve(Problem.single(model, ts, y))
    trace = np.asarray(sol.cost_trace)
    assert trace.shape == (5,)
    assert float(sol.cost) == trace[-1]
    assert trace[-1] < trace[0]
    assert np.all(np.diff(trace[1:]) <= 1e-4 * np.abs(trace[1:-1]))
    # the last entry IS the OM cost of the returned trajectory
    ref = float(om_cost_nonlinear(model, ts, y, sol.x))
    np.testing.assert_allclose(trace[-1], ref, rtol=1e-9)
    # and iteration counts agree with separately-run shorter solves
    for it in (1, 3):
        s = _ieks(model, "parallel_rts",
                  ParallelOptions(nsub=10, mode="discrete"),
                  iterations=it).solve(Problem.single(model, ts, y))
        np.testing.assert_allclose(np.asarray(s.cost_trace), trace[:it],
                                   rtol=1e-8)


def test_ieks_tracks_truth(ct_problem):
    model, ts, xs, y = ct_problem
    sol = _ieks(model, "parallel_rts",
                ParallelOptions(nsub=10, mode="discrete"),
                iterations=5).solve(Problem.single(model, ts, y))
    rmse = float(jnp.sqrt(jnp.mean((sol.x[:, :2] - xs[:, :2]) ** 2)))
    # positions are observed through (range, bearing) with tight noise
    assert rmse < 0.5, rmse


def test_euler_mode_ieks(ct_problem):
    model, ts, _, y = ct_problem
    problem = Problem.single(model, ts, y)
    par = _ieks(model, "parallel_rts", ParallelOptions(nsub=10, mode="euler"),
                iterations=3).solve(problem)
    seq = _ieks(model, "sequential_rts", SequentialOptions(mode="euler"),
                iterations=3).solve(problem)
    assert float(jnp.max(jnp.abs(par.x - seq.x))) < 5e-2


def test_divergence_correction_runs(ct_problem):
    """the beyond-paper Onsager-Machlup divergence knob must run and stay
    close to the uncorrected solution (div f = 0 for coordinated turn!)."""
    model, ts, _, y = ct_problem
    problem = Problem.single(model, ts, y)
    inner = ParallelOptions(nsub=10, mode="discrete")
    a = _ieks(model, "parallel_rts", inner, iterations=2).solve(problem)
    b = _ieks(model, "parallel_rts", inner, iterations=2,
              divergence_correction=True).solve(problem)
    # f = (v, -w zdot, w xidot, 0): div f = d(-w zdot)/dzdot ... = 0 + w - w = 0
    np.testing.assert_allclose(a.x, b.x, rtol=1e-7, atol=1e-7)


def test_two_filter_ieks(ct_problem):
    model, ts, _, y = ct_problem
    problem = Problem.single(model, ts, y)
    rts = _ieks(model, "parallel_rts",
                ParallelOptions(nsub=10, mode="discrete"),
                iterations=3).solve(problem)
    tf = _ieks(model, "parallel_two_filter",
               TwoFilterOptions(nsub=10, mode="discrete"),
               iterations=3).solve(problem)
    np.testing.assert_allclose(tf.x, rts.x, rtol=1e-5, atol=1e-5)


def test_x_init_warm_start(ct_problem):
    """A converged trajectory as x_init must keep the solution at the
    optimum in one pass; a single-point x_init must broadcast."""
    model, ts, _, y = ct_problem
    problem = Problem.single(model, ts, y)
    inner = ParallelOptions(nsub=10, mode="discrete")
    # The IEKS contracts linearly here, by about 0.42 per pass (RMS step
    # 1.6e-3 after pass 5): 20 passes reach a 3e-9 RMS step, so one more
    # pass from that point moves x by about 1e-9, well inside the bound.
    ref = _ieks(model, "parallel_rts", inner, iterations=20).solve(problem)
    warm = _ieks(model, "parallel_rts", inner, iterations=1).solve(
        Problem.single(model, ts, y, x_init=ref.x))
    np.testing.assert_allclose(warm.x, ref.x, atol=1e-7, rtol=0)
    point = _ieks(model, "parallel_rts", inner, iterations=1).solve(
        Problem.single(model, ts, y, x_init=model.m0))
    cold = _ieks(model, "parallel_rts", inner, iterations=1).solve(problem)
    np.testing.assert_allclose(point.x, cold.x, rtol=1e-9, atol=1e-9)
