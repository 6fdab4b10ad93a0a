"""The unified Estimator/Problem/Solution surface: registry error paths,
construction-time option validation, problem validation, diagnostics, the
AOT ``lower`` path, and live method registration."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import coordinated_turn, random_ltv, wiener_velocity
from repro.core import (
    Estimator,
    IteratedOptions,
    ParallelOptions,
    Problem,
    SequentialOptions,
    SolverOptions,
    TwoFilterOptions,
    get_method,
    grid_lqt_from_linear,
    method_names,
    om_cost_grid,
    om_cost_linear,
    register_method,
    sequential_rts,
    simulate_linear,
    simulate_nonlinear,
    time_grid,
)

NSUB = 5


@pytest.fixture(scope="module")
def linear_problem():
    model = wiener_velocity()
    ts = time_grid(0.0, 1.0, 4 * NSUB)
    _, y = simulate_linear(model, ts, jax.random.PRNGKey(0))
    return model, ts, y


# -- registry error paths ---------------------------------------------------


def test_unknown_method_name(linear_problem):
    model, _, _ = linear_problem
    with pytest.raises(ValueError, match="method must be one of"):
        Estimator(model, method="no_such_method")
    with pytest.raises(ValueError, match="no_such_method"):
        get_method("no_such_method")


def test_duplicate_registration_requires_overwrite():
    register_method("_dup_test", lambda g, o: sequential_rts(g, o.mode),
                    SequentialOptions, overwrite=True)
    with pytest.raises(ValueError, match="already registered"):
        register_method("_dup_test", lambda g, o: None, SequentialOptions)
    # overwrite=True replaces silently
    register_method("_dup_test", lambda g, o: sequential_rts(g, o.mode),
                    SequentialOptions, overwrite=True)
    assert "_dup_test" in method_names()


def test_register_method_rejects_bad_options_cls():
    with pytest.raises(TypeError, match="SolverOptions subclass"):
        register_method("_bad_opts", lambda g, o: None, dict,
                        overwrite=True)


def test_registered_method_is_solvable(linear_problem):
    model, ts, y = linear_problem
    register_method("_seq_alias", lambda g, o: sequential_rts(g, o.mode),
                    SequentialOptions, overwrite=True)
    problem = Problem.single(model, ts, y)
    sol = Estimator(model, method="_seq_alias",
                    options=SequentialOptions(mode="discrete")).solve(problem)
    ref = Estimator(model, method="sequential_rts",
                    options=SequentialOptions(mode="discrete")).solve(problem)
    np.testing.assert_allclose(sol.x, ref.x, atol=1e-12, rtol=0)


# -- option validation (construction time) ----------------------------------


def test_unknown_option_field_errors():
    with pytest.raises(TypeError):
        ParallelOptions(nsubb=10)            # typo'd field
    with pytest.raises(TypeError):
        SequentialOptions(nsub=10)           # field of a DIFFERENT method
    with pytest.raises(TypeError):
        IteratedOptions(iteration=3)


def test_option_value_validation():
    with pytest.raises(ValueError, match="mode"):
        ParallelOptions(mode="bogus")
    with pytest.raises(ValueError, match="nsub"):
        ParallelOptions(nsub=0)
    with pytest.raises(ValueError, match="iterations"):
        IteratedOptions(iterations=0)
    with pytest.raises(ValueError, match="block0_fill"):
        TwoFilterOptions(block0_fill="nope")
    with pytest.raises(TypeError, match="inner"):
        IteratedOptions(inner="parallel_rts")


def test_options_are_frozen_and_hashable():
    o = ParallelOptions(nsub=7, mode="discrete")
    with pytest.raises(dataclasses.FrozenInstanceError):
        o.nsub = 3
    assert hash(o) == hash(ParallelOptions(nsub=7, mode="discrete"))
    assert o.replace(nsub=3).nsub == 3


def test_estimator_rejects_mismatched_options(linear_problem):
    model, _, _ = linear_problem
    with pytest.raises(TypeError, match="TwoFilterOptions"):
        Estimator(model, method="parallel_two_filter",
                  options=ParallelOptions())
    with pytest.raises(TypeError, match="IteratedOptions is for Nonlinear"):
        Estimator(model, method="parallel_rts", options=IteratedOptions())
    ct = coordinated_turn()
    with pytest.raises(TypeError, match="inner"):
        Estimator(ct, method="parallel_rts",
                  options=IteratedOptions(inner=SequentialOptions()))
    # bare inner options are auto-wrapped for nonlinear models
    est = Estimator(ct, method="parallel_rts",
                    options=ParallelOptions(nsub=NSUB))
    assert isinstance(est.options, IteratedOptions)
    assert est.options.inner == ParallelOptions(nsub=NSUB)
    assert est.block_size == NSUB


# -- problem validation ------------------------------------------------------


def test_measurement_mask_validation(linear_problem):
    model, ts, y = linear_problem
    N = y.shape[0]
    with pytest.raises(ValueError, match="measurement_mask"):
        Problem.single(model, ts, y,
                       measurement_mask=jnp.ones(N - 1))   # wrong length
    with pytest.raises(ValueError, match="0/1 array"):     # wrong dtype
        Problem.single(model, ts, y,
                       measurement_mask=jnp.ones(N, dtype=jnp.complex64))
    with pytest.raises(ValueError, match="measurement_mask"):
        Problem.stacked(model, ts, y[None],
                        measurement_mask=jnp.ones(N))      # needs (B, N)
    ok = Problem.single(model, ts, y, measurement_mask=jnp.ones(N))
    assert ok.measurement_mask.shape == (N,)
    # integer/bool 0/1 masks are cast to float, not rejected
    as_int = Problem.single(model, ts, y,
                            measurement_mask=np.ones(N, dtype=np.int32))
    assert jnp.issubdtype(as_int.measurement_mask.dtype, jnp.floating)
    as_bool = Problem.single(model, ts, y,
                             measurement_mask=np.ones(N, dtype=bool))
    assert jnp.issubdtype(as_bool.measurement_mask.dtype, jnp.floating)


def test_x_init_validation(linear_problem):
    model, ts, y = linear_problem
    with pytest.raises(ValueError, match="NonlinearSDE"):
        Problem.single(model, ts, y, x_init=jnp.zeros(model.nx))
    ct = coordinated_turn()
    ts3 = time_grid(0.0, 1.0, 4 * NSUB)
    _, y3 = simulate_nonlinear(ct, ts3, jax.random.PRNGKey(3))
    with pytest.raises(ValueError, match="x_init"):
        Problem.single(ct, ts3, y3, x_init=jnp.zeros(3))   # wrong nx
    with pytest.raises(ValueError, match="x_init"):
        Problem.stacked(ct, ts3, y3[None],
                        x_init=jnp.zeros((2, ct.nx)))      # wrong batch


def test_problem_model_must_match_estimator(linear_problem):
    model, ts, y = linear_problem
    other = wiener_velocity()
    est = Estimator(model, method="sequential_rts")
    with pytest.raises(ValueError, match="model"):
        est.solve(Problem.single(other, ts, y))


def test_ragged_record_validation():
    model = wiener_velocity()
    with pytest.raises(ValueError, match="non-empty"):
        Problem.ragged(model, [])
    ts = np.linspace(0.0, 1.0, 11)
    y = np.zeros((10, 2))
    with pytest.raises(ValueError, match="record 1"):
        Problem.ragged(model, [(ts, y), (ts[:-1], y)])


# -- diagnostics & AOT -------------------------------------------------------


def test_solution_cost_matches_om_cost():
    """Solution.cost == the om_cost_linear objective (invertible-Q model,
    where pinv == inv and the quadratures match term by term)."""
    model = random_ltv(jax.random.PRNGKey(7))
    ts = time_grid(0.0, 2.0, 4 * NSUB)
    _, y = simulate_linear(model, ts, jax.random.PRNGKey(1))
    sol = Estimator(model, method="parallel_rts",
                    options=ParallelOptions(nsub=NSUB, mode="discrete")
                    ).solve(Problem.single(model, ts, y))
    ref = float(om_cost_linear(model, ts, y, sol.x))
    np.testing.assert_allclose(float(sol.cost), ref, rtol=1e-9)


@pytest.mark.parametrize("method,options", [
    ("sequential_rts", SequentialOptions(mode="discrete")),
    ("parallel_rts", ParallelOptions(nsub=10, mode="discrete")),
    ("parallel_two_filter", TwoFilterOptions(nsub=10, mode="discrete")),
])
def test_cost_term_leaves_trajectory_finite(method, options):
    """The cost term must not change the trajectory.  With a host
    pseudo-inverse of ``Q`` as a constant in the float64 program, beside
    ``core.linalg``'s dots and LAPACK solves, XLA:CPU returned a NaN
    trajectory from parallel_two_filter on this problem (PERF.md, open
    questions), so float64 keeps the per-point pseudo-inverse; this test
    fails if it takes the constant."""
    model = wiener_velocity()
    ts = time_grid(0.0, 5.0, 2560)
    _, y = simulate_linear(model, ts, jax.random.PRNGKey(0))
    problem = Problem.single(model, ts, y)
    sol = Estimator(model, method=method, options=options).solve(problem)
    plain = Estimator(model, method=method, options=options,
                      diagnostics=False).solve(problem)
    assert np.isfinite(np.asarray(sol.x)).all()
    np.testing.assert_allclose(sol.x, plain.x, rtol=1e-12, atol=1e-12)
    ref = om_cost_grid(grid_lqt_from_linear(model, ts, y), sol.x)
    np.testing.assert_allclose(float(sol.cost), float(ref), rtol=1e-9)


def test_lower_compile_aot(linear_problem):
    model, ts, y = linear_problem
    est = Estimator(model, method="parallel_rts",
                    options=ParallelOptions(nsub=NSUB, mode="discrete"))
    problem = Problem.single(model, ts, y)
    compiled = est.lower(problem).compile()
    sol_aot = compiled(ts, y)
    sol = est.solve(problem)
    np.testing.assert_array_equal(np.asarray(sol_aot.x), np.asarray(sol.x))
    recs = [(np.asarray(ts), np.asarray(y))]
    with pytest.raises(ValueError, match="ragged"):
        est.lower(Problem.ragged(model, recs))


def test_solver_options_base_rejects_bad_mode():
    with pytest.raises(ValueError):
        SolverOptions(mode="")


def test_cache_distinguishes_mask_from_x_init():
    """Regression: a (N,) float mask and an (nx,) x_init with nx == N have
    identical argument shapes/dtypes; the cache key must still separate
    the two executables (it keys on has_mask/has_xinit, not just shapes).
    """
    from repro.core import ExecutableCache, cache_stats

    model = coordinated_turn()            # nx = 5
    ts = time_grid(0.0, 1.0, 5)           # N = 5 == nx
    _, y = simulate_nonlinear(model, ts, jax.random.PRNGKey(4))
    est = Estimator(model, method="sequential_rts",
                    options=IteratedOptions(
                        iterations=2, inner=SequentialOptions(mode="euler")))
    mask = jnp.array([1.0, 1.0, 1.0, 0.0, 0.0])   # drops two intervals
    x0 = jnp.asarray(model.m0)
    assert mask.shape == x0.shape and mask.dtype == x0.dtype

    before = cache_stats()
    masked = est.solve(Problem.single(model, ts, y, measurement_mask=mask))
    warmed = est.solve(Problem.single(model, ts, y, x_init=x0))
    after = cache_stats()
    assert after["misses"] == before["misses"] + 2   # two executables

    # and the x_init solve matches a fresh private-cache estimator (i.e. it
    # did NOT run through the masked executable)
    fresh = Estimator(model, method="sequential_rts",
                      options=IteratedOptions(
                          iterations=2,
                          inner=SequentialOptions(mode="euler")),
                      cache=ExecutableCache())
    ref = fresh.solve(Problem.single(model, ts, y, x_init=x0))
    np.testing.assert_array_equal(np.asarray(warmed.x), np.asarray(ref.x))
    assert not np.allclose(np.asarray(masked.x), np.asarray(warmed.x))


def test_diagnostics_opt_out(linear_problem):
    model, ts, y = linear_problem
    problem = Problem.single(model, ts, y)
    options = ParallelOptions(nsub=NSUB, mode="discrete")
    lean = Estimator(model, method="parallel_rts", options=options,
                     diagnostics=False).solve(problem)
    full = Estimator(model, method="parallel_rts",
                     options=options).solve(problem)
    assert lean.cost is None and lean.cost_trace is None
    assert full.cost is not None
    np.testing.assert_array_equal(np.asarray(lean.x), np.asarray(full.x))
    # nonlinear: no cost trace either
    ct = coordinated_turn()
    ts3 = time_grid(0.0, 1.0, 4 * NSUB)
    _, y3 = simulate_nonlinear(ct, ts3, jax.random.PRNGKey(5))
    lean_nl = Estimator(ct, method="parallel_rts",
                        options=IteratedOptions(
                            iterations=2,
                            inner=ParallelOptions(nsub=NSUB)),
                        diagnostics=False).solve(Problem.single(ct, ts3, y3))
    assert lean_nl.cost is None and lean_nl.cost_trace is None
    assert bool(jnp.isfinite(lean_nl.x).all())
