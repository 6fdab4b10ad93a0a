"""The kernel-backed ``parallel_kernel`` method: shared verification
harness asserting ``parallel_kernel == parallel == sequential`` across
precisions, state dims, grid lengths (incl. non-power-of-two scan lengths
that force lane padding), masks and ragged buckets -- plus the registry /
options / cache semantics the new backend must honour.

Compile budget note: every distinct (layout, options) pair compiles a
fresh kernel-scan executable (~15s under the Pallas interpreter), so the
suite shares one module-scoped wiener model/data and leans on the
module-level executable cache instead of re-deriving fixtures per test.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import coordinated_turn, random_ltv, wiener_velocity
from repro.core import (
    Estimator,
    ExecutableCache,
    IteratedOptions,
    KernelOptions,
    ParallelOptions,
    Problem,
    SequentialOptions,
    cache_stats,
    get_method,
    method_names,
    simulate_linear,
    simulate_nonlinear,
    time_grid,
)

pytestmark = pytest.mark.kernel_interpret

NSUB = 5
N = 20                       # T+1 = 5 scan elements: non-pow2, lane pad -> 8

KOPTS = KernelOptions(nsub=NSUB, mode="discrete", interpret=True)
POPTS = ParallelOptions(nsub=NSUB, mode="discrete")


def _max_abs(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))


def _assert_sol_close(got, ref, *, precision="default"):
    """parallel_kernel vs a jnp method, tolerance per kernel precision.

    ``x`` is held to the acceptance-criteria max-abs bound; the
    information-form ``S``/``v`` grow with the horizon, so those use
    relative tolerances at the same precision level.
    """
    if precision == "float32":
        assert _max_abs(got.x, ref.x) < 1e-5
        rtol, atol = 2e-5, 1e-5
    else:
        assert _max_abs(got.x, ref.x) < 1e-8
        rtol, atol = 1e-9, 1e-8
    np.testing.assert_allclose(np.asarray(got.S), np.asarray(ref.S),
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(got.v), np.asarray(ref.v),
                               rtol=rtol, atol=atol)


@pytest.fixture(scope="module")
def wiener():
    """One shared model instance + data: the executable cache keys on the
    model object, so every test reusing this fixture (and KOPTS) reuses
    ONE compiled kernel executable per layout."""
    model = wiener_velocity()
    ts = time_grid(0.0, 1.0, N)
    _, y = simulate_linear(model, ts, jax.random.PRNGKey(0))
    return model, ts, y


@pytest.fixture(scope="module")
def wiener_refs(wiener):
    """Reference solutions of the jnp parallel + sequential methods."""
    model, ts, y = wiener
    problem = Problem.single(model, ts, y)
    par = Estimator(model, method="parallel_rts", options=POPTS).solve(problem)
    seq = Estimator(model, method="sequential_rts",
                    options=SequentialOptions(mode="discrete")).solve(problem)
    return par, seq


# ---------------------------------------------------------------------------
# the shared equivalence harness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("precision", ["default", "float32"])
def test_parallel_kernel_matches_parallel_and_sequential(wiener, wiener_refs,
                                                         precision):
    model, ts, y = wiener
    par, seq = wiener_refs
    got = Estimator(
        model, method="parallel_kernel",
        options=KOPTS.replace(precision=precision),
    ).solve(Problem.single(model, ts, y))
    _assert_sol_close(got, par, precision=precision)
    # transitivity anchor: jnp parallel == sequential to round-off, so the
    # kernel method agrees with the sequential baseline too.
    assert _max_abs(par.x, seq.x) < 1e-8
    _assert_sol_close(got, seq, precision=precision)


@pytest.mark.parametrize("case", [
    # (model key, N intervals, nsub, block_size) -- T+1 scan elements:
    ("wiener", 40, 5, 8),     # nx=4, 9 elems: multi-block grid + lane pad
    ("ltv", 24, 4, 512),      # nx=3, 7 elems, time-varying F/c
], ids=["wiener-n40-b8", "ltv-n24"])
def test_parallel_kernel_across_dims_and_lengths(case):
    key, n, nsub, block_size = case
    model = wiener_velocity() if key == "wiener" else \
        random_ltv(jax.random.PRNGKey(2))
    ts = time_grid(0.0, 1.0, n)
    _, y = simulate_linear(model, ts, jax.random.PRNGKey(n))
    problem = Problem.single(model, ts, y)
    got = Estimator(model, method="parallel_kernel",
                    options=KernelOptions(nsub=nsub, mode="discrete",
                                          interpret=True,
                                          block_size=block_size)
                    ).solve(problem)
    ref = Estimator(model, method="parallel_rts",
                    options=ParallelOptions(nsub=nsub, mode="discrete")
                    ).solve(problem)
    _assert_sol_close(got, ref)


def test_parallel_kernel_with_measurement_mask(wiener):
    model, ts, y = wiener
    mask = jnp.ones(N).at[8:14].set(0.0)           # a missing-data gap
    problem = Problem.single(model, ts, y, measurement_mask=mask)
    got = Estimator(model, method="parallel_kernel",
                    options=KOPTS).solve(problem)
    ref = Estimator(model, method="parallel_rts",
                    options=POPTS).solve(problem)
    _assert_sol_close(got, ref)
    # and the mask actually changed the answer vs the unmasked solve
    unmasked = Estimator(model, method="parallel_kernel",
                         options=KOPTS).solve(Problem.single(model, ts, y))
    assert _max_abs(got.x, unmasked.x) > 1e-6


def test_parallel_kernel_stacked_non_pow2_batch(wiener):
    """B=3 stacked records: the vmapped Pallas call and per-record
    correctness (each row must match its own single solve)."""
    model, ts, y = wiener
    ys = jnp.stack([y] + [simulate_linear(model, ts, jax.random.PRNGKey(k))[1]
                          for k in (1, 2)])
    est = Estimator(model, method="parallel_kernel", options=KOPTS)
    sol = est.solve(Problem.stacked(model, ts, ys))
    assert sol.x.shape == (3, N + 1, model.nx)
    for b in range(3):
        one = est.solve(Problem.single(model, ts, ys[b]))
        assert _max_abs(sol.x[b], one.x) < 1e-10


def test_parallel_kernel_ragged_buckets(wiener):
    """Unequal record lengths -> pad-and-bucket, one kernel executable per
    bucket; each record matches the jnp parallel method's ragged solve."""
    model, _, _ = wiener
    lengths = [14, 20, 40]                       # two distinct buckets
    recs = []
    for i, n in enumerate(lengths):
        ts_i = time_grid(0.0, 0.05 * n, n)
        _, y_i = simulate_linear(model, ts_i, jax.random.PRNGKey(10 + i))
        recs.append((np.asarray(ts_i), np.asarray(y_i)))
    got = Estimator(model, method="parallel_kernel",
                    options=KOPTS).solve(Problem.ragged(model, recs))
    ref = Estimator(model, method="parallel_rts",
                    options=POPTS).solve(Problem.ragged(model, recs))
    assert len(got) == len(lengths)
    for g, r, n in zip(got, ref, lengths):
        assert g.x.shape == (n + 1, model.nx)
        assert _max_abs(g.x, r.x) < 1e-8
        assert g.padding is not None
    assert len(got[0].padding.buckets) == 2


def test_parallel_kernel_nonlinear_coordinated_turn():
    """Iterated linearisation with the kernel backend solving every inner
    linearised subproblem (the acceptance-criteria config pair), incl.
    the float32 kernel precision staying inside the 1e-5 envelope."""
    ct = coordinated_turn()
    ts = time_grid(0.0, 1.0, N)
    _, y = simulate_nonlinear(ct, ts, jax.random.PRNGKey(3))
    problem = Problem.single(ct, ts, y)
    ref = Estimator(ct, method="parallel_rts",
                    options=IteratedOptions(
                        iterations=2,
                        inner=ParallelOptions(nsub=NSUB))).solve(problem)
    got = Estimator(ct, method="parallel_kernel",
                    options=IteratedOptions(
                        iterations=2,
                        inner=KernelOptions(nsub=NSUB, interpret=True))
                    ).solve(problem)
    assert _max_abs(got.x, ref.x) < 1e-8
    got32 = Estimator(ct, method="parallel_kernel",
                      options=IteratedOptions(
                          iterations=2,
                          inner=KernelOptions(nsub=NSUB, interpret=True,
                                              precision="float32"))
                      ).solve(problem)
    assert _max_abs(got32.x, ref.x) < 1e-5


def test_parallel_kernel_euler_mode(wiener):
    """euler elements differ from discrete ones; the kernel scan must
    track the jnp scan in that mode too (same elements, same tree)."""
    model, ts, y = wiener
    problem = Problem.single(model, ts, y)
    got = Estimator(model, method="parallel_kernel",
                    options=KOPTS.replace(mode="euler")).solve(problem)
    ref = Estimator(model, method="parallel_rts",
                    options=POPTS.replace(mode="euler")).solve(problem)
    _assert_sol_close(got, ref)


# ---------------------------------------------------------------------------
# registry / options / cache semantics of the new backend
# ---------------------------------------------------------------------------


def test_kernel_options_validation():
    with pytest.raises(TypeError):
        KernelOptions(block=128)                  # unknown field
    with pytest.raises(TypeError):
        KernelOptions(blocksize=128)              # typo'd field
    with pytest.raises(ValueError, match="block_size"):
        KernelOptions(block_size=4)
    with pytest.raises(ValueError, match="precision"):
        KernelOptions(precision="float16")
    with pytest.raises(ValueError, match="interpret"):
        KernelOptions(interpret=1)
    with pytest.raises(ValueError, match="nsub"):
        KernelOptions(nsub=0)                     # inherited validation
    with pytest.raises(ValueError, match="mode"):
        KernelOptions(mode="bogus")
    # frozen + hashable (cache-key requirement)
    o = KernelOptions(nsub=5, block_size=128, precision="float32")
    assert hash(o) == hash(KernelOptions(nsub=5, block_size=128,
                                         precision="float32"))
    assert o.replace(block_size=256).block_size == 256


def test_kernel_options_interpret_resolution():
    assert KernelOptions(interpret=True).resolve_interpret() is True
    assert KernelOptions(interpret=False).resolve_interpret() is False
    # auto mode on the test backend: the interpreter on the CPU
    assert jax.default_backend() == "cpu"
    assert KernelOptions().resolve_interpret() is True


@pytest.mark.parametrize("backend,expected", [
    ("tpu", False), ("cpu", True), ("gpu", None), ("rocm", None)])
def test_kernel_options_auto_interpret_by_backend(monkeypatch, backend,
                                                  expected):
    """``interpret=None`` is Mosaic on a TPU and the interpreter on the
    CPU; any other backend raises instead of falling back silently."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expected is None:
        with pytest.raises(RuntimeError, match=backend):
            KernelOptions().resolve_interpret()
    else:
        assert KernelOptions().resolve_interpret() is expected
    # an explicit flag is never overridden by the backend
    assert KernelOptions(interpret=True).resolve_interpret() is True


def test_parallel_kernel_registered_and_in_live_methods_view():
    assert "parallel_kernel" in method_names()
    spec = get_method("parallel_kernel")
    assert spec.options_cls is KernelOptions
    assert isinstance(spec.default_options(), KernelOptions)
    import repro.core
    with pytest.warns(DeprecationWarning, match="METHODS"):
        live = repro.core.METHODS
    assert "parallel_kernel" in live


def test_parallel_kernel_cache_key_bit_exact(wiener):
    """Two solves with identical options must reuse ONE executable and
    return bit-identical arrays; the shared module cache keys on the
    options value, not the instance."""
    model, ts, y = wiener
    problem = Problem.single(model, ts, y)
    a = Estimator(model, method="parallel_kernel", options=KOPTS
                  ).solve(problem)
    mid = cache_stats()
    b = Estimator(model, method="parallel_kernel",
                  options=KernelOptions(nsub=NSUB, mode="discrete",
                                        interpret=True)).solve(problem)
    after = cache_stats()
    assert after["misses"] == mid["misses"]    # equal options: no recompile
    assert after["hits"] == mid["hits"] + 1    # the second solve was a hit
    np.testing.assert_array_equal(np.asarray(a.x), np.asarray(b.x))
    np.testing.assert_array_equal(np.asarray(a.S), np.asarray(b.S))
    np.testing.assert_array_equal(np.asarray(a.v), np.asarray(b.v))

    # distinct kernel options (block_size) -> distinct executable key,
    # same numerics; private cache isolates the count assertion.
    private = ExecutableCache()
    c = Estimator(model, method="parallel_kernel",
                  options=KOPTS.replace(block_size=8),
                  cache=private).solve(problem)
    assert private.misses == 1
    assert _max_abs(a.x, c.x) < 1e-10


def test_parallel_kernel_lower_aot(wiener):
    model, ts, y = wiener
    est = Estimator(model, method="parallel_kernel", options=KOPTS)
    problem = Problem.single(model, ts, y)
    compiled = est.lower(problem).compile()
    sol_aot = compiled(ts, y)
    sol = est.solve(problem)
    np.testing.assert_array_equal(np.asarray(sol_aot.x), np.asarray(sol.x))
