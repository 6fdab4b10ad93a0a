"""The ``repro.obs`` telemetry spine: registry semantics (thread safety,
disabled no-ops, tracer safety), estimator/engine instrumentation, and the
guarantee that observability never changes numerics.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import coordinated_turn, wiener_velocity
from repro import obs
from repro.core import (
    Estimator,
    IteratedOptions,
    ParallelOptions,
    Problem,
    SequentialOptions,
    cache_stats,
    get_method,
    simulate_linear,
    simulate_nonlinear,
    time_grid,
)
from repro.serving import TrajectoryEngine

NSUB = 5


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts disabled + empty and leaves no obs state behind
    (the suite's other tests must keep running on the uninstrumented
    path)."""
    was = obs.enabled()
    obs.disable()
    obs.reset()
    yield
    obs.reset()
    (obs.enable if was else obs.disable)()


def _linear_problem(T=4 * NSUB):
    model = wiener_velocity()
    ts = time_grid(0.0, 1.0, T)
    _, y = simulate_linear(model, ts, jax.random.PRNGKey(0))
    return model, ts, y


# -- registry semantics -----------------------------------------------------


def test_counter_gauge_histogram_basics():
    obs.enable()
    obs.inc("a.count")
    obs.inc("a.count", 4)
    obs.set_gauge("a.depth", 3)
    obs.set_gauge("a.depth", 7.5)          # last write wins
    for v in (0.001, 0.01, 0.01, 0.1):
        obs.record("a.lat", v)
    snap = obs.snapshot()
    assert snap["enabled"] is True
    assert snap["counters"]["a.count"] == 5
    assert snap["gauges"]["a.depth"] == 7.5
    h = snap["histograms"]["a.lat"]
    assert h["count"] == 4
    assert h["min"] == pytest.approx(0.001)
    assert h["max"] == pytest.approx(0.1)
    assert h["sum"] == pytest.approx(0.121)
    assert snap["dropped_records"] == 0


def test_histogram_percentiles_bucket_accurate():
    obs.enable()
    vals = [i / 1000.0 for i in range(1, 1001)]      # 1ms .. 1s uniform
    for v in vals:
        obs.record("h", v)
    h = obs.histogram("h")
    # geometric buckets are ~2.15x wide; the interpolated estimate must
    # land within one bucket of the true quantile and inside [min, max]
    for q, true in ((0.5, 0.5), (0.9, 0.9), (0.99, 0.99)):
        est = h.percentile(q)
        assert vals[0] <= est <= vals[-1]
        assert true / 2.2 <= est <= true * 2.2, (q, est)
    assert h.percentile(1.0) == pytest.approx(1.0)


def test_exact_counts_under_threads():
    obs.enable()
    threads = [
        threading.Thread(target=lambda: [
            (obs.inc("t.count"), obs.record("t.hist", 0.01))
            for _ in range(1000)])
        for _ in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert obs.counter("t.count").value == 8000
    assert obs.histogram("t.hist").count == 8000


def test_disabled_is_a_noop_that_allocates_nothing():
    assert not obs.enabled()
    obs.inc("x")
    obs.set_gauge("y", 1.0)
    obs.record("z", 0.5)
    with obs.trace_span("w"):
        pass
    assert obs.REGISTRY.is_empty()
    assert obs.span_trees() == []
    snap = obs.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}


def test_tracer_values_dropped_never_captured():
    obs.enable()

    @jax.jit
    def f(x):
        obs.record("traced.value", x)        # abstract tracer: must drop
        obs.set_gauge("traced.gauge", x)
        return x * 2.0

    out = f(jnp.asarray(3.0))
    assert float(out) == 6.0                 # trace unbroken
    snap = obs.snapshot()
    assert "traced.value" not in snap["histograms"]
    assert "traced.gauge" not in snap["gauges"]
    assert snap["dropped_records"] >= 2


# -- estimator instrumentation ----------------------------------------------


def test_solve_bit_exact_with_obs_on_and_off():
    model, ts, y = _linear_problem()
    est = Estimator(model, method="parallel_rts",
                    options=ParallelOptions(nsub=NSUB))
    problem = Problem.single(model, ts, y)
    sol_off = est.solve(problem)
    obs.enable()
    sol_on = est.solve(problem)
    np.testing.assert_array_equal(np.asarray(sol_off.x), np.asarray(sol_on.x))
    np.testing.assert_array_equal(np.asarray(sol_off.cov),
                                  np.asarray(sol_on.cov))
    assert obs.snapshot()["dropped_records"] == 0


def test_solve_phases_and_cache_metrics():
    obs.enable()
    model, ts, y = _linear_problem()
    est = Estimator(model, method="parallel_rts",
                    options=ParallelOptions(nsub=NSUB))
    before = cache_stats()
    est.solve(Problem.single(model, ts, y))      # fresh: compiles
    est.solve(Problem.single(model, ts, y))      # cached
    after = cache_stats()
    assert after["misses"] == before["misses"] + 1
    assert after["hits"] >= before["hits"] + 1
    assert set(after) == {"size", "hits", "misses", "evictions"}
    snap = obs.snapshot()
    assert snap["counters"]["cache.misses"] >= 1
    assert snap["counters"]["cache.hits"] >= 1
    assert snap["counters"]["estimator.solves"] == 2
    assert snap["histograms"]["cache.compile_seconds"]["count"] == 1
    h = snap["histograms"]
    assert h["span.estimator.problem"]["count"] == 2
    assert h["span.estimator.solve"]["count"] == 2
    assert h["span.estimator.prepare"]["count"] == 2
    assert h["span.estimator.compile"]["count"] == 1
    assert h["span.estimator.dispatch"]["count"] == 1
    # the compile span holds the first call's trace and compile: it must
    # outlast the cached executable's dispatch
    assert (h["span.estimator.compile"]["max"]
            > h["span.estimator.dispatch"]["min"])
    solves = [t for t in obs.span_trees() if t["name"] == "estimator.solve"]
    assert [[c["name"] for c in t["children"]] for t in solves] == [
        ["estimator.prepare", "estimator.compile"],
        ["estimator.prepare", "estimator.dispatch"]]
    for t in solves:
        ids = {c["metadata"]["solve_id"] for c in t["children"]}
        assert ids == {t["metadata"]["solve_id"]}
        assert t["t0_ns"] <= t["children"][0]["t0_ns"]
        assert t["children"][-1]["t1_ns"] <= t["t1_ns"]
    assert solves[0]["metadata"] != solves[1]["metadata"]


def test_nonlinear_iteration_metrics_and_step_norms():
    obs.enable()
    model = coordinated_turn()
    ts = time_grid(0.0, 1.0, 4 * NSUB)
    _, y = simulate_nonlinear(model, ts, jax.random.PRNGKey(0))
    est = Estimator(model, method="parallel_rts",
                    options=IteratedOptions(
                        inner=ParallelOptions(nsub=NSUB), iterations=5))
    sol = est.solve(Problem.single(model, ts, y))
    assert sol.step_norms is not None
    steps = np.asarray(sol.step_norms)
    assert steps.shape == (5,)
    assert steps[-1] < steps[0]          # Gauss-Newton converging
    trace = np.asarray(sol.cost_trace)
    assert trace.shape == (5,)
    assert trace[-1] <= trace[0]
    assert float(sol.cost) == trace[-1]
    snap = obs.snapshot()
    assert snap["gauges"]["nonlinear.iterations"] == 5
    assert snap["counters"]["linearize.taylor.solves"] == 1
    # the solution's diagnostics stay on the device: no host readback of
    # them lands in the registry
    assert not {"estimator.final_cost", "nonlinear.cost_decrease",
                "nonlinear.final_step_norm"} & set(snap["histograms"])
    assert snap["histograms"]["span.estimator.compile"]["count"] == 1


def test_diagnostics_false_keeps_hot_path_silent():
    """``diagnostics=False`` skips the cost and records no diagnostics;
    the solve takes the same one path, spans and all."""
    obs.enable()
    model, ts, y = _linear_problem()
    est = Estimator(model, method="sequential_rts",
                    options=SequentialOptions(), diagnostics=False)
    sol = est.solve(Problem.single(model, ts, y))
    assert sol.cost is None and sol.cost_trace is None  # diagnostics skipped
    snap = obs.snapshot()
    assert snap["counters"]["estimator.solves"] == 1
    assert snap["gauges"] == {}
    assert set(snap["histograms"]) == {
        "cache.compile_seconds", "span.estimator.problem",
        "span.estimator.solve", "span.estimator.prepare",
        "span.estimator.compile"}
    assert all(h["count"] == 1 for h in snap["histograms"].values())


def test_ragged_solve_reports_padding_metrics():
    obs.enable()
    model = wiener_velocity()
    rng = np.random.default_rng(0)
    records = []
    for n in (7, 12, 18, 25):
        ts = np.linspace(0.0, n / 32.0, n + 1)
        records.append((ts, rng.standard_normal((n, 2))))
    est = Estimator(model, method="parallel_rts",
                    options=ParallelOptions(nsub=NSUB))
    sols = est.solve(Problem.ragged(model, records))
    assert len(sols) == 4
    snap = obs.snapshot()
    assert snap["counters"]["padding.records"] == 4
    assert snap["counters"]["padding.real_intervals"] == 7 + 12 + 18 + 25
    assert (snap["counters"]["padding.solved_intervals"]
            >= snap["counters"]["padding.real_intervals"])
    assert 0.0 <= snap["gauges"]["padding.waste"] < 1.0


# -- engine instrumentation -------------------------------------------------


def _engine_records(lengths, rng):
    out = []
    for n in lengths:
        ts = np.linspace(0.0, n / 32.0, n + 1)
        out.append((ts, rng.standard_normal((n, 2))))
    return out


def test_engine_wave_and_latency_metrics():
    obs.enable()
    model = wiener_velocity()
    engine = TrajectoryEngine(model, batch=4, method="parallel_rts",
                              options=ParallelOptions(nsub=NSUB))
    recs = _engine_records([7, 12, 9, 14, 8, 11], np.random.default_rng(0))
    sols = engine.estimate(recs)
    assert len(sols) == 6
    snap = obs.snapshot()
    c, g, h = snap["counters"], snap["gauges"], snap["histograms"]
    assert c["engine.submitted"] == 6
    assert c["engine.completed"] == 6
    assert c["engine.waves"] == engine.waves
    assert c["engine.real_intervals"] == 7 + 12 + 9 + 14 + 8 + 11
    assert c["engine.padded_intervals"] >= c["engine.real_intervals"]
    assert 0.0 <= g["engine.padding_waste"] < 1.0
    assert g["engine.queue_depth"] == 0          # drained
    assert g["engine.tracks_per_sec"] > 0
    assert h["engine.record_latency_seconds"]["count"] == 6
    assert h["engine.record_latency_seconds"]["p50"] > 0
    assert h["engine.wave_occupancy"]["count"] == engine.waves
    assert h["span.engine.step"]["count"] == engine.waves


def test_engine_threaded_submits_counted_exactly():
    obs.enable()
    model = wiener_velocity()
    engine = TrajectoryEngine(model, batch=4, method="parallel_rts",
                              options=ParallelOptions(nsub=NSUB))
    per_thread = 5

    def submit_some(seed):
        for ts, y in _engine_records([10] * per_thread,
                                     np.random.default_rng(seed)):
            engine.submit(ts, y)

    threads = [threading.Thread(target=submit_some, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert engine.run() == 4 * per_thread
    snap = obs.snapshot()
    assert snap["counters"]["engine.submitted"] == 4 * per_thread
    assert snap["counters"]["engine.completed"] == 4 * per_thread
    assert (snap["histograms"]["engine.record_latency_seconds"]["count"]
            == 4 * per_thread)


# -- tracing ----------------------------------------------------------------


def test_span_trees_nest_and_time():
    obs.enable()
    with obs.trace_span("outer"):
        with obs.trace_span("inner"):
            pass
        with obs.trace_span("inner"):
            pass
    trees = obs.span_trees()
    assert len(trees) == 1
    root = trees[0]
    assert root["name"] == "outer"
    assert [c["name"] for c in root["children"]] == ["inner", "inner"]
    assert root["dur_s"] >= max(c["dur_s"] for c in root["children"]) >= 0
    snap = obs.snapshot()
    assert snap["histograms"]["span.outer"]["count"] == 1
    assert snap["histograms"]["span.inner"]["count"] == 2


def test_spans_record_nothing_while_obs_off():
    """Disabled, the spans are bare profiler annotations: a solve leaves
    the registry and the span ring empty."""
    model, ts, y = _linear_problem()
    est = Estimator(model, method="parallel_rts",
                    options=ParallelOptions(nsub=NSUB))
    for _ in range(2):                       # fresh, then cached
        est.solve(Problem.single(model, ts, y))
    with obs.trace_span("outer", solve_id=1):
        with obs.trace_span("inner"):
            pass
    assert obs.REGISTRY.is_empty()
    assert obs.span_trees() == []


def _host_events(logdir):
    import glob
    import os

    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    return [(e.name, dict(e.stats)) for p in data.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events if e.name.startswith("estimator.")]


# jaxlib's profile reader builds its stats type on first use, with a
# DeprecationWarning that the suite's error filter would make fatal.
@pytest.mark.filterwarnings("ignore:builtin type:DeprecationWarning")
@pytest.mark.parametrize("enabled", [False, True], ids=["obs-off", "obs-on"])
def test_profiler_trace_shows_solve_spans(tmp_path, enabled):
    """The program's host spans land on the profiler's host plane whether
    or not obs records them, and the spans of one solve share its id."""
    if enabled:
        obs.enable()
    model, ts, y = _linear_problem()
    est = Estimator(model, method="parallel_rts",
                    options=ParallelOptions(nsub=NSUB))
    est.solve(Problem.single(model, ts, y))        # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            jax.block_until_ready(est.solve(Problem.single(model, ts, y)).x)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    names = [n for n, _ in events]
    for name in ("estimator.problem", "estimator.solve", "estimator.prepare",
                 "estimator.dispatch"):
        assert names.count(name) == 2, names
    assert "estimator.compile" not in names
    by_id = {}
    for name, stats in events:
        if name != "estimator.problem":
            by_id.setdefault(stats["solve_id"], []).append(name)
    assert len(by_id) == 2
    for group in by_id.values():
        assert sorted(group) == ["estimator.dispatch", "estimator.prepare",
                                 "estimator.solve"]


SCOPES = ("smoother.elements", "smoother.scan", "smoother.fill",
          "smoother.recover", "estimator.cost", "nonlinear.linearize")


@pytest.mark.parametrize("kind", ["linear", "two_filter", "nonlinear"])
def test_named_scopes_in_the_lowered_program(kind):
    """Each stage of a solve carries its named scope into the program's
    debug info (the ops' ``op_name``, which a device profile shows)."""
    if kind == "nonlinear":
        model = coordinated_turn()
        ts = time_grid(0.0, 1.0, 4 * NSUB)
        _, y = simulate_nonlinear(model, ts, jax.random.PRNGKey(0))
        est = Estimator(model, method="parallel_rts",
                        options=IteratedOptions(
                            inner=ParallelOptions(nsub=NSUB), iterations=2))
        want = SCOPES
    else:
        model, ts, y = _linear_problem()
        method = "parallel_rts" if kind == "linear" else "parallel_two_filter"
        est = Estimator(model, method=method,
                        options=get_method(method).options_cls(nsub=NSUB))
        want = SCOPES[:-1]
    text = est.lower(Problem.single(model, ts, y)).as_text(debug_info=True)
    assert [s for s in want if s not in text] == []
    if kind != "nonlinear":
        assert "nonlinear.linearize" not in text


def test_solve_neither_blocks_nor_reads_back(monkeypatch):
    """With obs enabled, a cached solve dispatches and returns: nothing in
    the call waits for the device or copies a result to the host."""
    obs.enable()
    model = coordinated_turn()
    ts = time_grid(0.0, 1.0, 4 * NSUB)
    _, y = simulate_nonlinear(model, ts, jax.random.PRNGKey(0))
    est = Estimator(model, method="parallel_rts",
                    options=IteratedOptions(
                        inner=ParallelOptions(nsub=NSUB), iterations=2))
    want = est.solve(Problem.single(model, ts, y))
    jax.block_until_ready(want)

    def refuse(*args, **kwargs):
        raise AssertionError("the solve waited for or read a device array")

    array_type = type(want.x)
    monkeypatch.setattr(jax, "block_until_ready", refuse)
    monkeypatch.setattr(array_type, "block_until_ready", refuse)
    monkeypatch.setattr(array_type, "__array__", refuse)
    monkeypatch.setattr(jax.Array, "__array__", refuse, raising=False)
    sol = est.solve(Problem.single(model, ts, y))
    monkeypatch.undo()
    np.testing.assert_array_equal(np.asarray(sol.x), np.asarray(want.x))
    np.testing.assert_array_equal(np.asarray(sol.cost_trace),
                                  np.asarray(want.cost_trace))
    assert obs.snapshot()["histograms"]["span.estimator.dispatch"][
        "count"] == 1


def test_fresh_solve_keys_its_cache_entry_by_metadata(monkeypatch):
    """A fresh executable compiles with the module's metadata in its
    persistent-cache key, so an entry of a build with other scopes is
    never handed back for it; the flag is restored after the compile and
    the next solve of the layout compiles nothing."""
    from jax._src import compiler
    from jax._src import config as jax_config

    flag = jax_config.compilation_cache_include_metadata_in_key
    seen = []
    real = compiler.compile_or_get_cached

    def spy(*args, **kwargs):
        seen.append(flag.value)
        return real(*args, **kwargs)

    model, ts, y = _linear_problem(T=6 * NSUB)
    problem = Problem.single(model, ts, y)
    est = Estimator(model, method="parallel_rts",
                    options=ParallelOptions(nsub=NSUB))
    monkeypatch.setattr(compiler, "compile_or_get_cached", spy)
    est.solve(problem)
    compiled = len(seen)
    assert compiled >= 1 and all(seen)
    assert flag.value is False
    est.solve(problem)
    assert len(seen) == compiled
