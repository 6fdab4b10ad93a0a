"""``repro.obs`` -- the telemetry spine: metrics, tracing, export.

Process-local observability every hot path reports through (taxonomy and
JSON schema in ``docs/OBSERVABILITY.md``):

    import repro.obs as obs

    obs.enable()                      # default off; REPRO_OBS=1 also works
    with obs.trace_span("estimator.solve", solve_id=7):
        ...                           # a jax.profiler.TraceAnnotation
    obs.inc("cache.hits")
    obs.record("engine.record_latency_seconds", dt)
    obs.snapshot()                    # -> dict (counters/gauges/histograms)

Disabled (the default) every metric helper is a no-op that allocates
nothing, so instrumented hot paths cost one bool check, and a span one
``jax.profiler.TraceAnnotation`` enter/exit.  Values that refuse ``float``
concretisation (JAX tracers reaching instrumentation under ``jit``) are
dropped, never captured.  ``benchmarks/run.py --json`` serialises
``snapshot()`` plus seeds and an environment fingerprint into the
schema-versioned ``BENCH_<name>.json`` artifacts that
``benchmarks/compare.py`` gates in CI.
"""
from .export import (
    ROW_KEYS,
    SCHEMA_VERSION,
    bench_record,
    env_fingerprint,
    snapshot,
    validate_bench,
    write_bench_json,
)
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    REGISTRY,
    counter,
    disable,
    enable,
    enabled,
    gauge,
    histogram,
    inc,
    record,
    set_gauge,
)
from .tracing import Span, span_trees, trace_span
from . import metrics as _metrics, tracing as _tracing


def reset() -> None:
    """Clear every recorded metric and span (keeps the enabled flag)."""
    _metrics.reset()
    _tracing.reset()


__all__ = [
    "enable", "disable", "enabled", "reset",
    "counter", "gauge", "histogram", "inc", "record", "set_gauge",
    "Counter", "Gauge", "Histogram", "REGISTRY",
    "trace_span", "span_trees", "Span",
    "snapshot", "env_fingerprint",
    "bench_record", "validate_bench", "write_bench_json",
    "SCHEMA_VERSION", "ROW_KEYS",
]
