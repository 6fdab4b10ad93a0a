"""Snapshot + benchmark-artifact export (the ``BENCH_<name>.json`` files).

``snapshot()`` is the one-call readout of everything recorded: counters,
gauges, histogram summaries (p50/p90/p99) and, optionally, the recent
span trees.

``bench_record``/``write_bench_json`` produce the schema-versioned
benchmark artifact emitted by ``benchmarks/run.py --json`` and diffed by
``benchmarks/compare.py`` in CI (``docs/OBSERVABILITY.md`` documents the
schema).  Every record carries the RNG seeds used and an environment
fingerprint (device, jax versions, ``XLA_FLAGS``, x64 policy) so a
number is never detached from the machine state that produced it.
"""
from __future__ import annotations

import json
import os
import platform
from typing import Dict, List, Optional, Sequence

from . import metrics, tracing

SCHEMA_VERSION = 1

# every benchmark row must carry exactly these (run.py's CSV columns)
ROW_KEYS = ("name", "us_per_call", "derived")


def snapshot(include_trees: bool = False) -> dict:
    """Everything recorded so far: ``{"enabled", "counters", "gauges",
    "histograms", "dropped_records"[, "span_trees"]}``."""
    out = {"enabled": metrics.enabled()}
    out.update(metrics.REGISTRY.snapshot())
    if include_trees:
        out["span_trees"] = tracing.span_trees()
    return out


def env_fingerprint() -> dict:
    """Machine/runtime state a benchmark number depends on.  ``jax`` is
    imported lazily; its fields are ``None`` only where it cannot be
    imported, and a failing ``jax.devices()`` raises."""
    fp = {
        "python": platform.python_version(),
        "platform": platform.platform(),
        "cpu_count": os.cpu_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "jax_platforms": os.environ.get("JAX_PLATFORMS", ""),
    }
    try:
        import jax
    except ImportError:
        fp.update({"jax": None, "backend": None, "device_kind": None,
                   "device_count": None, "x64": None})
        return fp
    devs = jax.devices()
    fp.update({
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "x64": bool(jax.config.jax_enable_x64),
    })
    return fp


def bench_record(name: str, rows: Sequence[Dict],
                 seeds: Optional[Dict[str, int]] = None,
                 obs_snapshot: Optional[dict] = None) -> dict:
    """Assemble a schema-v1 benchmark artifact from harness rows."""
    rows = [
        {"name": str(r["name"]),
         "us_per_call": float(r["us_per_call"]),
         "derived": str(r["derived"])}
        for r in rows
    ]
    return {
        "schema_version": SCHEMA_VERSION,
        "benchmark": name,
        "seeds": dict(seeds or {}),
        "env": env_fingerprint(),
        "rows": rows,
        "obs": snapshot() if obs_snapshot is None else obs_snapshot,
    }


def validate_bench(record: dict) -> List[str]:
    """Schema-check a benchmark record; returns a list of problems
    (empty == valid).  Kept in sync with ``docs/OBSERVABILITY.md``."""
    problems: List[str] = []
    if not isinstance(record, dict):
        return ["record is not a JSON object"]
    if record.get("schema_version") != SCHEMA_VERSION:
        problems.append(
            f"schema_version must be {SCHEMA_VERSION}, "
            f"got {record.get('schema_version')!r}")
    for key, typ in (("benchmark", str), ("seeds", dict), ("env", dict),
                     ("rows", list), ("obs", dict)):
        if not isinstance(record.get(key), typ):
            problems.append(f"missing or mistyped field {key!r} "
                            f"(want {typ.__name__})")
    for i, row in enumerate(record.get("rows") or []):
        if not isinstance(row, dict):
            problems.append(f"rows[{i}] is not an object")
            continue
        for k in ROW_KEYS:
            if k not in row:
                problems.append(f"rows[{i}] missing {k!r}")
        if not isinstance(row.get("us_per_call", 0.0), (int, float)):
            problems.append(f"rows[{i}].us_per_call is not a number")
    obs = record.get("obs")
    if isinstance(obs, dict):
        for key in ("counters", "gauges", "histograms"):
            if not isinstance(obs.get(key), dict):
                problems.append(f"obs.{key} missing or mistyped")
    return problems


def write_bench_json(path: str, record: dict) -> str:
    """Validate and write a benchmark artifact; returns ``path``."""
    problems = validate_bench(record)
    if problems:
        raise ValueError("invalid benchmark record: " + "; ".join(problems))
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
        f.write("\n")
    return path
