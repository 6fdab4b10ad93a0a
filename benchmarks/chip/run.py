#!/usr/bin/env python3
"""Chip benchmark of the MAP estimator: one cell of ``BENCHMARK.json`` a run.

    python3 benchmarks/chip/run.py --workload wv_offline_long --seed 7 \\
        --seconds 10 --trace 0

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration's file (``configs/<name>.json``) and its module beside it
(``configs/<name>.py``: the program's model, the simulator, the plain
reference), the traffic mix (``traffic/<name>.json``, driven by
``traffic.py``), the per-layer metric readers (``metrics/<name>.py``) and
the cell's limits (``limits/<workload>.json``).  A new cell, mix or metric
is new files and new entries, never an edit of this file.

A run: set-up (JAX and the chip, data from ``--seed``, executables from
the persistent compilation cache, one warm pass of each of the cell's
shapes), then the measured window of ``--seconds``, then the comparison of
a sample of the window's answers, drawn from the seed, with the float64
reference.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics from a profiler trace of the window.  The last
line of standard output is one JSON object; the compared numbers and
their limits close it (``checks``) and close standard error.

The run refuses to start where JAX finds no TPU, or fewer chips than the
cell asks for; ``--rehearse`` runs the cell at the mix's small sizes on
whatever JAX finds (a CPU rehearsal, not a measurement).
"""
from __future__ import annotations

import time

START = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# Fixed paths in the checkout: the compilation cache's directory is part of
# what an entry is found by, so it never moves.
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"


def fail(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr)
    sys.exit(2)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, workload: str, rehearse: bool = False):
    """The cell ``workload`` of ``root/BENCHMARK.json`` with everything it
    names, found by name: its configuration (file and module), traffic
    mix, end-to-end and per-layer metrics (with their readers) and
    limits."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    here = (root / bench["command"][-1]).parent
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg_file = root / conf["file"]
    cfg = json.loads(cfg_file.read_text())
    cfgmod = load_module(cfg_file.with_suffix(".py"), f"config_{conf['name']}")
    traffic = json.loads((here / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    small = traffic.pop("rehearse", {})
    if rehearse:
        traffic.update(small)
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [])
                 or ("workloads" not in m and m["moves"] in reported)]
    readers = {m["name"]: load_module(here / "metrics" / f"{m['name']}.py",
                                      f"metric_{m['name']}")
               for m in per_layer}
    limits = json.loads((here / "limits" / f"{workload}.json").read_text())
    return SimpleNamespace(name=workload, cell=cell, cfg=cfg, cfgmod=cfgmod,
                           traffic=traffic, end_to_end=end_to_end,
                           per_layer=per_layer, readers=readers,
                           limits=limits)


def configure_jax(jax) -> str:
    """float32 as the configuration states, and the persistent
    compilation cache at ``$JAX_COMPILATION_CACHE_DIR`` where that is set,
    else at the fixed ``CACHE_DIR`` in the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    Path(path).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_driver(c, seed: int, seconds: float):
    import traffic as gen

    kinds = {"offline": gen.Offline}
    return kinds[c.traffic["kind"]](c.cfgmod, c.cfg, c.traffic, seed, seconds)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="small sizes on whatever device JAX finds")
    args = ap.parse_args(argv)
    seed = args.seed % 2 ** 64

    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        c = load_cell(ROOT, args.workload, args.rehearse)
    except (OSError, KeyError, ValueError) as e:
        fail(f"cannot load the cell: {e!r}")

    import jax

    configure_jax(jax)
    devices = jax.devices()
    dev = devices[0]
    chips = c.cell["chips"]
    if dev.platform != "tpu" and not args.rehearse:
        fail(f"no TPU: JAX found {len(devices)} {dev.platform} device(s)")
    if len(devices) < chips:
        fail(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    import roofline

    peaks = None if args.rehearse else roofline.peaks(dev.device_kind)

    try:
        import repro  # noqa: F401
    except ImportError as e:
        fail(f"cannot import the program: {e!r}")

    # A traced run measures a window of at most the mix's `trace_seconds`:
    # reading the trace has to fit in the run's time.
    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, c.traffic.get("trace_seconds", seconds))
    driver = make_driver(c, seed, seconds)
    driver.warm()

    trace_dir = TRACE_DIR / c.name
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        setup_s = time.perf_counter() - START
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    else:
        setup_s = time.perf_counter() - START
    import traffic as gen

    with gen.annotate("bench.window"):
        e2e = driver.window(seconds)
    if args.trace:
        jax.profiler.stop_trace()
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    attempted, failed = driver.counts()
    if args.trace:
        import tracereduce as reduction

        ops, host = reduction.events(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        try:
            summary = reduction.reduce(ops, host)
        except ValueError as e:
            fail(f"the trace cannot be read: {e}")
    driver.release()
    gc.collect()

    readings = driver.check()
    checks = {k: {"value": v, "limit": c.limits[k]}
              for k, v in readings.items()}
    correct = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
                  for v in checks.values()) and failed == 0

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        ctx = SimpleNamespace(trace=summary, driver=driver, cfg=c.cfg,
                              traffic=c.traffic, peaks=peaks)
        metrics = {}
        for m in c.per_layer:
            value = c.readers[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": summary["device_ops"],
            "idle_gaps": summary["idle_gaps"]})
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in c.end_to_end if m["name"] in e2e}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        result.update(metrics=metrics, device=device)
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
