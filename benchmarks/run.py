"""Benchmark harness entry point -- one section per paper figure/table.

Prints ``name,us_per_call,derived`` CSV lines; with ``--json PATH`` also
writes the schema-versioned ``BENCH_<name>.json`` artifact (rows + RNG
seeds + environment fingerprint + the full ``repro.obs`` snapshot --
cache hit/miss, compile seconds, solve-phase spans, engine latency
percentiles, padding waste).  CI's ``bench-baseline`` job runs
``--smoke --json BENCH_smoke.json`` and diffs the artifact against the
committed ``benchmarks/baselines/BENCH_seed.json`` with
``benchmarks/compare.py`` (see docs/OBSERVABILITY.md).

  fig1/*    paper Fig. 1  (linear Wiener velocity, seq vs parallel)
  fig2/*    paper Fig. 2  (coordinated-turn iterated MAP)
  nonlin/*  linearisation strategies (taylor vs sigma-point SLR):
            per-iteration wall time + final OM cost
  kern/*    kernel micro-benchmarks
  batch/*   request-axis throughput (problems/sec vs batch size)
  serve/*   TrajectoryEngine tracks/sec + latency percentiles
  stream/*  StreamingEngine window latency + tracks/sec: fixed-lag
            in-order, 10% late pushes through the reorder-slack path
            (merge/drop accounting), and adaptive-lag self-tuning

Every row names the device it ran on (platform, device kind, device
count).  The time-sharded scaling sweep is not a section here: it needs
several devices, and ``benchmarks/distributed_scaling.py`` rehearses it
standalone on forced CPU host devices.

``--fast`` shrinks the sweeps (CI-sized); ``--smoke`` shrinks further to
bit-rot-check sizes (every section runs in seconds); default runs the full
grids.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# fixed RNG seeds per section -- recorded into the JSON artifact so every
# number is reproducible from the file alone
SEEDS = {"fig1": 0, "fig2": 1, "nonlin": 3, "kern": 0, "batch": 0,
         "serve": 0, "stream": 0}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: CI bit-rot check for every section")
    ap.add_argument("--only", default="",
                    help="comma list: fig1,fig2,nonlin,kern,batch,serve,"
                         "stream")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write the BENCH_<name>.json artifact here "
                         "(CI: BENCH_smoke.json)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    import jax

    import repro.obs as obs
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    obs.enable()
    obs.reset()

    rows = []
    from benchmarks import (
        batch_throughput, engine_latency, fig1_linear, fig2_nonlinear,
        kernels_bench, nonlinear_linearization, streaming_latency,
    )
    if only is None or "fig1" in only:
        if args.smoke:
            rows += fig1_linear.run(T_list=(16,), repeats=1)
        else:
            rows += fig1_linear.run(
                T_list=(128, 256) if args.fast
                else (128, 256, 512, 1024, 2048),
                repeats=3 if args.fast else 5)
    if only is None or "fig2" in only:
        if args.smoke:
            rows += fig2_nonlinear.run(T_list=(16,), repeats=1, iterations=2)
        else:
            rows += fig2_nonlinear.run(
                T_list=(64, 128) if args.fast else (64, 128, 256, 512),
                repeats=2 if args.fast else 5)
    if only is None or "nonlin" in only:
        if args.smoke:
            rows += nonlinear_linearization.run(smoke=True)
        else:
            rows += nonlinear_linearization.run(
                T_list=(64,) if args.fast else (64, 256),
                repeats=2 if args.fast else 3)
    if only is None or "kern" in only:
        rows += kernels_bench.run(smoke=args.smoke)
    if only is None or "batch" in only:
        rows += batch_throughput.run(smoke=args.smoke or args.fast)
    if only is None or "serve" in only:
        rows += engine_latency.run(smoke=args.smoke or args.fast)
    if only is None or "stream" in only:
        rows += streaming_latency.run(smoke=args.smoke or args.fast)

    dev = jax.devices()[0]
    device = (f"platform={dev.platform},device_kind={dev.device_kind},"
              f"device_count={len(jax.devices())}")
    for r in rows:
        r["derived"] = f"{r['derived']},{device}" if r["derived"] else device
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")

    if args.json:
        name = "smoke" if args.smoke else ("fast" if args.fast else "full")
        record = obs.bench_record(name, rows, seeds=SEEDS)
        path = obs.write_bench_json(args.json, record)
        print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
