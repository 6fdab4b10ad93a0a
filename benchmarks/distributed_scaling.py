"""Distributed-scan scaling: ``method="distributed"`` vs single-device.

A standalone CPU rehearsal, not a chip benchmark: it forces host-platform
devices (CPU) and times the SAME MAP problem solved
through the public Estimator surface at increasing time-shard counts P:

* **strong scaling** -- total block count T fixed, P grows: per-solve
  wall time should fall toward ``O(T/P + P)`` span (on forced HOST
  devices all shards share the physical cores, so the numbers measure
  harness overhead, not real speedup -- the shape of the curve and the
  schema of the rows are what CI gates);
* **weak scaling** -- blocks per shard fixed, T = P * blocks: per-solve
  wall time should stay flat.

``P = 1`` rows run the single-device ``parallel_rts`` scan via the
distributed method's fallback, so each sweep carries its own baseline.

    PYTHONPATH=src python benchmarks/distributed_scaling.py [--smoke] \\
        [--json PATH]
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

DEVICES = int(os.environ.get("REPRO_BENCH_DEVICES", "8"))
# must precede the first jax import: the device count locks at init
os.environ.setdefault("XLA_FLAGS",
                      f"--xla_force_host_platform_device_count={DEVICES}")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp


def _time_solve(est, problem, ts, ys, repeats: int) -> float:
    compiled = est.lower(problem).compile()          # AOT: no retrace
    compiled(ts, ys).x.block_until_ready()           # warmup
    t0 = time.perf_counter()
    for _ in range(repeats):
        compiled(ts, ys).x.block_until_ready()
    return (time.perf_counter() - t0) / repeats


def run(strong_T=512, weak_blocks=128, nsub=10, repeats=3, smoke=False):
    from repro.configs.wiener_velocity import WienerVelocityConfig
    from repro.core import DistributedOptions, Estimator, Problem
    from repro.core import simulate_linear, time_grid

    if smoke:
        strong_T, weak_blocks, nsub, repeats = 32, 16, 5, 1

    shard_counts = [p for p in (1, 2, 4, 8) if p <= jax.device_count()]
    wcfg = WienerVelocityConfig(p0=1.0)
    model = wcfg.model()

    def solve_time(T: int, P: int) -> float:
        ts = time_grid(wcfg.t0, wcfg.tf, T * nsub, dtype=jnp.float32)
        _, y = simulate_linear(model, ts, jax.random.PRNGKey(0))
        est = Estimator(model, method="distributed",
                        options=DistributedOptions(
                            nsub=nsub, mode="discrete",
                            devices_per_time=P))
        return _time_solve(est, Problem.single(model, ts, y), ts, y,
                           repeats)

    rows = []
    base = None
    for P in shard_counts:                            # strong: T fixed
        dt = solve_time(strong_T, P)
        base = dt if P == 1 else base
        rows.append({
            "name": f"dist/strong/P{P}_T{strong_T}",
            "us_per_call": dt * 1e6,
            "derived": f"speedup_vs_p1={base / dt:.2f}",
        })
    base = None
    for P in shard_counts:                            # weak: T/P fixed
        dt = solve_time(weak_blocks * P, P)
        base = dt if P == 1 else base
        rows.append({
            "name": f"dist/weak/P{P}_T{weak_blocks * P}",
            "us_per_call": dt * 1e6,
            "derived": f"efficiency_vs_p1={base / dt:.2f}",
        })
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problem sizes (CI bit-rot check)")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write a BENCH json artifact for this section")
    args = ap.parse_args()
    import repro.obs as obs
    if args.json:
        obs.enable()
        obs.reset()
    rows = run(smoke=args.smoke)
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    if args.json:
        obs.write_bench_json(
            args.json, obs.bench_record("dist", rows, seeds={"dist": 0}))


if __name__ == "__main__":
    main()
