#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip, in one process.

    python3 benchmarks/chip/control.py --workload wv_offline_long \\
        --seconds 3 --program-seeds 11 12 13 --control-seeds 21 22 23

For each program seed: the cell's set-up, a short window at the cell's
own load and sizes, and the numbers the run compares (``check``).  For
each control seed: the same window, then the same numbers with the
control, the reference computed in the precision below the
configuration's, put in the program's place.  One JSON line per seed.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(run.ROOT / "src"))
    cell = run.load_cell(run.ROOT, args.workload, args.rehearse)
    import jax

    run.configure_jax(jax)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        run.fail(f"no TPU: JAX found {dev.platform}")
    runs = [(s, False) for s in args.program_seeds] + \
        [(s, True) for s in args.control_seeds]
    for seed, control in runs:
        t0 = time.perf_counter()
        driver = run.make_driver(cell, seed % 2 ** 64, args.seconds)
        driver.warm()
        e2e = driver.window(args.seconds)
        attempted, failed = driver.counts()
        driver.release()
        gc.collect()
        readings = driver.check(control=control)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "side": "control" if control else "program",
                          "readings": readings, "end_to_end": e2e,
                          "attempted": attempted, "failed": failed,
                          "device": dev.device_kind,
                          "seconds": time.perf_counter() - t0}), flush=True)
        del driver
        gc.collect()


if __name__ == "__main__":
    main()
