"""The one general generator: drives a cell's traffic mix through the
program, from the mix's data file alone.

A mix file (``traffic/<name>.json``) names its ``kind`` and the sizes,
rates and shares of its traffic.  Each kind is a driver here:

``offline``
    Closed loop, one solve in flight: ``Estimator.solve`` on one record of
    ``intervals`` intervals at a time, from host numpy arrays to the host
    trajectory.  It cycles through ``records`` records made from the seed,
    so no solve repeats the last one's input.  End to end: ``solve_ms``.

Every driver gives each seed the same set of sizes and arrivals, in
another order, so the seed changes the data and never the work.  After
the window a driver keeps a sample of its answers, drawn from the seed,
for the comparison with the plain reference (``check``).
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np


def f32_grid(N: int, dt: float) -> np.ndarray:
    """``N + 1`` time points ``k dt`` as float32 values, held in float64:
    the float32 program and the float64 reference solve the same grid
    (``chip_smoke.f32_grid``)."""
    return (np.arange(N + 1) * dt).astype(np.float32).astype(np.float64)


def annotate(name: str):
    """A ``jax.profiler.TraceAnnotation``: a host span on the trace's
    timeline, which names the idle gaps of the device."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


class Reservoir:
    """A uniform sample of ``size`` items from a stream of unknown length,
    drawn from ``rng`` (reservoir sampling)."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng, self.seen = size, rng, 0
        self.items: List = []

    def offer(self, item) -> None:
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = item
        self.seen += 1


def relative_gap(x: np.ndarray, ref: np.ndarray) -> float:
    """Largest absolute error over the largest reference magnitude;
    infinite where the answer has another shape than the reference."""
    if np.shape(x) != np.shape(ref):
        return float("inf")
    return float(np.max(np.abs(np.asarray(x, np.float64) - ref))
                 / np.max(np.abs(ref)))


class Offline:
    """``offline``: closed loop over ``Estimator.solve``."""

    def __init__(self, cfgmod, cfg, traffic, seed, seconds):
        from repro.core import Estimator

        self.cfgmod, self.cfg = cfgmod, cfg
        rng = np.random.default_rng([seed, 0])
        N, K = traffic["intervals"], traffic["records"]
        self.ts = f32_grid(N, traffic["dt"])
        self.ys = cfgmod.simulate(cfg, rng, self.ts, K)
        self.ts32 = self.ts.astype(np.float32)
        self.ys32 = self.ys.astype(np.float32)
        self.model, method, options = cfgmod.build(cfg)
        self.est = Estimator(self.model, method=method, options=options)
        self.sample = Reservoir(traffic["checked"],
                                np.random.default_rng([seed, 1]))
        self.solves = 0

    def _solve(self, i: int):
        import jax

        from repro.core import Problem

        with annotate("bench.solve"):
            sol = self.est.solve(Problem.single(
                self.model, self.ts32, self.ys32[i % len(self.ys32)]))
            return jax.device_get((sol.x, sol.cost))

    def warm(self) -> None:
        for i in range(2):
            self._solve(i)

    def window(self, seconds: float) -> Dict[str, float]:
        t0 = time.perf_counter()
        end = t0 + seconds
        n = 0
        while True:
            x, cost = self._solve(n)
            self.sample.offer((n % len(self.ys), x, float(cost)))
            n += 1
            now = time.perf_counter()
            if now >= end:
                break
        self.solves = n
        return {"solve_ms": (now - t0) / n * 1e3}

    def counts(self) -> Tuple[int, int]:
        return self.solves, 0

    def release(self) -> None:
        self.est = None

    def check(self, control: bool = False) -> Dict[str, float]:
        """Trajectory gap and OM-cost gap (the cost the solve returned)
        of the sampled solves against the float64 reference of their
        records.  ``control=True`` puts the
        reference in lower precision (``cfgmod.control``) in the program's
        place, on the same records."""
        if not self.sample.items:
            return {"traj_gap": float("inf"), "cost_gap": float("inf")}
        recs = sorted({r for r, _, _ in self.sample.items})
        refs = dict(zip(recs, self.cfgmod.reference(
            self.cfg, self.ts, self.ys[recs])))
        costs = {r: self.cfgmod.cost(self.cfg, self.ts, self.ys[r], refs[r])
                 for r in recs}
        items = self.sample.items
        if control:
            low = dict(zip(recs, self.cfgmod.control(
                self.cfg, self.ts, self.ys[recs])))
            items = [(r, low[r], None) for r in recs]
        traj = max(relative_gap(x, refs[r]) for r, x, _ in items)
        # The cost the program returned with its trajectory; the control
        # returns none, so its trajectory's float64 cost stands in.
        cost = max(abs((self.cfgmod.cost(self.cfg, self.ts, self.ys[r], x)
                        if c is None else c) - costs[r]) / abs(costs[r])
                   for r, x, c in items)
        return {"traj_gap": traj, "cost_gap": cost}

