"""Batched multi-trajectory estimation: the request axis in ~50 lines.

One ``Estimator`` serves every layout: a stack of independent
Wiener-velocity problems as one compiled program (``Problem.stacked``), a
ragged mix of record lengths via pad-and-bucket (``Problem.ragged``, with
the padding report on the solutions), and the same workload through the
serving-style ``TrajectoryEngine``.

    PYTHONPATH=src python examples/batch_estimation.py
"""
import jax

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import enable_compile_cache

enable_compile_cache()

import jax.numpy as jnp
import numpy as np

from repro.configs.wiener_velocity import WienerVelocityConfig
from repro.core import (
    Estimator, ParallelOptions, Problem, cache_stats, simulate_linear,
    time_grid,
)
from repro.serving import TrajectoryEngine

cfg = WienerVelocityConfig(p0=1.0)
model = cfg.model()
T, n = 64, 10
est = Estimator(model, method="parallel_rts",
                options=ParallelOptions(nsub=n, mode="discrete"))

# --- stacked batch: B records sharing one time grid -> ONE compiled solve
B = 16
ts = time_grid(cfg.t0, cfg.tf, T * n)
ys = jnp.stack([simulate_linear(model, ts, jax.random.PRNGKey(i))[1]
                for i in range(B)])
sol = est.solve(Problem.stacked(model, ts, ys))
ref = est.solve(Problem.single(model, ts, ys[0]))
gap = float(jnp.abs(sol.x[0] - ref.x).max())
print(f"stacked batch     : {sol.x.shape} (batch, time, state)")
print(f"per-record OM cost: {np.asarray(sol.cost).round(1)}")
print(f"batched vs single solve max gap: {gap:.2e}")
assert gap < 1e-9

# --- ragged lengths: pad-and-bucket keeps the executable count tiny
lengths = [130, 250, 460, 250, 900, 130]
records = []
for i, N in enumerate(lengths):
    ts_i = time_grid(cfg.t0, cfg.tf * N / (T * n), N)
    _, y_i = simulate_linear(model, ts_i, jax.random.PRNGKey(100 + i))
    records.append((np.asarray(ts_i), np.asarray(y_i)))
sols = est.solve(Problem.ragged(model, records))
report = sols[0].padding
print(f"ragged lengths    : {lengths}")
print(f"returned lengths  : {[s.x.shape[0] - 1 for s in sols]}")
print(f"padding report    : buckets={[(b.n_pad, b.records, b.batch) for b in report.buckets]}"
      f" interval_util={report.interval_utilisation:.2f}"
      f" row_util={report.row_utilisation:.2f}")
print(f"executable cache  : {cache_stats()}")

# --- serving engine: queue + submit/collect with fixed-batch waves
engine = TrajectoryEngine(model, batch=4, method="parallel_rts",
                          options=ParallelOptions(nsub=n, mode="discrete"))
tickets = [engine.submit(ts_i, y_i) for ts_i, y_i in records]
engine.run()
done = engine.collect()
print(f"engine solved     : {len(done)} requests in {engine.waves} waves "
      f"({engine.recycled_rows} rows recycled)")
assert [t for t, _ in done] == tickets
print("OK")
