"""Quickstart: parallel-in-time MAP trajectory estimation in ~30 lines.

Simulates the paper's Wiener velocity model (section 5.1), runs the
parallel continuous-time RTS smoother through the unified
``Estimator``/``Problem`` surface, and compares it against the sequential
baseline and the ground truth.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import enable_compile_cache

enable_compile_cache()

import jax.numpy as jnp

from repro.configs.wiener_velocity import WienerVelocityConfig
from repro.core import (
    Estimator, ParallelOptions, Problem, SequentialOptions, simulate_linear,
    time_grid,
)

cfg = WienerVelocityConfig(p0=1.0)      # see DESIGN.md S6 on the prior
model = cfg.model()

T, n = 256, 10                           # T scan blocks x n Euler substeps
ts = time_grid(cfg.t0, cfg.tf, T * n)
x_true, y = simulate_linear(model, ts, jax.random.PRNGKey(0))
problem = Problem.single(model, ts, y)

# "discrete" composes exact substep elements -> parallel == sequential to
# round-off; "euler" is the paper's literal ODE mode (O(dt) agreement).
par = Estimator(model, method="parallel_rts",
                options=ParallelOptions(nsub=n, mode="discrete"))
seq = Estimator(model, method="sequential_rts",
                options=SequentialOptions(mode="discrete"))
sol_par = par.solve(problem)
sol_seq = seq.solve(problem)

rmse = jnp.sqrt(jnp.mean((sol_par.x[:, :2] - x_true[:, :2]) ** 2))
gap = jnp.abs(sol_par.x - sol_seq.x).max()

print(f"trajectory points : {sol_par.x.shape[0]}")
print(f"position RMSE     : {float(rmse):.4f}")
print(f"Onsager-Machlup cost of the MAP estimate: {float(sol_par.cost):.2f}")
print(f"parallel vs sequential max gap: {float(gap):.2e}")
print("filter information S(t_f) diag:",
      jnp.diagonal(sol_par.S[-1]).round(2))
assert float(gap) < 1e-8
assert float(jnp.abs(sol_par.cost - sol_seq.cost)) < 1e-6
print("OK")
