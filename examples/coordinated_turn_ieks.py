"""Nonlinear tracking: iterated parallel MAP on the coordinated-turn model.

Reproduces the paper's section 5.2 setup (range-bearing measurements of a
turning target, 5 linearisation iterations).  The per-iteration
Onsager-Machlup cost now comes straight off ``Solution.cost_trace`` --
ONE compiled solve yields the whole Gauss-Newton descent curve of the
continuous-time IEKS with a parallel-in-time inner solver.  A second pass
swaps the Taylor linearisation for derivative-free sigma-point SLR
(``method="sigma_point"``, docs/LINEARIZATION.md) and prints the final
cost gap at the same iteration count.

    PYTHONPATH=src python examples/coordinated_turn_ieks.py
"""
import jax

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import enable_compile_cache

enable_compile_cache()

import jax.numpy as jnp

from repro.configs.coordinated_turn import CoordinatedTurnConfig
from repro.core import (
    Estimator, IteratedOptions, ParallelOptions, Problem,
    SequentialOptions, SigmaPointOptions, simulate_nonlinear, time_grid,
)

cfg = CoordinatedTurnConfig()
model = cfg.model()
T, n = 128, 10
ts = time_grid(cfg.t0, cfg.tf, T * n)
x_true, y = simulate_nonlinear(model, ts, jax.random.PRNGKey(42))
problem = Problem.single(model, ts, y)

par = Estimator(model, method="parallel_rts",
                options=IteratedOptions(
                    iterations=cfg.iterations,
                    inner=ParallelOptions(nsub=n, mode="discrete")))
sol = par.solve(problem)
rmse = float(jnp.sqrt(jnp.mean((sol.x[:, :2] - x_true[:, :2]) ** 2)))

print("iter | OM cost")
for it, cost in enumerate(sol.cost_trace, start=1):
    print(f"  {it}  | {float(cost):12.2f}")
print(f"final position RMSE: {rmse:.4f}")
assert bool(jnp.all(jnp.diff(sol.cost_trace) <= 1e-3 * jnp.abs(
    sol.cost_trace[:-1]))), "IEKS cost must not increase"

seq = Estimator(model, method="sequential_rts",
                options=IteratedOptions(
                    iterations=cfg.iterations,
                    inner=SequentialOptions(mode="discrete")))
gap = float(jnp.abs(sol.x - seq.solve(problem).x).max())
print(f"parallel vs sequential IEKS max gap: {gap:.2e}")
assert gap < 1e-6

# Sigma-point variant: same iteration count, same parallel inner solver,
# but each pass linearises by statistical linear regression through
# unscented points instead of Jacobians (posterior-linearisation smoother).
sp = Estimator(model, method="sigma_point",
               options=SigmaPointOptions(
                   iterations=cfg.iterations,
                   inner=ParallelOptions(nsub=n, mode="discrete")))
sp_sol = sp.solve(problem)
t_cost, s_cost = float(sol.cost), float(sp_sol.cost)
print(f"final OM cost  taylor={t_cost:.6f}  unscented={s_cost:.6f}  "
      f"gap={s_cost - t_cost:+.2e}")
assert s_cost <= t_cost * (1 + 1e-6), \
    "sigma-point SLR must not end above the Taylor IEKS cost"
print("OK")
