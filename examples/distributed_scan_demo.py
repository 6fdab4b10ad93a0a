"""Temporal parallelism across devices: the paper's scan, sharded in time.

Forces 8 host devices and solves one T=512-block MAP problem through the
PUBLIC estimation surface with ``method="distributed"`` -- the solver
shards both global associative scans over the mesh's time axis (local
Blelloch scan + one all-gather of carries + redundant carry scan + local
fix-up; the multi-pod decomposition of DESIGN.md S3).  Verifies exact
agreement with the single-device ``parallel_rts`` method.

    PYTHONPATH=src python examples/distributed_scan_demo.py
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import enable_compile_cache

enable_compile_cache()

import jax.numpy as jnp

from repro.configs.wiener_velocity import WienerVelocityConfig
from repro.core import (
    DistributedOptions, Estimator, ParallelOptions, Problem,
    simulate_linear, time_grid,
)
from repro.distributed import MeshSpec

cfg = WienerVelocityConfig(p0=1.0)
model = cfg.model()
T, n = 512, 10
ts = time_grid(cfg.t0, cfg.tf, T * n)
_, y = simulate_linear(model, ts, jax.random.PRNGKey(0))
problem = Problem.single(model, ts, y)

# One mesh entry point: MeshSpec describes the (time x batch) layout and
# is passed wherever a mesh= is accepted (or entered via .activate()).
mesh = MeshSpec(time=8)

dist = Estimator(model, method="distributed", mesh=mesh,
                 options=DistributedOptions(nsub=n, mode="discrete"))
single = Estimator(model, method="parallel_rts",
                   options=ParallelOptions(nsub=n, mode="discrete"))

sol_dist = dist.solve(problem)
sol_single = single.solve(problem)
gap = max(float(jnp.abs(sol_dist.x - sol_single.x).max()),
          float(jnp.abs(sol_dist.S - sol_single.S).max()))

print(f"devices           : {jax.device_count()}")
print(f"time blocks       : {T} ({T // 8} per device)")
print(f"distributed vs single-device parallel max gap: {gap:.2e}")
print("filter info at t_f (diag):",
      jnp.diagonal(sol_dist.S[-1]).round(3))
assert gap < 1e-8
print("OK")
