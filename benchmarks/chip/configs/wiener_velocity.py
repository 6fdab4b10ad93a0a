"""Paper section 5.1, the partially observed Wiener velocity model
(eqs. 52-54): the program's model, a simulator, and the plain reference.

The model is built from ``wiener_velocity.json`` alone, so a later change
to the program's own configuration classes does not move the benchmark.
The simulator and the reference take nothing from the program.
"""
from __future__ import annotations

import numpy as np

import reference as ref


def matrices(cfg):
    """``F, c, H, r, Q, R, m0, P0`` in float64 numpy: nearly constant
    velocity in 2-D, positions observed, ``Q = L (q I2) L^T`` with ``L``
    the velocity rows (singular in the position rows)."""
    F = np.zeros((4, 4))
    F[0, 2] = F[1, 3] = 1.0
    H = np.eye(2, 4)
    L = np.vstack([np.zeros((2, 2)), np.eye(2)])
    Q = L @ (cfg["q"] * np.eye(2)) @ L.T
    R = cfg["r"] * np.eye(2)
    m0 = np.asarray(cfg["m0"], np.float64)
    P0 = cfg["p0"] * np.eye(4)
    return F, np.zeros(4), H, np.zeros(2), Q, R, m0, P0


def build(cfg):
    """The system under test: ``(model, method, options)``."""
    import jax.numpy as jnp

    from repro.core import LinearSDE, ParallelOptions

    F, c, H, r, Q, R, m0, P0 = (jnp.asarray(a, jnp.float32)
                                for a in matrices(cfg))
    model = LinearSDE(F=F, c=c, H=H, r=r, Q=Q, R=R, m0=m0, P0=P0)
    return model, cfg["method"], ParallelOptions(nsub=cfg["nsub"],
                                                 mode=cfg["mode"])


def simulate(cfg, rng, ts, count):
    """``count`` measurement records on the grid ``ts`` (``(N+1,)``), each
    from its own Euler-Maruyama path of the model started from the prior;
    values rounded to float32.  Returns ``(count, N, 2)`` float64."""
    F, _, H, _, Q, R, m0, P0 = matrices(cfg)
    L = np.sqrt(cfg["q"]) * np.vstack([np.zeros((2, 2)), np.eye(2)])
    return simulate_paths(rng, lambda x: x @ F.T, L, lambda x: x @ H.T, R,
                          m0, P0, ts, count)


def simulate_paths(rng, f, L, h, R, m0, P0, ts, count):
    """Euler-Maruyama paths of ``dx = f(x) dt + L dW`` and measurements
    ``y_k = h(x_{k+1}) + N(0, R / dt_k)``, rounded to float32 values
    (``chip_smoke.simulate``, batched over ``count`` paths)."""
    N = ts.shape[0] - 1
    nx = m0.shape[0]
    dt = np.diff(ts)
    x = np.empty((N + 1, count, nx))
    x[0] = m0 + rng.standard_normal((count, nx)) @ np.linalg.cholesky(P0).T
    w = rng.standard_normal((N, count, L.shape[1])) @ L.T
    sq = np.sqrt(dt)
    for k in range(N):
        x[k + 1] = x[k] + dt[k] * f(x[k]) + sq[k] * w[k]
    noise = rng.standard_normal((N, count, R.shape[0])) @ \
        np.linalg.cholesky(R).T
    y = h(x[1:]) + noise / sq[:, None, None]
    return np.swapaxes(y, 0, 1).astype(np.float32).astype(np.float64)


def reference(cfg, ts, y):
    """Float64 MAP trajectories ``(B, N+1, 4)`` of records ``y`` ``(B, N,
    2)`` on grids ``ts`` (``(N+1,)`` or ``(B, N+1)``)."""
    F, c, H, r, Q, R, m0, P0 = matrices(cfg)
    return ref.reference_map(F, c, H, r, Q, R, y, np.diff(ts, axis=-1),
                             m0, P0)


def control(cfg, ts, y):
    """The reference in float32 with three-pass bfloat16 products."""
    F, c, H, r, Q, R, m0, P0 = matrices(cfg)
    return ref.control_map(F, c, H, r, Q, R, y, np.diff(ts, axis=-1),
                           m0, P0)


def cost(cfg, ts, y, x):
    """Float64 Onsager-Machlup cost of trajectory ``x`` (``(N+1, 4)``):
    the quadrature of the program's ``om_cost_grid`` (``chip_smoke.
    linear_cost``)."""
    F, c, H, r, Q, R, m0, P0 = matrices(cfg)
    dt = np.diff(ts)
    d0 = x[0] - m0
    resid = np.diff(x, axis=0) / dt[:, None] - (x[1:] @ F.T + c)
    innov = y - (x[1:] @ H.T + r)
    Qp, Ri = np.linalg.pinv(Q), np.linalg.inv(R)
    return float(0.5 * d0 @ np.linalg.solve(P0, d0)
                 + 0.5 * np.sum(dt * np.einsum("ki,ij,kj->k", resid, Qp,
                                               resid))
                 + 0.5 * np.sum(dt * np.einsum("ki,ij,kj->k", innov, Ri,
                                               innov)))

