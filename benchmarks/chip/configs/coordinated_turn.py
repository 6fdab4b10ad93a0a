"""Paper section 5.2, the coordinated-turn model (eqs. 55-58): the
program's model, a simulator, and the plain reference, an iterated
extended Kalman smoother of the same Taylor passes.

Built from ``coordinated_turn.json`` alone; the simulator and the
reference take nothing from the program.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

import reference as ref

_spec = importlib.util.spec_from_file_location(
    "wiener_velocity_sim", Path(__file__).with_name("wiener_velocity.py"))
_wv = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_wv)
simulate_paths = _wv.simulate_paths


def matrices(cfg):
    """``Q, R, m0, P0`` in float64 numpy: ``Q = L L^T + jitter I`` with
    ``L`` the noise gains ``sigma_v`` on both velocities and ``sigma_w`` on
    the turn rate."""
    L = np.zeros((5, 3))
    L[2, 0] = L[3, 1] = cfg["sigma_v"]
    L[4, 2] = cfg["sigma_w"]
    Q = L @ L.T + cfg["q_jitter"] * np.eye(5)
    R = np.diag(cfg["r_diag"])
    return (Q, R, np.asarray(cfg["m0"], np.float64),
            np.diag(np.asarray(cfg["p0_diag"], np.float64)))


def f(x):
    """Drift: positions follow velocities, velocities turn at rate x4."""
    return np.stack([x[..., 2], x[..., 3], -x[..., 4] * x[..., 3],
                     x[..., 4] * x[..., 2], np.zeros_like(x[..., 0])],
                    axis=-1)


def h(x):
    """Range and bearing from the origin."""
    return np.stack([np.hypot(x[..., 0], x[..., 1]),
                     np.arctan2(x[..., 1], x[..., 0])], axis=-1)


def build(cfg):
    """The system under test: ``(model, method, options)``."""
    import jax.numpy as jnp

    from repro.core import IteratedOptions, NonlinearSDE, ParallelOptions

    Q, R, m0, P0 = (jnp.asarray(a, jnp.float32) for a in matrices(cfg))

    def f_jax(x, t):
        return jnp.array([x[2], x[3], -x[4] * x[3], x[4] * x[2], 0.0])

    def h_jax(x, t):
        return jnp.array([jnp.sqrt(x[0] ** 2 + x[1] ** 2),
                          jnp.arctan2(x[1], x[0])])

    model = NonlinearSDE(f=f_jax, h=h_jax, Q=Q, R=R, m0=m0, P0=P0)
    options = IteratedOptions(
        iterations=cfg["iterations"], linearization=cfg["linearization"],
        inner=ParallelOptions(nsub=cfg["nsub"], mode=cfg["mode"]))
    return model, cfg["method"], options


def simulate(cfg, rng, ts, count):
    """``count`` measurement records ``(count, N, 2)`` on grid ``ts``."""
    Q, R, m0, P0 = matrices(cfg)
    return simulate_paths(rng, f, np.linalg.cholesky(Q), h, R, m0, P0, ts,
                          count)


def _ieks(cfg, ts, y, solve, dtype):
    """Iterated extended Kalman smoother: ``cfg["iterations"]`` Taylor
    passes about the left grid points, from the constant prior mean (the
    program's linearisation; ``chip_smoke.nonlinear.ref_solve``)."""
    Q, R, m0, P0 = (a.astype(dtype) for a in matrices(cfg))
    y = np.asarray(y, dtype)
    B, N, _ = y.shape
    dt = np.diff(ts, axis=-1).astype(dtype)
    xbar = np.broadcast_to(m0, (B, N + 1, 5)).astype(dtype)
    for _ in range(cfg["iterations"]):
        xb = xbar[:, :-1]
        Fk = np.zeros((B, N, 5, 5), dtype)
        Fk[..., 0, 2] = Fk[..., 1, 3] = 1.0
        Fk[..., 2, 3], Fk[..., 2, 4] = -xb[..., 4], -xb[..., 3]
        Fk[..., 3, 2], Fk[..., 3, 4] = xb[..., 4], xb[..., 2]
        rr = np.hypot(xb[..., 0], xb[..., 1])
        Hk = np.zeros((B, N, 2, 5), dtype)
        Hk[..., 0, 0], Hk[..., 0, 1] = xb[..., 0] / rr, xb[..., 1] / rr
        Hk[..., 1, 0] = -xb[..., 1] / rr ** 2
        Hk[..., 1, 1] = xb[..., 0] / rr ** 2
        c = f(xb) - np.einsum("bkij,bkj->bki", Fk, xb)
        r = h(xb) - np.einsum("bkij,bkj->bki", Hk, xb)
        xbar = solve(Fk, c, Hk, r, Q, R, y, dt, m0, P0).astype(dtype)
    return xbar.astype(np.float64)


def reference(cfg, ts, y):
    """Float64 IEKS trajectories ``(B, N+1, 5)``."""
    return _ieks(cfg, ts, y, ref.reference_map, np.float64)


def control(cfg, ts, y):
    """The IEKS in float32 with three-pass bfloat16 products."""
    return _ieks(cfg, ts, y, ref.control_map, np.float32)


def cost(cfg, ts, y, x):
    """Float64 Onsager-Machlup cost of trajectory ``x`` (``(N+1, 5)``)
    (``chip_smoke.nonlinear.cost``)."""
    Q, R, m0, P0 = matrices(cfg)
    dt = np.diff(ts)
    d0 = x[0] - m0
    resid = np.diff(x, axis=0) / dt[:, None] - f(x[1:])
    innov = y - h(x[1:])
    return float(
        0.5 * d0 @ np.linalg.solve(P0, d0)
        + 0.5 * np.sum(dt * np.einsum("ki,ij,kj->k", resid,
                                      np.linalg.inv(Q), resid))
        + 0.5 * np.sum(dt * np.einsum("ki,ij,kj->k", innov,
                                      np.linalg.inv(R), innov)))

