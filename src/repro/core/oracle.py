"""Dense quadratic-program oracle for the discretised MAP problem.

The Euler-discretised (backward-Euler in original time, matching the
reversed-time solvers -- see ``sde.py`` docstring) Onsager-Machlup /
minimum-energy functional is an unconstrained convex quadratic in the
stacked trajectory ``X = (x_0, ..., x_N)``:

    M(X) = 1/2 (x_0 - m_0)^T P_0^{-1} (x_0 - m_0)
         + sum_k dt/2 || (x_{k+1}-x_k)/dt - F_k x_{k+1} - c_k ||^2_{Q_k^{-1}}
         + sum_k dt/2 || y_k - H_k x_{k+1} - r_k ||^2_{R_k^{-1}}
         (+ sum_k dt lin_k . x_{k+1})

Building the dense Hessian and solving gives the EXACT discrete MAP
trajectory -- the ground truth the scan-based solvers are tested against
(``discrete`` mode must match to round-off; ``euler`` mode to O(dt)).
Only intended for small N (tests); cost O((N nx)^3).

:func:`rts_map_host` reaches the same minimiser in O(N) with a float64
Kalman filter and RTS smoother in numpy, for horizons the dense solve
cannot hold: the float64 reference that chip runs are checked against.
"""
from __future__ import annotations

import jax.numpy as jnp

import numpy as np

from .sde import LinearSDE
from .types import GridLQT


def qp_map_estimate(model: LinearSDE, ts: jnp.ndarray, y: jnp.ndarray,
                    lin: jnp.ndarray | None = None) -> jnp.ndarray:
    F, c, H, r, Q, R = model.grids(ts)
    dt = jnp.diff(ts)
    return _qp_solve(F, c, H, r, Q, R, y, dt, model.m0, model.P0, lin)


def qp_map_from_grid(grid: GridLQT) -> jnp.ndarray:
    """Solve the QP directly from a (reversed-time) GridLQT; returns the
    trajectory in ORIGINAL time order (N+1, nx)."""
    flip = lambda a: jnp.flip(a, axis=0)
    F = -flip(grid.F)
    c = -flip(grid.c)
    H = flip(grid.H)
    r = flip(grid.r)
    Q = flip(grid.Q)
    Rinv = flip(grid.Rinv)
    y = flip(grid.y)
    dt = flip(grid.dt)
    lin = None if grid.lin is None else flip(grid.lin)
    P0 = jnp.linalg.inv(grid.S_T)
    m0 = P0 @ grid.v_T
    return _qp_solve(F, c, H, r, Q, jnp.linalg.inv(Rinv), y, dt, m0, P0, lin)


def _qp_solve(F, c, H, r, Q, R, y, dt, m0, P0, lin=None):
    # Test oracle: plain numpy (no tracing) -- the unrolled .at[] graph a
    # jnp version produces is pathologically slow to compile for large N.
    F, c, H, r, Q, R, y, dt, m0, P0 = (
        np.asarray(a, dtype=np.float64)
        for a in (F, c, H, r, Q, R, y, dt, m0, P0))
    if lin is not None:
        lin = np.asarray(lin, dtype=np.float64)
    N, nx = F.shape[0], F.shape[-1]
    n_tot = (N + 1) * nx
    Hmat = np.zeros((n_tot, n_tot))
    g = np.zeros((n_tot,))
    I = np.eye(nx)

    P0inv = np.linalg.inv(P0)
    Hmat[:nx, :nx] += P0inv
    g[:nx] += P0inv @ m0

    Qinv = np.linalg.inv(Q)
    Rinv = np.linalg.inv(R)
    for k in range(N):
        dtk = dt[k]
        # dynamics residual  D_k x_k + E_k x_{k+1} - c_k  with
        # D_k = -I/dt, E_k = I/dt - F_k (backward-Euler), weight dt * Qinv
        D = -I / dtk
        E = I / dtk - F[k]
        W = dtk * Qinv[k]
        sl0 = slice(k * nx, (k + 1) * nx)
        sl1 = slice((k + 1) * nx, (k + 2) * nx)
        Hmat[sl0, sl0] += D.T @ W @ D
        Hmat[sl0, sl1] += D.T @ W @ E
        Hmat[sl1, sl0] += E.T @ W @ D
        Hmat[sl1, sl1] += E.T @ W @ E
        g[sl0] += D.T @ W @ c[k]
        g[sl1] += E.T @ W @ c[k]
        # measurement  y_k ~ H_k x_{k+1} + r_k, weight dt * Rinv
        Wm = dtk * Rinv[k]
        Hmat[sl1, sl1] += H[k].T @ Wm @ H[k]
        g[sl1] += H[k].T @ Wm @ (y[k] - r[k])
        if lin is not None:
            g[sl1] += -dtk * lin[k]

    X = np.linalg.solve(Hmat, g)
    return jnp.asarray(X.reshape(N + 1, nx))


def rts_map_host(F, c, H, r, Q, R, y, dt, m0, P0, mask=None) -> np.ndarray:
    """The discrete MAP trajectory of :func:`qp_map_estimate`, in float64
    numpy, by a Kalman filter and RTS smoother: O(N), any batch.

    Writing the backward-Euler dynamics residual as a transition in
    original time, ``x_{k+1} = G_k (x_k + dt_k c_k) + G_k w_k`` with
    ``G_k = (I - dt_k F_k)^{-1}`` and ``w_k ~ N(0, dt_k Q_k)``, and the
    measurement term as ``y_k ~ N(H_k x_{k+1} + r_k, R_k / dt_k)``, the
    objective is the negative log posterior of that linear-Gaussian model,
    whose smoothed mean is the minimiser.  ``Q`` may be singular (the
    filter never inverts it).

    Every argument may carry a leading batch axis: ``F``, ``H``, ``Q``,
    ``R`` broadcast to ``(B, N, ., .)``, ``c``, ``r``, ``y`` to
    ``(B, N, .)``, ``dt`` and the 0/1 ``mask`` (masked intervals carry no
    measurement) to ``(B, N)``, ``m0``/``P0`` to ``(B, nx)``/``(B, nx,
    nx)``.  Returns ``(B, N+1, nx)``, or ``(N+1, nx)`` when ``y`` has no
    batch axis.
    """
    y = np.asarray(y, np.float64)
    single = y.ndim == 2
    if single:
        y = y[None]
    B, N, ny = y.shape
    nx = np.shape(m0)[-1]
    f64 = lambda a, shape: np.broadcast_to(np.asarray(a, np.float64), shape)
    F = f64(F, (B, N, nx, nx))
    c = f64(c, (B, N, nx))
    H = f64(H, (B, N, ny, nx))
    r = f64(r, (B, N, ny))
    Q = f64(Q, (B, N, nx, nx))
    R = f64(R, (B, N, ny, ny))
    dt = f64(dt, (B, N))
    mask = f64(1.0 if mask is None else mask, (B, N))
    m = f64(m0, (B, nx)).copy()
    P = f64(P0, (B, nx, nx)).copy()

    G = np.linalg.inv(np.eye(nx) - dt[..., None, None] * F)
    u = np.einsum("bkij,bkj->bki", G, dt[..., None] * c)
    Qd = G @ (dt[..., None, None] * Q) @ np.swapaxes(G, -1, -2)
    Rd = R / dt[..., None, None]
    ms = np.empty((B, N + 1, nx))
    Ps = np.empty((B, N + 1, nx, nx))
    mp = np.empty((B, N, nx))
    Pp = np.empty((B, N, nx, nx))
    ms[:, 0], Ps[:, 0] = m, P
    for k in range(N):
        Gk = G[:, k]
        m = np.einsum("bij,bj->bi", Gk, m) + u[:, k]
        P = Gk @ P @ np.swapaxes(Gk, -1, -2) + Qd[:, k]
        mp[:, k], Pp[:, k] = m, P
        Hk = H[:, k]
        PHt = P @ np.swapaxes(Hk, -1, -2)
        S = Hk @ PHt + Rd[:, k]
        K = np.swapaxes(np.linalg.solve(S, np.swapaxes(PHt, -1, -2)),
                        -1, -2) * mask[:, k, None, None]
        innov = y[:, k] - np.einsum("bij,bj->bi", Hk, m) - r[:, k]
        m = m + np.einsum("bij,bj->bi", K, innov)
        IKH = np.eye(nx) - K @ Hk
        # Joseph form: stays symmetric positive definite in long runs.
        P = (IKH @ P @ np.swapaxes(IKH, -1, -2)
             + K @ Rd[:, k] @ np.swapaxes(K, -1, -2))
        ms[:, k + 1], Ps[:, k + 1] = m, P
    xs = np.empty((B, N + 1, nx))
    xs[:, N] = ms[:, N]
    for k in range(N - 1, -1, -1):
        # Smoother gain P_k G_k^T (P^-_{k+1})^{-1}, from a symmetric solve.
        Ck = np.swapaxes(np.linalg.solve(
            Pp[:, k], G[:, k] @ Ps[:, k]), -1, -2)
        xs[:, k] = ms[:, k] + np.einsum("bij,bj->bi", Ck,
                                        xs[:, k + 1] - mp[:, k])
    return xs[0] if single else xs
