"""Sequential baselines (the paper's comparison algorithms, section 5).

These are the O(T)-span algorithms the parallel methods are benchmarked
against:

* :func:`sequential_backward`   -- Euler on the Riccati ODEs (15) (``euler``
  mode) or exact information-form steps (``discrete`` mode); equivalent to
  the Kalman-Bucy filter (22) in original time (section 2.5).
* :func:`sequential_rts`        -- + forward Euler of eq. (18): the
  sequential continuous-time RTS smoother.
* :func:`sequential_two_filter` -- + forward HJB (51) integration and the
  two-filter combination (48).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import linalg
from .linalg import mT, mm, mv
from .combine import apply_element_to_value, elem_min_initial, lqt_combine
from .elements import _lin_term, identity_element, one_step_elements
from .types import GridLQT, LQTElement, MAPSolution, ValueFn


def _stack_with_terminal(head, terminal):
    return jax.tree_util.tree_map(
        lambda h, t: jnp.concatenate([h, t[None]], axis=0), head, terminal)


def sequential_backward(grid: GridLQT, mode: str = "euler") -> ValueFn:
    """S(tau_j), v(tau_j) for j = 0..N (reversed time), O(N) span."""
    from .elements import _ode_step_backward

    term = ValueFn(grid.S_T, grid.v_T)
    lin = _lin_term(grid)

    if mode == "discrete":
        elems = one_step_elements(grid)

        def step(carry, e):
            nxt = apply_element_to_value(e, carry)
            return nxt, nxt

        _, head = jax.lax.scan(step, term, elems, reverse=True)
        return _stack_with_terminal(head, term)

    def step(carry, inp):
        dtk, Fk, ck, Hk, rk, Qk, Rik, yk, lk = inp
        HtRi = mm(mT(Hk), Rik)

        def derivs(sv):
            S, v = sv
            dS = (mm(mm(S, Qk), S) - mm(S, Fk) - mm(mT(Fk), S)
                  - mm(HtRi, Hk))
            dv = (mv(S, mv(Qk, v) + ck) - mv(mT(Fk), v)
                  - mv(HtRi, yk - rk) + lk)
            return (dS, dv)

        Sn, vn = _ode_step_backward(derivs, tuple(carry), dtk, mode)
        Sn = 0.5 * (Sn + mT(Sn))
        nxt = ValueFn(Sn, vn)
        return nxt, nxt

    _, head = jax.lax.scan(
        step, term,
        (grid.dt, grid.F, grid.c, grid.H, grid.r, grid.Q, grid.Rinv,
         grid.y, lin),
        reverse=True)
    return _stack_with_terminal(head, term)


def affine_recovery_maps(grid: GridLQT, values: ValueFn, mode: str = "euler"):
    """Per-substep affine maps phi(tau_{j+1}) = Phi_j phi(tau_j) + beta_j.

    ``euler`` mode: eq. (18)-(19) with left-point values,
    ``discrete`` mode: exact argmin step
    ``z* = (I + C_j S_{j+1})^{-1} (A_j phi + b_j + C_j v_{j+1})``.
    """
    if mode == "discrete":
        e = one_step_elements(grid)
        S1 = values.S[1:]
        v1 = values.v[1:]
        I = jnp.eye(grid.nx, dtype=grid.F.dtype)
        M = I + mm(e.C, S1)
        rhs = jnp.concatenate(
            [e.A, (e.b + mv(e.C, v1))[..., None]],
            axis=-1)
        sol = linalg.solve(M, rhs)
        return sol[..., :-1], sol[..., -1]

    S0 = values.S[:-1]
    v0 = values.v[:-1]
    dt = grid.dt[:, None, None]
    I = jnp.eye(grid.nx, dtype=grid.F.dtype)
    Fbar = grid.F - mm(grid.Q, S0)
    Phi = I + dt * Fbar
    beta = grid.dt[:, None] * (mv(grid.Q, v0) + grid.c)
    return Phi, beta


def sequential_rts(grid: GridLQT, mode: str = "euler") -> MAPSolution:
    """Sequential continuous-time RTS smoother (backward (15) + forward (18))."""
    values = sequential_backward(grid, mode)
    Phi, beta = affine_recovery_maps(grid, values, mode)
    phi0 = jnp.linalg.solve(values.S[0], values.v[0])

    def step(phi, inp):
        P, b = inp
        nxt = mv(P, phi) + b
        return nxt, nxt

    _, tail = jax.lax.scan(step, phi0, (Phi, beta))
    phi = jnp.concatenate([phi0[None], tail], axis=0)
    return MAPSolution(
        x=jnp.flip(phi, axis=0),
        S=jnp.flip(values.S, axis=0),
        v=jnp.flip(values.v, axis=0))


def two_filter_combine(fwd: LQTElement, S: jnp.ndarray, v: jnp.ndarray):
    """Eq. (48): phi* = (I + Cbar S)^{-1} (bbar + Cbar v) (+ covariance)."""
    I = jnp.broadcast_to(jnp.eye(S.shape[-1], dtype=S.dtype), S.shape)
    M = I + mm(fwd.C, S)
    rhs = jnp.concatenate(
        [(fwd.b + mv(fwd.C, v))[..., None], fwd.C],
        axis=-1)
    sol = linalg.solve(M, rhs)
    phi = sol[..., 0]
    cov = sol[..., 1:]
    return phi, 0.5 * (cov + mT(cov))


def sequential_two_filter(
    grid: GridLQT, mode: str = "euler", jitter: float = 1e-9,
) -> MAPSolution:
    """Sequential two-filter smoother.

    Integrates the forward HJB (51) from the identity element, then folds
    the free-initial-condition minimisation (eqs. 39/50) pointwise, after
    which (b, C) are the backward-time filter mean/covariance (section 4.3)
    and eq. (48) recovers the trajectory.  ``jitter`` regularises the
    near-singular early-time J (few measurements seen yet).
    """
    values = sequential_backward(grid, mode)
    lin = _lin_term(grid)
    e0 = identity_element(grid.nx, grid.F.dtype)

    if mode == "discrete":
        elems = one_step_elements(grid)

        def step(carry, e):
            nxt = lqt_combine(carry, e)
            return nxt, nxt

        _, fwd = jax.lax.scan(step, e0, elems)
    else:
        def step(carry, inp):
            A, b, C, eta, J = carry
            dtk, Fk, ck, Hk, rk, Qk, Rik, yk, lk = inp
            HtRi = mm(mT(Hk), Rik)
            CHtRi = mm(C, HtRi)
            innov = mv(HtRi, yk - rk)
            dA = -mm(CHtRi, mm(Hk, A)) + mm(Fk, A)
            db = (mv(C, innov) + mv(Fk, b) + ck - mv(CHtRi, mv(Hk, b))
                  - mv(C, lk))
            dC = -mm(CHtRi, mm(Hk, C)) + Qk + mm(Fk, C) + mm(C, mT(Fk))
            deta = mv(mT(A), innov - mv(HtRi, mv(Hk, b)) - lk)
            dJ = mm(mm(mT(A), HtRi), mm(Hk, A))
            Cn = C + dtk * dC
            Jn = J + dtk * dJ
            nxt = LQTElement(
                A + dtk * dA, b + dtk * db, 0.5 * (Cn + mT(Cn)),
                eta + dtk * deta, 0.5 * (Jn + mT(Jn)))
            return nxt, nxt

        _, fwd = jax.lax.scan(
            step, e0,
            (grid.dt, grid.F, grid.c, grid.H, grid.r, grid.Q, grid.Rinv,
             grid.y, lin))

    # Fold the free-initial-condition minimisation pointwise (eq. 39/50).
    folded = jax.vmap(lambda e: elem_min_initial(e, jitter=jitter))(fwd)
    phi_tail, cov_tail = two_filter_combine(
        folded, values.S[1:], values.v[1:])
    phi0 = jnp.linalg.solve(values.S[0], values.v[0])
    cov0 = jnp.linalg.inv(values.S[0])
    phi = jnp.concatenate([phi0[None], phi_tail], axis=0)
    cov = jnp.concatenate([cov0[None], cov_tail], axis=0)
    return MAPSolution(
        x=jnp.flip(phi, axis=0),
        S=jnp.flip(values.S, axis=0),
        v=jnp.flip(values.v, axis=0),
        cov=jnp.flip(cov, axis=0))
