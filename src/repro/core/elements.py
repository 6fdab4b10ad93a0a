"""Per-block scan-element construction and within-block fills.

Two element modes are provided (DESIGN.md S1):

* ``euler`` (paper-faithful): integrate the backward conditional HJB ODEs of
  eq. (43) with explicit Euler over the n substeps of each block (blocks are
  independent -> vmap).  Matches the paper's experimental setup exactly.
* ``discrete`` (beyond-paper numerical upgrade): each Euler substep of the
  control problem admits a CLOSED-FORM conditional value function

      A = I + dt F~,  b = dt c~,  C = dt Q~,
      J = dt H~^T R~^{-1} H~,     eta = dt (H~^T R~^{-1} (y~ - r~) - lin)

  (one Euler step of (43) from the identity boundary, exactly); composing
  these with the exact combine (42) solves the Euler-discretised problem
  EXACTLY, so parallel == sequential to float round-off instead of O(dt).

Also provides the within-block interior fills: backward value fill
(eq. 15 / information-form steps) and forward-value fill (eq. 51).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .combine import apply_element_to_value, lqt_combine
from .linalg import mT, mm, mv
from .types import GridLQT, LQTElement, ValueFn


def _block_view(grid: GridLQT, nsub: int) -> GridLQT:
    """Reshape the substep axis N -> (T, n).  N must be divisible by n."""
    N = grid.N
    assert N % nsub == 0, f"N={N} not divisible by nsub={nsub}"
    T = N // nsub

    def rs(a):
        return None if a is None else a.reshape((T, nsub) + a.shape[1:])

    return GridLQT(
        dt=rs(grid.dt), F=rs(grid.F), c=rs(grid.c), H=rs(grid.H),
        r=rs(grid.r), Q=rs(grid.Q), Rinv=rs(grid.Rinv), y=rs(grid.y),
        S_T=grid.S_T, v_T=grid.v_T, lin=rs(grid.lin),
    )


def _lin_term(grid: GridLQT) -> jnp.ndarray:
    if grid.lin is None:
        return jnp.zeros(grid.c.shape, dtype=grid.c.dtype)
    return grid.lin


def one_step_elements(grid: GridLQT) -> LQTElement:
    """Closed-form single-substep elements (N, ...) -- ``discrete`` mode."""
    dt = grid.dt[:, None, None]
    I = jnp.eye(grid.nx, dtype=grid.F.dtype)
    HtRi = mm(mT(grid.H), grid.Rinv)
    A = I + dt * grid.F
    b = grid.dt[:, None] * grid.c
    C = dt * grid.Q
    J = dt * mm(HtRi, grid.H)
    eta = grid.dt[:, None] * (
        mv(HtRi, grid.y - grid.r) - _lin_term(grid))
    return LQTElement(A, b, C, eta, J)


def terminal_element(grid: GridLQT) -> LQTElement:
    """The prior element ``a_T`` (section 3.4); A = 0 makes its C inert."""
    Z = jnp.zeros((grid.nx, grid.nx), dtype=grid.F.dtype)
    z = jnp.zeros((grid.nx,), dtype=grid.F.dtype)
    return LQTElement(Z, z, Z, grid.v_T, grid.S_T)


def _hjb_derivs(e: LQTElement, F, c, H, r, Q, Rinv, y, lin):
    """Right-hand sides of eq. (43) (with the optional linear-cost term)."""
    A, b, C, eta, J = e
    HtRi = mm(mT(H), Rinv)
    innov = mv(HtRi, y - r)
    dA = -mm(A, mm(Q, J) + F)
    db = -mv(A, mv(Q, eta) + c)
    dC = -mm(mm(A, Q), mT(A))
    deta = mv(J, mv(Q, eta) + c) - mv(mT(F), eta) - innov + lin
    dJ = mm(mm(J, Q), J) - mm(J, F) - mm(mT(F), J) - mm(HtRi, H)
    return LQTElement(dA, db, dC, deta, dJ)


def _ode_step_backward(deriv_fn, y, dtk, integrator: str):
    """One backward step y(s - dt) of an autonomous-per-interval ODE.

    ``rk4`` treats the interval's coefficients as frozen (they are grid
    samples) but integrates the state nonlinearity (the Riccati quadratic
    terms) to 4th order -- a beyond-paper accuracy/stiffness upgrade over
    the paper's explicit Euler; exact coefficient handling for LTI models.
    """
    tm = jax.tree_util.tree_map
    if integrator == "euler":
        k1 = deriv_fn(y)
        return tm(lambda a, d: a - dtk * d, y, k1)
    if integrator == "rk4":
        h = -dtk
        k1 = deriv_fn(y)
        k2 = deriv_fn(tm(lambda a, d: a + 0.5 * h * d, y, k1))
        k3 = deriv_fn(tm(lambda a, d: a + 0.5 * h * d, y, k2))
        k4 = deriv_fn(tm(lambda a, d: a + h * d, y, k3))
        return tm(
            lambda a, d1, d2, d3, d4: a + (h / 6.0) * (
                d1 + 2 * d2 + 2 * d3 + d4),
            y, k1, k2, k3, k4)
    raise ValueError(f"unknown integrator: {integrator}")


def euler_block_elements(grid: GridLQT, nsub: int,
                         integrator: str = "euler") -> LQTElement:
    """Paper mode: per-block ODE integration of (43), vmapped over blocks.

    Within block ``i`` the integration runs BACKWARD from the identity
    boundary at the block end (eq. 34 boundary conditions A=I, b=0, C=0,
    eta=0, J=0), using the substep-j coefficients for step [tau_j, tau_j+1].
    ``integrator``: "euler" (paper) or "rk4" (beyond-paper, see
    ``_ode_step_backward``).
    """
    g = _block_view(grid, nsub)
    lin = _lin_term(grid).reshape(g.c.shape)

    def block(dt, F, c, H, r, Q, Rinv, y, linb):
        nx = F.shape[-1]
        e0 = LQTElement(
            jnp.eye(nx, dtype=F.dtype), jnp.zeros((nx,), F.dtype),
            jnp.zeros((nx, nx), F.dtype), jnp.zeros((nx,), F.dtype),
            jnp.zeros((nx, nx), F.dtype))

        def step(e, inp):
            dtk, Fk, ck, Hk, rk, Qk, Rik, yk, lk = inp
            nxt = _ode_step_backward(
                lambda ee: _hjb_derivs(ee, Fk, ck, Hk, rk, Qk, Rik, yk,
                                       lk),
                e, dtk, integrator)
            return nxt, None

        out, _ = jax.lax.scan(
            step, e0, (dt, F, c, H, r, Q, Rinv, y, linb), reverse=True)
        return out

    return jax.vmap(block)(g.dt, g.F, g.c, g.H, g.r, g.Q, g.Rinv, g.y, lin)


def discrete_block_elements(
    grid: GridLQT, nsub: int
) -> Tuple[LQTElement, LQTElement]:
    """Exact composition mode: block elements by in-block combine fold.

    Returns ``(block_elems (T,...), substep_elems (T, n, ...))``.

    In float32 with nx <= 8, a one-device program lowered for a TPU folds
    the blocks in the Pallas kernel
    :func:`repro.kernels.lqt_combine.ops.block_fold`, which keeps each
    block's carry on chip for all n substeps.  The choice is made from the
    lowering platform (``lax.platform_dependent``), so an ahead-of-time
    compile for a TPU takes it too; every other program folds with a
    ``lax.scan`` of :func:`lqt_combine` per block, vmapped over blocks.
    """
    if (jnp.result_type(grid.dt, grid.F) != jnp.float32 or grid.nx > 8
            or _several_devices()):
        return _scan_block_elements(grid, nsub)
    from repro.kernels.lqt_combine.ops import block_fold

    return jax.lax.platform_dependent(
        grid, tpu=lambda g: block_fold(g, nsub),
        default=lambda g: _scan_block_elements(g, nsub))


def _several_devices() -> bool:
    """Whether a mesh of several devices is active (``mesh_context``, which
    ``Estimator(mesh=...)`` and ``method="distributed"`` enter): XLA then
    partitions the program, and it cannot partition a Mosaic kernel."""
    from repro.distributed.sharding import active_mesh

    mesh = active_mesh()
    return mesh is not None and mesh.size > 1


def _scan_block_elements(grid: GridLQT, nsub: int
                         ) -> Tuple[LQTElement, LQTElement]:
    ones = one_step_elements(grid)
    T = grid.N // nsub
    sub = jax.tree_util.tree_map(
        lambda a: a.reshape((T, nsub) + a.shape[1:]), ones)

    def block(es):
        first = jax.tree_util.tree_map(lambda a: a[0], es)
        rest = jax.tree_util.tree_map(lambda a: a[1:], es)

        def step(carry, e):
            return lqt_combine(carry, e), None

        out, _ = jax.lax.scan(step, first, rest)
        return out

    return jax.vmap(block)(sub), sub


# ---------------------------------------------------------------------------
# Within-block interior fills
# ---------------------------------------------------------------------------

def backward_value_fill_euler(grid: GridLQT, nsub: int, boundary: ValueFn,
                              integrator: str = "euler") -> ValueFn:
    """ODE-integrate the Riccati eqs. (15) backwards inside each block.

    ``boundary`` holds (S, v) at the RIGHT end of each block, i.e. shapes
    (T, nx, nx) / (T, nx).  Returns per-substep values at the LEFT points of
    every substep: shapes (T, n, ...).  ``integrator``: euler (paper) / rk4.
    """
    g = _block_view(grid, nsub)
    lin = _lin_term(grid).reshape(g.c.shape)

    def block(dt, F, c, H, r, Q, Rinv, y, linb, S1, v1):
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

            Sn, vn = _ode_step_backward(derivs, carry, dtk, integrator)
            Sn = 0.5 * (Sn + mT(Sn))
            return (Sn, vn), (Sn, vn)

        _, (Ss, vs) = jax.lax.scan(
            step, (S1, v1), (dt, F, c, H, r, Q, Rinv, y, linb), reverse=True)
        return ValueFn(Ss, vs)

    return jax.vmap(block)(g.dt, g.F, g.c, g.H, g.r, g.Q, g.Rinv, g.y, lin,
                           boundary.S, boundary.v)


def backward_value_fill_discrete(sub_elems: LQTElement, boundary: ValueFn) -> ValueFn:
    """Exact information-form steps inside each block (``discrete`` mode)."""

    def block(es, S1, v1):
        def step(carry, e):
            nxt = apply_element_to_value(e, carry)
            return nxt, nxt

        _, out = jax.lax.scan(step, ValueFn(S1, v1), es, reverse=True)
        return out

    return jax.vmap(block)(sub_elems, boundary.S, boundary.v)


def forward_value_fill_euler(
    grid: GridLQT, nsub: int, left: LQTElement
) -> LQTElement:
    """Euler-integrate the forward HJB ODEs (51) inside each block.

    ``left`` holds the forward conditional value function parameters at the
    LEFT end of each block (shapes (T, ...)); returns parameters at the
    RIGHT point of every substep (shapes (T, n, ...)).  All five equations
    of (51) are propagated: for the usual A = 0 (min-initial-folded) left
    element the (eta, J) equations are identically zero, recovering the
    paper's remark that only the first three are needed; a full-rank left
    element (identity, for block-0 interiors via eq. 39) needs all five.
    """
    g = _block_view(grid, nsub)
    lin = _lin_term(grid).reshape(g.c.shape)

    def block(dt, F, c, H, r, Q, Rinv, y, linb, e0):
        def step(carry, inp):
            A, b, C, eta, J = carry
            dtk, Fk, ck, Hk, rk, Qk, Rik, yk, lk = inp
            HtRi = mm(mT(Hk), Rik)
            CHtRi = mm(C, HtRi)
            innov = mv(HtRi, yk - rk)
            dA = -mm(CHtRi, mm(Hk, A)) + mm(Fk, A)
            db = (mv(C, innov) + mv(Fk, b) + ck
                  - mv(CHtRi, mv(Hk, b)) - mv(C, lk))
            dC = -mm(CHtRi, mm(Hk, C)) + Qk + mm(Fk, C) + mm(C, mT(Fk))
            deta = mv(mT(A), innov - mv(HtRi, mv(Hk, b)) - lk)
            dJ = mm(mm(mT(A), HtRi), mm(Hk, A))
            An = A + dtk * dA
            bn = b + dtk * db
            Cn = 0.5 * ((C + dtk * dC) + mT(C + dtk * dC))
            en = eta + dtk * deta
            Jn = 0.5 * ((J + dtk * dJ) + mT(J + dtk * dJ))
            nxt = LQTElement(An, bn, Cn, en, Jn)
            return nxt, nxt

        _, out = jax.lax.scan(
            step, e0, (dt, F, c, H, r, Q, Rinv, y, linb))
        return out

    return jax.vmap(block)(g.dt, g.F, g.c, g.H, g.r, g.Q, g.Rinv, g.y, lin,
                           left)


def identity_element(nx: int, dtype) -> LQTElement:
    """V(phi, tau; z, tau): the zero-length-interval identity (eq. 34)."""
    I = jnp.eye(nx, dtype=dtype)
    Z = jnp.zeros((nx, nx), dtype=dtype)
    z = jnp.zeros((nx,), dtype=dtype)
    return LQTElement(I, z, Z, z, Z)


def forward_value_fill_discrete(
    sub_elems: LQTElement, left: LQTElement
) -> LQTElement:
    """Exact in-block forward combine (``discrete`` mode)."""

    def block(es, e0):
        def step(carry, e):
            nxt = lqt_combine(carry, e)
            return nxt, nxt

        _, out = jax.lax.scan(step, e0, es)
        return out

    return jax.vmap(block)(sub_elems, left)
