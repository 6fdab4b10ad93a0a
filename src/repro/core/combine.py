"""Associative combination operators (paper eqs. 42, 45-46, and the
value-application step used for within-block interior fills).

All operators broadcast over arbitrary leading batch axes: the products and
solves of :mod:`repro.core.linalg` batch over leading dimensions, so the same
code path is used for single pairs, vmapped blocks, and the Pallas kernel oracle
(``repro.kernels.lqt_combine.ref`` re-exports :func:`lqt_combine`).

Orientation convention: ``combine(e1, e2)`` composes ``e1`` on the EARLIER
(reversed-time) interval ``[s, gamma]`` with ``e2`` on ``[gamma, t]``,
exactly eq. (42) with ``1 -> (s, gamma)`` and ``2 -> (gamma, t)``.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import linalg
from .linalg import mT, mm, mv
from .types import AffineElement, LQTElement, ValueFn


def _sym(M: jnp.ndarray) -> jnp.ndarray:
    """Numerically symmetrise a (batched) matrix."""
    return 0.5 * (M + mT(M))


def _eye_like(M: jnp.ndarray) -> jnp.ndarray:
    n = M.shape[-1]
    return jnp.broadcast_to(jnp.eye(n, dtype=M.dtype), M.shape)


def lqt_combine(e1: LQTElement, e2: LQTElement) -> LQTElement:
    """Eq. (42): min-plus composition of two conditional value functions.

    Uses two batched linear solves with ``M = I + C1 J2`` (and its transpose
    ``I + J2 C1 = M^T`` since C1, J2 are symmetric) instead of explicit
    inverses.  Outputs C and J are re-symmetrised to stop round-off drift.
    """
    A1, b1, C1, eta1, J1 = e1
    A2, b2, C2, eta2, J2 = e2

    I = _eye_like(C1)
    M = I + mm(C1, J2)                   # (..., nx, nx)
    Mt = mT(M)                           # = I + J2 C1

    # Right-hand sides solved against M:   M^{-1} [A1 | b1 + C1 eta2 | C1]
    rhs1 = jnp.concatenate(
        [A1, (b1 + mv(C1, eta2))[..., None], C1], axis=-1
    )
    sol1 = linalg.solve(M, rhs1)
    nx = A1.shape[-1]
    MiA1 = sol1[..., :nx]
    Mib = sol1[..., nx]
    MiC1 = sol1[..., nx + 1:]

    # Solved against M^T:   (I + J2 C1)^{-1} [eta2 - J2 b1 | J2 A1]
    rhs2 = jnp.concatenate(
        [(eta2 - mv(J2, b1))[..., None], mm(J2, A1)], axis=-1
    )
    sol2 = linalg.solve(Mt, rhs2)
    Mte = sol2[..., 0]
    MtJA = sol2[..., 1:]

    A1T = mT(A1)
    A = mm(A2, MiA1)
    b = mv(A2, Mib) + b2
    C = _sym(mm(mm(A2, MiC1), mT(A2)) + C2)
    eta = mv(A1T, Mte) + eta1
    J = _sym(mm(A1T, MtJA) + J1)
    return LQTElement(A, b, C, eta, J)


def affine_combine(e1: AffineElement, e2: AffineElement) -> AffineElement:
    """Eqs. (45)-(46): compose phi -> Phi2 (Phi1 phi + beta1) + beta2.

    ``e1`` maps over the earlier interval, ``e2`` over the later one.
    """
    Phi = mm(e2.Phi, e1.Phi)
    beta = mv(e2.Phi, e1.beta) + e2.beta
    return AffineElement(Phi, beta)


def apply_element_to_value(e: LQTElement, vf: ValueFn) -> ValueFn:
    """Fold a one-interval element into a terminal value function.

    Computes the (J, eta) block of ``lqt_combine(e, value_as_element)``:

        S' = A^T (I + S C)^{-1} S A + J
        v' = A^T (I + S C)^{-1} (v - S b) + eta

    i.e. one information-form Kalman-Bucy step backwards in reversed time
    (equivalently one filter step forwards in original time).  Cheaper than
    the full 5-tuple combine; used for within-block interior value fills.
    """
    A, b, C, eta, J = e
    S2, v2 = vf
    I = _eye_like(C)
    Mt = I + mm(S2, C)  # (I + J2 C1) with J2 = S2, C1 = C
    rhs = jnp.concatenate(
        [(v2 - mv(S2, b))[..., None], mm(S2, A)], axis=-1
    )
    sol = linalg.solve(Mt, rhs)
    At = mT(A)
    v = mv(At, sol[..., 0]) + eta
    S = _sym(mm(At, sol[..., 1:]) + J)
    return ValueFn(S, v)


def value_as_element(vf: ValueFn) -> LQTElement:
    """Embed a terminal value function as a scan element (section 3.4).

    The terminal element ``a_T`` has A = 0, b = 0 and carries the prior in
    (J, eta).  With A = 0 the C entry of any combined range containing a_T
    never feeds a subsequent combine (a_T is always rightmost), so the
    kappa -> infinity boundary of eq. (34) can be represented with C = 0;
    see DESIGN.md S1 and the associativity tests.
    """
    S, v = vf
    Z = jnp.zeros_like(S)
    z = jnp.zeros_like(v)
    return LQTElement(Z, z, Z, v, S)


def elem_min_initial(e0: LQTElement, jitter: float = 0.0) -> LQTElement:
    """Eq. (50): fold the free-initial-condition element ``e`` (eq. 49,
    kappa -> infinity) into the first element: ``a0_bar = e (x) a0``.

    Requires J0 invertible; an optional diagonal ``jitter`` (scaled by the
    mean diagonal of J0) regularises near-singular first blocks.
    """
    A0, b0, C0, eta0, J0 = e0
    nx = A0.shape[-1]
    I = jnp.eye(nx, dtype=A0.dtype)
    if jitter:
        scale = jnp.trace(J0) / nx
        J0 = J0 + (jitter * scale) * I
    sol = linalg.solve(J0, jnp.concatenate([eta0[..., None], mT(A0)], axis=-1))
    J0ie = sol[..., 0]
    J0iA0T = sol[..., 1:]
    Abar = jnp.zeros_like(A0)
    bbar = b0 + mv(A0, J0ie)
    Cbar = _sym(mm(A0, J0iA0T) + C0)
    return LQTElement(Abar, bbar, Cbar, eta0, J0)
