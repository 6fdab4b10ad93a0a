"""Parallel-in-time MAP estimation (the paper's contribution, sections 3-4).

Pipeline (all reversed-time; results are flipped back to original time):

1. **Element init** (parallel over blocks): eq. (43) Euler integration
   (``euler`` mode) or exact substep-element composition (``discrete``).
2. **Backward pass**: suffix associative scan with the combine (42) over
   ``[a_0 .. a_{T-1}, a_T]`` -> value functions S(tau_i), v(tau_i) at all
   block boundaries = parallel Kalman-Bucy filter, section 4 (log-span).
3. **Interior fill** (parallel over blocks): backward HJB/(15) within each
   block from its right-boundary value.
4. **Recovery**:
   * method 1 (parallel RTS smoother, section 4.3): per-substep affine maps
     -> within-block compose -> prefix scan with (45)-(46) -> eq. (47);
   * method 2 (parallel two-filter smoother): prefix scan of
     ``[e (x) a_0, a_1, ...]`` (eqs. 49-50) -> eq. (48), forward HJB (51)
     interior fill, plus smoothing covariances (beyond-paper extra).

Every stage is either an associative scan or an embarrassingly parallel
vmap over blocks; ``scan_fn`` lets callers swap the on-chip scan for the
distributed multi-chip scan (``core.pscan.distributed_scan``) or a kernel-
backed combine (``repro.kernels.lqt_combine``).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from . import pscan
from .combine import affine_combine, elem_min_initial, lqt_combine
from .linalg import mv
from .elements import (
    backward_value_fill_discrete,
    backward_value_fill_euler,
    discrete_block_elements,
    euler_block_elements,
    forward_value_fill_discrete,
    forward_value_fill_euler,
    identity_element,
    one_step_elements,
    terminal_element,
)
from .sequential import affine_recovery_maps, two_filter_combine
from .types import AffineElement, GridLQT, LQTElement, MAPSolution, ValueFn


def _append_elem(elems: LQTElement, last: LQTElement) -> LQTElement:
    return jax.tree_util.tree_map(
        lambda a, l: jnp.concatenate([a, l[None]], axis=0), elems, last)


def _prepend_elem(first: LQTElement, elems: LQTElement) -> LQTElement:
    return jax.tree_util.tree_map(
        lambda f, a: jnp.concatenate([f[None], a], axis=0), first, elems)


def parallel_backward(
    grid: GridLQT,
    nsub: int,
    mode: str = "euler",
    combine_fn: Callable = lqt_combine,
    suffix_scan_fn: Optional[Callable] = None,
):
    """Parallel Kalman-Bucy filter (information form).

    Returns ``(values_full, boundary, block_elems, sub_elems)`` where
    ``values_full`` holds S(tau_j), v(tau_j) for every substep j = 0..N,
    ``boundary`` the block-boundary values (T+1, ...), ``block_elems`` the
    scan elements, and ``sub_elems`` the per-substep elements (``discrete``
    mode only, else None).
    """
    if mode == "discrete":
        blocks, sub = discrete_block_elements(grid, nsub)
    elif mode in ("euler", "rk4"):
        blocks = euler_block_elements(grid, nsub, integrator=mode)
        sub = None
    else:
        raise ValueError(f"unknown element mode: {mode}")

    elems = _append_elem(blocks, terminal_element(grid))
    if suffix_scan_fn is not None:
        sbar = suffix_scan_fn(elems)
    else:
        sbar = pscan.suffix_scan(combine_fn, elems)
    boundary = ValueFn(sbar.J, sbar.eta)                      # (T+1, ...)

    right = ValueFn(boundary.S[1:], boundary.v[1:])           # (T, ...)
    if mode == "discrete":
        interior = backward_value_fill_discrete(sub, right)   # (T, n, ...)
    else:
        interior = backward_value_fill_euler(grid, nsub, right,
                                             integrator=mode)

    # Replace each block's left point with the scan-combined boundary value
    # (identical in discrete mode; the parallel-consistent choice in euler
    # mode), then flatten to the full (N+1) substep grid.
    S_blk = interior.S.at[:, 0].set(boundary.S[:-1])
    v_blk = interior.v.at[:, 0].set(boundary.v[:-1])
    N = grid.N
    values_full = ValueFn(
        jnp.concatenate(
            [S_blk.reshape((N,) + S_blk.shape[2:]), boundary.S[-1:]], axis=0),
        jnp.concatenate(
            [v_blk.reshape((N,) + v_blk.shape[2:]), boundary.v[-1:]], axis=0),
    )
    return values_full, boundary, blocks, sub


def _recover_affine(grid: GridLQT, values_full: ValueFn, nsub: int,
                    mode: str,
                    prefix_scan_fn: Optional[Callable] = None) -> jnp.ndarray:
    """Method 1 (eq. 47): parallel RTS trajectory recovery."""
    Phi, beta = affine_recovery_maps(grid, values_full, mode)
    T = grid.N // nsub
    maps = AffineElement(
        Phi.reshape((T, nsub) + Phi.shape[1:]),
        beta.reshape((T, nsub) + beta.shape[1:]))

    # Within-block cumulative compose (collecting intermediates), vmapped.
    def block(ms):
        first = jax.tree_util.tree_map(lambda a: a[0], ms)
        rest = jax.tree_util.tree_map(lambda a: a[1:], ms)

        def step(carry, e):
            nxt = affine_combine(carry, e)
            return nxt, nxt

        last, tail = jax.lax.scan(step, first, rest)
        cum = jax.tree_util.tree_map(
            lambda f, t: jnp.concatenate([f[None], t], axis=0), first, tail)
        return cum, last

    cum, totals = jax.vmap(block)(maps)           # (T, n, ...), (T, ...)

    # Global prefix scan over block totals (eqs. 45-46).
    if prefix_scan_fn is not None:
        prefix = prefix_scan_fn(totals)                       # (T, ...)
    else:
        prefix = pscan.prefix_scan(affine_combine, totals)    # (T, ...)

    phi0 = jnp.linalg.solve(values_full.S[0], values_full.v[0])
    bound = mv(prefix.Phi, phi0) + prefix.beta
    starts = jnp.concatenate([phi0[None], bound[:-1]], axis=0)  # (T, nx)

    # phi at tau_{i*n + l + 1} = cum[i, l] applied to starts[i].
    sub = mv(cum.Phi, starts[:, None, :]) + cum.beta
    phi = jnp.concatenate(
        [phi0[None], sub.reshape((grid.N,) + sub.shape[2:])], axis=0)
    return phi


def parallel_rts(
    grid: GridLQT, nsub: int, mode: str = "euler",
    combine_fn: Callable = lqt_combine,
    suffix_scan_fn: Optional[Callable] = None,
    prefix_scan_fn: Optional[Callable] = None,
) -> MAPSolution:
    """Parallel continuous-time RTS smoother (sections 4.1-4.3, method 1).

    ``suffix_scan_fn`` (elems -> inclusive suffix combine) replaces the
    default on-chip associative scan of the backward pass; the
    ``parallel_kernel`` method passes the lane-major Pallas scan
    (:func:`repro.kernels.lqt_combine.ops.kernel_suffix_scan`) here, the
    ``distributed`` method passes the time-axis-sharded scan
    (:func:`repro.core.pscan.sharded_scan`).  ``prefix_scan_fn`` does the
    same for the affine recovery scan of the forward pass (eqs. 45-46).
    """
    values_full, _, _, _ = parallel_backward(
        grid, nsub, mode, combine_fn=combine_fn,
        suffix_scan_fn=suffix_scan_fn)
    phi = _recover_affine(grid, values_full, nsub, mode,
                          prefix_scan_fn=prefix_scan_fn)
    return MAPSolution(
        x=jnp.flip(phi, axis=0),
        S=jnp.flip(values_full.S, axis=0),
        v=jnp.flip(values_full.v, axis=0))


def parallel_two_filter(
    grid: GridLQT, nsub: int, mode: str = "euler",
    combine_fn: Callable = lqt_combine,
    jitter: float = 1e-9,
    block0_fill: str = "affine",
    tf_fill: str = "combine",
) -> MAPSolution:
    """Parallel continuous-time two-filter smoother (section 4.3, method 2).

    ``block0_fill`` selects the interior recovery inside the first block,
    where the forward value function has not yet accumulated invertible
    information: ``"affine"`` (default) propagates the exact optimal
    trajectory maps from phi*(tau_0) (robust, no jitter); ``"min_initial"``
    follows eq. (39) with jitter-regularised eq. (50) pointwise (pure
    two-filter form).  Covariances inside block 0 are only available with
    ``"min_initial"`` (NaN otherwise); boundary and later-block covariances
    are always exact.

    ``tf_fill`` selects the interior fill for blocks >= 1 in ``euler``
    mode: ``"combine"`` (default) composes closed-form one-substep elements
    exactly -- unconditionally stable; ``"hjb_euler"`` is the paper-literal
    explicit Euler on the forward HJB ODEs (51), which is stiff in the
    covariance form when C H^T R^{-1} H dt approaches 1 (weakly observed
    state directions grow C without bound); see DESIGN.md S6 stability
    note.  ``discrete`` mode always uses exact combines.
    """
    values_full, boundary, blocks, sub = parallel_backward(
        grid, nsub, mode, combine_fn=combine_fn)
    T = grid.N // nsub
    nx = grid.nx

    # Forward prefix scan of [e (x) a_0, a_1, ..., a_{T-1}]  (eqs. 49-50).
    a0 = jax.tree_util.tree_map(lambda a: a[0], blocks)
    a0bar = elem_min_initial(a0, jitter=jitter)
    rest = jax.tree_util.tree_map(lambda a: a[1:], blocks)
    fwd_elems = _prepend_elem(a0bar, rest)
    fwd = pscan.prefix_scan(combine_fn, fwd_elems)            # (T, ...)

    # Block-boundary states via eq. (48).
    phi_b, cov_b = two_filter_combine(fwd, boundary.S[1:], boundary.v[1:])
    phi0 = jnp.linalg.solve(boundary.S[0], boundary.v[0])
    cov0 = jnp.linalg.inv(boundary.S[0])

    # Interior fill for blocks 1..T-1: forward HJB (51) from fwd[i-1].
    left = jax.tree_util.tree_map(lambda a: a[:-1], fwd)      # (T-1, ...)
    grid_tail = GridLQT(
        dt=grid.dt[nsub:], F=grid.F[nsub:], c=grid.c[nsub:],
        H=grid.H[nsub:], r=grid.r[nsub:], Q=grid.Q[nsub:],
        Rinv=grid.Rinv[nsub:], y=grid.y[nsub:],
        S_T=grid.S_T, v_T=grid.v_T,
        lin=None if grid.lin is None else grid.lin[nsub:])
    if mode == "discrete":
        sub_tail = jax.tree_util.tree_map(lambda a: a[1:], sub)
        fill = forward_value_fill_discrete(sub_tail, left)
    elif tf_fill == "combine":
        ones = one_step_elements(grid)
        T_blocks = grid.N // nsub
        sub_all = jax.tree_util.tree_map(
            lambda a: a.reshape((T_blocks, nsub) + a.shape[1:]), ones)
        sub_tail = jax.tree_util.tree_map(lambda a: a[1:], sub_all)
        fill = forward_value_fill_discrete(sub_tail, left)
    elif tf_fill == "hjb_euler":
        fill = forward_value_fill_euler(grid_tail, nsub, left)
    else:
        raise ValueError(f"unknown tf_fill: {tf_fill}")
    # fill: (T-1, n, ...) at right points tau_{i*n + l + 1}, blocks i>=1.
    S_right = values_full.S[nsub + 1:]
    v_right = values_full.v[nsub + 1:]
    flat_fill = jax.tree_util.tree_map(
        lambda a: a.reshape((grid.N - nsub,) + a.shape[2:]), fill)
    phi_tail, cov_tail = two_filter_combine(flat_fill, S_right, v_right)
    # parallel-consistent block boundaries: overwrite l = n-1 entries
    phi_tail = phi_tail.reshape(T - 1, nsub, nx).at[:, -1].set(phi_b[1:])
    cov_tail = cov_tail.reshape(T - 1, nsub, nx, nx).at[:, -1].set(cov_b[1:])
    phi_tail = phi_tail.reshape(grid.N - nsub, nx)
    cov_tail = cov_tail.reshape(grid.N - nsub, nx, nx)

    # Block-0 interior (tau_1 .. tau_{n-1}) + its right boundary tau_n.
    if block0_fill == "affine":
        Phi, beta = affine_recovery_maps(
            GridLQT(dt=grid.dt[:nsub], F=grid.F[:nsub], c=grid.c[:nsub],
                    H=grid.H[:nsub], r=grid.r[:nsub], Q=grid.Q[:nsub],
                    Rinv=grid.Rinv[:nsub], y=grid.y[:nsub],
                    S_T=grid.S_T, v_T=grid.v_T,
                    lin=None if grid.lin is None else grid.lin[:nsub]),
            ValueFn(values_full.S[:nsub + 1], values_full.v[:nsub + 1]),
            mode)

        def step(carry, inp):
            P, b = inp
            nxt = mv(P, carry) + b
            return nxt, nxt

        _, phi_blk0 = jax.lax.scan(step, phi0, (Phi, beta))   # (n, nx)
        cov_blk0 = jnp.full((nsub, nx, nx), jnp.nan, dtype=cov_b.dtype)
    elif block0_fill == "min_initial":
        e_id = identity_element(nx, grid.F.dtype)
        if mode == "discrete":
            sub0 = jax.tree_util.tree_map(lambda a: a[0][None], sub)
        else:
            sub0 = None
        left0 = jax.tree_util.tree_map(lambda a: a[None], e_id)
        grid_head = GridLQT(
            dt=grid.dt[:nsub], F=grid.F[:nsub], c=grid.c[:nsub],
            H=grid.H[:nsub], r=grid.r[:nsub], Q=grid.Q[:nsub],
            Rinv=grid.Rinv[:nsub], y=grid.y[:nsub],
            S_T=grid.S_T, v_T=grid.v_T,
            lin=None if grid.lin is None else grid.lin[:nsub])
        if mode == "discrete":
            f0 = forward_value_fill_discrete(sub0, left0)
        else:
            f0 = forward_value_fill_euler(grid_head, nsub, left0)
        f0 = jax.tree_util.tree_map(lambda a: a[0], f0)       # (n, ...)
        folded = jax.vmap(lambda e: elem_min_initial(e, jitter=jitter))(f0)
        phi_blk0, cov_blk0 = two_filter_combine(
            folded, values_full.S[1:nsub + 1], values_full.v[1:nsub + 1])
    else:
        raise ValueError(f"unknown block0_fill: {block0_fill}")
    phi_blk0 = phi_blk0.at[-1].set(phi_b[0])
    cov_blk0 = cov_blk0.at[-1].set(cov_b[0])

    phi = jnp.concatenate([phi0[None], phi_blk0, phi_tail], axis=0)
    cov = jnp.concatenate([cov0[None], cov_blk0, cov_tail], axis=0)
    return MAPSolution(
        x=jnp.flip(phi, axis=0),
        S=jnp.flip(values_full.S, axis=0),
        v=jnp.flip(values_full.v, axis=0),
        cov=jnp.flip(cov, axis=0))
