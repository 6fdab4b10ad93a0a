"""Continuous-time state-space models, grid discretisation and simulation.

Implements the model classes of eq. (1)/(12), the time reversal of section
2.2 producing the :class:`~repro.core.types.GridLQT` problem, Euler-Maruyama
simulation for generating synthetic data, and the (discretised)
Onsager-Machlup cost functional of eq. (2).

Grid conventions (see DESIGN.md S1 and tests/test_oracle.py):

* original time grid ``t_k = t0 + k dt`` for ``k = 0..N``; coefficient /
  measurement index ``k`` covers ``[t_k, t_{k+1}]``;
* the reversed problem has ``phi_j = x(t_{N-j})``; reversed interval ``j``
  maps to original interval ``k = N-1-j`` and its Euler step evaluates the
  drift at the reversed-left point ``phi_j = x_{k+1}`` (backward-Euler in
  original time);
* continuous-time measurement noise with spectral density R discretises to
  ``y_k ~ N(h(x), R/dt)`` so that ``dt * y_k^T R^{-1} y_k`` is the correct
  quadrature of the Onsager-Machlup measurement integral.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from . import linalg
from .linalg import mm, mT, mv, quad
from .types import GridLQT


Array = jnp.ndarray

# Information-form prior override (S0, v0): the initial boundary enters the
# reversed LQT as terminal information S_T = S0, v_T = v0 (= P0^{-1},
# P0^{-1} m0 for a covariance-form prior).  Fixed-lag streaming hands the
# forward-filter information at a window's left edge through this -- see
# docs/STREAMING.md.
Prior = Tuple[Array, Array]


@dataclasses.dataclass(frozen=True)
class LinearSDE:
    """Linear-affine model (eq. 12), possibly time-varying via callables.

    ``F, c, H, r, Q, R`` may each be a constant array or a callable of t.
    ``Q = L W L^T`` must be invertible (paper assumption, section 2.1).
    """

    F: Array | Callable[[Array], Array]
    c: Array | Callable[[Array], Array]
    H: Array | Callable[[Array], Array]
    r: Array | Callable[[Array], Array]
    Q: Array | Callable[[Array], Array]
    R: Array | Callable[[Array], Array]
    m0: Array
    P0: Array

    @property
    def nx(self) -> int:
        return self.m0.shape[-1]

    @property
    def ny(self) -> Optional[int]:
        """Measurement dimension, or ``None`` when ``R`` is time-varying
        (a callable) and the dimension is not statically known."""
        return None if callable(self.R) else jnp.asarray(self.R).shape[-1]

    def _eval(self, item, ts):
        if callable(item):
            return jax.vmap(item)(ts)
        arr = jnp.asarray(item)
        return jnp.broadcast_to(arr, ts.shape + arr.shape)

    def grids(self, ts: Array):
        """Evaluate all coefficients on the left points of the N intervals."""
        tl = ts[:-1]
        return (
            self._eval(self.F, tl),
            self._eval(self.c, tl),
            self._eval(self.H, tl),
            self._eval(self.r, tl),
            self._eval(self.Q, tl),
            self._eval(self.R, tl),
        )


@dataclasses.dataclass(frozen=True)
class NonlinearSDE:
    """Nonlinear model (eq. 1): drift f(x, t), observation h(x, t)."""

    f: Callable[[Array, Array], Array]
    h: Callable[[Array, Array], Array]
    Q: Array | Callable[[Array], Array]
    R: Array | Callable[[Array], Array]
    m0: Array
    P0: Array

    @property
    def nx(self) -> int:
        return self.m0.shape[-1]

    @property
    def ny(self) -> Optional[int]:
        """Measurement dimension, or ``None`` when ``R`` is time-varying
        (a callable) and the dimension is not statically known."""
        return None if callable(self.R) else jnp.asarray(self.R).shape[-1]

    def _eval(self, item, ts):
        if callable(item):
            return jax.vmap(item)(ts)
        arr = jnp.asarray(item)
        return jnp.broadcast_to(arr, ts.shape + arr.shape)

    def linearise(self, xbar: Array, ts: Array):
        """First-order Taylor expansion about a nominal trajectory.

        Returns grid arrays (F, c, H, r) with ``f(x,t) ~= F x + c`` and
        ``h(x,t) ~= H x + r`` at each interval left point (section 4.4).
        Delegates to :mod:`repro.linearize.taylor`, which holds the same
        jacfwd-vmap computation this method used to inline.
        """
        from repro.linearize.taylor import taylor_linearize_grid

        tl = ts[:-1]
        xb = xbar[:-1]
        F, c = taylor_linearize_grid(self.f, xb, tl)
        H, r = taylor_linearize_grid(self.h, xb, tl)
        return F, c, H, r

    def divergence_gradient(self, xbar: Array, ts: Array) -> Array:
        """grad_x (div f)(xbar, t): the linearised Onsager-Machlup
        divergence correction (optional, DESIGN.md S1)."""
        tl = ts[:-1]
        xb = xbar[:-1]

        def div_f(x, t):
            return jnp.trace(jax.jacfwd(self.f, argnums=0)(x, t))

        return jax.vmap(jax.grad(div_f, argnums=0))(xb, tl)


def time_grid(t0: float, tf: float, num_steps: int, dtype=jnp.float64) -> Array:
    return jnp.linspace(t0, tf, num_steps + 1, dtype=dtype)


def build_grid_lqt(
    F: Array, c: Array, H: Array, r: Array, Q: Array, R: Array,
    y: Array, dt: Array, m0: Array, P0: Array,
    lin: Optional[Array] = None,
    measurement_mask: Optional[Array] = None,
    prior: Optional[Prior] = None,
) -> GridLQT:
    """Time-reverse grid coefficients into the LQT problem of section 2.4.

    Reversed interval ``j`` <- original interval ``N-1-j``;
    ``F~ = -F``, ``c~ = -c`` (section 2.2 definitions).

    ``measurement_mask`` (``(N,)``, original time order, 1.0 = real) zeroes
    ``R^{-1}`` (and the optional linear cost) on masked intervals, removing
    their measurement information while keeping the dynamics prior.  A
    masked tail beyond the last real measurement contributes zero cost at
    the optimum (the extension just follows the drift), so the MAP estimate
    at real points is unchanged -- the basis of exact length-padding in
    :mod:`repro.core.batching`.

    ``prior`` ``(S0, v0)`` replaces the covariance-form ``(m0, P0)``
    boundary with information-form terminal values directly (no inversion):
    fixed-lag window solves pass the forward-filter information at the
    window's left edge here, which makes the window solve exactly the full
    MAP restricted to the window (docs/STREAMING.md).
    """
    flip = lambda a: jnp.flip(a, axis=0)
    Rinv = linalg.inv(R)
    if measurement_mask is not None:
        Rinv = Rinv * measurement_mask[:, None, None]
        if lin is not None:
            lin = lin * measurement_mask[:, None]
    if prior is not None:
        S_T, v_T = jnp.asarray(prior[0]), jnp.asarray(prior[1])
    else:
        S_T = jnp.linalg.inv(P0)
        v_T = mv(S_T, m0)
    return GridLQT(
        dt=flip(jnp.broadcast_to(dt, y.shape[:1])),
        F=-flip(F), c=-flip(c),
        H=flip(H), r=flip(r),
        Q=flip(Q), Rinv=flip(Rinv), y=flip(y),
        S_T=S_T, v_T=v_T,
        lin=None if lin is None else flip(lin),
    )


def grid_lqt_from_linear(
    model: LinearSDE, ts: Array, y: Array,
    measurement_mask: Optional[Array] = None,
    prior: Optional[Prior] = None,
) -> GridLQT:
    F, c, H, r, Q, R = model.grids(ts)
    dt = jnp.diff(ts)
    return build_grid_lqt(F, c, H, r, Q, R, y, dt, model.m0, model.P0,
                          measurement_mask=measurement_mask, prior=prior)


def grid_lqt_from_nonlinear(
    model: NonlinearSDE, ts: Array, y: Array, xbar: Array,
    divergence_correction: bool = False,
    measurement_mask: Optional[Array] = None,
    prior: Optional[Prior] = None,
    linearization=None,
) -> GridLQT:
    """Linearise the nonlinear model about ``xbar`` and time-reverse into
    the grid LQT problem.

    ``linearization`` selects the strategy (``None``/"taylor" = the
    Jacobian path, unchanged from before the subsystem existed).  SLR
    strategies return a residual covariance per grid point, folded into
    the noise as ``Q + Omega_f`` / ``R + Omega_h`` -- the
    posterior-linearisation construction; their spread covariance is the
    model's ``P0`` (scaled by the strategy's ``spread``), a fixed proxy
    until posterior covariances are plumbed through.
    """
    from repro.linearize import get_linearization

    lin_strategy = get_linearization(linearization)
    tl = ts[:-1]
    Q = model._eval(model.Q, tl)
    R = model._eval(model.R, tl)
    if not lin_strategy.has_residual:
        F, c, H, r = model.linearise(xbar, ts)
    else:
        xb = xbar[:-1]
        covs = jnp.broadcast_to(model.P0, xb.shape[:1] + model.P0.shape)
        F, c, Of = lin_strategy.linearize_grid(model.f, xb, tl, covs)
        H, r, Oh = lin_strategy.linearize_grid(model.h, xb, tl, covs)
        Q = Q + Of
        R = R + Oh
    dt = jnp.diff(ts)
    lin = None
    if divergence_correction:
        # Onsager-Machlup adds +1/2 int div f dt; linearised about xbar the
        # phi-dependent part is  1/2 g(xbar)^T phi with g = grad div f.
        lin = 0.5 * model.divergence_gradient(xbar, ts)
    return build_grid_lqt(F, c, H, r, Q, R, y, dt, model.m0, model.P0,
                          lin=lin, measurement_mask=measurement_mask,
                          prior=prior)


# ---------------------------------------------------------------------------
# Simulation + cost functional
# ---------------------------------------------------------------------------


def _psd_sqrt(Q):
    """Matrix square root of a (possibly singular) PSD matrix via eigh --
    Q = L W L^T is singular for most physical models (paper section 2.1
    allows this; only simulation needs a noise square root)."""
    w, V = jnp.linalg.eigh(Q)
    return mm(V * jnp.sqrt(jnp.clip(w, 0.0))[..., None, :], mT(V))


def simulate_linear(model: LinearSDE, ts: Array, key: jax.Array):
    """Euler-Maruyama simulation of (12) + discretised measurements."""
    F, c, H, r, Q, R = model.grids(ts)
    dt = jnp.diff(ts)
    kx, ky, k0 = jax.random.split(key, 3)
    x0 = model.m0 + mv(jnp.linalg.cholesky(model.P0), jax.random.normal(
        k0, model.m0.shape, dtype=model.m0.dtype))

    def step(x, inp):
        Fk, ck, Qk, dtk, eps = inp
        xn = x + dtk * (mv(Fk, x) + ck) + jnp.sqrt(dtk) * (
            mv(_psd_sqrt(Qk), eps))
        return xn, xn

    eps = jax.random.normal(kx, (dt.shape[0],) + model.m0.shape,
                            dtype=model.m0.dtype)
    _, xs = jax.lax.scan(step, x0, (F, c, Q, dt, eps))
    xs = jnp.concatenate([x0[None], xs], axis=0)

    ny = H.shape[-2]
    noise = jax.random.normal(ky, (dt.shape[0], ny), dtype=model.m0.dtype)
    Rch = jnp.linalg.cholesky(R)
    # measurement for interval k uses the reversed-left point x_{k+1}
    # (backward-Euler convention, see module docstring)
    y = (mv(H, xs[1:]) + r + mv(Rch, noise) / jnp.sqrt(dt)[:, None])
    return xs, y


def simulate_nonlinear(model: NonlinearSDE, ts: Array, key: jax.Array):
    dt = jnp.diff(ts)
    tl = ts[:-1]
    Q = model._eval(model.Q, tl)
    R = model._eval(model.R, tl)
    kx, ky, k0 = jax.random.split(key, 3)
    x0 = model.m0 + mv(jnp.linalg.cholesky(model.P0), jax.random.normal(
        k0, model.m0.shape, dtype=model.m0.dtype))

    def step(x, inp):
        t, Qk, dtk, eps = inp
        xn = x + dtk * model.f(x, t) + jnp.sqrt(dtk) * (
            mv(_psd_sqrt(Qk), eps))
        return xn, xn

    eps = jax.random.normal(kx, (dt.shape[0],) + model.m0.shape,
                            dtype=model.m0.dtype)
    _, xs = jax.lax.scan(step, x0, (tl, Q, dt, eps))
    xs = jnp.concatenate([x0[None], xs], axis=0)

    hx = jax.vmap(model.h)(xs[1:], tl)
    Rch = jnp.linalg.cholesky(R)
    noise = jax.random.normal(ky, hx.shape, dtype=model.m0.dtype)
    y = hx + mv(Rch, noise) / jnp.sqrt(dt)[:, None]
    return xs, y


def _prior_cost(model, x0: Array, prior: Optional[Prior]) -> Array:
    """Initial-boundary cost 1/2 (x0 - m)^T P^{-1} (x0 - m), from the
    model's covariance-form prior or an information-form override."""
    if prior is not None:
        S0, v0 = prior
        d0 = x0 - jnp.linalg.solve(S0, v0)
        return 0.5 * quad(d0, S0)
    d0 = x0 - model.m0
    return 0.5 * jnp.sum(d0 * jnp.linalg.solve(model.P0, d0))


def om_cost_linear(model: LinearSDE, ts: Array, y: Array, x: Array,
                   measurement_mask: Optional[Array] = None,
                   prior: Optional[Prior] = None) -> Array:
    """Discretised Onsager-Machlup / minimum-energy cost of a trajectory.

    Uses the backward-Euler quadrature matching the reversed-time solvers
    (drift and measurement evaluated at ``x_{k+1}``); the divergence term is
    constant for linear models and omitted (it cannot change the argmin).
    ``measurement_mask`` (``(N,)`` of 0/1) zeroes the measurement term on
    masked intervals, matching the solvers' missing-data semantics.
    ``prior`` ``(S0, v0)`` replaces the initial-boundary term with the
    information-form prior (fixed-lag window solves).
    """
    F, c, H, r, Q, R = model.grids(ts)
    dt = jnp.diff(ts)
    cost = _prior_cost(model, x[0], prior)
    xr = x[1:]
    resid = (x[1:] - x[:-1]) / dt[:, None] - (mv(F, xr) + c)
    cost = cost + 0.5 * jnp.sum(dt * quad(resid, linalg.inv(Q)))
    innov = y - (mv(H, xr) + r)
    meas = quad(innov, linalg.inv(R))
    if measurement_mask is not None:
        meas = meas * measurement_mask
    cost = cost + 0.5 * jnp.sum(dt * meas)
    return cost


def om_cost_nonlinear(
    model: NonlinearSDE, ts: Array, y: Array, x: Array,
    divergence_correction: bool = False,
    measurement_mask: Optional[Array] = None,
    prior: Optional[Prior] = None,
) -> Array:
    dt = jnp.diff(ts)
    tl = ts[:-1]
    Q = model._eval(model.Q, tl)
    R = model._eval(model.R, tl)
    cost = _prior_cost(model, x[0], prior)
    xr = x[1:]
    fx = jax.vmap(model.f)(xr, tl)
    resid = (x[1:] - x[:-1]) / dt[:, None] - fx
    cost = cost + 0.5 * jnp.sum(dt * quad(resid, linalg.inv(Q)))
    innov = y - jax.vmap(model.h)(xr, tl)
    meas = quad(innov, linalg.inv(R))
    if measurement_mask is not None:
        meas = meas * measurement_mask
    cost = cost + 0.5 * jnp.sum(dt * meas)
    if divergence_correction:
        def div_f(xk, t):
            return jnp.trace(jax.jacfwd(model.f, argnums=0)(xk, t))
        cost = cost + 0.5 * jnp.sum(dt * jax.vmap(div_f)(xr, tl))
    return cost


def om_cost_grid(grid: GridLQT, x: Array,
                 Qpinv: Optional[Array] = None) -> Array:
    """Onsager-Machlup cost of trajectory ``x`` under a built grid problem.

    ``x`` is in ORIGINAL time order (``(N+1, nx)``); the quadrature is the
    reversed-time backward-Euler one the solvers minimise, so this is the
    objective value of a :class:`~repro.core.types.MAPSolution`.  Any
    measurement mask is already folded into ``grid.Rinv`` (masked
    intervals cost nothing).  ``Q`` may be singular (``Q = L W L^T``):
    the dynamics term uses the pseudo-inverse, i.e. the minimum-energy
    cost over noise directions the model actually drives -- identical to
    ``inv(Q)`` whenever ``Q`` is invertible.  ``Qpinv`` passes that
    pseudo-inverse in, shared ``(nx, nx)`` or per reversed interval; by
    default it is computed per interval, an SVD per grid point that the TPU
    compiler handles poorly at long horizons.
    """
    phi = jnp.flip(x, axis=0)                     # phi_j = x_{N-j}
    dt = grid.dt
    resid = (phi[1:] - phi[:-1]) / dt[:, None] - (
        mv(grid.F, phi[:-1]) + grid.c)
    if Qpinv is None:
        Qpinv = jnp.linalg.pinv(grid.Q)
    cost = 0.5 * jnp.sum(dt * quad(resid, Qpinv))
    innov = grid.y - (mv(grid.H, phi[:-1]) + grid.r)
    cost = cost + 0.5 * jnp.sum(dt * quad(innov, grid.Rinv))
    if grid.lin is not None:
        cost = cost + jnp.sum(dt * jnp.sum(grid.lin * phi[:-1], axis=-1))
    # terminal (reversed) boundary = the initial prior N(m0, P0)
    m0 = jnp.linalg.solve(grid.S_T, grid.v_T)
    d0 = phi[-1] - m0
    return cost + 0.5 * quad(d0, grid.S_T)
