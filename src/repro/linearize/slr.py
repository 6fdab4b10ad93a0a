"""Statistical linear regression (SLR) through sigma points.

Instead of differentiating ``g`` at a point, SLR fits the best affine
surrogate *in expectation* under a Gaussian spread ``N(m, P)`` around the
nominal point (Yaghoobi et al., arXiv 2102.00514, section 3):

    zbar = E[g(x)]            (sigma-point quadrature)
    Pxz  = Cov[x, g(x)]
    Pzz  = Cov[g(x)]
    A    = Pxz^T P^{-1}
    b    = zbar - A m
    Omega = Pzz - A P A^T     (PSD linearisation-error covariance)

``Omega`` is folded into the process / measurement noise by the grid
builder (``Q + Omega_f``, ``R + Omega_h``), which is exactly what turns
the iterated smoother into the posterior-linearisation smoother.  For an
affine ``g`` the regression is exact: ``A`` and ``b`` are recovered to
machine precision and ``Omega == 0``, so SLR coincides with Taylor on
linear models (pinned by tests).

Everything here is jit/vmap/scan-safe: the sigma points are host-side
static constants (see :mod:`repro.linearize.sigma_points`); the per-point
regression is pure ``jnp``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

import repro.obs as obs
from .base import Linearization, register_linearization
from .sigma_points import (
    Cubature,
    GaussHermite,
    SigmaPointFamily,
    Unscented,
    unit_points,
)


def slr_linearize_point(g: Callable, m, t, cov, family: SigmaPointFamily,
                        spread: float = 1.0):
    """SLR of ``g(., t)`` about ``N(m, spread * cov)``.

    ``m`` ``(nx,)``, ``cov`` ``(nx, nx)`` (symmetric PD).  Returns
    ``(A, b, Omega)`` with ``Omega`` symmetrised PSD ``(nz, nz)``.
    """
    n = m.shape[-1]
    unit = unit_points(family, n)
    pts = jnp.asarray(unit.points, dtype=m.dtype)
    wm = jnp.asarray(unit.wm, dtype=m.dtype)
    wc = jnp.asarray(unit.wc, dtype=m.dtype)

    P = spread * cov
    L = jnp.linalg.cholesky(P)
    xs = m + pts @ L.T                     # (S, nx)
    zs = jax.vmap(lambda x: g(x, t))(xs)   # (S, nz)

    zbar = wm @ zs
    dx = xs - m
    dz = zs - zbar
    Pxz = jnp.einsum("s,si,sj->ij", wc, dx, dz)
    Pzz = jnp.einsum("s,si,sj->ij", wc, dz, dz)

    # A = Pxz^T P^{-1} via the solve against the (PD) spread covariance.
    A = jnp.linalg.solve(P, Pxz).T
    b = zbar - A @ m
    Omega = Pzz - A @ P @ A.T
    Omega = 0.5 * (Omega + Omega.T)
    return A, b, Omega


@dataclasses.dataclass(frozen=True)
class SLR(Linearization):
    """Sigma-point statistical linear regression.

    ``family`` picks the quadrature rule; ``spread`` scales the
    covariance the regression averages over (1.0 = use the supplied
    spread covariance as-is).  The grid builder supplies the model's
    ``P0`` as the spread covariance -- a PRIOR-width proxy, since
    posterior covariances are not plumbed through yet -- so the default
    shrinks it (``spread=0.01``) toward the posterior scale; as
    ``spread -> 0`` SLR converges to Taylor for smooth models.  Frozen
    and hashable, so it can sit inside ``IteratedOptions`` and key the
    executable cache.
    """

    family: SigmaPointFamily = Unscented()
    spread: float = 0.01

    has_residual = True

    def __post_init__(self) -> None:
        if not isinstance(self.family, SigmaPointFamily):
            raise TypeError(
                f"family must be a SigmaPointFamily, got "
                f"{type(self.family).__name__}")
        if not (isinstance(self.spread, (int, float)) and self.spread > 0):
            raise ValueError(f"spread must be > 0, got {self.spread!r}")

    def __call__(self, g: Callable, x, t, cov=None):
        if cov is None:
            raise ValueError(
                "SLR needs a spread covariance (cov=None is only valid for "
                "derivative-based linearisations)")
        return slr_linearize_point(g, x, t, cov, self.family, self.spread)

    def linearize_grid(self, g: Callable, xb, tl, covs=None):
        if covs is None:
            raise ValueError(
                "SLR needs per-point spread covariances on the grid")
        if obs.enabled():
            obs.inc("linearize.slr.regressions", xb.shape[0])
            obs.inc("linearize.slr.sigma_points",
                    xb.shape[0] * self.family.num_points(xb.shape[-1]))

        def one(x, t, c):
            return slr_linearize_point(g, x, t, c, self.family, self.spread)
        return jax.vmap(one)(xb, tl, covs)

    @property
    def obs_name(self) -> str:
        return self.family.name

    def num_points(self, n: int) -> int:
        return self.family.num_points(n)


def unscented(alpha: float = 1.0, beta: float = 0.0, kappa=None,
              spread: float = 0.01) -> SLR:
    """SLR through unscented-transform points (2n + 1)."""
    return SLR(Unscented(alpha, beta, kappa), spread)


def cubature(spread: float = 0.01) -> SLR:
    """SLR through spherical-radial cubature points (2n)."""
    return SLR(Cubature(), spread)


def gauss_hermite(order: int = 3, spread: float = 0.01) -> SLR:
    """SLR through tensor-product Gauss-Hermite points (order**n)."""
    return SLR(GaussHermite(order), spread)


register_linearization("unscented", unscented)
register_linearization("cubature", cubature)
register_linearization("gauss_hermite", gauss_hermite)
