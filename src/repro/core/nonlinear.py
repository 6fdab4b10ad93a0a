"""Iterated linearisation for nonlinear models (section 4.4).

Continuous-time iterated extended Kalman smoother: linearise (1) about the
current nominal trajectory, solve the resulting linear-affine MAP problem
with the sequential or PARALLEL smoother, re-linearise, repeat.  Every
iteration is parallel-in-time when the inner method is a parallel solver,
which is exactly the paper's Fig.-2 experiment (5 iterations on the
coordinated-turn model).

The default drops the second-order Onsager-Machlup divergence correction
(as the paper's IEKS does -- for linear-affine subproblems div f~ is
constant); ``divergence_correction=True`` folds the linearised 1/2 div f
term in as an extra linear running cost (DESIGN.md S1).

:func:`iterated_solve` is the engine room used by
:class:`repro.core.Estimator`; the old :func:`iterated_map` entry point
remains as a deprecation shim around the Estimator surface.
"""
from __future__ import annotations

import warnings
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from .sde import (
    NonlinearSDE,
    Prior,
    grid_lqt_from_nonlinear,
    om_cost_nonlinear,
)
from .types import MAPSolution


def iterated_solve(
    model: NonlinearSDE,
    ts: jnp.ndarray,
    y: jnp.ndarray,
    solver: Callable,
    *,
    iterations: int = 5,
    divergence_correction: bool = False,
    x_init: jnp.ndarray | None = None,
    measurement_mask: Optional[jnp.ndarray] = None,
    prior: Optional[Prior] = None,
    track_costs: bool = True,
    linearization=None,
) -> Tuple[MAPSolution, Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """Continuous-time iterated MAP estimation (paper section 5.2).

    ``solver`` maps a linearised :class:`~repro.core.types.GridLQT` to a
    :class:`MAPSolution` (method + options already bound).  ``iterations``
    fixed Gauss-Newton style passes (paper uses 5); the initial nominal
    trajectory defaults to the constant prior mean.  ``x_init`` may be a
    full nominal trajectory ``(N+1, nx)`` or a single state ``(nx,)`` that
    is broadcast along time -- the latter is the batch-friendly form (a
    per-record warm-start point vmaps over records of any padded length).
    ``measurement_mask`` (``(N,)`` of 0/1) zeroes masked measurement
    intervals in every linearisation pass (padding / missing data).
    ``prior`` ``(S0, v0)`` replaces the model's ``(m0, P0)`` initial
    boundary with an information-form prior in every linearised subproblem
    AND in the cost trace -- fixed-lag window re-solves pass the filter
    information at the window's left edge here (docs/STREAMING.md).

    Returns the 3-tuple ``(solution, cost_trace, step_norms)``:
    ``cost_trace[i]``
    is the true (nonlinear) Onsager-Machlup cost of the iterate produced
    by pass ``i+1`` -- the Gauss-Newton descent curve; ``cost_trace[-1]``
    is the cost of the returned solution.  ``step_norms[i]`` is the RMS
    update norm ``sqrt(mean((x_{i+1} - x_i)^2))`` of pass ``i+1`` -- the
    convergence indicator surfaced as ``Solution.step_norms`` (and into
    the ``repro.obs`` registry by the Estimator).  ``track_costs=False``
    skips both trace evaluations (returning ``(solution, None, None)``)
    -- one model f/h sweep plus Q/R inversions saved per iteration.

    ``linearization`` selects the per-iteration linearisation strategy
    (``None`` = Taylor, i.e. the IEKS; a registered name or
    :class:`repro.linearize.Linearization` instance -- sigma-point SLR
    turns this into the iterated posterior-linearisation smoother).  The
    cost trace is always the TRUE nonlinear Onsager-Machlup cost, so
    traces are comparable across strategies.
    """
    from repro.linearize import get_linearization

    linearization = get_linearization(linearization)
    N = y.shape[0]
    if x_init is None:
        mean = (model.m0 if prior is None
                else jnp.linalg.solve(prior[0], prior[1]))
        x_init = jnp.broadcast_to(mean, (N + 1,) + mean.shape)
    elif x_init.ndim == 1:
        x_init = jnp.broadcast_to(x_init, (N + 1,) + x_init.shape)

    def cost_of(x):
        with jax.named_scope("estimator.cost"):
            return om_cost_nonlinear(
                model, ts, y, x, divergence_correction=divergence_correction,
                measurement_mask=measurement_mask, prior=prior)

    def step_norm(x_new, x_old):
        with jax.named_scope("estimator.cost"):
            return jnp.sqrt(jnp.mean(jnp.square(x_new - x_old)))

    def linearize(xbar):
        with jax.named_scope("nonlinear.linearize"):
            return grid_lqt_from_nonlinear(
                model, ts, y, xbar,
                divergence_correction=divergence_correction,
                measurement_mask=measurement_mask, prior=prior,
                linearization=linearization)

    def body(xbar, _):
        sol = solver(linearize(xbar))
        aux = ((cost_of(sol.x), step_norm(sol.x, xbar))
               if track_costs else None)
        return sol.x, aux

    # iterations-1 passes inside lax.scan (keeps the compiled graph O(1) in
    # iteration count), plus one final pass returning the full solution --
    # ``iterations`` linearise+solve passes total, matching the paper.
    x_last, aux = jax.lax.scan(body, x_init, None, length=iterations - 1)
    sol = solver(linearize(x_last))
    if not track_costs:
        return sol, None, None
    costs, steps = aux
    trace = jnp.concatenate([costs, cost_of(sol.x)[None]], axis=0)
    step_norms = jnp.concatenate(
        [steps, step_norm(sol.x, x_last)[None]], axis=0)
    return sol, trace, step_norms


def iterated_map(
    model: NonlinearSDE,
    ts: jnp.ndarray,
    y: jnp.ndarray,
    *,
    iterations: int = 5,
    method: str = "parallel_rts",
    nsub: int = 10,
    mode: str = "euler",
    divergence_correction: bool = False,
    x_init: jnp.ndarray | None = None,
    measurement_mask: Optional[jnp.ndarray] = None,
):
    """Deprecated shim: use ``Estimator(model, method=..., options=
    IteratedOptions(...)).solve(Problem.single(...))`` instead."""
    warnings.warn(
        "iterated_map is deprecated; use repro.core.Estimator with "
        "IteratedOptions and Problem.single (see docs/MIGRATION.md)",
        DeprecationWarning, stacklevel=2)
    from .estimator import Estimator, Problem, legacy_options

    est = Estimator(model, method=method,
                    options=legacy_options(
                        model, method, nsub=nsub, mode=mode,
                        iterations=iterations,
                        divergence_correction=divergence_correction))
    return est.solve(Problem.single(model, ts, y,
                                    measurement_mask=measurement_mask,
                                    x_init=x_init))
