"""Element construction vs direct minimisation (eq. 41/43 ground truth)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    grid_lqt_from_linear, qp_map_from_grid, simulate_linear, time_grid,
)
from repro.core.elements import (
    discrete_block_elements, euler_block_elements, one_step_elements,
)

from helpers import random_ltv, wiener_velocity


def _dense_conditional_min(grid, j0, j1, phi, z):
    """Directly minimise the discretised reversed-time cost over the
    interior states of substeps [j0, j1) with endpoints pinned."""
    nx = grid.nx
    n_int = j1 - j0 - 1
    idx = lambda k: slice(k * nx, (k + 1) * nx)

    def cost(inner):
        states = [phi] + [inner[idx(k)] for k in range(n_int)] + [z]
        c = 0.0
        for k in range(j0, j1):
            s0 = states[k - j0]
            s1 = states[k - j0 + 1]
            dt = grid.dt[k]
            u = (s1 - s0) / dt - (grid.F[k] @ s0 + grid.c[k])
            c = c + 0.5 * dt * u @ jnp.linalg.solve(grid.Q[k], u)
            innov = grid.y[k] - (grid.H[k] @ s0 + grid.r[k])
            c = c + 0.5 * dt * innov @ grid.Rinv[k] @ innov
        return c

    if n_int == 0:
        return cost(jnp.zeros((0,)))
    x0 = jnp.zeros((n_int * nx,))
    # quadratic -> one Newton step from zero is exact
    g = jax.grad(cost)(x0)
    Hm = jax.hessian(cost)(x0)
    xstar = -jnp.linalg.solve(Hm, g)
    return cost(xstar)


def _elem_value(e, phi, z):
    d = z - e.A @ phi - e.b
    return (0.5 * phi @ e.J @ phi - phi @ e.eta
            + 0.5 * d @ jnp.linalg.solve(e.C, d))


def test_discrete_block_element_is_exact_conditional_value():
    """block element == min over interior states of the discretised cost
    (up to the measurement-constant), for several (phi, z) pairs.

    NOTE the solvers' one-step element uses the reversed-left drift point
    (u = (z-phi)/dt - F phi - c with coefficients at the step), which the
    dense cost above replicates exactly.
    """
    model = random_ltv(jax.random.PRNGKey(0))
    T, n = 4, 5
    ts = time_grid(0.0, 1.0, T * n)
    _, y = simulate_linear(model, ts, jax.random.PRNGKey(1))
    grid = grid_lqt_from_linear(model, ts, y)
    blocks, _ = discrete_block_elements(grid, n)
    e = jax.tree_util.tree_map(lambda a: a[1], blocks)   # block 1

    rng = np.random.default_rng(2)
    vals_direct, vals_elem = [], []
    for _ in range(4):
        phi = jnp.asarray(rng.standard_normal(grid.nx))
        z = jnp.asarray(rng.standard_normal(grid.nx))
        vals_direct.append(float(_dense_conditional_min(grid, n, 2 * n,
                                                        phi, z)))
        vals_elem.append(float(_elem_value(e, phi, z)))
    # equal up to a single additive constant
    d = np.asarray(vals_direct) - np.asarray(vals_elem)
    np.testing.assert_allclose(d, d[0] * np.ones_like(d),
                               rtol=1e-6, atol=1e-6)


def test_euler_block_elements_converge_to_discrete():
    model = wiener_velocity()
    errs = []
    for T in (128, 256, 512):
        n = 10
        ts = time_grid(0.0, 5.0, T * n)
        _, y = simulate_linear(model, ts, jax.random.PRNGKey(0))
        grid = grid_lqt_from_linear(model, ts, y)
        eu = euler_block_elements(grid, n)
        di, _ = discrete_block_elements(grid, n)
        err = max(float(jnp.abs(a - b).max()) for a, b in zip(eu, di))
        errs.append(err)
    assert errs[2] < errs[1] < errs[0]


def test_one_step_element_matches_one_euler_step():
    """for n=1 the euler-ODE element IS the closed-form element."""
    model = random_ltv(jax.random.PRNGKey(5))
    ts = time_grid(0.0, 1.0, 16)
    _, y = simulate_linear(model, ts, jax.random.PRNGKey(6))
    grid = grid_lqt_from_linear(model, ts, y)
    eu = euler_block_elements(grid, 1)
    ones = one_step_elements(grid)
    for a, b in zip(eu, ones):
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-12)


def test_qp_oracle_self_consistency():
    """QP oracle from the model == QP oracle from the reversed grid."""
    from repro.core import qp_map_estimate
    model = random_ltv(jax.random.PRNGKey(8))
    ts = time_grid(0.0, 2.0, 40)
    _, y = simulate_linear(model, ts, jax.random.PRNGKey(9))
    grid = grid_lqt_from_linear(model, ts, y)
    a = qp_map_from_grid(grid)
    b = qp_map_estimate(model, ts, y)
    np.testing.assert_allclose(a, b, rtol=1e-8, atol=1e-8)


@pytest.mark.parametrize("masked", [False, True])
def test_rts_host_oracle_matches_qp(masked):
    """The O(N) float64 Kalman/RTS oracle reaches the dense QP's
    minimiser, on a time-varying model, with and without missing data."""
    from repro.core import qp_map_estimate
    from repro.core.oracle import rts_map_host
    model = random_ltv(jax.random.PRNGKey(10))
    ts = time_grid(0.0, 2.0, 40)
    _, y = simulate_linear(model, ts, jax.random.PRNGKey(11))
    mask = np.ones(40)
    if masked:
        mask[10:25] = 0.0
    F, c, H, r, Q, R = model.grids(ts)
    got = rts_map_host(F, c, H, r, Q, R, y, np.diff(ts), model.m0,
                       model.P0, mask=mask)
    if masked:
        # the dense QP takes missing data as a huge measurement variance
        from repro.core.oracle import _qp_solve
        Rbig = np.array(R) * np.where(mask > 0, 1.0, 1e30)[:, None, None]
        want = _qp_solve(F, c, H, r, Q, Rbig, y, np.diff(ts), model.m0,
                         model.P0)
    else:
        want = qp_map_estimate(model, ts, y)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-8, atol=1e-8)
