"""The block-fold Pallas kernel (``kernels/lqt_combine``: ``block_fold``)
against the XLA fold it replaces on a TPU, in interpret mode on the CPU.

Both fold the same float32 one-step elements and differ only in rounding:
the kernel inverts ``M = I + C1 J2`` and multiplies, the XLA fold solves
against the right-hand sides, and the two sum products in other orders.
Each of the ``nsub - 1`` combines rounds a few ``nx``-term sums, so both
land within some float32 eps of the float64 fold of the same inputs.  The
kernel may carry at most 4x the XLA fold's error against that fold, plus
16 eps of the part's largest magnitude for parts the XLA fold happens to
round exactly (observed on these cases: both within 6 eps).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.elements import discrete_block_elements
from repro.core.types import GridLQT
from repro.kernels.lqt_combine.ops import block_fold

pytestmark = pytest.mark.kernel_interpret

EPS = float(jnp.finfo(jnp.float32).eps)


def _grid(rng, T, nsub, nx, *, dt=0.05, Q=None, r_diag=(0.1, 0.2),
          dtype=np.float32):
    """A float32 grid with time-varying F, c, H, r, y (as after a Taylor
    linearisation) and time-invariant Q (by default L L^T + 1e-3 I) and
    R^-1."""
    N, ny = T * nsub, len(r_diag)
    if Q is None:
        L = rng.standard_normal((nx, nx)) * 0.5
        Q = L @ L.T + 1e-3 * np.eye(nx)

    def arr(a):
        return jnp.asarray(a, dtype)

    return GridLQT(
        dt=arr(np.full(N, dt)),
        F=arr(0.3 * rng.standard_normal((N, nx, nx))),
        c=arr(rng.standard_normal((N, nx))),
        H=arr(rng.standard_normal((N, ny, nx))),
        r=arr(rng.standard_normal((N, ny))),
        Q=arr(np.broadcast_to(Q, (N, nx, nx))),
        Rinv=arr(np.broadcast_to(np.diag(1.0 / np.asarray(r_diag)),
                                 (N, ny, ny))),
        y=arr(rng.standard_normal((N, ny))),
        S_T=arr(np.eye(nx)), v_T=arr(np.zeros(nx)), lin=None)


def _f64(grid):
    return jax.tree_util.tree_map(
        lambda a: None if a is None else jnp.asarray(a, jnp.float64), grid,
        is_leaf=lambda a: a is None)


def _assert_as_accurate(got, xla, exact):
    for name, g, x, w in zip("A b C eta J".split(), got, xla, exact):
        g, x, w = (np.asarray(a, np.float64) for a in (g, x, w))
        scale = np.max(np.abs(w))
        err_kernel = np.max(np.abs(g - w)) / scale
        err_xla = np.max(np.abs(x - w)) / scale
        assert np.all(np.isfinite(g)), name
        assert err_kernel <= 4 * err_xla + 16 * EPS, (name, err_kernel,
                                                      err_xla)


def _check(grid, nsub):
    """Block elements as accurate as the XLA fold's; the substep elements
    the kernel path hands the interior fill as accurate as XLA's too."""
    got, got_sub = jax.jit(lambda g: block_fold(g, nsub, interpret=True))(
        grid)
    xla, xla_sub = jax.jit(lambda g: discrete_block_elements(g, nsub))(grid)
    exact, exact_sub = jax.jit(lambda g: discrete_block_elements(g, nsub))(
        _f64(grid))
    assert got.A.shape == xla.A.shape == (grid.N // nsub,) + grid.F.shape[1:]
    assert got_sub.A.shape == xla_sub.A.shape
    _assert_as_accurate(got, xla, exact)
    _assert_as_accurate(got_sub, xla_sub, exact_sub)


@pytest.mark.parametrize("nsub", [1, 2, 10])
@pytest.mark.parametrize("nx", [2, 4, 5])
def test_block_fold_matches_xla_fold(nx, nsub):
    """T = 130 blocks: one kernel tile of two 128-lane rows, 126 of them
    padding."""
    rng = np.random.default_rng(100 * nx + nsub)
    _check(_grid(rng, 130, nsub, nx), nsub)


def test_block_fold_several_tiles():
    """T = 1 100 blocks: nine rows of 128 padded to two tiles of eight;
    with the linear cost term of a divergence-corrected linearisation."""
    rng = np.random.default_rng(1)
    grid = _grid(rng, 1_100, 2, 4)
    _check(grid._replace(lin=jnp.asarray(
        rng.standard_normal((grid.N, 4)), jnp.float32)), 2)


def test_block_fold_at_coordinated_turn_conditioning():
    """The ct cell's numbers: nx 5, nsub 10, dt 5e-5, Q from diag 1.2e-7
    (float32 eps on the position rows) to 4e-4, R = diag(0.005, 0.001),
    and a range-bearing Jacobian for H, so H^T R^-1 H is not diagonal."""
    rng = np.random.default_rng(7)
    grid = _grid(rng, 200, 10, 5, dt=5e-5, r_diag=(0.005, 0.001),
                 Q=np.diag([EPS, EPS, 2.5e-7, 2.5e-7, 4e-4]))
    x = rng.uniform(3.0, 7.0, (grid.N, 2))
    rr = np.hypot(x[:, 0], x[:, 1])
    H = np.zeros((grid.N, 2, 5))
    H[:, 0, 0], H[:, 0, 1] = x[:, 0] / rr, x[:, 1] / rr
    H[:, 1, 0], H[:, 1, 1] = -x[:, 1] / rr ** 2, x[:, 0] / rr ** 2
    grid = grid._replace(H=jnp.asarray(H, jnp.float32))
    _check(grid, 10)


def test_block_fold_under_vmap():
    """Stacked records, as the engines solve them: the kernel batches."""
    rng = np.random.default_rng(3)
    grids = [_grid(rng, 130, 10, 4) for _ in range(3)]
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *grids)
    got = jax.jit(jax.vmap(
        lambda g: block_fold(g, 10, interpret=True)[0]))(stacked)
    for i, grid in enumerate(grids):
        xla, _ = discrete_block_elements(grid, 10)
        exact, _ = discrete_block_elements(_f64(grid), 10)
        _assert_as_accurate(
            jax.tree_util.tree_map(lambda a: a[i], got), xla, exact)


def test_block_fold_counts_lanes():
    grid = _grid(np.random.default_rng(4), 130, 2, 2)
    obs.reset()
    obs.enable()
    try:
        jax.eval_shape(lambda g: block_fold(g, 2, interpret=True), grid)
        counters = obs.snapshot()["counters"]
    finally:
        obs.disable()
    assert counters["kernel.block_fold.lanes"] == 130
    assert counters["kernel.block_fold.pad_lanes"] == 256 - 130


def test_cpu_program_keeps_the_xla_fold():
    """Lowered for the CPU, a float32 solve folds in XLA: the kernel branch
    is traced, but the compiled program holds no kernel and no conditional
    from the platform selection."""
    from repro.configs.wiener_velocity import WienerVelocityConfig
    from repro.core import ParallelOptions, grid_lqt_from_linear
    from repro.core.registry import get_method

    solver = get_method("parallel_rts").solver
    opts = ParallelOptions(nsub=10, mode="discrete")
    with jax.enable_x64(False):
        model = WienerVelocityConfig().model()
        ts = jnp.linspace(0.0, 1.0, 201, dtype=jnp.float32)
        y = jnp.zeros((200, 2), jnp.float32)
        fn = jax.jit(
            lambda ts, y: solver(grid_lqt_from_linear(model, ts, y), opts).x)
        assert "pallas_call" in str(jax.make_jaxpr(fn)(ts, y))
        text = fn.lower(ts, y).compile().as_text()
    assert "tpu_custom_call" not in text
    assert "conditional" not in text
