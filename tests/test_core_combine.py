"""Property tests for the associative operators (paper eqs. 29/42, 45-46)."""
import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -e '.[test]')")
from hypothesis import given, settings, strategies as st

from repro.core import (
    AffineElement, LQTElement, ValueFn,
    affine_combine, apply_element_to_value, lqt_combine,
    prefix_scan, suffix_scan, value_as_element,
)


def _rand_psd(rng, n, scale=1.0):
    A = rng.standard_normal((n, n))
    return scale * (A @ A.T / n + 0.1 * np.eye(n))


def _rand_element(rng, n):
    return LQTElement(
        A=jnp.asarray(rng.standard_normal((n, n)) * 0.7),
        b=jnp.asarray(rng.standard_normal(n)),
        C=jnp.asarray(_rand_psd(rng, n)),
        eta=jnp.asarray(rng.standard_normal(n)),
        J=jnp.asarray(_rand_psd(rng, n)),
    )


def _elem_value(e: LQTElement, x, z):
    """Evaluate V(x; z) of eq. (41) up to its constant."""
    d = z - e.A @ x - e.b
    return (0.5 * x @ e.J @ x - x @ e.eta
            + 0.5 * d @ jnp.linalg.solve(e.C, d))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 5))
def test_lqt_combine_associative(seed, n):
    rng = np.random.default_rng(seed)
    e1, e2, e3 = (_rand_element(rng, n) for _ in range(3))
    left = lqt_combine(lqt_combine(e1, e2), e3)
    right = lqt_combine(e1, lqt_combine(e2, e3))
    for a, b in zip(left, right):
        np.testing.assert_allclose(a, b, rtol=1e-8, atol=1e-8)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 4))
def test_lqt_combine_is_minplus(seed, n):
    """combine == min_z [V1(x, z) + V2(z, y)] evaluated pointwise."""
    rng = np.random.default_rng(seed)
    e1, e2 = _rand_element(rng, n), _rand_element(rng, n)
    e12 = lqt_combine(e1, e2)
    x = jnp.asarray(rng.standard_normal(n))
    y = jnp.asarray(rng.standard_normal(n))

    # analytic minimisation over z of V1(x,z)+V2(z,y):
    def total(z):
        return _elem_value(e1, x, z) + _elem_value(e2, z, y)

    zstar = jnp.linalg.solve(
        jnp.linalg.inv(e1.C) + e2.J + e2.A.T @ jnp.linalg.inv(e2.C) @ e2.A,
        jnp.linalg.inv(e1.C) @ (e1.A @ x + e1.b) + e2.eta
        + e2.A.T @ jnp.linalg.inv(e2.C) @ (y - e2.b))
    # difference of combined vs direct min must be x/y-independent (const):
    v_direct = total(zstar)
    v_comb = _elem_value(e12, x, y)
    x2 = jnp.asarray(rng.standard_normal(n))
    y2 = jnp.asarray(rng.standard_normal(n))
    zstar2 = jnp.linalg.solve(
        jnp.linalg.inv(e1.C) + e2.J + e2.A.T @ jnp.linalg.inv(e2.C) @ e2.A,
        jnp.linalg.inv(e1.C) @ (e1.A @ x2 + e1.b) + e2.eta
        + e2.A.T @ jnp.linalg.inv(e2.C) @ (y2 - e2.b))

    def total2(z):
        return _elem_value(e1, x2, z) + _elem_value(e2, z, y2)

    v_comb2 = _elem_value(e12, x2, y2)
    np.testing.assert_allclose(
        float(v_direct - v_comb), float(total2(zstar2) - v_comb2),
        rtol=1e-6, atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 5))
def test_affine_combine_associative(seed, n):
    rng = np.random.default_rng(seed)

    def re():
        return AffineElement(jnp.asarray(rng.standard_normal((n, n))),
                             jnp.asarray(rng.standard_normal(n)))

    e1, e2, e3 = re(), re(), re()
    l = affine_combine(affine_combine(e1, e2), e3)
    r = affine_combine(e1, affine_combine(e2, e3))
    np.testing.assert_allclose(l.Phi, r.Phi, rtol=1e-10, atol=1e-10)
    np.testing.assert_allclose(l.beta, r.beta, rtol=1e-10, atol=1e-10)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(3, 17))
def test_scan_orientation_vs_fold(seed, T):
    """prefix/suffix scans must match sequential folds for a
    non-commutative operator (matrix product via affine_combine)."""
    rng = np.random.default_rng(seed)
    n = 3
    elems = AffineElement(
        jnp.asarray(rng.standard_normal((T, n, n))),
        jnp.asarray(rng.standard_normal((T, n))))

    pre = prefix_scan(affine_combine, elems)
    pre_ref = prefix_scan(affine_combine, elems, sequential=True)
    np.testing.assert_allclose(pre.Phi, pre_ref.Phi, rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(pre.beta, pre_ref.beta, rtol=1e-9, atol=1e-9)

    suf = suffix_scan(affine_combine, elems)
    suf_ref = suffix_scan(affine_combine, elems, sequential=True)
    np.testing.assert_allclose(suf.Phi, suf_ref.Phi, rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(suf.beta, suf_ref.beta, rtol=1e-9, atol=1e-9)

    # explicit fold semantics
    acc = jax.tree_util.tree_map(lambda x: x[0], elems)
    for i in range(1, T):
        acc = affine_combine(acc, jax.tree_util.tree_map(
            lambda x: x[i], elems))
    np.testing.assert_allclose(
        pre.Phi[-1], acc.Phi, rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(
        suf.Phi[0], acc.Phi, rtol=1e-9, atol=1e-9)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 5))
def test_value_element_embedding(seed, n):
    """combine(e, value_as_element(vf)) (J, eta) == apply_element_to_value."""
    rng = np.random.default_rng(seed)
    e = _rand_element(rng, n)
    vf = ValueFn(jnp.asarray(_rand_psd(rng, n)),
                 jnp.asarray(rng.standard_normal(n)))
    via_elem = lqt_combine(e, value_as_element(vf))
    direct = apply_element_to_value(e, vf)
    np.testing.assert_allclose(via_elem.J, direct.S, rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(via_elem.eta, direct.v, rtol=1e-9, atol=1e-9)
    # the terminal element's A must be inert
    np.testing.assert_allclose(via_elem.A, np.zeros((n, n)), atol=1e-12)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_combine_psd_preserved(seed):
    """C and J stay symmetric PSD under combination."""
    rng = np.random.default_rng(seed)
    n = 4
    e = _rand_element(rng, n)
    for _ in range(5):
        e2 = _rand_element(rng, n)
        e = lqt_combine(e, e2)
    for M in (e.C, e.J):
        np.testing.assert_allclose(M, M.T, atol=1e-9)
        w = np.linalg.eigvalsh(np.asarray(M))
        assert w.min() > -1e-8, f"lost PSD: {w}"


@pytest.mark.parametrize("n", [1, 2, 4, 5, 8])
def test_small_linalg_float32_forms_match_numpy(n):
    """``core.linalg`` (elementwise products, the pivoting
    Gauss-Jordan solve) in float32, the chip's dtype, against float64
    numpy."""
    _check_small_linalg(n, jnp.float32, tol=1e-4)


@pytest.mark.parametrize("n", [1, 2, 4, 5, 8])
def test_small_linalg_float64_matches_numpy(n):
    """The same functions in float64, which the CPU tests run: ``jnp``'s
    dots and LAPACK calls."""
    _check_small_linalg(n, jnp.float64, tol=1e-10)


def _check_small_linalg(n, dtype, tol):
    """Products and solves against numpy, including systems that need row
    exchanges and a shared matrix broadcast against a batch of right-hand
    sides."""
    from repro.core import linalg

    rng = np.random.default_rng(n)
    A = rng.standard_normal((64, n, n)) + 3.0 * np.eye(n)
    A[0] = np.eye(n)[::-1] + 1e-3            # zero diagonal: must pivot
    B = rng.standard_normal((64, n, 3))
    x = rng.standard_normal((64, n))
    cast = lambda a: jnp.asarray(a, dtype)
    tol = dict(rtol=tol, atol=tol)
    np.testing.assert_allclose(linalg.mm(cast(A), cast(B)), A @ B, **tol)
    np.testing.assert_allclose(linalg.mv(cast(A), cast(x)),
                               np.einsum("bij,bj->bi", A, x), **tol)
    np.testing.assert_allclose(linalg.quad(cast(x), cast(A)),
                               np.einsum("bi,bij,bj->b", x, A, x), **tol)
    np.testing.assert_allclose(linalg.solve(cast(A), cast(B)),
                               np.linalg.solve(A, B), **tol)
    np.testing.assert_allclose(linalg.solve(cast(A[1]), cast(B)),
                               np.linalg.solve(A[1], B), **tol)
    np.testing.assert_allclose(linalg.inv(cast(A)), np.linalg.inv(A), **tol)
    assert linalg.solve(cast(A), cast(B)).dtype == dtype


def _systems(n, kind, batch=8):
    """Seeded float32 systems ``(A, B)``.  ``zero_lead``: a zero in the
    leading entry, so the first step must exchange rows.  ``tied``: random
    sign matrices, whose columns hold equal magnitudes at most steps of the
    elimination, so the rule for ties decides the pivot.  Draws with a
    condition number of 1e4 or more are drawn again."""
    rng = np.random.default_rng([n, len(kind)])
    As = []
    while len(As) < batch:
        if kind == "tied":
            A = rng.choice([-1.0, 1.0], size=(n, n))
        else:
            A = rng.standard_normal((n, n))
            if kind == "zero_lead":
                A[0, 0] = 0.0
        if np.linalg.cond(A) < 1e4:
            As.append(A)
    B = rng.standard_normal((batch, n, 3))
    return np.float32(As), np.float32(B)


def _gauss_jordan_f32(A, B, last_of_ties=False):
    """Plain numpy Gauss-Jordan in float32 with partial pivoting, a tie
    going to the first of equal maxima (or the last): ``A^{-1} B`` and the
    pivot row of each step."""
    n = A.shape[0]
    M = np.concatenate([A, B], axis=-1).astype(np.float32)
    pivots = []
    for k in range(n):
        col = np.abs(M[k:, k])
        p = k + (len(col) - 1 - int(np.argmax(col[::-1])) if last_of_ties
                 else int(np.argmax(col)))
        pivots.append(p)
        M[[k, p]] = M[[p, k]]
        M[k] = M[k] / M[k, k]
        for i in range(n):
            if i != k:
                M[i] = M[i] - M[i, k] * M[k]
    return M[:, n:], pivots


@pytest.mark.parametrize("kind", ["random", "zero_lead", "tied"])
@pytest.mark.parametrize("n", [2, 4, 5, 8, 16, 40])
def test_float32_solve_and_inv_match_float64(n, kind):
    """The float32 solve and inverse against float64 ``jnp.linalg``, with
    an error bound scaled by each system's condition number: partial
    pivoting is backward stable, so the forward error is at most about
    ``cond(A) n eps``."""
    from repro.core import linalg

    A, B = _systems(n, kind)
    pairs = [(jax.jit(linalg.solve)(A, B),
              jnp.linalg.solve(np.float64(A), np.float64(B))),
             (jax.jit(linalg.inv)(A), jnp.linalg.inv(np.float64(A)))]
    eps = np.finfo(np.float32).eps
    for X, X64 in pairs:
        assert X.dtype == np.float32
        for a, x, x64 in zip(A, np.asarray(X), np.asarray(X64)):
            err = np.max(np.abs(x - x64)) / np.max(np.abs(x64))
            assert err < 4 * n * eps * np.linalg.cond(np.float64(a)), err


@pytest.mark.parametrize("kind", ["random", "zero_lead", "tied"])
@pytest.mark.parametrize("n", [4, 5, 8])
def test_float32_solve_takes_numpy_pivots(n, kind):
    """Run op by op with jit off (no fusion, so no contraction or
    reordering of the arithmetic), the float32 solve equals a plain numpy
    float32 Gauss-Jordan bit for bit: it takes the same pivot at every
    step, the first of equal maxima on a tie."""
    from repro.core import linalg

    A, B = _systems(n, kind, batch=4)
    with jax.disable_jit():
        X = np.asarray(linalg.solve(A, B))
    decided_by_tie = 0
    for a, b, x in zip(A, B, X):
        ref, pivots = _gauss_jordan_f32(a, b)
        np.testing.assert_array_equal(x, ref)
        decided_by_tie += pivots != _gauss_jordan_f32(a, b, True)[1]
    assert decided_by_tie or kind != "tied"


def _count_eqns(jaxpr):
    """Equations of a jaxpr, those of its sub-jaxprs included."""
    total = 0
    for eqn in jaxpr.eqns:
        total += 1
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if isinstance(sub, jax.extend.core.Jaxpr):
                total += _count_eqns(sub)
    return total


def test_float32_solve_traced_size_is_linear_in_n():
    """A fixed number of operations per elimination step: the traced size
    of a 40 x 40 solve stays under a bound linear in n (a form with a
    loop over rows inside the loop over steps traces about n^3 / 6)."""
    from repro.core import linalg

    n = 40
    jaxpr = jax.make_jaxpr(linalg.solve)(np.zeros((n, n), np.float32),
                                         np.zeros((n, 3), np.float32))
    assert _count_eqns(jaxpr.jaxpr) <= 40 * n
