"""Batched products and solves of small dense matrices.

The smoothers multiply and solve one ``nx x nx`` system per grid point or
per scan element, at up to ~1e6 points.  Written as ``@``/``jnp.linalg.solve``,
these lower to XLA dots and pivoted LU decompositions batched over the time
axis, which the TPU compiler handles badly at long horizons:

* a batched dot becomes a convolution whose compile cost grows with the
  batch, and where its operands are the model's constant coefficients
  broadcast along the grid the compiler folds it on the host: one
  ``(N, 2, 4) x (N, 2, 2)`` product took 7.6 s to compile for v5e at
  N = 2 560 and 112 s at N = 20 480;
* the gather that applies LU's pivot permutation compiles in about 75 s for
  a ``(1e5, 4, 4)`` batch, where the elimination below compiles in about 2 s;
* a float32 dot on the TPU runs at the MXU's default (bfloat16-pass)
  precision, while the products below are exact float32 arithmetic.

So in float32, the chip's dtype, every function here is built from broadcasts,
multiplies, selects and reductions over the small trailing axes, which XLA
fuses.  The solve is a rolled loop over the ``n`` steps of the elimination,
each a fixed few ops on the augmented matrix, so its traced size does not grow
with ``n``: a float32 ``parallel_rts`` solve at N = 400 lowers on the CPU to
4 264 StableHLO lines at nx 10 and 40 alike, against 22 233 and 605 037 (134 s)
with a loop over rows inside each step, and 8 812 and 27 682 with the steps
unrolled (a five-pass 40-wide solve at N = 5e4 then compiled for v5e in 2 379 s,
against 25-35 s rolled).  In float64, which only the CPU runs (the tests, with
x64 on), they are ``jnp``'s dots and LAPACK calls.  One form for both dtypes
was tried and measured on the CPU:

* the elementwise forms in float64 made XLA:CPU's compiles about three
  times slower (the elimination) and the full test suite 1.7 times slower;
* elementwise products with LAPACK solves gave a wrong trajectory for one
  record of a time-sharded stacked solve, right again with XLA:CPU's
  concurrency-optimized scheduler turned off (PERF.md, open questions).

The float32 forms run end to end on the CPU in
``tests/test_float32_smoothers.py``.  The dtype is known when the program is
traced, so an ahead-of-time compile for a TPU from a CPU-only process takes
the float32 form the chip runs.  All functions broadcast over arbitrary
leading batch axes, like ``jnp.linalg``.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def _f64(*args) -> bool:
    return any(jnp.result_type(a) == jnp.float64 for a in args)


def mm(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """Matrix product ``A B`` of ``(..., n, k)`` and ``(..., k, m)``."""
    if _f64(A, B):
        return jnp.matmul(A, B)
    return jnp.sum(A[..., :, :, None] * B[..., None, :, :], axis=-2)


def mv(A: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Matrix-vector product ``A x`` of ``(..., n, k)`` and ``(..., k)``."""
    if _f64(A, x):
        return jnp.matmul(A, x[..., None])[..., 0]
    return jnp.sum(A * x[..., None, :], axis=-1)


def mT(A: jnp.ndarray) -> jnp.ndarray:
    """Transpose of the two trailing axes."""
    return jnp.swapaxes(A, -1, -2)


def quad(x: jnp.ndarray, M: jnp.ndarray) -> jnp.ndarray:
    """Quadratic form ``x^T M x`` of ``(..., n)`` and ``(..., n, n)``."""
    return jnp.sum(x * mv(M, x), axis=-1)


def solve(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """``A^{-1} B`` for ``A`` ``(..., n, n)`` and ``B`` ``(..., n, m)``, by
    Gauss-Jordan elimination with partial pivoting (LAPACK in float64)."""
    if _f64(A, B):
        return jnp.linalg.solve(A, B)
    n = A.shape[-1]
    batch = jnp.broadcast_shapes(A.shape[:-2], B.shape[:-2])
    M = jnp.concatenate([jnp.broadcast_to(A, batch + A.shape[-2:]),
                         jnp.broadcast_to(B, batch + B.shape[-2:])],
                        axis=-1).astype(jnp.result_type(A, B))
    row = jnp.arange(n)[:, None]

    def step(k, M):
        # Pivot: the first row i >= k with the largest |M[..., i, k]|.  Rows
        # are read by one-hot sums and exchanged by selects, not gathers.
        col = lax.dynamic_index_in_dim(M, k, axis=-1, keepdims=False)
        p = jnp.argmax(jnp.where(row[:, 0] >= k, jnp.abs(col), -1.0), axis=-1)
        at_p = row == p[..., None, None]
        piv = jnp.sum(jnp.where(at_p, M, 0.0), axis=-2, keepdims=True)
        rk = jnp.sum(jnp.where(row == k, M, 0.0), axis=-2)
        u = piv / lax.dynamic_index_in_dim(piv, k, axis=-1)
        c = jnp.where(at_p[..., 0], lax.dynamic_index_in_dim(rk, k, -1), col)
        S = jnp.where(at_p, rk[..., None, :], M)
        return jnp.where(row == k, u, S - c[..., None] * u)

    return lax.fori_loop(0, n, step, M)[..., n:]


def inv(A: jnp.ndarray) -> jnp.ndarray:
    """``A^{-1}`` for ``A`` ``(..., n, n)``."""
    if _f64(A):
        return jnp.linalg.inv(A)
    return solve(A, jnp.eye(A.shape[-1], dtype=A.dtype))

