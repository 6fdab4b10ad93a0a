"""Batched products and solves of small dense matrices.

The smoothers multiply and solve one ``nx x nx`` system per grid point or
per scan element, with ``nx`` of 2-8 and up to ~1e6 points.  Written as
``@``/``jnp.linalg.solve``, these lower to XLA dots and pivoted LU
decompositions batched over the time axis, which the TPU compiler handles
badly at long horizons:

* a batched dot becomes a convolution whose compile cost grows with the
  batch, and where its operands are the model's constant coefficients
  broadcast along the grid the compiler folds it on the host: one
  ``(N, 2, 4) x (N, 2, 2)`` product took 7.6 s to compile for v5e at
  N = 2 560 and 112 s at N = 20 480;
* the gather that applies LU's pivot permutation compiles in about 75 s for
  a ``(1e5, 4, 4)`` batch, where the elimination below compiles in about
  2 s;
* a float32 dot on the TPU runs at the MXU's default (bfloat16-pass)
  precision, while the products below are exact float32 arithmetic.

So in float32, the chip's dtype, every function here is built from
broadcasts, multiplies, selects and reductions over the small trailing
axes, which XLA fuses.  In float64, which only the CPU runs (the tests, with
x64 on), they are ``jnp``'s dots and LAPACK calls.  One form for both
dtypes was tried and measured on the CPU:

* the elementwise forms in float64 made XLA:CPU's compiles about three
  times slower (the unrolled elimination) and the full test suite 1.7
  times slower;
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
    dtype = jnp.result_type(A, B)
    A = jnp.broadcast_to(A, batch + A.shape[-2:]).astype(dtype)
    B = jnp.broadcast_to(B, batch + B.shape[-2:]).astype(dtype)
    rows = [jnp.concatenate([A[..., i, :], B[..., i, :]], axis=-1)
            for i in range(n)]
    for k in range(n):
        # Pivot: the row at or below k with the largest |entry| in column k.
        piv, best = rows[k], jnp.abs(rows[k][..., k])
        which = [None] * n
        for i in range(k + 1, n):
            cand = jnp.abs(rows[i][..., k])
            which[i] = cand > best
            # A later strictly larger candidate supersedes earlier picks.
            for j in range(k + 1, i):
                which[j] = which[j] & ~which[i]
            piv = jnp.where(which[i][..., None], rows[i], piv)
            best = jnp.maximum(cand, best)
        for i in range(k + 1, n):
            rows[i] = jnp.where(which[i][..., None], rows[k], rows[i])
        piv = piv / piv[..., k:k + 1]
        rows = [piv if i == k else rows[i] - rows[i][..., k:k + 1] * piv
                for i in range(n)]
    return jnp.stack([r[..., n:] for r in rows], axis=-2)


def inv(A: jnp.ndarray) -> jnp.ndarray:
    """``A^{-1}`` for ``A`` ``(..., n, n)``."""
    if _f64(A):
        return jnp.linalg.inv(A)
    n = A.shape[-1]
    return solve(A, jnp.broadcast_to(jnp.eye(n, dtype=A.dtype), A.shape))

