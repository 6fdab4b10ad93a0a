"""Pallas TPU kernel for the batched LQT combine (paper eq. 42).

TPU adaptation (DESIGN.md S2): the elements are tiny (nx x nx with
nx <= ~8) but the scan feeds the operator BATCHES of element pairs (one per
tree node per level, times any outer batch).  A GPU implementation maps one
element to one thread block; on TPU we instead put the BATCH in the 128-wide
lane (minor) dimension and keep the matrix indices as tiny major dimensions:

    layout (nx, nx, TB): element (i, j) entries of TB elements live in one
    VREG row -> every small-matrix op becomes an elementwise VPU op over
    lanes, with static Python loops over i/j/k (nx is tiny and static).

The (I + C1 J2)^{-1} solve is an in-register Gauss-Jordan WITHOUT pivoting,
which is safe here: C1, J2 are symmetric PSD, so C1 J2 has real nonnegative
eigenvalues and every pivot of I + C1 J2 is >= 1 during elimination (the
paper's invertibility argument, section 4.1).

Block sizing: each grid step processes TB elements; all ten operand blocks
plus temporaries fit comfortably in VMEM for TB = 512, nx <= 8
(10 * nx^2 * TB * 4B ~ 1.3 MiB << 16 MiB VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _matmat(X, Y, nx):
    """(nx, nx, TB) @ (nx, nx, TB) -> (nx, nx, TB), lanes = batch."""
    rows = []
    for i in range(nx):
        cols = []
        for k in range(nx):
            acc = X[i, 0] * Y[0, k]
            for j in range(1, nx):
                acc = acc + X[i, j] * Y[j, k]
            cols.append(acc)
        rows.append(jnp.stack(cols, axis=0))
    return jnp.stack(rows, axis=0)


def _matvec(X, v, nx):
    """(nx, nx, TB) @ (nx, TB) -> (nx, TB)."""
    rows = []
    for i in range(nx):
        acc = X[i, 0] * v[0]
        for j in range(1, nx):
            acc = acc + X[i, j] * v[j]
        rows.append(acc)
    return jnp.stack(rows, axis=0)


def _transpose(X):
    return jnp.swapaxes(X, 0, 1)


def _gauss_jordan_inverse(M, nx):
    """Unpivoted Gauss-Jordan on (nx, nx, TB); rows are lane vectors."""
    a = [[M[i, j] for j in range(nx)] for i in range(nx)]
    inv = [[jnp.where(i == j, jnp.ones_like(M[0, 0]),
                      jnp.zeros_like(M[0, 0]))
            for j in range(nx)] for i in range(nx)]
    for k in range(nx):
        piv = 1.0 / a[k][k]
        a[k] = [x * piv for x in a[k]]
        inv[k] = [x * piv for x in inv[k]]
        for i in range(nx):
            if i == k:
                continue
            f = a[i][k]
            a[i] = [x - f * y for x, y in zip(a[i], a[k])]
            inv[i] = [x - f * y for x, y in zip(inv[i], inv[k])]
    return jnp.stack([jnp.stack(r, axis=0) for r in inv], axis=0)


def _combine_kernel(A1, b1, C1, e1, J1, A2, b2, C2, e2, J2,
                    oA, ob, oC, oe, oJ, *, nx):
    A1v, C1v, J2v, A2v, C2v, J1v = (
        A1[...], C1[...], J2[...], A2[...], C2[...], J1[...])
    b1v, e1v, b2v, e2v = b1[...], e1[...], b2[...], e2[...]

    # M = I + C1 J2; Minv once, M^-T via index transpose (free).
    M = _matmat(C1v, J2v, nx)
    eye_rows = []
    for i in range(nx):
        eye_rows.append(jnp.stack(
            [M[i, j] + (1.0 if i == j else 0.0) for j in range(nx)], axis=0))
    M = jnp.stack(eye_rows, axis=0)
    Minv = _gauss_jordan_inverse(M, nx)
    MinvT = _transpose(Minv)

    MiA1 = _matmat(Minv, A1v, nx)
    oA[...] = _matmat(A2v, MiA1, nx)

    tmp = b1v + _matvec(C1v, e2v, nx)
    ob[...] = _matvec(A2v, _matvec(Minv, tmp, nx), nx) + b2v

    MiC1 = _matmat(Minv, C1v, nx)
    C12 = _matmat(A2v, _matmat(MiC1, _transpose(A2v), nx), nx) + C2v
    oC[...] = 0.5 * (C12 + _transpose(C12))

    w = e2v - _matvec(J2v, b1v, nx)
    oe[...] = _matvec(_transpose(A1v), _matvec(MinvT, w, nx), nx) + e1v

    MtJ2 = _matmat(MinvT, J2v, nx)
    J12 = _matmat(_transpose(A1v), _matmat(MtJ2, A1v, nx), nx) + J1v
    oJ[...] = 0.5 * (J12 + _transpose(J12))


def lqt_combine_lanes(ops1, ops2, *, block_b: int = 512,
                      interpret: bool = False):
    """Batched eq.-(42) combine in lane-major layout.

    ``ops1``/``ops2``: tuples (A, b, C, eta, J) with shapes
    (nx, nx, B) / (nx, B); B must be a multiple of ``block_b``.
    """
    A1, b1, C1, e1, J1 = ops1
    A2, b2, C2, e2, J2 = ops2
    nx, _, B = A1.shape
    assert B % block_b == 0, (B, block_b)
    grid = (B // block_b,)

    mat_spec = pl.BlockSpec((nx, nx, block_b), lambda i: (0, 0, i))
    vec_spec = pl.BlockSpec((nx, block_b), lambda i: (0, i))
    specs = [mat_spec, vec_spec, mat_spec, vec_spec, mat_spec]

    out_shapes = (
        jax.ShapeDtypeStruct((nx, nx, B), A1.dtype),
        jax.ShapeDtypeStruct((nx, B), A1.dtype),
        jax.ShapeDtypeStruct((nx, nx, B), A1.dtype),
        jax.ShapeDtypeStruct((nx, B), A1.dtype),
        jax.ShapeDtypeStruct((nx, nx, B), A1.dtype),
    )
    return pl.pallas_call(
        functools.partial(_combine_kernel, nx=nx),
        grid=grid,
        in_specs=specs + specs,
        out_specs=tuple(specs),
        out_shape=out_shapes,
        # lane blocks are independent element batches -> parallel grid
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(A1, b1, C1, e1, J1, A2, b2, C2, e2, J2)
