"""Pallas TPU kernels for the LQT combine (paper eq. 42).

TPU adaptation (DESIGN.md S2): the elements are tiny (nx x nx with
nx <= ~8) but the smoother combines BATCHES of them (one per tree node per
scan level, one per block inside a block, times any outer batch).  A GPU
implementation maps one element to one thread block; on TPU we instead put
the BATCH on the vector unit's lanes and keep the matrix indices as tiny
major dimensions, so that every entry (i, j) of a batch of elements is one
array over the batch and every small-matrix op is an elementwise VPU op
with static Python loops over i/j/k (nx is tiny and static).
:func:`combine_entries` is that arithmetic, written once; two kernels run
it:

* :func:`lqt_combine_lanes`, one combine of two element batches in the
  layout (nx, nx, TB) / (nx, TB): entry (i, j) of TB elements is one VREG
  row.  The scans of ``ops.py`` call it once per tree level.
* :func:`block_fold_lanes`, the fold of each block's ``nsub`` substep
  elements into the block element, in the layout (nsub, nx, nx, R, 128) /
  (nsub, nx, R, 128): block ``t`` sits at row ``t // 128``, lane
  ``t % 128``, so entry (i, j) of a tile of 8 x 128 blocks fills a whole
  VREG.  Its grid runs over tiles of blocks and, innermost, over the
  substeps; the carry stays in the output block in VMEM for all of them.

The (I + C1 J2)^{-1} inverse is an in-register Gauss-Jordan elimination
WITHOUT pivoting (with pivoting by selects, the interpreter's compile at
nx = 8 did not finish in ten minutes).  C1, J2 are symmetric PSD, so
C1 J2 has real nonnegative eigenvalues and M is invertible (the paper's
argument, section 4.1).  The pivots are ratios of M's leading principal
minors: where J2 is diagonal (a measurement of state components, R
diagonal) each minor is det(I + C1' J2') >= 1; where ||C1 J2|| < 1 none
can vanish, and the pivots stay near 1, as in the block fold, whose J2 is
a one-step dt H^T R^-1 H.  Otherwise a leading minor can vanish:
C1 = [[1, 2], [2, 4]], J2 = [[1, -1], [-1, 1]] give M = [[0, 1], [-2, 3]].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Sublane rows of 128 blocks in one grid step of the fold kernel: one
# float32 VREG per matrix entry.
FOLD_ROWS = 8


def _dot(xs, ys):
    acc = xs[0] * ys[0]
    for x, y in zip(xs[1:], ys[1:]):
        acc = acc + x * y
    return acc


def _mm(X, Y):
    """Product of two matrices of entries (nested lists of batch arrays)."""
    cols = _tr(Y)
    return [[_dot(row, col) for col in cols] for row in X]


def _mv(X, v):
    return [_dot(row, v) for row in X]


def _tr(X):
    return [list(col) for col in zip(*X)]


def _add(x, y):
    return [a + b for a, b in zip(x, y)]


def _sym_add(X, Y):
    """``(X + Y + (X + Y)^T) / 2``, entrywise."""
    n = len(X)
    S = [_add(X[i], Y[i]) for i in range(n)]
    return [[0.5 * (S[i][j] + S[j][i]) for j in range(n)] for i in range(n)]


def _inverse(M):
    """Gauss-Jordan inverse without pivoting of a matrix of entries."""
    n = len(M)
    one, zero = jnp.ones_like(M[0][0]), jnp.zeros_like(M[0][0])
    rows = [list(M[i]) + [one if j == i else zero for j in range(n)]
            for i in range(n)]
    for k in range(n):
        piv = 1.0 / rows[k][k]
        rows[k] = [x * piv for x in rows[k]]
        for i in range(n):
            if i != k:
                f = rows[i][k]
                rows[i] = [x - f * y for x, y in zip(rows[i], rows[k])]
    return [row[n:] for row in rows]


def combine_entries(e1, e2):
    """Eq. (42), ``e1`` on the earlier interval, on elements held as
    entries: ``(A, b, C, eta, J)`` with ``A``, ``C``, ``J`` nested lists of
    ``nx x nx`` arrays and ``b``, ``eta`` lists of ``nx``, every entry an
    array over the batch (of any one shape)."""
    A1, b1, C1, eta1, J1 = e1
    A2, b2, C2, eta2, J2 = e2
    n = len(A1)
    # M = I + C1 J2, inverted once; M^-T by index transpose (free).
    M = _mm(C1, J2)
    M = [[M[i][j] + 1.0 if i == j else M[i][j] for j in range(n)]
         for i in range(n)]
    Minv = _inverse(M)
    MinvT, A1T = _tr(Minv), _tr(A1)
    A = _mm(A2, _mm(Minv, A1))
    b = _add(_mv(A2, _mv(Minv, _add(b1, _mv(C1, eta2)))), b2)
    C = _sym_add(_mm(A2, _mm(_mm(Minv, C1), _tr(A2))), C2)
    w = [x - y for x, y in zip(eta2, _mv(J2, b1))]
    eta = _add(_mv(A1T, _mv(MinvT, w)), eta1)
    J = _sym_add(_mm(A1T, _mm(_mm(MinvT, J2), A1)), J1)
    return A, b, C, eta, J


def _entries(e, nx):
    """Entries of an element ``(A, b, C, eta, J)`` of arrays or refs whose
    two (matrix) or one (vector) leading axes are the indices."""
    A, b, C, eta, J = e

    def mat(X):
        return [[X[i, j] for j in range(nx)] for i in range(nx)]

    def vec(v):
        return [v[i] for i in range(nx)]

    return mat(A), vec(b), mat(C), vec(eta), mat(J)


def _combine_kernel(*refs, nx):
    e1, e2 = (_entries(tuple(r[...] for r in refs[s:s + 5]), nx)
              for s in (0, 5))
    A, b, C, eta, J = combine_entries(e1, e2)
    oA, ob, oC, oe, oJ = refs[10:]

    def stack(X):
        return jnp.stack([jnp.stack(row, axis=0) for row in X], axis=0)

    oA[...], oC[...], oJ[...] = stack(A), stack(C), stack(J)
    ob[...], oe[...] = jnp.stack(b, axis=0), jnp.stack(eta, axis=0)


def lqt_combine_lanes(ops1, ops2, *, block_b: int = 512,
                      interpret: bool = False):
    """Batched eq.-(42) combine in lane-major layout.

    ``ops1``/``ops2``: tuples (A, b, C, eta, J) with shapes
    (nx, nx, B) / (nx, B); B must be a multiple of ``block_b``.
    All ten operand blocks plus temporaries fit in VMEM for
    ``block_b = 512``, nx <= 8 (10 * nx^2 * TB * 4 B ~ 1.3 MiB).
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


def _fold_kernel(A, b, C, eta, J, oA, ob, oC, oeta, oJ, *, nx):
    """Grid step (tile i, substep k): out <- e_k at k = 0, else
    out <- combine(out, e_k).  The output block is the same for every k,
    so the carry stays in VMEM until the tile is done."""
    step, out = (A, b, C, eta, J), (oA, ob, oC, oeta, oJ)

    def store(e):
        for ref, x in zip(out, e):
            for i in range(nx):
                if isinstance(x[i], list):
                    for j in range(nx):
                        ref[i, j] = x[i][j]
                else:
                    ref[i] = x[i]

    @pl.when(pl.program_id(1) == 0)
    def _first():
        store(_entries(step, nx))

    @pl.when(pl.program_id(1) > 0)
    def _fold():
        store(combine_entries(_entries(out, nx), _entries(step, nx)))


def block_fold_lanes(ops, *, rows: int = FOLD_ROWS,
                     interpret: bool = False):
    """Fold each block's substep elements left to right with eq. (42).

    ``ops``: (A, b, C, eta, J) with shapes (nsub, nx, nx, R, 128) /
    (nsub, nx, R, 128), block ``t`` at ``[..., t // 128, t % 128]``;
    R must be a multiple of ``rows``.  Returns the block elements,
    (nx, nx, R, 128) / (nx, R, 128):
    ``e_0 (x) e_1 (x) ... (x) e_{nsub-1}`` per block.
    """
    A = ops[0]
    nsub, nx, _, R, lanes = A.shape
    assert R % rows == 0 and lanes == 128, (A.shape, rows)
    mat_in = pl.BlockSpec((None, nx, nx, rows, 128),
                          lambda i, k: (k, 0, 0, i, 0))
    vec_in = pl.BlockSpec((None, nx, rows, 128), lambda i, k: (k, 0, i, 0))
    mat_out = pl.BlockSpec((nx, nx, rows, 128), lambda i, k: (0, 0, i, 0))
    vec_out = pl.BlockSpec((nx, rows, 128), lambda i, k: (0, i, 0))
    mat = jax.ShapeDtypeStruct((nx, nx, R, 128), A.dtype)
    vec = jax.ShapeDtypeStruct((nx, R, 128), A.dtype)
    return pl.pallas_call(
        functools.partial(_fold_kernel, nx=nx),
        grid=(R // rows, nsub),
        in_specs=[mat_in, vec_in, mat_in, vec_in, mat_in],
        out_specs=(mat_out, vec_out, mat_out, vec_out, mat_out),
        out_shape=(mat, vec, mat, vec, mat),
        # tiles are independent; the substeps of a tile fold in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*ops)
