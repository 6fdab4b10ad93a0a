"""Jitted wrappers for the LQT-combine Pallas kernel.

``lqt_combine_batched`` takes the natural (B, nx, nx)/(B, nx) layout,
re-lays out to the kernel's lane-major form (batch minor), pads B to the
block size, runs the kernel and restores the layout.  When the whole scan
runs kernel-side, keep the lane-major layout across levels instead --
``kernel_prefix_scan`` / ``kernel_suffix_scan`` below do exactly that:
ONE ``_to_lanes``/``_from_lanes`` round-trip total, with every scan level
slicing/combining lane-major operands in place.  The multi-level tree is
the same work-efficient recursion as ``jax.lax.associative_scan``, so the
combine ORDER matches the jnp scan; the per-combine arithmetic still
differs (an inverse by Gauss-Jordan vs ``linalg.solve``), so results
agree to tolerance, not bit-exactly.

On the CPU backend ``interpret=True`` executes the kernel body with the
Pallas interpreter (bit-accurate semantics, no Mosaic), which is how the
tests run it; on a TPU the kernel compiles with Mosaic.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.elements import one_step_elements
from repro.core.types import GridLQT, LQTElement

from .kernel import FOLD_ROWS, block_fold_lanes, lqt_combine_lanes


def _to_lanes(e: LQTElement):
    return (
        jnp.transpose(e.A, (1, 2, 0)),
        jnp.transpose(e.b, (1, 0)),
        jnp.transpose(e.C, (1, 2, 0)),
        jnp.transpose(e.eta, (1, 0)),
        jnp.transpose(e.J, (1, 2, 0)),
    )


def _from_lanes(ops) -> LQTElement:
    A, b, C, eta, J = ops
    return LQTElement(
        jnp.transpose(A, (2, 0, 1)), jnp.transpose(b, (1, 0)),
        jnp.transpose(C, (2, 0, 1)), jnp.transpose(eta, (1, 0)),
        jnp.transpose(J, (2, 0, 1)))


def _pad_lanes(ops, pad):
    if pad == 0:
        return ops
    out = []
    for a in ops:
        width = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
        out.append(jnp.pad(a, width))
    return tuple(out)


def _combine_lanes(ops1, ops2, *, block_b: int, interpret: bool):
    """Kernel combine on lane-major 5-tuples of ANY lane count.

    Pads both operand tuples to a ``block_b`` multiple (zero lanes are
    garbage-free: C1 J2 = 0, so the Gauss-Jordan sees M = I) and slices
    the pad back off.  ``B == 0`` (empty tree levels) short-circuits.

    Obs: each call increments the ``kernel.lqt_combine.*`` launch
    counters.  These run at TRACE time (shapes are static ints, no tracer
    is captured), so they count kernel call sites emitted into the
    compiled program -- i.e. launches per execution of one compiled scan;
    cached executables do not re-count on reuse.
    """
    B = ops1[0].shape[-1]
    if B == 0:
        return ops1
    bb = min(block_b, max(8, B))
    pad = (-B) % bb
    if obs.enabled():
        obs.inc("kernel.lqt_combine.launches")
        obs.inc("kernel.lqt_combine.lanes", B)
        obs.inc("kernel.lqt_combine.pad_lanes", pad)
    out = lqt_combine_lanes(_pad_lanes(ops1, pad), _pad_lanes(ops2, pad),
                            block_b=bb, interpret=interpret)
    return tuple(a[..., :B] for a in out)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def lqt_combine_batched(e1: LQTElement, e2: LQTElement, *,
                        block_b: int = 512,
                        interpret: bool = False) -> LQTElement:
    """Kernel-backed eq. (42) combine on (B, nx, nx)-layout elements."""
    if e1.A.shape[0] == 0:  # associative_scan emits empty tree levels
        return e1
    return _from_lanes(_combine_lanes(_to_lanes(e1), _to_lanes(e2),
                                      block_b=block_b, interpret=interpret))


# ---------------------------------------------------------------------------
# In-block fold: each block's substep elements into its block element
# ---------------------------------------------------------------------------


def block_fold(grid: GridLQT, nsub: int, *, interpret: bool = False
               ) -> Tuple[LQTElement, LQTElement]:
    """Kernel-backed ``discrete``-mode elements:
    ``(block_elems (T, ...), substep_elems (T, nsub, ...))`` as
    :func:`repro.core.elements.discrete_block_elements` returns them, each
    block element the fold ``e_0 (x) ... (x) e_{nsub-1}`` of its block's
    one-step elements.

    The grid's samples are reordered substep-major first
    (:func:`_substep_lanes`), so the one-step elements come out in the
    kernel's order, (nsub, nx, nx, R, 128) with block ``t`` at row
    ``t // 128``, lane ``t % 128``; nothing is laid out with the substep
    index minor, which XLA pads from nsub to 128 lanes.  ``T`` is padded
    to a whole number of kernel tiles (up to 8 rows, else a multiple of 8)
    with ``dt = 0`` and zero samples, whose one-step elements are identity
    elements, so pad lanes stay finite.  Obs: the trace-time counters
    ``kernel.block_fold.lanes`` / ``.pad_lanes`` count the blocks folded
    and the pad lanes per kernel call site traced.
    """
    T = grid.N // nsub
    rows = -(-T // 128)
    tile = min(rows, FOLD_ROWS)
    rows = -(-rows // tile) * tile
    if obs.enabled():
        obs.inc("kernel.block_fold.lanes", T)
        obs.inc("kernel.block_fold.pad_lanes", rows * 128 - T)

    names = ("dt", "F", "c", "H", "r", "Q", "Rinv", "y", "lin")
    fields = {f: getattr(grid, f) for f in names
              if getattr(grid, f) is not None}
    lanes = _substep_lanes(jnp.concatenate(
        [a.reshape(grid.N, -1) for a in fields.values()], axis=1), nsub, rows)
    start = 0
    for f, a in fields.items():       # substep-major samples, (N', ...)
        width = math.prod(a.shape[1:])
        part = lanes[:, start:start + width].reshape(
            (nsub,) + a.shape[1:] + (rows * 128,))
        fields[f] = jnp.moveaxis(part, -1, 1).reshape((-1,) + a.shape[1:])
        start += width
    ones = one_step_elements(grid._replace(**fields))

    def to_lanes(a):       # (nsub * rows * 128, ...) -> (nsub, ..., rows, 128)
        a = jnp.moveaxis(a.reshape((nsub, rows * 128) + a.shape[1:]), 1, -1)
        return a.reshape(a.shape[:-1] + (rows, 128))

    ones = tuple(map(to_lanes, ones))

    def from_lanes(a):     # (..., rows, 128) -> (T, ...)
        a = jnp.moveaxis(a, (-2, -1), (0, 1))
        return a.reshape((rows * 128,) + a.shape[2:])[:T]

    blocks = block_fold_lanes(ones, rows=tile, interpret=interpret)
    sub = [from_lanes(a) for a in ones]
    return (LQTElement(*map(from_lanes, blocks)), LQTElement(*sub))


def _substep_lanes(x, nsub: int, rows: int):
    """``(T * nsub, W)`` samples -> ``(nsub, W, rows, 128)``: substep ``k``
    of block ``t`` at ``[k, :, t // 128, t % 128]``, zero past block ``T``.

    A ``(W, rows, 128 * nsub)`` view takes one strided lane slice per
    substep; XLA lays it out with the long axis minor throughout, where a
    ``(T, nsub)`` view would be padded from nsub to 128 lanes.
    """
    T = x.shape[0] // nsub
    x = jnp.pad(x.T, ((0, 0), (0, (rows * 128 - T) * nsub)))
    x = x.reshape(x.shape[0], rows, 128 * nsub)
    return jnp.stack([x[..., k::nsub] for k in range(nsub)])


# ---------------------------------------------------------------------------
# Whole-scan kernel path: multi-level associative scan in lane-major layout
# ---------------------------------------------------------------------------


def _interleave_lanes(even, odd):
    """Riffle two lane-major arrays: out[..., 0::2] = even, [1::2] = odd."""
    n = even.shape[-1] + odd.shape[-1]
    out = jnp.zeros(even.shape[:-1] + (n,), even.dtype)
    return out.at[..., 0::2].set(even).at[..., 1::2].set(odd)


def _scan_lanes(ops, combine):
    """Inclusive prefix scan over the LANE (last) axis, earlier operand
    first -- the recursive pair-reduce/odd-scan/even-fixup tree of
    ``jax.lax.associative_scan``, expressed on lane-major tuples so each
    level is one (or two) kernel combines over lane slices."""
    n = ops[0].shape[-1]
    if n < 2:
        return ops
    evens = tuple(a[..., 0:-1:2] for a in ops)          # lanes 0, 2, ...
    odds = tuple(a[..., 1::2] for a in ops)             # lanes 1, 3, ...
    odd_scanned = _scan_lanes(combine(evens, odds), combine)
    even_in = tuple(a[..., 2::2] for a in ops)          # lanes 2, 4, ...
    left = odd_scanned if n % 2 else tuple(a[..., :-1] for a in odd_scanned)
    even_scanned = combine(left, even_in)
    even_out = tuple(
        jnp.concatenate([a[..., :1], e], axis=-1)
        for a, e in zip(ops, even_scanned))
    return tuple(map(_interleave_lanes, even_out, odd_scanned))


def _scan_dtype(precision: str, dtype):
    if precision in (None, "default"):
        return dtype
    if precision == "float64" and not jax.config.jax_enable_x64:
        # astype would silently canonicalise the cast down to float32
        raise ValueError(
            "precision='float64' requires jax_enable_x64 (the cast would "
            "silently truncate to float32 under the default JAX config)")
    return jnp.dtype(precision)


def kernel_prefix_scan(elems: LQTElement, *, block_b: int = 512,
                       interpret: bool = False,
                       precision: str = "default") -> LQTElement:
    """Inclusive prefix combine along axis 0 (earlier operand first), run
    kernel-side in lane-major layout with one layout round-trip total.

    ``precision`` selects the kernel compute dtype (``"default"`` keeps the
    element dtype; ``"float32"``/``"float64"`` cast for the scan and cast
    the result back).
    """
    lanes = _to_lanes(elems)
    in_dtype = lanes[0].dtype
    cdtype = _scan_dtype(precision, in_dtype)
    lanes = tuple(a.astype(cdtype) for a in lanes)
    combine = functools.partial(_combine_lanes, block_b=block_b,
                                interpret=interpret)
    out = _scan_lanes(lanes, combine)
    return _from_lanes(tuple(a.astype(in_dtype) for a in out))


def kernel_suffix_scan(elems: LQTElement, *, block_b: int = 512,
                       interpret: bool = False,
                       precision: str = "default") -> LQTElement:
    """Inclusive suffix combine along axis 0 (earlier operand first):
    ``out[i] = a_i (x) ... (x) a_{T-1}``, matching
    :func:`repro.core.pscan.suffix_scan` -- flip on the lane axis plus an
    operand swap, so non-commutativity is preserved."""
    lanes = _to_lanes(elems)
    in_dtype = lanes[0].dtype
    cdtype = _scan_dtype(precision, in_dtype)
    flipped = tuple(jnp.flip(a.astype(cdtype), axis=-1) for a in lanes)

    def swapped(a, b):
        return _combine_lanes(b, a, block_b=block_b, interpret=interpret)

    out = _scan_lanes(flipped, swapped)
    out = tuple(jnp.flip(a, axis=-1).astype(in_dtype) for a in out)
    return _from_lanes(out)


def scan_combine_fn(*, block_b: int = 512, interpret: bool = False):
    """Combine callable for ``repro.core.pscan`` scans: kernel-backed and
    broadcast-compatible (rank-promotes a carried single element)."""

    def fn(a: LQTElement, b: LQTElement) -> LQTElement:
        def rank_of(e):
            return e.A.ndim

        if rank_of(a) == 2 and rank_of(b) == 3:
            a = jax.tree_util.tree_map(
                lambda x, y: jnp.broadcast_to(x, y.shape), a, b)
        elif rank_of(b) == 2 and rank_of(a) == 3:
            b = jax.tree_util.tree_map(
                lambda x, y: jnp.broadcast_to(x, y.shape), b, a)
        if rank_of(a) == 2:
            a3 = jax.tree_util.tree_map(lambda x: x[None], a)
            b3 = jax.tree_util.tree_map(lambda x: x[None], b)
            out = lqt_combine_batched(a3, b3, block_b=8,
                                      interpret=interpret)
            return jax.tree_util.tree_map(lambda x: x[0], out)
        return lqt_combine_batched(a, b, block_b=block_b,
                                   interpret=interpret)

    return fn
