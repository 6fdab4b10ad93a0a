"""Parallel associative scans: local (on-chip) and distributed (multi-chip).

The paper's span-reduction comes from ``jax.lax.associative_scan`` (Blelloch
[5]).  Orientation conventions (critical for the non-commutative operators of
``combine.py``):

* ``prefix_scan(fn, a)[i]  = a_0 (x) a_1 (x) ... (x) a_i``  (eq. 25)
* ``suffix_scan(fn, a)[i]  = a_i (x) a_{i+1} (x) ... (x) a_{T-1}``  (eq. 26)

where ``fn(x, y)`` always receives ``x`` as the EARLIER-interval operand.
``jax.lax.associative_scan(reverse=True)`` flips the sequence but keeps the
operand order, which would silently transpose non-commutative operators; the
wrappers below handle the swap explicitly and are property-tested against
sequential folds.

``distributed_scan`` shards the time axis across a mesh axis (inside
``shard_map``): local scan -> all-gather of the P per-shard carries ->
redundant small scan over carries -> local fix-up.  Work O(T/P + P) per
device, span O(log(T/P) + P) with one all-gather; this is the multi-pod
temporal decomposition described in DESIGN.md S3.

``sharded_scan`` is the TOP-LEVEL entry around it (used by
``method="distributed"``): it owns the ``shard_map`` wrapping, handles
scan lengths that do not divide the shard count (a divisible head runs
distributed, the remainder tail runs locally and is folded in with one
broadcast combine), and degrades to the plain on-chip scan when the mesh
axis has fewer than 2 devices or the scan is too short to shard.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, TypeVar

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro import obs

T = TypeVar("T")


def prefix_scan(fn: Callable[[T, T], T], elems: T, *, sequential: bool = False) -> T:
    """Inclusive prefix combine along axis 0 (earlier operand first)."""
    if sequential:
        return _sequential_prefix(fn, elems)
    return jax.lax.associative_scan(fn, elems, axis=0)


def suffix_scan(fn: Callable[[T, T], T], elems: T, *, sequential: bool = False) -> T:
    """Inclusive suffix combine along axis 0 (earlier operand first)."""
    if sequential:
        return _sequential_suffix(fn, elems)
    flipped = jax.tree_util.tree_map(lambda x: jnp.flip(x, axis=0), elems)
    swapped = lambda a, b: fn(b, a)
    out = jax.lax.associative_scan(swapped, flipped, axis=0)
    return jax.tree_util.tree_map(lambda x: jnp.flip(x, axis=0), out)


def _sequential_prefix(fn, elems):
    """O(T)-span reference fold (the paper's sequential baseline shape)."""
    first = jax.tree_util.tree_map(lambda x: x[0], elems)
    rest = jax.tree_util.tree_map(lambda x: x[1:], elems)

    def step(carry, e):
        nxt = fn(carry, e)
        return nxt, nxt

    _, tail = jax.lax.scan(step, first, rest)
    return jax.tree_util.tree_map(
        lambda f, t: jnp.concatenate([f[None], t], axis=0), first, tail
    )


def _sequential_suffix(fn, elems):
    last = jax.tree_util.tree_map(lambda x: x[-1], elems)
    rest = jax.tree_util.tree_map(lambda x: x[:-1], elems)

    def step(carry, e):
        nxt = fn(e, carry)
        return nxt, nxt

    _, head = jax.lax.scan(step, last, rest, reverse=True)
    return jax.tree_util.tree_map(
        lambda h, l: jnp.concatenate([h, l[None]], axis=0), head, last
    )


def _select_tree(pred, a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(pred, x, y), a, b
    )


def distributed_scan(
    fn: Callable[[T, T], T],
    elems: T,
    axis_name: str,
    *,
    reverse: bool = False,
    carry_dtype=None,
) -> T:
    """Associative scan over a time axis sharded across ``axis_name``.

    Must be called INSIDE ``shard_map``; ``elems`` is the local shard with
    the local time axis at position 0.  Returns the local shard of the
    global inclusive prefix (or suffix if ``reverse``).

    ``carry_dtype`` (optional) runs the redundant scan over the
    all-gathered per-shard carries in that dtype (e.g. ``jnp.float64``
    for float32 elements: the carry chain is the one O(P)-sequential
    composition, so it accumulates the most round-off), casting back to
    the element dtypes before the local fix-up combine.

    No identity element is required: shard 0 (resp. the last shard for the
    reverse scan) keeps its local result via a masked select.
    """
    local = suffix_scan(fn, elems) if reverse else prefix_scan(fn, elems)
    carry = jax.tree_util.tree_map(
        lambda x: x[0] if reverse else x[-1], local
    )
    # (P, ...) per-shard totals, replicated on every shard.
    totals = jax.lax.all_gather(carry, axis_name, axis=0, tiled=False)
    if carry_dtype is not None:
        dtypes = jax.tree_util.tree_map(lambda x: x.dtype, totals)
        totals = jax.tree_util.tree_map(
            lambda x: x.astype(carry_dtype), totals)
    idx = jax.lax.axis_index(axis_name)
    p = jax.lax.axis_size(axis_name)

    if reverse:
        # exclusive suffix of totals strictly AFTER this shard
        suff = suffix_scan(fn, totals, sequential=True)
        nxt = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(
                x, jnp.minimum(idx + 1, p - 1), axis=0, keepdims=False
            ),
            suff,
        )
        if carry_dtype is not None:
            nxt = jax.tree_util.tree_map(
                lambda x, dt: x.astype(dt), nxt, dtypes)
        # fn broadcasts the rank-reduced carry against the local time axis.
        combined = fn(local, nxt)
        return _select_tree(idx == p - 1, local, combined)

    pref = prefix_scan(fn, totals, sequential=True)
    prev = jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(
            x, jnp.maximum(idx - 1, 0), axis=0, keepdims=False
        ),
        pref,
    )
    if carry_dtype is not None:
        prev = jax.tree_util.tree_map(
            lambda x, dt: x.astype(dt), prev, dtypes)
    combined = fn(prev, local)
    return _select_tree(idx == 0, local, combined)


def sharded_scan(
    fn: Callable[[T, T], T],
    elems: T,
    *,
    mesh,
    axis_name: str,
    reverse: bool = False,
    carry_dtype=None,
) -> T:
    """Top-level time-axis-sharded associative scan (any length T).

    Owns the ``shard_map`` around :func:`distributed_scan` over
    ``mesh``'s ``axis_name`` axis.  A scan length that does not divide
    the shard count P is split: the largest P-divisible head runs
    distributed, the remainder tail (< P elements) runs locally and is
    folded in with one broadcast combine -- results match the on-chip
    scan orientation conventions exactly.  Degrades to the plain local
    scan when P < 2 or T < 2 P (nothing to shard / shards would be
    shorter than the carry chain).

    With ``repro.obs`` enabled, each TRACE of a sharded scan counts
    ``distributed.shards`` (time-shards used) and
    ``distributed.carry_bytes`` (bytes of per-shard carries all-gathered
    onto every device) -- static shapes, so cached executables do not
    re-count (same convention as the ``kernel.*`` counters, see
    docs/OBSERVABILITY.md).  On the device its ops fall under the
    caller's ``smoother.scan`` scope.
    """
    tm = jax.tree_util.tree_map
    leaves = jax.tree_util.tree_leaves(elems)
    length = leaves[0].shape[0]
    shards = mesh.shape[axis_name]
    if shards < 2 or length < 2 * shards:
        return suffix_scan(fn, elems) if reverse else prefix_scan(fn, elems)

    if obs.enabled():
        carry = sum(
            l.dtype.itemsize * math.prod(l.shape[1:]) for l in leaves)
        obs.inc("distributed.shards", shards)
        obs.inc("distributed.carry_bytes", carry * shards)

    spec = tm(lambda _: PartitionSpec(axis_name), elems)
    dist = jax.shard_map(
        partial(distributed_scan, fn, axis_name=axis_name,
                reverse=reverse, carry_dtype=carry_dtype),
        mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False)

    cut = (length // shards) * shards
    if cut == length:
        return dist(elems)
    # Non-divisible T: distributed head + local tail, one broadcast
    # combine to stitch (fn broadcasts a rank-reduced operand).
    head = tm(lambda x: x[:cut], elems)
    tail = tm(lambda x: x[cut:], elems)
    cat = lambda a, b: jnp.concatenate([a, b], axis=0)
    if reverse:
        tail_suf = suffix_scan(fn, tail)
        tail_total = tm(lambda x: x[0], tail_suf)
        head_out = fn(dist(head), tail_total)
        return tm(cat, head_out, tail_suf)
    head_out = dist(head)
    head_total = tm(lambda x: x[-1], head_out)
    tail_out = fn(head_total, prefix_scan(fn, tail))
    return tm(cat, head_out, tail_out)
