"""GPipe-style pipeline parallelism over a "pipe" mesh axis.

Layers are stacked per stage ((stages, layers_per_stage, ...) weights,
stage dim sharded over the pipe axis); microbatches stream through the
stages with ``collective_permute`` handoffs.  The schedule runs
M + S - 1 ticks for M microbatches over S stages (the classic GPipe
bubble); each tick every stage computes one microbatch and passes its
activation to the next stage.

This is the PP feature module (DESIGN.md S5): the 40-cell dry-run uses
data x model only, but the module is wired for production use and
verified against the sequential stack on an 8-device host mesh
(tests/test_distributed.py).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_fn: Callable, stage_params, x_micro,
                     mesh, axis_name: str = "pipe"):
    """Run microbatches through pipeline stages.

    stage_fn(params_stage, x) -> x            (one stage's computation)
    stage_params: leaves with leading dim = n_stages (sharded over pipe)
    x_micro: (M, ...) microbatched input (replicated; stage 0 consumes)
    Returns (M, ...) outputs (replicated from the last stage).
    """
    n_stages = mesh.shape[axis_name]
    M = x_micro.shape[0]
    ticks = M + n_stages - 1

    def body(params_stage, xm):
        # params_stage: (1, ...) local stage slice; xm: full (M, ...)
        params_local = jax.tree_util.tree_map(
            lambda a: a[0], params_stage)
        stage = jax.lax.axis_index(axis_name)
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        buf = jnp.zeros_like(xm[0])
        outs = jnp.zeros_like(xm)

        def tick(t, carry):
            buf, outs = carry
            mb_in = t                      # microbatch entering stage 0
            feed = jnp.where(mb_in < M, mb_in, M - 1)
            x0 = jax.lax.dynamic_index_in_dim(xm, feed, 0, keepdims=False)
            inp = jnp.where(stage == 0, x0, buf)
            # stage s works on microbatch t - s when 0 <= t - s < M
            active = jnp.logical_and(t - stage >= 0, t - stage < M)
            y = stage_fn(params_local, inp)
            y = jnp.where(active, y, buf)
            # deliver finished microbatches from the last stage
            done_mb = t - (n_stages - 1)
            outs = jax.lax.cond(
                jnp.logical_and(done_mb >= 0, stage == n_stages - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(done_mb, 0), 0),
                lambda o: o, outs)
            # hand activations forward
            buf_next = jax.lax.ppermute(y, axis_name, fwd_perm)
            return (buf_next, outs)

        buf, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # broadcast results from the last stage to every shard
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis_name)
        return outs

    spec_params = jax.tree_util.tree_map(lambda _: P(axis_name),
                                         stage_params)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x_micro)
