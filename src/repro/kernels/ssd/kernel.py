"""Pallas TPU kernel: chunked SSD scan (mamba2), TPU-tiled.

This is the TPU-native realisation of the paper's block-element + scan
decomposition (DESIGN.md S3): the sequence is split into chunks of Q steps;
each chunk reduces to an "element" (scalar decay, (P, S) state increment) =
the affine element (Phi, beta) of eqs. (45)-(46) with diagonal Phi, and the
inter-chunk recurrence folds elements left-to-right while the intra-chunk
part is a dense (Q, Q) masked matmul that feeds the MXU.

Grid: (batch*heads, num_chunks) with the chunk dimension ARBITRARY
(sequential) -- the running (P, S) state lives in a VMEM scratch buffer and
is carried across grid steps, exactly the blocked-scan pattern.  Block
shapes are MXU-aligned for P, S, Q multiples of 128 (Q=chunk len) and fall
back gracefully for smaller test shapes.

VMEM budget per step (f32): x(Q P) + B,C(Q S) + state(P S) + mask(Q Q)
~ 128*128*6*4B ~ 0.4 MiB for Q=P=S=128: far under the ~16 MiB VMEM limit,
leaving headroom for double buffering of the HBM->VMEM pipeline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(l_ref, dtx_ref, B_ref, C_ref, y_ref, state, *, chunk: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    l = l_ref[0]            # (Q,)  per-step log decay (dt * A), <= 0
    dtx = dtx_ref[0]        # (Q, P) dt-weighted inputs
    Bm = B_ref[0]           # (Q, S)
    Cm = C_ref[0]           # (Q, S)

    cum = jnp.cumsum(l)                         # (Q,)
    total = cum[-1]

    # inter-chunk contribution: y_t += exp(cum_t) * C_t . state
    carry_in = state[...]                        # (P, S)
    y_inter = jnp.exp(cum)[:, None] * jax.lax.dot_general(
        Cm, carry_in, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)      # (Q, P)

    # intra-chunk: masked decay kernel  M[t,s] = exp(cum_t - cum_s) [s<=t]
    ids = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jds = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ids >= jds
    logdecay = cum[:, None] - cum[None, :]
    M = jnp.where(causal, jnp.exp(logdecay), 0.0)
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    y_intra = jax.lax.dot_general(M * G, dtx, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    y_ref[0] = (y_inter + y_intra).astype(y_ref.dtype)

    # element fold (eqs. 45-46, diagonal Phi): state' = e^total * state + inc
    w = jnp.exp(total - cum)[:, None] * dtx      # (Q, P)
    inc = jax.lax.dot_general(w, Bm, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, S)
    state[...] = jnp.exp(total) * carry_in + inc


def ssd_chunked(l, dtx, B, C, *, chunk: int = 128, interpret: bool = False):
    """Chunked SSD scan.

    Args:
      l:   (BH, L)     log decays dt*A (<= 0)
      dtx: (BH, L, P)  dt-weighted inputs
      B:   (BH, L, S)
      C:   (BH, L, S)
    Returns:
      y: (BH, L, P)
    """
    BH, L, P = dtx.shape
    S = B.shape[-1]
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk

    f32 = jnp.float32
    grid = (BH, nc)
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk), lambda i, c: (i, c)),
            pl.BlockSpec((1, chunk, P), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, S), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, S), lambda i, c: (i, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda i, c: (i, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, L, P), dtx.dtype),
        scratch_shapes=[pltpu.VMEM((P, S), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(l, dtx, B, C)
    return y
