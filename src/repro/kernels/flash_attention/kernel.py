"""Pallas TPU kernel: causal / sliding-window GQA flash attention.

Standard streaming-softmax decomposition with TPU tiling:

* grid = (B*Hq, Lq/BQ, Lk/BK); the KV dimension is ARBITRARY (sequential)
  and carries the running max / normaliser / accumulator in VMEM scratch.
* BlockSpec index maps implement GQA by folding the q-head -> kv-head
  mapping into the K/V block indices (no repeated K/V materialisation).
* fully-masked KV blocks (beyond the causal frontier or outside the
  sliding window) are skipped with ``pl.when`` -- the O(L^2) -> O(L*W)
  saving for SWA happens here.
* MXU alignment: BQ/BK default to 128 and D is the model head_dim (a
  multiple of 8 for all configs in this repo); logits/accumulator are f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int | None,
                 bq: int, bk: int, lk_offset: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # global row/col positions of this tile (q offset by lk_offset for
    # decode-style Lq < Lk usage)
    q_start = qi * bq + lk_offset
    k_start = kj * bk

    def needed():
        ok = True
        if causal:
            ok = jnp.logical_and(ok, k_start <= q_start + bq - 1)
        if window is not None:
            ok = jnp.logical_and(ok, k_start + bk - 1 > q_start - window)
        return ok

    @pl.when(needed())
    def _compute():
        q = q_ref[0, 0]                    # (BQ, D)
        k = k_ref[0, 0]                    # (BK, D)
        v = v_ref[0, 0]                    # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (BQ, BK)

        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=jnp.bool_)
        if causal:
            mask &= rows >= cols
        if window is not None:
            mask &= (rows - cols) < window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(kj == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """Flash attention with GQA head folding.

    q: (B, Hq, Lq, D); k, v: (B, Hkv, Lk, D).  Lq may be < Lk (the q rows
    are aligned to the END of the key sequence, e.g. decode steps).
    """
    B, Hq, Lq, D = q.shape
    Hkv, Lk = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0
    rep = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    bq = min(block_q, Lq)
    bk = min(block_k, Lk)
    assert Lq % bq == 0 and Lk % bk == 0, (Lq, bq, Lk, bk)
    grid = (B * Hq, Lq // bq, Lk // bk)

    out = pl.pallas_call(
        functools.partial(
            _attn_kernel, scale=scale, causal=causal, window=window,
            bq=bq, bk=bk, lk_offset=Lk - Lq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D),
                         lambda bh, qi, kj: (bh // Hq, bh % Hq, qi, 0)),
            pl.BlockSpec(
                (1, 1, bk, D),
                lambda bh, qi, kj: (bh // Hq, (bh % Hq) // rep, kj, 0)),
            pl.BlockSpec(
                (1, 1, bk, D),
                lambda bh, qi, kj: (bh // Hq, (bh % Hq) // rep, kj, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, D), lambda bh, qi, kj: (bh // Hq, bh % Hq, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Lq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out
