"""Plain reference of the discrete MAP trajectory, independent of the program.

A Kalman filter and RTS smoother written as two ``lax.scan`` loops: the
float64 reference every cell's answers are compared with, and, computed
with every product in three bfloat16 passes, the control that must fail
that comparison.

The recursion is that of ``core/oracle.rts_map_host`` (copied, not
imported; the benchmark must stay fixed when the program changes): the
backward-Euler dynamics residual of the discretised Onsager-Machlup
functional, written as ``x_{k+1} = G_k (x_k + dt_k c_k) + G_k w_k`` with
``G_k = (I - dt_k F_k)^{-1}`` and ``w_k ~ N(0, dt_k Q_k)``, and the
measurement term as ``y_k ~ N(H_k x_{k+1} + r_k, R_k / dt_k)``; the
smoothed mean of that linear-Gaussian model is the minimiser.

``reference_map`` runs in float64 on the host's CPU device inside a scoped
``jax.enable_x64(True)``, so the float32 program under test in the same
process is never affected.  ``control_map`` runs in float32 on the
default device (the chip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _dot_exact(subscripts, a, b):
    return jnp.einsum(subscripts, a, b, precision=jax.lax.Precision.HIGHEST)


def _split(a):
    """``a`` as the sum of two bfloat16 values, held in float32."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def _dot_bf16x3(subscripts, a, b):
    """A float32 product in three bfloat16 passes (``Precision.HIGH``):
    ``a_hi b_hi + a_hi b_lo + a_lo b_hi``, the ``a_lo b_lo`` term dropped.
    The parts are multiplied as float32 at ``HIGHEST``, where a product of
    two bfloat16 values is exact, so every backend computes the same three
    passes: the chip's own bfloat16 products of these small batched
    matrices are coarser (PERF.md)."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    return _dot_exact(subscripts, ah, bh) + (_dot_exact(subscripts, ah, bl)
                                              + _dot_exact(subscripts, al, bh))


def _rts_one(dot, F, c, H, r, Q, R, y, dt, m0, P0):
    """One record: per-interval ``F (N,nx,nx)``, ``c (N,nx)``, ``H
    (N,ny,nx)``, ``r (N,ny)``, ``Q (N,nx,nx)``, ``R (N,ny,ny)``, ``y
    (N,ny)``, ``dt (N,)``; returns ``(N+1, nx)``."""
    nx = m0.shape[-1]
    eye = jnp.eye(nx, dtype=m0.dtype)
    T = lambda a: jnp.swapaxes(a, -1, -2)
    G = jnp.linalg.inv(eye - dt[:, None, None] * F)
    u = dot("kij,kj->ki", G, dt[:, None] * c)
    Qd = dot("kij,kjl->kil", dot("kij,kjl->kil", G, dt[:, None, None] * Q),
             T(G))
    Rd = R / dt[:, None, None]

    def forward(carry, k):
        m, P = carry
        Gk = G[k]
        m = dot("ij,j->i", Gk, m) + u[k]
        P = dot("ij,jl->il", dot("ij,jl->il", Gk, P), Gk.T) + Qd[k]
        mp, Pp = m, P
        Hk = H[k]
        PHt = dot("ij,jl->il", P, Hk.T)
        S = dot("ij,jl->il", Hk, PHt) + Rd[k]
        K = jnp.linalg.solve(S, PHt.T).T
        innov = y[k] - dot("ij,j->i", Hk, m) - r[k]
        m = m + dot("ij,j->i", K, innov)
        IKH = eye - dot("ij,jl->il", K, Hk)
        # Joseph form: stays symmetric positive definite in long runs.
        P = (dot("ij,jl->il", dot("ij,jl->il", IKH, P), IKH.T)
             + dot("ij,jl->il", dot("ij,jl->il", K, Rd[k]), K.T))
        return (m, P), (m, P, mp, Pp)

    N = y.shape[0]
    _, (ms, Ps, mp, Pp) = jax.lax.scan(forward, (m0, P0), jnp.arange(N))
    ms = jnp.concatenate([m0[None], ms])
    Ps = jnp.concatenate([P0[None], Ps])

    def backward(xn, k):
        # Smoother gain P_k G_k^T (P^-_{k+1})^{-1}, from a symmetric solve.
        C = jnp.linalg.solve(Pp[k], dot("ij,jl->il", G[k], Ps[k])).T
        x = ms[k] + dot("ij,j->i", C, xn - mp[k])
        return x, x

    _, xs = jax.lax.scan(backward, ms[N], jnp.arange(N - 1, -1, -1))
    return jnp.concatenate([xs[::-1], ms[N][None]])


def _batched(dot):
    return jax.jit(jax.vmap(functools.partial(_rts_one, dot)))


_REFERENCE = _batched(_dot_exact)
_CONTROL = _batched(_dot_bf16x3)


def _per_interval(a, B, N, tail):
    return np.broadcast_to(np.asarray(a), (B, N) + tail)


def _arrays(F, c, H, r, Q, R, y, dt, m0, P0, dtype):
    y = np.asarray(y)
    B, N, ny = y.shape
    nx = np.shape(m0)[-1]
    out = (_per_interval(F, B, N, (nx, nx)), _per_interval(c, B, N, (nx,)),
           _per_interval(H, B, N, (ny, nx)), _per_interval(r, B, N, (ny,)),
           _per_interval(Q, B, N, (nx, nx)), _per_interval(R, B, N, (ny, ny)),
           y, _per_interval(dt, B, N, ()),
           np.broadcast_to(np.asarray(m0), (B, nx)),
           np.broadcast_to(np.asarray(P0), (B, nx, nx)))
    return tuple(np.asarray(a, dtype) for a in out)


def reference_map(F, c, H, r, Q, R, y, dt, m0, P0) -> np.ndarray:
    """Float64 MAP trajectories ``(B, N+1, nx)`` of ``B`` records, on the
    host's CPU device.  Every argument broadcasts to its per-interval,
    per-record shape (``y`` is ``(B, N, ny)``)."""
    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True):
        args = [jax.device_put(a, cpu) for a in
                _arrays(F, c, H, r, Q, R, y, dt, m0, P0, np.float64)]
        return np.asarray(_REFERENCE(*args))


def control_map(F, c, H, r, Q, R, y, dt, m0, P0) -> np.ndarray:
    """The reference in the precision below the configuration's: float32
    with every product in three bfloat16 passes, on the default device."""
    args = _arrays(F, c, H, r, Q, R, y, dt, m0, P0, np.float32)
    with jax.default_matmul_precision("high"):
        return np.asarray(_CONTROL(*args), np.float64)
