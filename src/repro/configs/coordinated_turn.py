"""Paper section 5.2: the coordinated-turn model (eqs. 55-58) -- the
nonlinear experiment behind Fig. 2 (5 IEKS iterations).

``Q = L W L^T`` is singular in the two position rows, so the model adds a
small diagonal ``q_jitter`` to keep the Onsager-Machlup cost (which
inverts ``Q``) finite.  Its default depends on the dtype: ``1e-10`` in
float64, and float32's machine epsilon (about ``1.2e-7``) in float32.  At
``1e-10`` the cost of a float32 trajectory is dominated by the rounding
of its positions: a position residual of one float32 ulp over a step of
``dt = 5e-3``, weighted by ``1/1e-10``, costs about as much as a
measurement term, and the cost of a float32 trajectory evaluated in float64
came out 37 % above the float64 optimum at 1 000 points.  The float32
jitter is a regulariser of the same kind: it lets positions diffuse by
about ``sqrt(1.2e-7 * 5) = 8e-4`` over the 5 s horizon, where the velocity
noise alone moves them by about 3e-3.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from repro.core import NonlinearSDE


@dataclasses.dataclass(frozen=True)
class CoordinatedTurnConfig:
    t0: float = 0.0
    tf: float = 5.0
    sigma_v: float = 5e-4
    sigma_w: float = 0.02
    iterations: int = 5       # paper: 5 linearisation iterations
    nsub: int = 10
    q_jitter: Optional[float] = None   # None: by dtype (module docstring)

    def model(self) -> NonlinearSDE:
        L = (jnp.zeros((5, 3))
             .at[2, 0].set(self.sigma_v)
             .at[3, 1].set(self.sigma_v)
             .at[4, 2].set(self.sigma_w))
        jitter = self.q_jitter
        if jitter is None:
            jitter = max(1e-10, float(jnp.finfo(jnp.result_type(float)).eps))
        Q = L @ jnp.eye(3) @ L.T + jitter * jnp.eye(5)

        def f(x, t):
            return jnp.array([x[2], x[3], -x[4] * x[3], x[4] * x[2], 0.0])

        def h(x, t):
            return jnp.array([jnp.sqrt(x[0] ** 2 + x[1] ** 2),
                              jnp.arctan2(x[1], x[0])])

        return NonlinearSDE(
            f=f, h=h, Q=Q, R=jnp.diag(jnp.array([5e-3, 1e-3])),
            m0=jnp.array([5.0, 5.0, 0.0, 0.3, 0.0]),
            P0=jnp.diag(jnp.array([0.01, 0.01, 0.01, 0.01, 0.04])))


def config() -> CoordinatedTurnConfig:
    return CoordinatedTurnConfig()
