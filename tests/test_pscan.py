"""Scan machinery tests, incl. the multi-device distributed scan.

The distributed test spawns a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (device count is
locked at first jax init, so it cannot run in-process).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import affine_combine, prefix_scan, suffix_scan
from repro.core.types import AffineElement


def test_prefix_equals_suffix_on_reversed():
    rng = np.random.default_rng(3)
    T, n = 13, 3
    e = AffineElement(jnp.asarray(rng.standard_normal((T, n, n))),
                      jnp.asarray(rng.standard_normal((T, n))))
    suf = suffix_scan(affine_combine, e)
    # suffix of e == flip(prefix of flipped-with-swapped-op)
    flip = lambda x: jnp.flip(x, 0)
    pre = prefix_scan(lambda a, b: affine_combine(b, a),
                      AffineElement(flip(e.Phi), flip(e.beta)))
    np.testing.assert_allclose(suf.Phi, flip(pre.Phi), rtol=1e-9, atol=1e-9)


def test_scan_under_jit_and_grad():
    rng = np.random.default_rng(4)
    T, n = 8, 2
    Phi = jnp.asarray(rng.standard_normal((T, n, n)))
    beta = jnp.asarray(rng.standard_normal((T, n)))

    @jax.jit
    def loss(Phi, beta):
        out = prefix_scan(affine_combine, AffineElement(Phi, beta))
        return jnp.sum(out.beta ** 2)

    g = jax.grad(loss)(Phi, beta)
    assert g.shape == Phi.shape
    assert bool(jnp.isfinite(g).all())


_DISTRIBUTED_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    assert len(jax.devices()) == 8, jax.devices()
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core import (affine_combine, lqt_combine, prefix_scan,
                            suffix_scan, distributed_scan)
    from repro.core.types import AffineElement, LQTElement

    # Auto axes, as the meshes MeshSpec builds: sharded_scan slices its
    # output, which an Explicit-axis mesh (make_mesh's default) refuses.
    mesh = jax.make_mesh((8,), ("t",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(0)
    T, n = 64, 3

    # --- affine elements, prefix + suffix ---
    elems = AffineElement(jnp.asarray(rng.standard_normal((T, n, n))),
                          jnp.asarray(rng.standard_normal((T, n))))
    spec = AffineElement(P("t"), P("t"))
    for reverse in (False, True):
        f = jax.jit(shard_map(
            partial(distributed_scan, affine_combine, axis_name="t",
                    reverse=reverse),
            mesh=mesh, in_specs=(spec,), out_specs=spec))
        got = f(elems)
        want = (suffix_scan if reverse else prefix_scan)(
            affine_combine, elems)
        np.testing.assert_allclose(got.Phi, want.Phi, rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose(got.beta, want.beta, rtol=1e-9,
                                   atol=1e-9)

    # --- LQT elements (the paper's operator) ---
    def rand_psd(k):
        A = rng.standard_normal((k, n, n))
        return jnp.asarray(np.einsum("kij,klj->kil", A, A) / n
                           + 0.1 * np.eye(n))

    le = LQTElement(
        A=jnp.asarray(rng.standard_normal((T, n, n)) * 0.6),
        b=jnp.asarray(rng.standard_normal((T, n))),
        C=rand_psd(T), eta=jnp.asarray(rng.standard_normal((T, n))),
        J=rand_psd(T))
    lspec = LQTElement(*(P("t"),) * 5)
    f = jax.jit(shard_map(
        partial(distributed_scan, lqt_combine, axis_name="t", reverse=True),
        mesh=mesh, in_specs=(lspec,), out_specs=lspec))
    got = f(le)
    want = suffix_scan(lqt_combine, le)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-7, atol=1e-8)

    # --- sharded_scan: top-level entry, incl. non-divisible lengths ---
    from repro.core import sharded_scan
    for T2 in (64, 65, 67, 17, 8, 5):   # 5 < 2P: single-device degrade
        e2 = AffineElement(
            jnp.asarray(rng.standard_normal((T2, n, n)) * 0.5),
            jnp.asarray(rng.standard_normal((T2, n))))
        for reverse in (False, True):
            got = jax.jit(lambda e, r=reverse: sharded_scan(
                affine_combine, e, mesh=mesh, axis_name="t",
                reverse=r))(e2)
            want = (suffix_scan if reverse else prefix_scan)(
                affine_combine, e2)
            np.testing.assert_allclose(got.Phi, want.Phi,
                                       rtol=1e-9, atol=1e-9)
            np.testing.assert_allclose(got.beta, want.beta,
                                       rtol=1e-9, atol=1e-9)

    # carry_dtype: f32 elements with an f64 redundant carry scan stays
    # close to the full-f64 reference (and keeps the element dtype).
    e32 = AffineElement(
        jnp.asarray(rng.standard_normal((64, n, n)) * 0.5, jnp.float32),
        jnp.asarray(rng.standard_normal((64, n)), jnp.float32))
    got = jax.jit(lambda e: sharded_scan(
        affine_combine, e, mesh=mesh, axis_name="t",
        carry_dtype=jnp.float64))(e32)
    assert got.Phi.dtype == jnp.float32
    want = prefix_scan(affine_combine, e32)
    np.testing.assert_allclose(got.Phi, want.Phi, rtol=1e-4, atol=1e-4)
    print("DISTRIBUTED-SCAN-OK")
""")


@pytest.mark.slow
@pytest.mark.distributed
def test_distributed_scan_8_devices():
    """Real 8-device run: the subprocess pins the CPU platform, so the
    forced host-device count always materialises (no skip path)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _DISTRIBUTED_SNIPPET],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    assert "DISTRIBUTED-SCAN-OK" in out.stdout
