"""Multi-device feature tests (8 forced host devices, subprocess-isolated):
pipeline parallelism, compressed gradient all-reduce, and the sharded
train step (TP+ZeRO-1 NamedShardings) vs the single-device step."""
import os
import subprocess
import sys
import textwrap

import pytest

_COMMON = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
"""


def _run(snippet: str, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _COMMON + textwrap.dedent(snippet)],
        capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    return out.stdout


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    out = _run("""
    from repro.distributed.pipeline import pipeline_forward
    mesh = jax.make_mesh((8,), ("pipe",))
    rng = np.random.default_rng(0)
    S, D, M = 8, 16, 4          # stages, width, microbatches
    Ws = jnp.asarray(rng.standard_normal((S, D, D)) / np.sqrt(D))
    xs = jnp.asarray(rng.standard_normal((M, 3, D)))

    def stage(w, x):
        return jnp.tanh(x @ w)

    got = pipeline_forward(stage, Ws, xs, mesh, axis_name="pipe")

    ref = xs
    for i in range(S):
        ref = jnp.tanh(ref @ Ws[i])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-9, atol=1e-9)
    print("PIPELINE-OK")
    """)
    assert "PIPELINE-OK" in out


@pytest.mark.slow
def test_grad_compression_error_feedback():
    out = _run("""
    from repro.distributed.grad_compress import (
        compressed_psum, init_error_state, make_compressed_dp_step)
    from functools import partial
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)

    # 1) single compressed psum ~ exact psum within bf16 quantisation
    g = {"w": jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)}
    err = init_error_state({"w": g["w"][0]})
    f = shard_map(partial(compressed_psum, axis_name="data"),
                  mesh=mesh, in_specs=({"w": P("data")}, {"w": P()}),
                  out_specs=({"w": P()}, {"w": P()}), check_vma=False)
    mean, new_err = f(g, err)
    exact = g["w"].mean(axis=0)
    q_err = np.abs(np.asarray(mean["w"][0]) - np.asarray(exact)).max()
    assert q_err < 0.05, q_err

    # 2) error feedback: repeated compression of a CONSTANT gradient
    # converges (error is re-injected, not lost)
    tot = jnp.zeros((64,))
    err = init_error_state({"w": g["w"][0]})
    steps = 40
    for _ in range(steps):
        mean, err = f(g, err)
        tot = tot + mean["w"][0]
    drift = np.abs(np.asarray(tot / steps) - np.asarray(exact)).max()
    assert drift < 2e-3, drift
    print("GRADCOMP-OK", q_err, drift)
    """)
    assert "GRADCOMP-OK" in out


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    out = _run("""
    import dataclasses
    from repro.config import get_config, TrainConfig
    from repro.distributed.sharding import mesh_context, choose_pspec
    from repro.models import transformer
    from repro.train.optimizer import adamw_init
    from repro.train.trainer import make_shardings, make_train_step
    from jax.sharding import NamedSharding

    cfg = dataclasses.replace(get_config("smollm-135m-smoke"),
                              dtype="float32")
    tcfg = TrainConfig(total_steps=4, warmup_steps=1)
    params = transformer.init(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    B, S = 8, 32
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                     cfg.vocab_size),
    }
    step = make_train_step(cfg, tcfg)
    p_ref, o_ref, m_ref = jax.jit(step)(params, opt, batch)

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with mesh_context(mesh):
        p_sh, o_sh = make_shardings(cfg, tcfg, mesh)
        b_sh = jax.tree_util.tree_map(
            lambda x: NamedSharding(mesh, choose_pspec(
                x.shape, ("batch",) + (None,) * (x.ndim - 1), mesh)),
            batch)
        sharded = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                          out_shardings=(p_sh, o_sh, None))
        params_d = jax.device_put(params, p_sh)
        opt_d = jax.device_put(opt, o_sh)
        batch_d = jax.device_put(batch, b_sh)
        p_got, o_got, m_got = sharded(params_d, opt_d, batch_d)

    np.testing.assert_allclose(float(m_got["loss"]), float(m_ref["loss"]),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(p_got),
                    jax.tree_util.tree_leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)
    print("SHARDED-TRAIN-OK")
    """)
    assert "SHARDED-TRAIN-OK" in out


@pytest.mark.slow
def test_distributed_temporal_map_solver():
    """The paper's solver with its time axis sharded across 8 devices:
    the distributed backward scan == the single-device scan."""
    out = _run("""
    from functools import partial
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.core import (
        lqt_combine, suffix_scan, distributed_scan, grid_lqt_from_linear,
        simulate_linear, time_grid)
    from repro.core.elements import discrete_block_elements, terminal_element
    from repro.core.types import LQTElement
    import sys
    sys.path.insert(0, "tests")

    import jax.numpy as jnp
    F = jnp.block([[jnp.zeros((2, 2)), jnp.eye(2)], [jnp.zeros((2, 4))]])
    H = jnp.concatenate([jnp.eye(2), jnp.zeros((2, 2))], axis=1)
    L = jnp.concatenate([jnp.zeros((2, 2)), jnp.eye(2)], axis=0)
    from repro.core import LinearSDE
    model = LinearSDE(F=F, c=jnp.zeros(4), H=H, r=jnp.zeros(2),
                      Q=L @ (4.0 * jnp.eye(2)) @ L.T,
                      R=1e-2 * jnp.eye(2),
                      m0=jnp.array([5.0, 5.0, 0.0, 0.0]), P0=jnp.eye(4))
    T, n = 64, 5
    ts = time_grid(0.0, 5.0, T * n)
    _, y = simulate_linear(model, ts, jax.random.PRNGKey(0))
    grid = grid_lqt_from_linear(model, ts, y)
    blocks, _ = discrete_block_elements(grid, n)
    elems = jax.tree_util.tree_map(
        lambda a, t: jnp.concatenate([a, t[None]], axis=0),
        blocks, terminal_element(grid))
    # pad to multiple of 8 with identity elements on the right...
    # simpler: shard 65 -> use 64 blocks + fold terminal into last block
    last = jax.tree_util.tree_map(lambda a: a[-2], elems)
    term = jax.tree_util.tree_map(lambda a: a[-1], elems)
    folded = lqt_combine(last, term)
    elems64 = jax.tree_util.tree_map(
        lambda a, f: jnp.concatenate([a[:-2], f[None]], axis=0),
        elems, folded)

    want = suffix_scan(lqt_combine, elems64)
    mesh = jax.make_mesh((8,), ("t",))
    spec = LQTElement(*(P("t"),) * 5)
    f = jax.jit(shard_map(partial(distributed_scan, lqt_combine,
                                  axis_name="t", reverse=True),
                          mesh=mesh, in_specs=(spec,), out_specs=spec))
    got = f(elems64)
    import numpy as np
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-7, atol=1e-8)
    print("DIST-MAP-OK")
    """)
    assert "DIST-MAP-OK" in out
