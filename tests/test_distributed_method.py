"""The ``method="distributed"`` surface: options validation, MeshSpec,
single-device fallback (in-process -- tests see ONE device, see
conftest.py), and 8-forced-host-device agreement/cache-fingerprint suites
(subprocess-isolated, ``distributed`` marker)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.wiener_velocity import WienerVelocityConfig
from repro.core import (
    DistributedOptions,
    Estimator,
    ExecutableCache,
    ParallelOptions,
    Problem,
    method_names,
    simulate_linear,
    time_grid,
)
from repro.distributed import MeshSpec, as_mesh, mesh_fingerprint


@pytest.fixture(scope="module")
def lin_problem():
    cfg = WienerVelocityConfig(p0=1.0)
    model = cfg.model()
    ts = time_grid(cfg.t0, cfg.tf, 200)
    _, y = simulate_linear(model, ts, jax.random.PRNGKey(0))
    return model, ts, y


# ---------------------------------------------------------------------------
# construction-time validation (single device, in-process)
# ---------------------------------------------------------------------------


def test_method_registered():
    assert "distributed" in method_names()


def test_options_defaults_and_validation():
    o = DistributedOptions()
    assert (o.time_axis, o.batch_axes) == ("time", ("data",))
    assert o.devices_per_time is None
    assert o.resolve_carry_dtype() is None
    assert DistributedOptions(
        carry_dtype="float64").resolve_carry_dtype() == jnp.float64
    # batch_axes list form is normalised to a tuple (hashable: options
    # are part of the executable-cache key)
    assert DistributedOptions(batch_axes=["b"]).batch_axes == ("b",)
    hash(DistributedOptions(batch_axes=["b"]))

    with pytest.raises(ValueError, match="time_axis"):
        DistributedOptions(time_axis="")
    with pytest.raises(ValueError, match="batch_axes"):
        DistributedOptions(batch_axes=("ok", ""))
    with pytest.raises(ValueError, match="cannot also be a batch axis"):
        DistributedOptions(time_axis="t", batch_axes=("t",))
    with pytest.raises(ValueError, match="devices_per_time"):
        DistributedOptions(devices_per_time=0)
    with pytest.raises(ValueError, match="carry_dtype"):
        DistributedOptions(carry_dtype="bf16")
    with pytest.raises(ValueError, match="fallback"):
        DistributedOptions(fallback="maybe")
    with pytest.raises(ValueError, match="nsub"):
        DistributedOptions(nsub=0)          # inherited ParallelOptions check
    with pytest.raises(TypeError):
        DistributedOptions(shard_count=4)   # unknown names fail at init


def test_meshspec_validation():
    spec = MeshSpec(time=2, batch=3)
    assert spec.num_devices == 6
    with pytest.raises(ValueError, match="positive int"):
        MeshSpec(time=0)
    with pytest.raises(ValueError, match="positive int"):
        MeshSpec(batch=-1)
    with pytest.raises(ValueError, match="non-empty str"):
        MeshSpec(time_axis="")
    with pytest.raises(ValueError, match="must differ"):
        MeshSpec(time_axis="x", batch_axis="x")
    # more devices than this process has -> loud error at build
    with pytest.raises(ValueError, match="devices"):
        MeshSpec(time=max(2 * len(jax.devices()), 4096)).build()


def test_as_mesh_normalisation():
    assert as_mesh(None) is None
    mesh = MeshSpec().build()
    assert as_mesh(mesh) is mesh
    built = as_mesh(MeshSpec())
    assert tuple(built.axis_names) == ("time", "data")
    with pytest.raises(TypeError, match="MeshSpec"):
        as_mesh("time:8")


def test_mesh_fingerprint():
    assert mesh_fingerprint(None) is None
    fp = mesh_fingerprint(MeshSpec().build())
    assert fp[0] == ("time", "data") and fp[1] == (1, 1)
    assert mesh_fingerprint(MeshSpec().build()) == fp          # value-based
    assert mesh_fingerprint(
        MeshSpec(time_axis="T").build()) != fp
    hash(fp)


# ---------------------------------------------------------------------------
# single-device fallback (in-process: exactly one device)
# ---------------------------------------------------------------------------


def test_fallback_auto_matches_parallel(lin_problem):
    model, ts, y = lin_problem
    p = Problem.single(model, ts, y)
    sd = Estimator(model, method="distributed",
                   options=DistributedOptions(mode="discrete"),
                   cache=ExecutableCache()).solve(p)
    sp = Estimator(model, method="parallel_rts",
                   options=ParallelOptions(mode="discrete"),
                   cache=ExecutableCache()).solve(p)
    # the fallback IS the parallel solver: bit-exact, not just close
    np.testing.assert_array_equal(np.asarray(sd.x), np.asarray(sp.x))
    np.testing.assert_array_equal(np.asarray(sd.S), np.asarray(sp.S))


def test_fallback_error_raises(lin_problem):
    model, ts, y = lin_problem
    est = Estimator(model, method="distributed",
                    options=DistributedOptions(fallback="error"),
                    cache=ExecutableCache())
    with pytest.raises(RuntimeError, match="needs >= 2 devices"):
        est.solve(Problem.single(model, ts, y))


def test_devices_per_time_exceeding_available_raises(lin_problem):
    model, ts, y = lin_problem
    est = Estimator(
        model, method="distributed",
        options=DistributedOptions(
            devices_per_time=2 * len(jax.devices())),
        cache=ExecutableCache())
    with pytest.raises(ValueError, match="exceeds"):
        est.solve(Problem.single(model, ts, y))


# ---------------------------------------------------------------------------
# 8 forced host devices (subprocess-isolated)
# ---------------------------------------------------------------------------

_COMMON = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
assert len(jax.devices()) == 8, jax.devices()
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from repro.configs.wiener_velocity import WienerVelocityConfig
from repro.core import (DistributedOptions, Estimator, ParallelOptions,
                        Problem, SequentialOptions, cache_stats,
                        clear_cache, simulate_linear, time_grid)
from repro.distributed import MeshSpec

cfg = WienerVelocityConfig(p0=1.0)
model = cfg.model()
opts = DistributedOptions(mode="discrete")
ts = time_grid(cfg.t0, cfg.tf, 520)   # 52 blocks + terminal: 53 elems,
_, y = simulate_linear(model, ts, jax.random.PRNGKey(0))  # 53 % 8 != 0
dist = Estimator(model, method="distributed", options=opts)
par = Estimator(model, method="parallel_rts",
                options=ParallelOptions(mode="discrete"))

def close(a, b, tol=1e-9):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=tol, atol=tol)
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
@pytest.mark.distributed
def test_agreement_all_layouts_8_devices():
    out = _run("""
    # --- single, non-divisible T vs shard count, vs parallel + sequential
    p = Problem.single(model, ts, y)
    sd = dist.solve(p)
    sp = par.solve(p)
    close(sd.x, sp.x); close(sd.S, sp.S); close(sd.v, sp.v)
    seq = Estimator(model, method="sequential_rts",
                    options=SequentialOptions(mode="discrete"))
    ss = seq.solve(p)
    close(sd.x, ss.x, 1e-7)

    # --- masked measurements (dropout pattern)
    mask = (np.arange(520) % 3 != 0).astype(float)
    pm = Problem.single(model, ts, y, measurement_mask=mask)
    close(dist.solve(pm).x, par.solve(pm).x)

    # --- stacked (+ per-record masks), time-only default mesh
    ys = jnp.stack([y, y * 1.1, y * 0.9, y + 0.1])
    masks = jnp.asarray(np.stack([mask, 1 - mask, mask, np.ones(520)]))
    ps = Problem.stacked(model, ts, ys, measurement_mask=masks)
    close(dist.solve(ps).x, par.solve(ps).x)

    # --- ragged buckets (unequal lengths -> pad-and-bucket)
    recs = []
    for N in (130, 250, 520):
        tsr = time_grid(cfg.t0, cfg.tf, N)
        _, yr = simulate_linear(model, tsr, jax.random.PRNGKey(N))
        recs.append((np.asarray(tsr), np.asarray(yr)))
    pr = Problem.ragged(model, recs)
    for a, b in zip(dist.solve(pr), par.solve(pr)):
        close(a.x, b.x)
        assert a.padding is not None

    # --- obs: distributed.shards / carry_bytes counters + scan scope
    import repro.obs as obs
    obs.enable(); obs.reset()
    ts2 = time_grid(cfg.t0, cfg.tf, 480)     # new length -> fresh trace
    _, y2 = simulate_linear(model, ts2, jax.random.PRNGKey(7))
    p2 = Problem.single(model, ts2, y2)
    dist.solve(p2)
    snap = obs.snapshot()
    # two sharded scans per solve (backward LQT + forward affine)
    assert snap["counters"]["distributed.shards"] == 16, snap["counters"]
    assert snap["counters"]["distributed.carry_bytes"] > 0
    # the sharded scan's ops carry the smoother.scan scope
    text = dist.lower(p2).as_text(debug_info=True)
    assert "smoother.scan" in text
    print("LAYOUTS-OK")
    """)
    assert "LAYOUTS-OK" in out


@pytest.mark.slow
@pytest.mark.distributed
def test_mesh_surface_and_cache_fingerprint_8_devices():
    out = _run("""
    from repro.serving import TrajectoryEngine

    p = Problem.single(model, ts, y)
    ref = par.solve(p)
    ys = jnp.stack([y, y * 1.1, y * 0.9, y + 0.1])
    ps = Problem.stacked(model, ts, ys)
    ref_s = par.solve(ps)

    # --- explicit 2-D (time x batch) MeshSpec
    est2 = Estimator(model, method="distributed", options=opts,
                     mesh=MeshSpec(time=4, batch=2))
    close(est2.solve(ps).x, ref_s.x)
    # batch not divisible by the mesh batch axis -> loud error
    try:
        est2.solve(Problem.stacked(model, ts, jnp.stack([y, y, y])))
        raise AssertionError("expected ValueError")
    except ValueError as e:
        assert "not divisible" in str(e)

    # --- AOT lower() under the mesh
    aot = est2.lower(ps).compile()
    close(aot(ts, ys).x, ref_s.x)

    # --- ambient mesh via MeshSpec.activate(); the executable-cache key
    # fingerprints the RESOLVED mesh, so the same Estimator never replays
    # an executable compiled under a different ambient mesh.
    clear_cache()
    est = Estimator(model, method="distributed", options=opts)
    with MeshSpec(time=8).activate():
        close(est.solve(p).x, ref.x)
    with MeshSpec(time=4).activate():
        close(est.solve(p).x, ref.x)
    st = cache_stats()
    assert st["misses"] == 2 and st["hits"] == 0, st
    # replaying under a previously seen mesh IS a hit
    with MeshSpec(time=8).activate():
        close(est.solve(p).x, ref.x)
    assert cache_stats()["hits"] == 1, cache_stats()

    # --- devices_per_time mismatch with the ambient mesh is an error
    bad = Estimator(model, method="distributed",
                    options=DistributedOptions(mode="discrete",
                                               devices_per_time=2))
    with MeshSpec(time=8).activate():
        try:
            bad.solve(p)
            raise AssertionError("expected ValueError")
        except ValueError as e:
            assert "devices_per_time" in str(e)

    # --- TrajectoryEngine on the unified mesh entry point
    eng = TrajectoryEngine(model, batch=2, method="distributed",
                           options=opts, mesh=MeshSpec(time=4, batch=2))
    recs = [(np.asarray(ts), np.asarray(y)),
            (np.asarray(ts), np.asarray(y) * 1.1)]
    sols = eng.estimate(recs)
    close(sols[0].x, ref.x)
    print("MESH-SURFACE-OK")
    """)
    assert "MESH-SURFACE-OK" in out
