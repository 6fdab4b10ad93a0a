#!/usr/bin/env python3
"""Bring-up smoke run of the MAP estimator on one TPU chip.

Drives the estimator's public entry points once at the sizes users run,
in float32 on the chip, and checks every result against a float64
reference computed on the host in the same process:

* offline linear smoothing (paper section 5.1, Wiener velocity, 100 000
  points): ``Estimator.solve`` with ``sequential_rts``, ``parallel_rts``,
  ``parallel_two_filter`` and ``parallel_kernel`` (Mosaic-compiled, which
  the phase asserts from the compiled program);
* nonlinear coordinated turn (paper section 5.2, 10 000 points, five
  iterated-linearisation passes);
* serving: ``TrajectoryEngine(batch=32)`` on ragged records of 1 000 to
  10 000 points, and ``StreamingEngine(lag=64, batch=8)`` on tracks pushed
  over several rounds with late measurements.

Each phase prints one JSON line (shapes, dtype, compile and run seconds,
largest error against the reference, tolerance); the last line is the
device record ``{"ok": true, "device": {...}}``.  Any failed check exits
non-zero before that line is printed.

    python3 chip_smoke.py               # one TPU chip
    python3 chip_smoke.py --chips 4     # only the time-sharded phase, 4 chips
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse   # tiny sizes, CPU

Without ``--rehearse`` the script refuses to run where JAX finds no TPU.
The compilation cache follows ``repro.compile_cache.enable_compile_cache``
(``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache`` in the checkout).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# float32 rounding unit: every tolerance below is a multiple of it.
EPS32 = 2.0 ** -24
# Float32-against-float64 trajectory tolerance of the RTS-form smoothers
# (the justification is in offline_linear, where it was measured).
RTS_TOL = 2e4 * EPS32


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


try:
    import jax
    import numpy as np

    from repro.compile_cache import enable_compile_cache
    from repro.configs.coordinated_turn import CoordinatedTurnConfig
    from repro.configs.wiener_velocity import WienerVelocityConfig
    from repro.core import (
        Estimator, IteratedOptions, KernelOptions, ParallelOptions, Problem,
        SequentialOptions, TwoFilterOptions,
    )
    from repro.core.oracle import rts_map_host
    from repro.core.padding import bucket_length
    from repro.serving import StreamingEngine, TrajectoryEngine
except ImportError as e:          # run outside a checkout of this repo
    _fail(f"cannot import the estimator ({e}); run from the repository root")


class Clock:
    """Wall time since start and the compile seconds the phases report."""

    def __init__(self):
        self.start = time.perf_counter()
        self.compile_s = 0.0

    def solve(self, est, problem):
        """``est.solve(problem)`` twice: returns the solution, the compile
        seconds (first call less the second) and the run seconds."""
        _, first = timed(lambda: est.solve(problem))
        sol, run = timed(lambda: est.solve(problem))
        self.compile_s += first - run
        return sol, first - run, run


def timed(fn):
    """(result, seconds) of ``fn()``, ended by ``block_until_ready``."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def rel_err(x, ref) -> float:
    """Largest absolute error over the largest reference magnitude."""
    x = np.asarray(x, np.float64)
    return float(np.max(np.abs(x - ref)) / np.max(np.abs(ref)))


def check(phase: str, name: str, err: float, tol: float) -> None:
    if not np.isfinite(err) or err > tol:
        _fail(f"{phase}: {name} error {err:.3e} exceeds tolerance {tol:.1e}")


# ---------------------------------------------------------------------------
# Data, from --seed, on the host.  Timestamps are float32 values, so the
# float32 chip and the float64 reference solve the same grid: the chip's
# jnp.diff of two close float32 values is exact (Sterbenz).
# ---------------------------------------------------------------------------


def f32_grid(N: int, dt: float) -> np.ndarray:
    return (np.arange(N + 1) * dt).astype(np.float32).astype(np.float64)


def simulate(rng, f, L, H_of, R, m0, P0, ts):
    """Euler-Maruyama path of ``dx = f(x) dt + L dW`` and measurements
    ``y_k = h(x_{k+1}) + N(0, R / dt_k)``, rounded to float32 values."""
    N = ts.shape[0] - 1
    dt = np.diff(ts)
    x = np.empty((N + 1, m0.shape[0]))
    x[0] = m0 + np.linalg.cholesky(P0) @ rng.standard_normal(m0.shape[0])
    w = rng.standard_normal((N, L.shape[1]))
    for k in range(N):
        x[k + 1] = x[k] + dt[k] * f(x[k]) + np.sqrt(dt[k]) * (L @ w[k])
    noise = rng.standard_normal((N, R.shape[0])) @ np.linalg.cholesky(R).T
    y = H_of(x[1:]) + noise / np.sqrt(dt)[:, None]
    return x, y.astype(np.float32).astype(np.float64)


def wiener(cfg, rng, ts):
    m = cfg.model()
    F, H = np.asarray(m.F, np.float64), np.asarray(m.H, np.float64)
    L = np.sqrt(cfg.q) * np.concatenate([np.zeros((2, 2)), np.eye(2)])
    _, y = simulate(rng, lambda x: F @ x, L, lambda xs: xs @ H.T,
                    cfg.r * np.eye(2), np.asarray(m.m0, np.float64),
                    np.asarray(m.P0, np.float64), ts)
    return y


def linear_ref(model, ts, y, mask=None):
    """Float64 host MAP of a linear model (``core.oracle.rts_map_host``)."""
    a = lambda v: np.asarray(v, np.float64)
    return rts_map_host(a(model.F), a(model.c), a(model.H), a(model.r),
                        a(model.Q), a(model.R), y, np.diff(ts, axis=-1),
                        a(model.m0), a(model.P0), mask=mask)


def linear_cost(model, ts, y, x):
    """Float64 Onsager-Machlup cost (the quadrature of ``om_cost_grid``)."""
    a = lambda v: np.asarray(v, np.float64)
    F, c, H, r = a(model.F), a(model.c), a(model.H), a(model.r)
    dt = np.diff(ts)
    d0 = x[0] - a(model.m0)
    resid = np.diff(x, axis=0) / dt[:, None] - (x[1:] @ F.T + c)
    innov = y - (x[1:] @ H.T + r)
    Qp, Ri = np.linalg.pinv(a(model.Q)), np.linalg.inv(a(model.R))
    return float(0.5 * d0 @ np.linalg.solve(a(model.P0), d0)
                 + 0.5 * np.sum(dt * np.einsum("ki,ij,kj->k", resid, Qp, resid))
                 + 0.5 * np.sum(dt * np.einsum("ki,ij,kj->k", innov, Ri, innov)))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def offline_linear(clock, args, interpret):
    """Paper section 5.1 through ``Estimator.solve``, four methods."""
    cfg = WienerVelocityConfig()
    model = cfg.model()
    N = args.n_linear
    ts = f32_grid(N, cfg.tf / N)
    y = wiener(cfg, np.random.default_rng(args.seed), ts)
    t0 = time.perf_counter()
    ref = linear_ref(model, ts, y)
    ref_cost = linear_cost(model, ts, y, ref)
    report("linear/reference", points=N + 1, dtype="float64",
           host_seconds=time.perf_counter() - t0)
    problem = Problem.single(model, ts.astype(np.float32),
                             y.astype(np.float32))
    par = dict(nsub=10, mode="discrete")
    # Trajectory tolerances, relative to the largest state magnitude.
    # The RTS-form methods recover the trajectory by a forward affine
    # recursion from the backward information; in float32 at 1e5 points
    # that lands within about 1.5e-4 of float64 (1.1e-4 to 1.4e-4 on TPU
    # v5e and on the CPU), so RTS_TOL = 2e4 eps = 1.2e-3 leaves a factor
    # of 8.  The two-filter method instead solves (I + C S) phi = ... at
    # every point, with C the forward filter's covariance and S the
    # backward information.  On this problem (float64, seed 0) the largest
    # condition number of I + C S grows about as N^2: 3.5e4, 5.6e5, 8.9e6
    # and 5.2e7 at N = 2 560, 10 240, 40 960 and 1e5 (median 24), so eps
    # times it bounds nothing at 1e5.  Its float32 error on the CPU grows
    # with it, 1.9e-5, 1.7e-4, 3.7e-4 and 7.6e-4 at those N, against
    # 6.5e-6 to 1.4e-4 for parallel_rts; TPU v5e gave 1.9e-3 at 1e5.  So it
    # gets 1e5 eps = 6.0e-3, a factor of 3 over the chip.
    methods = [
        ("sequential_rts", SequentialOptions(mode="discrete"), RTS_TOL),
        ("parallel_rts", ParallelOptions(**par), RTS_TOL),
        ("parallel_two_filter", TwoFilterOptions(**par), 1e5 * EPS32),
        ("parallel_kernel", KernelOptions(**par, interpret=interpret),
         RTS_TOL),
    ]
    # Cost: a float32 sum of N positive terms whose velocity residuals
    # carry the eps / dt amplification of a difference quotient; 2e-7 to
    # 1.8e-4 observed on TPU v5e, so 1.2e-3 leaves a factor of 7.
    tol_cost = 2e4 * EPS32
    for method, opts, tol_x in methods:
        est = Estimator(model, method=method, options=opts)
        aot_s = 0.0
        if method == "parallel_kernel" and not interpret:
            # Compiled once here; est.solve then loads it from the cache.
            text, aot_s = timed(
                lambda: est.lower(problem).compile().as_text())
            clock.compile_s += aot_s
            if "tpu_custom_call" not in text:
                _fail("parallel_kernel did not compile to a Mosaic kernel")
        sol, compile_s, run_s = clock.solve(est, problem)
        compile_s += aot_s
        err = rel_err(sol.x, ref)
        cost_err = abs(float(sol.cost) - ref_cost) / abs(ref_cost)
        report(f"linear/{method}", points=N + 1, state=model.nx,
               dtype=str(sol.x.dtype), compile_seconds=compile_s,
               run_seconds=run_s, max_rel_error=err, tolerance=tol_x,
               cost_rel_error=cost_err, cost_tolerance=tol_cost)
        check(method, "trajectory", err, tol_x)
        check(method, "OM cost", cost_err, tol_cost)


def nonlinear(clock, args):
    """Paper section 5.2: five iterated-linearisation passes."""
    cfg = CoordinatedTurnConfig()
    model = cfg.model()
    N = args.n_nonlinear
    ts = f32_grid(N, cfg.tf / N)
    Q = np.asarray(model.Q, np.float64)
    R = np.asarray(model.R, np.float64)
    m0 = np.asarray(model.m0, np.float64)
    P0 = np.asarray(model.P0, np.float64)

    def f(x):
        return np.stack([x[..., 2], x[..., 3], -x[..., 4] * x[..., 3],
                         x[..., 4] * x[..., 2], np.zeros_like(x[..., 0])],
                        axis=-1)

    def h(x):
        return np.stack([np.hypot(x[..., 0], x[..., 1]),
                         np.arctan2(x[..., 1], x[..., 0])], axis=-1)

    rng = np.random.default_rng(args.seed + 1)
    L = np.linalg.cholesky(Q)
    _, y = simulate(rng, f, L, h, R, m0, P0, ts)

    def ref_solve():
        """Float64 iterated extended Kalman smoother, the same five
        Taylor passes about the left grid points as the library."""
        xbar = np.broadcast_to(m0, (N + 1, 5)).copy()
        for _ in range(cfg.iterations):
            xb = xbar[:-1]
            Fk = np.zeros((N, 5, 5))
            Fk[:, 0, 2] = Fk[:, 1, 3] = 1.0
            Fk[:, 2, 3], Fk[:, 2, 4] = -xb[:, 4], -xb[:, 3]
            Fk[:, 3, 2], Fk[:, 3, 4] = xb[:, 4], xb[:, 2]
            rr = np.hypot(xb[:, 0], xb[:, 1])
            Hk = np.zeros((N, 2, 5))
            Hk[:, 0, 0], Hk[:, 0, 1] = xb[:, 0] / rr, xb[:, 1] / rr
            Hk[:, 1, 0], Hk[:, 1, 1] = -xb[:, 1] / rr**2, xb[:, 0] / rr**2
            c = f(xb) - np.einsum("kij,kj->ki", Fk, xb)
            r = h(xb) - np.einsum("kij,kj->ki", Hk, xb)
            xbar = rts_map_host(Fk, c, Hk, r, Q, R, y, np.diff(ts), m0, P0)
        return xbar

    def cost(x):
        dt = np.diff(ts)
        d0 = x[0] - m0
        resid = np.diff(x, axis=0) / dt[:, None] - f(x[1:])
        innov = y - h(x[1:])
        return float(
            0.5 * d0 @ np.linalg.solve(P0, d0)
            + 0.5 * np.sum(dt * np.einsum("ki,ij,kj->k", resid,
                                          np.linalg.inv(Q), resid))
            + 0.5 * np.sum(dt * np.einsum("ki,ij,kj->k", innov,
                                          np.linalg.inv(R), innov)))

    t0 = time.perf_counter()
    ref = ref_solve()
    ref_cost = cost(ref)
    report("nonlinear/reference", points=N + 1, dtype="float64",
           host_seconds=time.perf_counter() - t0)

    est = Estimator(model, method="parallel_rts", options=IteratedOptions(
        iterations=cfg.iterations,
        inner=ParallelOptions(nsub=10, mode="discrete")))
    problem = Problem.single(model, ts.astype(np.float32),
                             y.astype(np.float32))
    sol, compile_s, run_s = clock.solve(est, problem)
    err = rel_err(sol.x, ref)
    # The OM cost of the float32 trajectory, evaluated in float64 on the
    # host, against the reference's: the two trajectories minimise the
    # same objective, so this measures how far from optimal the chip's
    # answer is, independent of float32 round-off in the cost sum.
    cost_err = abs(cost(np.asarray(sol.x, np.float64)) - ref_cost) / ref_cost
    # Each pass is an RTS-form solve (RTS_TOL); the cost of a float32
    # trajectory still carries the rounding of its positions weighted by
    # Q^-1 (see configs/coordinated_turn.py): 4.8e-4 in the CPU float32
    # rehearsal, bounded at 1e-2.
    tol_x, tol_cost = RTS_TOL, 1e-2
    report("nonlinear/coordinated_turn", points=N + 1, state=model.nx,
           iterations=cfg.iterations, dtype=str(sol.x.dtype),
           compile_seconds=compile_s, run_seconds=run_s,
           max_rel_error=err, tolerance=tol_x,
           cost_rel_error=cost_err, cost_tolerance=tol_cost,
           chip_cost=float(sol.cost), reference_cost=ref_cost)
    check("nonlinear", "trajectory", err, tol_x)
    check("nonlinear", "OM cost", cost_err, tol_cost)


def trajectory_engine(clock, args):
    """Ragged records through ``TrajectoryEngine`` waves."""
    cfg = WienerVelocityConfig()
    model = cfg.model()
    rng = np.random.default_rng(args.seed + 2)
    lo, hi = args.engine_lengths
    lengths = rng.integers(lo, hi + 1, size=args.engine_records)
    dt = 1e-3
    records = []
    for N in lengths:
        ts = f32_grid(int(N), dt)
        records.append((ts, wiener(cfg, rng, ts)))
    engine = TrajectoryEngine(model, batch=32, method="parallel_rts",
                              options=ParallelOptions(nsub=10,
                                                      mode="discrete"))
    # Warm-up: one record per bucket compiles every wave shape.
    est = engine.estimator
    t0 = time.perf_counter()
    by_bucket = {}
    for i, (ts, y) in enumerate(records):
        by_bucket.setdefault(bucket_length(y.shape[0], est.block_size),
                             []).append(i)
    for idx in by_bucket.values():
        engine.estimate([records[idx[0]]])
    warm_s = time.perf_counter() - t0
    clock.compile_s += warm_s
    sols, run_s = timed(lambda: engine.estimate(records))
    # Float64 reference: every record padded to the longest with
    # unmeasured intervals on a continued grid (exact: an unmeasured tail
    # leaves the MAP on the real points unchanged).
    Nmax = int(lengths.max())
    ts_b = np.stack([np.concatenate([ts, ts[-1] + dt * np.arange(
        1, Nmax - ts.shape[0] + 2)]) for ts, _ in records])
    y_b = np.stack([np.pad(y, ((0, Nmax - y.shape[0]), (0, 0)))
                    for _, y in records])
    mask = (np.arange(Nmax)[None] < lengths[:, None]).astype(np.float64)
    t0 = time.perf_counter()
    ref = linear_ref(model, ts_b, y_b, mask=mask)
    host_s = time.perf_counter() - t0
    err = max(rel_err(s.x, ref[i, :lengths[i] + 1])
              for i, s in enumerate(sols))
    # The same records through Estimator.solve (ragged layout), in chunks
    # of one wave per bucket so that no new program is compiled.
    gap = 0.0
    for idx in by_bucket.values():
        for k in range(0, len(idx), 32):
            chunk = idx[k:k + 32]
            pad = chunk + [chunk[0]] * (32 - len(chunk))
            direct = est.solve(Problem.ragged(
                model, [records[i] for i in pad]))
            gap = max(gap, max(rel_err(direct[j].x, np.asarray(
                sols[i].x, np.float64)) for j, i in enumerate(chunk)))
    # Each record is an RTS-form solve of at most 1e4 points (RTS_TOL).
    # The engine and Estimator.solve run the same executable on the same
    # rows, so they agree to a few ulp; 1e3 eps still flags any packing
    # or slicing fault, which would show as an O(1) error.
    tol_x, tol_gap = RTS_TOL, 1e3 * EPS32
    report("serving/trajectory_engine", records=len(records),
           lengths=[int(lo), int(hi)], batch=32, waves=engine.waves,
           buckets=sorted(int(b) for b in by_bucket),
           dtype=str(sols[0].x.dtype), compile_seconds=warm_s,
           run_seconds=run_s, reference_host_seconds=host_s,
           max_rel_error=err, tolerance=tol_x,
           estimator_gap=gap, estimator_gap_tolerance=tol_gap)
    check("trajectory_engine", "trajectory", err, tol_x)
    check("trajectory_engine", "gap to Estimator.solve", gap, tol_gap)


def streaming_engine(args):
    """Fixed-lag windows over several pushes, some of them late."""
    cfg = WienerVelocityConfig()
    model = cfg.model()
    rng = np.random.default_rng(args.seed + 3)
    tracks, rounds, per = 8, args.stream_rounds, 40
    dt = 1e-2
    N = rounds * per
    ts = f32_grid(N, dt)
    data = [wiener(cfg, rng, ts) for _ in range(tracks)]
    engine = StreamingEngine(model, lag=64, batch=8)
    ids = [engine.open_track(0.0) for _ in range(tracks)]
    held = {}
    late = 0
    t0 = time.perf_counter()
    for r in range(rounds):
        sl = slice(r * per, (r + 1) * per)
        for i, tid in enumerate(ids):
            k = np.arange(sl.start, sl.stop)
            if r % 3 == 1 and i % 4 == 0 and r < rounds - 1:
                # Hold three of this round's points back one round.
                keep = np.ones(per, bool)
                keep[[5, 17, 29]] = False
                held[tid] = k[~keep]
                k = k[keep]
            engine.push(tid, ts[1 + k], data[i][k])
            if tid in held and r % 3 == 2:
                kk = held.pop(tid)
                got = engine.push(tid, ts[1 + kk], data[i][kk])
                late += got["merged"]
        engine.run()
    stream_s = time.perf_counter() - t0
    wins = [engine.window(tid) for tid in ids]
    ref = linear_ref(model, np.broadcast_to(ts, (tracks, N + 1)),
                     np.stack(data))
    err = max(rel_err(w.x, ref[i, -w.x.shape[0]:])
              for i, w in enumerate(wins))
    # Each track's whole record through Estimator.solve: its tail is the
    # same window.
    est = Estimator(model, method="parallel_rts",
                    options=ParallelOptions(nsub=10, mode="discrete"))
    whole = est.solve(Problem.stacked(model, ts.astype(np.float32),
                                      np.stack(data).astype(np.float32)))
    gap = max(rel_err(w.x, np.asarray(whole.x[i, -w.x.shape[0]:],
                                      np.float64))
              for i, w in enumerate(wins))
    # The window solve is exact for a linear model (information-form
    # boundary prior), so the window carries only RTS-form round-off.
    tol_x = RTS_TOL
    report("serving/streaming_engine", tracks=tracks, rounds=rounds,
           points_per_track=N + 1, lag=64, batch=8, late_merged=late,
           window_points=int(wins[0].x.shape[0]),
           dtype=str(wins[0].x.dtype), stream_seconds=stream_s,
           max_rel_error=err, tolerance=tol_x, estimator_gap=gap,
           estimator_gap_tolerance=tol_x)
    if late < 1:
        _fail("streaming_engine: no late measurement was merged")
    check("streaming_engine", "window", err, tol_x)
    check("streaming_engine", "gap to Estimator.solve", gap, tol_x)


def four_chips(clock, args):
    """``method="distributed"`` on a 4-chip mesh against one device."""
    from repro.core import DistributedOptions
    from repro.distributed import MeshSpec

    cfg = WienerVelocityConfig()
    model = cfg.model()
    N = args.n_linear
    ts = f32_grid(N, cfg.tf / N)
    rng = np.random.default_rng(args.seed + 4)
    y = wiener(cfg, rng, ts)
    ys = np.stack([wiener(cfg, rng, ts[:args.n_stacked + 1])
                   for _ in range(4)])
    opts = DistributedOptions(nsub=10, mode="discrete")
    one = Estimator(model, method="parallel_rts",
                    options=ParallelOptions(nsub=10, mode="discrete"))
    cases = [
        ("time4", MeshSpec(time=4),
         Problem.single(model, ts.astype(np.float32), y.astype(np.float32))),
        ("time2_batch2", MeshSpec(time=2, batch=2),
         Problem.stacked(model, ts[:args.n_stacked + 1].astype(np.float32),
                         ys.astype(np.float32))),
    ]
    # The sharded scan combines the same elements in a different order.
    tol_x = RTS_TOL
    for name, mesh, problem in cases:
        est = Estimator(model, method="distributed", options=opts,
                        mesh=mesh)
        sol, compile_s, run_s = clock.solve(est, problem)
        devices = sol.x.sharding.device_set
        if len(devices) != 4:
            _fail(f"distributed/{name}: result on {len(devices)} devices")
        ref, _, one_s = clock.solve(one, problem)
        err = rel_err(sol.x, np.asarray(ref.x, np.float64))
        report(f"distributed/{name}", shape=list(sol.x.shape),
               dtype=str(sol.x.dtype), devices=len(devices),
               compile_seconds=compile_s, run_seconds=run_s,
               one_device_seconds=one_s,
               max_rel_error_vs_parallel_rts=err, tolerance=tol_x)
        check(f"distributed/{name}", "trajectory", err, tol_x)


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the time-sharded phase on 4 chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend JAX has (CPU "
                         "rehearsal); kernels in the Pallas interpreter")
    args = ap.parse_args()
    small = args.rehearse
    args.n_linear = 2_560 if small else 100_000
    args.n_nonlinear = 1_000 if small else 10_000
    args.n_stacked = 640 if small else 20_000
    args.engine_records = 12 if small else 96
    args.engine_lengths = (100, 1_000) if small else (1_000, 10_000)
    args.stream_rounds = 4 if small else 10

    jax.config.update("jax_enable_x64", False)
    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        _fail(f"no TPU: JAX found {dev.platform} devices "
              f"(use --rehearse for a CPU rehearsal)")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} but JAX found {len(devices)} devices")
    report("device", platform=dev.platform, kind=dev.device_kind,
           count=len(devices), compile_cache=cache)

    clock = Clock()
    if args.chips == 4:
        four_chips(clock, args)
    else:
        offline_linear(clock, args, interpret=dev.platform != "tpu")
        nonlinear(clock, args)
        trajectory_engine(clock, args)
        streaming_engine(args)
    print(json.dumps({"total_wall_seconds": time.perf_counter() - clock.start,
                      "total_compile_seconds": clock.compile_s}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
