"""The benchmark's yardstick on the CPU: trace reduction, compulsory work,
the float64 reference, discovery by name, and the refusal without a chip."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import roofline
import run
import tracereduce

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
DATA = Path(__file__).with_name("data")


# -- trace reduction ---------------------------------------------------------

def test_reduce_hand_computed():
    """Busy share, op sums and idle gaps of a hand-made window."""
    host = [("bench.window", 100, 1000),          # window [100, 1100]
            ("bench.solve", 100, 400), ("bench.solve", 600, 500),
            ("estimator.solve.execute", 650, 100)]
    ops = [("/device:TPU:0", "fusion.1", 50, 100),    # clipped to [100, 150]
           ("/device:TPU:0", "fusion.2", 120, 80),    # overlaps: [120, 200]
           ("/device:TPU:0", "fusion.1", 500, 100),   # [500, 600]
           ("/device:TPU:0", "copy.3", 1050, 200)]    # clipped to [1050, 1100]
    r = tracereduce.reduce(ops, host)
    assert r["window_s"] == pytest.approx(1000e-9)
    # union: [100, 200] + [500, 600] + [1050, 1100] = 250 ns
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["op_s"] == pytest.approx(
        {"fusion.1": 150e-9, "fusion.2": 80e-9, "copy.3": 50e-9})
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(150e-9)]
    # gaps [200, 500] (mid 350: bench.solve), [600, 1050] (mid 825:
    # bench.solve; execute ended at 750)
    assert r["idle_gaps"] == [["bench.solve", pytest.approx(450e-9)],
                              ["bench.solve", pytest.approx(300e-9)]]


def test_reduce_recorded_chip_trace():
    """The reduction of 20 ms cut from a window traced on a TPU v5e
    (``data/trace_v5e.json``: its device ops and host spans), against busy
    time, op sums and idle gaps worked out from the same events by a
    brute-force sweep over a 1 ns grid when the data was cut."""
    rec = json.loads((DATA / "trace_v5e.json").read_text())
    r = tracereduce.reduce(rec["ops"], rec["host"])
    want = rec["expected"]
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] == [[k, pytest.approx(v, rel=1e-12)]
                               for k, v in want["device_ops"]]
    assert [g for _, g in r["idle_gaps"]] == pytest.approx(rec["gaps_s"],
                                                           rel=1e-12)


@pytest.mark.parametrize("ops", [
    [("/device:TPU:0", "fusion.1", 100, 900)],     # stops at 1000 of 1100
    [("/device:TPU:0", "fusion.1", 200, 900)],     # starts at 200
], ids=["ends-early", "starts-late"])
def test_reduce_refuses_a_cut_trace(ops):
    """Device ops that leave more than 5% of the window bare at an end:
    the profiler lost events, and the busy share would read low."""
    host = [("bench.window", 100, 1000)]
    with pytest.raises(ValueError, match="events were lost"):
        tracereduce.reduce(ops, host)


def test_events_read_a_profile(tmp_path):
    """``events`` finds the window annotation and host spans in a profile
    taken here (the CPU has no device plane, so no ops)."""
    import jax
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: x * 2)
    f(np.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.solve"):
            f(np.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    ops, host = tracereduce.events(str(tmp_path))
    names = [h[0] for h in host]
    assert names.count("bench.window") == 1 and "bench.solve" in names
    r = tracereduce.reduce(ops, host)
    assert r["window_s"] > 0


# -- compulsory work -----------------------------------------------------------

@pytest.mark.parametrize("nx,ny,iterated,floats", [
    # ts + y: 1 + 2; S and v written and read: 2 (16 + 4); x: 4
    (4, 2, False, 3 + 40 + 4),
    # ts + y: 3; S and v: 2 (25 + 5); x written: 5; x read: 5
    (5, 2, True, 3 + 60 + 5 + 5),
])
def test_compulsory_bytes_hand_count(nx, ny, iterated, floats):
    assert roofline.compulsory_bytes(1, nx, ny, 1, iterated) == 4 * floats
    assert roofline.compulsory_bytes(1000, nx, ny, 5, iterated) == \
        4 * floats * 1000 * 5


@pytest.mark.parametrize("nx,ny,flops", [
    # predict 4*64 + 2*16, gain 2*16*2 + 2*4*4 + 8, update 4*8 + 2*16*2
    # + 2*64, smooth 3*64 + 2*16
    (4, 2, 288 + 104 + 224 + 224),
    (5, 2, 550 + 148 + 390 + 425),
])
def test_compulsory_flops_hand_count(nx, ny, flops):
    assert roofline.compulsory_flops(1, nx, ny) == flops
    assert roofline.compulsory_flops(10, nx, ny, 5) == 50 * flops


def test_peak_table_refuses_unknown_device():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


# -- the float64 reference -----------------------------------------------------

def test_reference_matches_host_oracle():
    """The compiled float64 scan against ``core/oracle.rts_map_host`` on
    the Wiener velocity model, on an uneven grid."""
    from repro.core.oracle import rts_map_host

    cell = run.load_cell(ROOT, "wv_offline_long")
    cfg, mod = cell.cfg, cell.cfgmod
    rng = np.random.default_rng(0)
    ts = np.cumsum(np.r_[0.0, rng.uniform(5e-4, 2e-3, 300)])
    y = mod.simulate(cfg, rng, ts, 3)
    got = mod.reference(cfg, ts, y)
    F, c, H, r, Q, R, m0, P0 = mod.matrices(cfg)
    want = rts_map_host(F, c, H, r, Q, R, y, np.diff(ts), m0, P0)
    assert got.dtype == np.float64
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-10 * np.abs(
        want).max())


def test_reference_leaves_x64_off():
    import jax.numpy as jnp

    cell = run.load_cell(ROOT, "wv_offline_long")
    ts = np.arange(11) * 1e-2
    cell.cfgmod.reference(cell.cfg, ts, np.zeros((1, 10, 2)))
    assert jnp.zeros(1).dtype == jnp.float32


# -- discovery by name -----------------------------------------------------------

def test_every_cell_loads_from_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run.load_cell(ROOT, w["name"])
        assert cell.traffic["kind"] == "offline"
        assert {m["name"] for m in cell.per_layer} == set(cell.readers)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert cell.limits
    for m in bench["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()


def test_new_files_alone_add_a_cell(tmp_path):
    """A new configuration, traffic mix and per-layer metric are picked up
    from new files and new ``BENCHMARK.json`` entries alone."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    cfg = json.loads((chip / "configs" / "wiener_velocity.json").read_text())
    cfg["q"] = 1.0
    (chip / "configs" / "wv_slow.json").write_text(json.dumps(cfg))
    shutil.copy(chip / "configs" / "wiener_velocity.py",
                chip / "configs" / "wv_slow.py")
    mix = json.loads((chip / "traffic" / "offline_long.json").read_text())
    mix["intervals"] = 50_000
    (chip / "traffic" / "offline_half.json").write_text(json.dumps(mix))
    (chip / "metrics" / "solves.offline.py").write_text(
        "def read(ctx):\n    return ctx.driver.solves\n")
    (chip / "limits" / "slow_half.json").write_text('{"traj_gap": 1}')
    bench["configs"].append({"name": "wv_slow", "source": "test",
                             "file": "benchmarks/chip/configs/wv_slow.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "slow_half", "config": "wv_slow",
                               "traffic": "offline_half", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("slow_half")
    bench["per_layer"].append({"name": "solves.offline", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "estimator", "moves": "solve_ms",
                               "workloads": ["slow_half"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = run.load_cell(root, "slow_half")
    assert cell.cfg["q"] == 1.0 and cell.traffic["intervals"] == 50_000
    assert [m["name"] for m in cell.per_layer] == ["solves.offline"]
    assert cell.readers["solves.offline"].read(
        type("ctx", (), {"driver": type("d", (), {"solves": 7})})) == 7
    assert {m["name"] for m in cell.end_to_end} == {"solve_ms", "setup_s"}


# -- refusal without a chip --------------------------------------------------------

def test_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "wv_offline_long", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_refuses_without_the_program(tmp_path):
    """A checkout holding only ``BENCHMARK.json`` and the benchmark's own
    files has no program to run: no result, a non-zero exit."""
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "wv_offline_long", "--seed", "1", "--seconds", "1", "--trace", "0",
         "--rehearse"], capture_output=True, text=True, cwd=tmp_path,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
