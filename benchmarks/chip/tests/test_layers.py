"""The split of a traced window's device time by the program's named
scopes (``layers.py``): the instruction table read from an executable's
text, the leaf rule, the innermost scope, the readers, and the table of
a real executable of both cells."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import layers
import run
import tracereduce

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
DATA = Path(__file__).with_name("data")
NEW = ("scan_ms.offline", "block_ms.offline", "linearize_ms.offline",
       "cost_ms.offline")

# A cut of an optimised module's text: a fusion in a while body, a tuple
# shape, a ROOT, a while and a call (containers), an op with no metadata.
HLO = """\
HloModule jit_solve_one, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.9 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(solve_one)/smoother.fill/add"}
}

%body.2 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %fusion.1 = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(solve_one)/smoother.recover/smoother.scan/while/body/add" source_file="parallel.py" source_line=92}
  %copy-start.3 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%fusion.1), metadata={op_name="jit(solve_one)/estimator.cost/nonlinear.linearize/jvp(h)/mul"}
  ROOT %tuple.4 = (s32[], f32[4]{0}) tuple(%c, %fusion.1)
}

ENTRY %main.5 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="ts"}
  %while.6 = (s32[], f32[4]{0}) while(%tuple.0), condition=%cond.3, body=%body.2, metadata={op_name="jit(solve_one)/smoother.scan/while"}
  %call.7 = f32[4]{0} call(%Arg_0.1), to_apply=%body.2, metadata={op_name="jit(solve_one)/smoother.elements/closed_call"}
  %copy.8 = f32[4]{1,0:T(8,128)} copy(%Arg_0.1)
  ROOT %reverse.10 = f32[4]{0} reverse(%copy.8), dimensions={0}, metadata={op_name="jit(solve_one)/smoother.elements/rev"}
}
"""


def test_recorded_trace_reads_as_before():
    """The recorded chip trace keeps its window, busy time and top ops,
    and the accepted per-layer metrics read on it as before."""
    rec = json.loads((DATA / "trace_v5e.json").read_text())
    r = tracereduce.reduce(rec["ops"], rec["host"])
    want = rec["expected"]
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    assert r["device_ops"] == [[k, pytest.approx(v, rel=1e-12)]
                               for k, v in want["device_ops"]]
    cell = run.load_cell(ROOT, "wv_offline_long")
    idle = cell.readers["idle_share.offline"].read(_ctx(r))
    assert idle == pytest.approx(
        100 * (1 - want["busy_s"] / want["window_s"]), rel=1e-12)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(solve_one)/smoother.recover/smoother.scan/while/body/add",
     "smoother.scan"),
    ("jit(solve_one)/while/body/nonlinear.linearize/jvp(h)/atan2",
     "nonlinear.linearize"),
    ("jit(solve_one)/while/body/estimator.cost/reduce_sum",
     "estimator.cost"),
    ("jit(solve_one)/smoother.scan/smoother.fill/concatenate",
     "smoother.fill"),
    ("jit(solve_one)/flip", None),
])
def test_innermost_scope(op_name, scope):
    assert layers.scope_of(op_name) == scope


def test_instructions_of_an_executable_text():
    table = layers.instructions(HLO)
    assert table["%fusion.1"] == ("fusion", "smoother.scan")
    assert table["%copy-start.3"] == ("copy-start", "nonlinear.linearize")
    assert table["%tuple.4"] == ("tuple", None)
    assert table["%while.6"] == ("while", "smoother.scan")
    assert table["%call.7"] == ("call", "smoother.elements")
    assert table["%copy.8"] == ("copy", None)
    assert table["%reverse.10"] == ("reverse", "smoother.elements")
    assert table["%add.9"] == ("add", "smoother.fill")
    assert table["%Arg_0.1"] == ("parameter", None)
    assert "%main.5" not in table and "%body.2" not in table


def _ctx(trace, solves=2):
    return SimpleNamespace(trace=trace, driver=SimpleNamespace(solves=solves),
                           cfg={}, traffic={}, peaks=None)


def _trace(op_s, devices=1):
    return {"window_s": 1.0, "busy_s": sum(op_s.values()),
            "devices": devices, "op_s": op_s, "device_ops": [],
            "idle_gaps": []}


@pytest.fixture
def text(monkeypatch):
    """Readers see ``HLO`` (or what the test sets) as the executable's
    text."""
    box = {"text": HLO}
    monkeypatch.setattr(layers, "executable_text", lambda d: box["text"])
    monkeypatch.setattr(layers, "_tables", {})
    return box


def _readers():
    cell = run.load_cell(ROOT, "ct_offline_long")
    return {n: cell.readers[n].read for n in NEW}


def test_split_skips_containers_and_counts_other_programs(text):
    """Leaf time per scope, per device and per solve; a ``while`` or a
    ``call`` is skipped; ops not in the executable are ``other``."""
    ctx = _ctx(_trace({"%while.6": 1.0, "%call.7": 0.5, "%fusion.1": 0.4,
                       "%copy-start.3": 0.01, "%copy.8": 0.2,
                       "%reverse.10": 0.1, "%fusion.99": 0.05},
                      devices=2), solves=4)
    split = layers.split_ms(ctx)
    per = 1e3 / 2 / 4
    assert split == pytest.approx({
        "smoother.elements": 0.1 * per, "smoother.scan": 0.4 * per,
        "smoother.fill": 0.0, "smoother.recover": 0.0,
        "nonlinear.linearize": 0.01 * per, "estimator.cost": 0.0,
        "unscoped": 0.2 * per, "other": 0.05 * per})


def test_readers_on_a_scoped_window(text):
    ctx = _ctx(_trace({"%while.6": 1.0, "%fusion.1": 0.4,
                       "%copy-start.3": 0.01, "%reverse.10": 0.1,
                       "%copy.8": 0.2}))
    got = {n: read(ctx) for n, read in _readers().items()}
    assert got == pytest.approx({
        "scan_ms.offline": 200.0, "block_ms.offline": 50.0,
        "linearize_ms.offline": 5.0, "cost_ms.offline": None})


@pytest.mark.parametrize("name", NEW)
def test_reader_is_none_where_its_scope_is_absent(name, text):
    """An older program (no scopes in its text) gives no reading, and a
    run without a trace or without solves gives none either."""
    read = _readers()[name]
    text["text"] = "\n".join(line.partition(", metadata=")[0]
                             for line in HLO.splitlines())
    assert read(_ctx(_trace({"%fusion.1": 0.4, "%copy.8": 0.2}))) is None
    assert read(_ctx(None, solves=0)) is None
    assert read(_ctx(_trace({"%fusion.1": 0.4}), solves=0)) is None


@pytest.mark.parametrize("workload,scopes", [
    ("wv_offline_long", ("smoother.elements", "smoother.scan",
                         "smoother.fill", "smoother.recover",
                         "estimator.cost")),
    ("ct_offline_long", layers.SCOPES),
])
def test_executable_of_a_rehearsed_window(workload, scopes):
    """After a window at the mix's small sizes, the driver's executable
    is found again without a compile, and its leaf instructions carry
    the cell's scopes."""
    import jax

    from repro.core import cache_stats

    cell = run.load_cell(ROOT, workload, rehearse=True)
    jax.config.update("jax_enable_x64", False)
    driver = run.make_driver(cell, 2 ** 31 + 12345, 0.05)
    driver.warm()
    driver.window(0.05)
    driver.release()
    misses = cache_stats()["misses"]
    table = layers.instructions(layers.executable_text(driver))
    assert cache_stats()["misses"] == misses
    leaf = {scope for opcode, scope in table.values()
            if opcode not in layers.CONTAINERS}
    assert leaf - {None} == set(scopes)
