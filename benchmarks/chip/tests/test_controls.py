"""The control must fail the comparison that decides ``correct``.

The control is the plain reference put in the program's place in the
precision below the configuration's: float32 with every product in three
bfloat16 passes (spelled out in ``reference._dot_bf16x3``, so the CPU
computes what the chip's ``Precision.HIGH`` does).  Here it runs on the
CPU at each cell's own sizes, on the records a run would sample, and at
least one compared number must exceed the cell's limit.  On the chip the
same readings come from ``control.py``.
"""
import math

import pytest

import run

SEEDS = [2147483647, 3000000123]


def _readings(workload, seed):
    cell = run.load_cell(run.ROOT, workload)
    d = run.make_driver(cell, seed, 1.0)
    d.sample.items = [(r, None, None) for r in range(len(d.ys))]
    return cell, d.check(control=True)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", ["wv_offline_long", "ct_offline_long"])
def test_control_fails_a_limit(workload, seed):
    cell, got = _readings(workload, seed)
    assert set(got) == set(cell.limits)
    assert any(not math.isfinite(v) or v > cell.limits[k]
               for k, v in got.items()), got
