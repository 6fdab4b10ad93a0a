"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole run of a cell at its small sizes on the CPU
(``--rehearse`` skips only the look for a chip) with one fault planted in
the program, and reads ``correct`` from the result line.  The faults are
those the cells can have: a trajectory altered where it is produced, a
cost altered where it is produced, and a solve that hands back its
starting state.  A cell solves one record at a time, so there is no batch
to leave half of, and no cell spans chips, so there is no exchange
between chips to leave out.
"""
import dataclasses
import json

import jax.numpy as jnp
import pytest

import run


def _run(capsys, workload):
    run.main(["--workload", workload, "--seed", "2147483999", "--seconds",
              "2", "--trace", "0", "--rehearse"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _broken_solve(monkeypatch, change):
    from repro.core.estimator import Estimator

    solve = Estimator.solve

    def broken(self, problem):
        return change(self, solve(self, problem))

    monkeypatch.setattr(Estimator, "solve", broken)


def _altered(monkeypatch):
    _broken_solve(monkeypatch, lambda est, sol: dataclasses.replace(
        sol, x=sol.x.at[..., 3, 0].add(1.0)))


def _cost_altered(monkeypatch):
    _broken_solve(monkeypatch, lambda est, sol: dataclasses.replace(
        sol, cost=sol.cost * 1.1))


def _unchanged(monkeypatch):
    """The solve hands back its starting state, the prior mean."""
    _broken_solve(monkeypatch, lambda est, sol: dataclasses.replace(
        sol, x=jnp.broadcast_to(est.model.m0, sol.x.shape)))


CASES = [(w, f) for w in ("wv_offline_long", "ct_offline_long")
         for f in (_altered, _cost_altered, _unchanged)]


@pytest.mark.parametrize("workload,fault", CASES,
                         ids=[f"{w}-{f.__name__[1:]}" for w, f in CASES])
def test_fault_is_not_correct(capsys, monkeypatch, workload, fault):
    fault(monkeypatch)
    out = _run(capsys, workload)
    assert out["correct"] is False


@pytest.mark.parametrize("workload", ["wv_offline_long", "ct_offline_long"])
def test_sound_run_is_correct(capsys, workload):
    out = _run(capsys, workload)
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
