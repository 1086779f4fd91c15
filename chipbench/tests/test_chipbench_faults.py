"""With the timed path broken underneath, a run's ``correct`` comes out
false; so does a run with the bfloat16 control in the program's place.
Each cell of the executor runner in ``BENCHMARK.json`` is run on the CPU
with the device gate skipped."""
import dataclasses
import json

import pytest

import repro.core.tick as tick
from conftest import REPO, load_bench


def _executor_cells():
    bench = load_bench()
    runner = {c["name"]: json.loads((REPO / c["file"]).read_text())["runner"]
              for c in bench["configs"]}
    return [w["name"] for w in bench["workloads"]
            if runner[w["config"]] == "executor"]


CELLS = _executor_cells()


def _unchanged(orig):
    """A tick that returns its state as it came in."""
    def step(state, obs, size, host_deadjust):
        mean, std = tick.predict_state(state, size)
        return state, mean, std, obs[:, 4]
    return step


def _bias_dropped(orig):
    """A tick that updates the task models but returns the bias statistics
    it was given."""
    def step(state, obs, size, host_deadjust):
        # copies: the tick consumes (donates) the state it is given
        counts, log_sum, log_sq = (state.bias_counts.copy(),
                                   state.bias_log_sum.copy(),
                                   state.bias_log_sq.copy())
        new, mean, std, y = orig(state, obs, size,
                                 host_deadjust=host_deadjust)
        kept = dataclasses.replace(new, bias_counts=counts,
                                   bias_log_sum=log_sum, bias_log_sq=log_sq)
        return kept, mean, std, y
    return step


def _altered(orig):
    """A tick whose estimate matrix comes out 2% high."""
    def step(state, obs, size, host_deadjust):
        new, mean, std, y = orig(state, obs, size,
                                 host_deadjust=host_deadjust)
        return new, mean * 1.02, std, y
    return step


FAULTS = [(c, f) for c in CELLS for f in (_unchanged, _bias_dropped,
                                           _altered)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__[1:]}" for c, f in FAULTS])
def test_fault_fails(tree, run_cell, monkeypatch, cell, fault):
    monkeypatch.setattr(tick, "tick_step", fault(tick.tick_step))
    rc, line, err = run_cell(tree, "--workload", cell, "--seed", 5,
                             "--seconds", 1, "--trace", 0)
    assert rc == 0, err
    assert line["correct"] is False, err
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_fails(tree, run_cell, cell):
    """With the reference computed in bfloat16 in the program's place, the
    run comes out not correct, by a number over its limit."""
    rc, line, err = run_cell(tree, "--workload", cell, "--seed", 9,
                             "--seconds", 1, "--trace", 0, "--control", 1)
    assert rc == 0, err
    assert line["correct"] is False, err
    assert line["checks"]["estimate_gap"]["value"] > \
        line["checks"]["estimate_gap"]["limit"]
    assert err.strip().splitlines()[-1] == \
        "chipbench: correct False (bfloat16 control)"
