"""The per-layer metrics of the fault path: a traced run of
``eager-ds1-5n.faults`` prints ``fault.ms_per_tick`` and
``fault_replan.ms_per_tick`` and no metric of another cell; the eager
cell's traced run prints neither; the readers take the re-plans out of
the fault time and read nothing where the program has no such span."""
from types import SimpleNamespace

import pytest

import harness
from conftest import BENCH, load_bench

CELL = "eager-ds1-5n.faults"
NEW = ("fault.ms_per_tick", "fault_replan.ms_per_tick")


def reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def test_faults_cell_is_committed_with_its_metrics():
    bench = load_bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "faults"
    scoped = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])]
    assert tuple(scoped) == NEW


def test_traced_faults_run_reads_the_fault_path(tree, run_cell):
    rc, line, err = run_cell(tree, "--workload", CELL, "--seed",
                             3_000_000_017, "--seconds", 2, "--trace", 1)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert set(line["metrics"]) == set(NEW)
    for name in NEW:
        assert line["metrics"][name]["value"] > 0.0, name
        assert line["metrics"][name]["unit"] == "ms"
    for name in ("fault_schedule_errors", "fault_shortfall"):
        assert line["checks"][name]["value"] == 0, name


def test_traced_eager_run_reads_no_fault_path(tree, run_cell):
    rc, line, err = run_cell(tree, "--workload", "eager-ds1-5n.runs8",
                             "--seed", 3_000_000_019, "--seconds", 1,
                             "--trace", 1)
    assert rc == 0 and line["correct"] is True, err
    assert line["metrics"] and not set(NEW) & set(line["metrics"])


def _ctx(spans, t_open=-100.0, t_end=100.0):
    rec = harness.Recorder()
    rec.spans = [(p, t0, t1, run, 1) for p, t0, t1, run in spans]
    return SimpleNamespace(kind="executor", rec=rec,
                           win=SimpleNamespace(t_open=t_open, t_end=t_end))


def test_fault_time_less_its_replans():
    ctx = _ctx([
        ("tick_step", 0.0, 1.0, 0), ("tick_step", 10.0, 11.0, 0),
        # a lost node: 3 s, a re-plan of 1 s inside it
        ("replan", 2.5, 3.5, 0), ("fault", 2.0, 5.0, 0),
        # a failed attempt with no re-plan: 0.5 s
        ("fault", 6.0, 6.5, 0),
        # a re-plan after a rejoin, outside any fault: 2 s
        ("replan", 7.0, 9.0, 0),
        ("tick_step", 20.0, 21.0, 0), ("tick_step", 30.0, 31.0, 1),
    ])
    assert reader("fault.ms_per_tick").read(ctx) == \
        pytest.approx(1e3 * (3.0 - 1.0 + 0.5) / 4)
    assert reader("fault_replan.ms_per_tick").read(ctx) == \
        pytest.approx(1e3 * (1.0 + 2.0) / 4)


def test_nothing_to_read_without_the_spans():
    """A program without fault-path spans, or a window without a fault
    or a tick, reads as nothing, not as zero."""
    ctx = _ctx([("tick_step", 0.0, 1.0, 0), ("plan", 2.0, 3.0, 0),
                ("tick_step", 10.0, 11.0, 0)])
    for name in NEW:
        assert reader(name).read(ctx) is None, name
    ctx = _ctx([("fault", 2.0, 3.0, 0), ("replan", 2.1, 2.9, 0)])
    for name in NEW:                    # no tick in the window
        assert reader(name).read(ctx) is None, name
    ctx.kind = "other"
    assert all(reader(name).read(ctx) is None for name in NEW)
