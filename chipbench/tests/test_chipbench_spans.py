"""The per-layer metrics that read the program's spans inside a tick: a
traced run of the cell prints them, they split ``executor.self_ms``, and
``executor.unspanned_ms`` takes the union of nested and overlapping
spans."""
from types import SimpleNamespace

import pytest

import harness
from conftest import BENCH

NEW = ("surprise_gate.ms_per_tick", "tick_fetch.ms_per_tick",
       "bias_fetch.ms_per_tick", "executor.unspanned_ms")


def reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def test_traced_run_splits_executor_self_time(tree, run_cell):
    rc, line, err = run_cell(tree, "--workload", "eager-ds1-5n.runs8",
                             "--seed", 3_000_000_011, "--seconds", 2,
                             "--trace", 1)
    assert rc == 0, err
    assert line["correct"] is True, err
    m = {k: v["value"] for k, v in line["metrics"].items()}
    for name in NEW:
        assert m[name] >= 0.0, name
        assert line["metrics"][name]["unit"] == "ms"
    parts = (m["surprise_gate.ms_per_tick"] + m["bias_fetch.ms_per_tick"]
             + m["executor.unspanned_ms"])
    assert parts == pytest.approx(m["executor.self_ms"], rel=0.10)
    assert m["tick_fetch.ms_per_tick"] <= m["tick_step.span_ms"]


def _ctx(spans, t_open=-100.0, t_end=100.0):
    rec = harness.Recorder()
    rec.spans = [(p, t0, t1, run, 1) for p, t0, t1, run in spans]
    return SimpleNamespace(kind="executor", rec=rec,
                           win=SimpleNamespace(t_open=t_open, t_end=t_end))


def test_unspanned_takes_the_union_of_spans():
    ctx = _ctx([
        ("window", -10.0, 50.0, 0), ("run", -5.0, 25.0, 0),
        ("run_setup", -5.0, -4.0, 0),
        # tick [0, 10): step with a nested fetch, a bias fetch that a
        # plan overlaps, the next tick's gate; covered 2 + 1 of 10
        ("tick_step", 0.0, 1.0, 0), ("tick_fetch", 0.2, 0.8, 0),
        ("bias_fetch", 1.0, 1.5, 0), ("plan", 1.4, 2.0, 0),
        ("surprise_gate", 9.0, 10.0, 0),
        # tick [10, 20): a plan that runs past the next tick's start is
        # cut at it; covered 2.5 + 1.5 of 10
        ("tick_step", 10.0, 11.0, 0), ("plan", 10.5, 12.5, 0),
        ("surprise_gate", 19.0, 20.0, 0), ("plan", 18.5, 20.5, 0),
        ("tick_step", 20.0, 21.0, 0),
        # a tick of another run does not pair with the last one
        ("tick_step", 30.0, 31.0, 1), ("surprise_gate", 29.0, 30.0, 1),
    ])
    assert reader("executor.unspanned_ms").read(ctx) == \
        pytest.approx(1e3 * (7.0 + 6.0) / 2)
    # per tick (four tick_step spans): gates 3 s, fetches 0.6 and 0.5 s
    assert reader("surprise_gate.ms_per_tick").read(ctx) == \
        pytest.approx(1e3 * 3.0 / 4)
    assert reader("tick_fetch.ms_per_tick").read(ctx) == \
        pytest.approx(1e3 * 0.6 / 4)
    assert reader("bias_fetch.ms_per_tick").read(ctx) == \
        pytest.approx(1e3 * 0.5 / 4)


def test_union_helpers():
    mod = reader("executor.unspanned_ms")
    union = mod.merge([(5.0, 6.0), (0.0, 2.0), (1.0, 3.0), (3.0, 4.0),
                       (5.5, 5.8)])
    assert union == [(0.0, 4.0), (5.0, 6.0)]
    assert mod.covered(union, 0.0, 10.0) == pytest.approx(5.0)
    assert mod.covered(union, 3.5, 5.5) == pytest.approx(1.0)
    assert mod.covered(union, 4.0, 5.0) == 0.0
    assert mod.covered(union, 7.0, 9.0) == 0.0
    assert mod.covered([], 0.0, 1.0) == 0.0


def test_nothing_to_read_without_the_spans():
    """A program without spans inside the tick (the parent of this
    change) reads as nothing, not as zero."""
    ctx = _ctx([("tick_step", 0.0, 1.0, 0), ("plan", 2.0, 3.0, 0),
                ("tick_step", 10.0, 11.0, 0)])
    for name in NEW:
        assert reader(name).read(ctx) is None, name
    ctx.kind = "other"
    assert all(reader(name).read(ctx) is None for name in NEW)
