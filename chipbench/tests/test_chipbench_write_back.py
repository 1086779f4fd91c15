"""The per-layer metric ``write_back.span_ms``: the mean ``write_back``
span of the window, which a program without the span leaves out."""
from types import SimpleNamespace

import pytest

import harness
from conftest import BENCH


def reader():
    return harness.load_module(BENCH / "metrics" / "write_back.span_ms.py")


def _ctx(spans, kind="executor"):
    rec = harness.Recorder()
    rec.spans = [(p, t0, t1, run, n) for p, t0, t1, run, n in spans]
    return SimpleNamespace(kind=kind, rec=rec,
                           win=SimpleNamespace(t_open=0.0, t_end=10.0))


def test_mean_of_the_window_spans():
    ctx = _ctx([("write_back", -1.0, -0.5, 0, 13),     # before the window
                ("tick_step", 0.5, 0.6, 1, 1),
                ("write_back", 1.0, 1.002, 1, 13),
                ("write_back", 5.0, 5.004, 2, 12),
                ("write_back", 10.0, 10.1, 3, 13)])    # after it
    assert reader().read(ctx) == pytest.approx(3.0)


def test_nothing_to_read():
    ticks = [("tick_step", 0.5, 0.6, 0, 1)]
    assert reader().read(_ctx(ticks)) is None
    assert reader().read(_ctx(ticks + [("write_back", 1.0, 1.1, 0, 13)],
                              kind="other")) is None


def test_traced_run_reports_it(tree, run_cell):
    rc, line, err = run_cell(tree, "--workload", "eager-ds1-5n.runs8",
                             "--seed", 3_002_000_001, "--seconds", 2,
                             "--trace", 1)
    assert rc == 0, err
    assert line["correct"] is True, err
    got = line["metrics"]["write_back.span_ms"]
    assert got["unit"] == "ms" and got["value"] > 0.0
