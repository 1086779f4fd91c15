"""The reduction from a device trace to busy time, per-program time, idle
gaps and the roofline share."""
import json
from pathlib import Path

import pytest

import devtrace
import roofline

FIX = Path(__file__).parent / "fixtures"

#: two executions overlap, one straddles the window's start; host spans
#: nest (run > tick_step)
HAND = {"devices": [[["jit__tick_core(1)", 100, 50],
                     ["jit_copy(2)", 140, 30],
                     ["jit__tick_core(1)", 400, 100],
                     ["jit_multiply(3)", 50, 100]]],
        "host": [["window", 100, 900], ["run", 100, 900],
                 ["tick_step", 380, 150], ["plan", 600, 100]]}


def test_hand_trace():
    r = devtrace.reduce(HAND)
    # busy: [100,170] (50..150 clipped, 100..150, 140..170) and [400,500]
    assert r["busy_s"] == pytest.approx(170e-9)
    assert r["window_s"] == pytest.approx(900e-9)
    assert devtrace.program_time(r, "tick_core") == (2, pytest.approx(150e-9))
    assert devtrace.program_time(r, "no_such") is None
    idle = dict(r["breakdown"]["idle_gaps"])
    # gaps [170, 400] and [500, 1000], split by the innermost span:
    # run 210 + 70 + 300, tick_step 20 + 30, plan 100
    assert idle == {"run": pytest.approx(580e-9),
                    "tick_step": pytest.approx(50e-9),
                    "plan": pytest.approx(100e-9)}
    ops = r["breakdown"]["device_ops"]
    assert ops[0] == ["jit__tick_core", pytest.approx(150e-9)]


def test_gap_split_by_innermost_span():
    ev = {"devices": [[["a(1)", 0, 10], ["a(1)", 100, 10]]],
          "host": [["window", 0, 110], ["run", 0, 110],
                   ["tick_step", 20, 80]]}
    assert dict(devtrace.reduce(ev)["breakdown"]["idle_gaps"]) == {
        "run": pytest.approx(10e-9), "tick_step": pytest.approx(80e-9)}


def test_no_window_or_device_reads_nothing():
    assert devtrace.reduce({"devices": [], "host": [["window", 0, 1]]}) is None
    assert devtrace.reduce({"devices": [[["a", 0, 1]]], "host": []}) is None


def test_recorded_chip_trace():
    """A few ticks of `tick_step` at (4096, 256), B = 64, recorded on one
    TPU v5e."""
    ev = json.loads((FIX / "site_ticks.json").read_text())
    r = devtrace.reduce(ev)
    n, secs = devtrace.program_time(r, "tick_core")
    assert n == ev["ticks"]
    assert 0 < r["busy_s"] <= r["window_s"]
    # every execution of the tick program lies inside the busy time
    assert secs <= r["busy_s"]
    share = (roofline.tick_step_bytes(**ev["shape"]) / 8.19e11) / (secs / n)
    assert 0 < share < 1


def test_tick_bytes():
    # (4096, 256) with 257 bias columns and 64 observations
    assert roofline.tick_step_bytes(4096, 256, 257, 64) == 4 * (
        3 * 4096 * 256 + 3 * 4096 * 257 + 13 * 4096 + 64 * (8 + 48))


def test_bounds_cut_the_window():
    """Only the counted part of the window: here [200, 600]."""
    r = devtrace.reduce(HAND, bounds=(100e-9, 500e-9))
    assert r["window_s"] == pytest.approx(400e-9)
    assert r["busy_s"] == pytest.approx(100e-9)
    # gaps [200, 400] and [500, 600]: tick_step covers 380..530
    assert dict(r["breakdown"]["idle_gaps"]) == {
        "run": pytest.approx(250e-9), "tick_step": pytest.approx(50e-9)}
