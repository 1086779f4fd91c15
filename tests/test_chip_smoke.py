"""``chip_smoke.py`` on the CPU: the device gate refuses to run, and each
phase's chip-vs-reference harness passes at a tiny size when both sides
are the CPU (so a later API change breaks here, not on the chip)."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import smoke_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cpu():
    return jax.devices("cpu")[0]


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_refuses_to_run_without_a_tpu(smoke, argv, capsys):
    assert smoke.main(argv) == 1
    out = capsys.readouterr()
    assert "no TPU chip found" in out.err
    assert out.out == ""


def test_tick_phase_tiny(smoke, cpu):
    smoke.phase_ticks(cpu, cpu, shape=(64, 8), batch=8, n_ticks=3)


def test_fleet_phase_tiny(smoke, cpu):
    smoke.phase_fleet([cpu], shape=(4, 16, 4), batch=8, n_ticks=2)


def test_serve_phase_tiny(smoke, cpu):
    smoke.phase_serve(cpu, smoke_config("stablelm-1.6b"), n_requests=3,
                      max_new=4)


def test_loop_phase_tiny(smoke, cpu):
    smoke.phase_loop(cpu, cpu, n_samples=2)


def test_check_raises_with_its_message(smoke):
    with pytest.raises(smoke.SmokeFailure, match="off by 3"):
        smoke.check(False, "off by 3")
    assert smoke.max_rel([1.0, float("nan")], [1.0, 1.0]) == float("inf")
