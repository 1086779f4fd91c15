"""Fixtures for the benchmark's own tests: a copy of the benchmark at tiny
sizes, and an in-process run of a cell on the CPU with the device gate and
the compile cache switched off inside the test."""
from __future__ import annotations

import io
import json
import shutil
import sys
from contextlib import redirect_stderr, redirect_stdout
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def load_bench(root: Path = REPO) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def make_tree(dst: Path, src: Path = REPO) -> Path:
    """A benchmark tree under ``dst``: the benchmark's files and
    ``BENCHMARK.json`` of the checkout ``src``, each configuration of it
    changed to fit a test run by its own file.  A configuration file brings
    its test sizes as an optional ``test_sizes`` object, whose keys replace
    the file's top-level keys here (absent: nothing changes); the harness
    and the runners never read it."""
    shutil.copytree(src / BENCH.name, dst / BENCH.name,
                    ignore=shutil.ignore_patterns("out", "tests",
                                                  "__pycache__"))
    bench = load_bench(src)
    for conf in bench["configs"]:
        path = dst / conf["file"]
        cfg = json.loads(path.read_text())
        cfg.update(cfg.get("test_sizes", {}))
        path.write_text(json.dumps(cfg))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


@pytest.fixture
def tree(tmp_path):
    return make_tree(tmp_path)


@pytest.fixture
def run_cell(monkeypatch):
    """``run_cell(root, *args) -> (rc, last stdout line as dict or None,
    stderr)``, on the CPU: the gate hands back the CPU devices, the compile
    cache stays off and the peaks table answers for the CPU."""
    import jax

    import harness
    import run
    monkeypatch.setattr(harness, "device_gate",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    monkeypatch.setattr(harness, "peaks_for",
                        lambda kind: {"hbm_bytes_per_s": 8.19e11})

    def go(root, *args):
        out, err = io.StringIO(), io.StringIO()
        with redirect_stdout(out), redirect_stderr(err):
            rc = run.main([str(a) for a in args], root=root)
        lines = out.getvalue().strip().splitlines()
        return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
    return go
