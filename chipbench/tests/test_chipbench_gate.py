"""Without a TPU, or without the program beside it, the benchmark exits
non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

from conftest import BENCH, REPO

ARGS = ["--workload", "eager-ds1-5n.runs8", "--seed", "0", "--seconds", "10",
        "--trace", "0"]


def _run(cwd, env):
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(REPO, env)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert p.stdout.strip() == ""


def test_refuses_without_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert "not beside the benchmark" in p.stderr
    assert p.stdout.strip() == ""
