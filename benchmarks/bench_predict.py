"""Prediction-engine throughput: scalar per-pair loop vs the batched,
jit-compiled matrix path, plus dict-HEFT vs array-HEFT — the hot path a
HEFT-class scheduler re-runs on every elastic reschedule / straggler check
(paper §2.2).  Writes ``BENCH_predict.json`` at the repo root.

Scale: ~1000 tasks x 64 nodes by default.  x64 is enabled so the
agreement check between the two paths is limited by algorithmic, not
float32, differences.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro.core import LotaruEstimator
from repro.core.blr import fit_task
from repro.core.estimator import FittedTask
from repro.core.profiler import BenchResult
from repro.launch.cache import enable_compile_cache
from repro.sched.heft import (SchedTask, heft_schedule_array,
                              heft_schedule_reference)

OUT = Path(__file__).resolve().parents[1] / "BENCH_predict.json"


def _synthetic_estimator(n_tasks: int, n_nodes: int, seed: int = 0):
    """An estimator with T fitted tasks over N synthetic node benches —
    no simulator in the loop, so the benchmark times prediction only."""
    rng = np.random.default_rng(seed)
    local = BenchResult(node="local-cpu", cpu_events_s=450.0,
                        matmul_gflops=90.0, mem_gbps=18.0,
                        io_read_mbps=420.0, io_write_mbps=400.0,
                        link_gbps=0.0)
    benches = {}
    for j in range(n_nodes):
        nm = f"node{j:03d}"
        benches[nm] = BenchResult(
            node=nm, cpu_events_s=float(rng.uniform(150, 900)),
            matmul_gflops=float(rng.uniform(50, 5000)),
            mem_gbps=float(rng.uniform(10, 900)),
            io_read_mbps=float(rng.uniform(100, 900)),
            io_write_mbps=float(rng.uniform(100, 900)),
            link_gbps=float(rng.uniform(0, 100)))
    est = LotaruEstimator(local, benches)
    n_part = 8
    for i in range(n_tasks):
        sizes = np.geomspace(1.0, 256.0, n_part) * rng.uniform(0.5, 2.0)
        if rng.random() < 0.7:      # size-correlated task -> BLR
            rts = (rng.uniform(0.1, 5.0) * sizes + rng.uniform(1, 50)
                   + rng.normal(0, 0.05, n_part))
        else:                       # flat -> median fallback
            rts = rng.uniform(20, 200) + rng.normal(0, 0.5, n_part)
        est.tasks[f"task{i:04d}"] = FittedTask(
            model=fit_task(sizes, rts), w=float(rng.uniform(0, 1)),
            sizes=sizes, runtimes=np.abs(rts))
    return est


def _layered_dag(n_tasks: int, depth: int, rng) -> dict[str, SchedTask]:
    """Layered DAG (width = n_tasks/depth) with random cross-layer edges."""
    width = max(1, n_tasks // depth)
    ids = [f"t{i}" for i in range(n_tasks)]
    tasks = {tid: SchedTask(id=tid) for tid in ids}
    for i in range(width, n_tasks):
        for p in rng.choice(i, size=min(2, i), replace=False):
            p = int(p)
            if p >= i - 2 * width and rng.random() < 0.7:
                tasks[ids[p]].succ.append(ids[i])
                tasks[ids[i]].pred.append(ids[p])
    return tasks


def run(n_tasks: int = 1000, n_nodes: int = 64) -> list[tuple]:
    rng = np.random.default_rng(3)
    est = _synthetic_estimator(n_tasks, n_nodes)
    nodes = list(est.target_benches)
    names = est.task_names()
    size = 128.0

    # --- scalar per-pair loop (the seed's hot path) ------------------------
    t0 = time.perf_counter()
    M_s = np.empty((n_tasks, n_nodes))
    S_s = np.empty((n_tasks, n_nodes))
    for i, tn in enumerate(names):
        for j, nd in enumerate(nodes):
            M_s[i, j], S_s[i, j] = est.predict(tn, nd, size)
    scalar_s = time.perf_counter() - t0

    # --- batched matrix path ----------------------------------------------
    est.predict_matrix(nodes, size)            # build cache + jit warm-up
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        M_b, S_b = est.predict_matrix(nodes, size)
    batched_s = (time.perf_counter() - t0) / reps

    rel_mean = np.max(np.abs(M_b - M_s) / np.maximum(np.abs(M_s), 1e-12))
    rel_std = np.max(np.abs(S_b - S_s) / np.maximum(np.abs(S_s), 1e-12))
    pairs = n_tasks * n_nodes
    speedup = scalar_s / batched_s

    # --- HEFT: dict reference vs ndarray fast path -------------------------
    tasks = _layered_dag(n_tasks, depth=10, rng=rng)
    ids = list(tasks)
    cost_d = {tid: {nd: float(M_s[i, j]) for j, nd in enumerate(nodes)}
              for i, tid in enumerate(ids)}
    t0 = time.perf_counter()
    ref = heft_schedule_reference(tasks, cost_d, nodes)
    heft_dict_s = time.perf_counter() - t0

    idx = {tid: i for i, tid in enumerate(ids)}
    succ = [[idx[s] for s in tasks[t].succ] for t in ids]
    pred = [[idx[p] for p in tasks[t].pred] for t in ids]
    heft_schedule_array(succ, pred, M_b)       # warm-up (numpy, ~no-op)
    t0 = time.perf_counter()
    arr = heft_schedule_array(succ, pred, M_b)
    heft_array_s = time.perf_counter() - t0
    heft_match = (abs(arr["makespan"] - ref["makespan"])
                  / max(ref["makespan"], 1e-12) < 1e-9)

    result = {
        "config": {"n_tasks": n_tasks, "n_nodes": n_nodes, "pairs": pairs,
                   "x64": True},
        "scalar_predict_s": scalar_s,
        "batched_predict_s": batched_s,
        "scalar_pairs_per_s": pairs / scalar_s,
        "batched_pairs_per_s": pairs / batched_s,
        "predict_speedup": speedup,
        "max_rel_diff_mean": float(rel_mean),
        "max_rel_diff_std": float(rel_std),
        "heft_dict_s": heft_dict_s,
        "heft_array_s": heft_array_s,
        "heft_speedup": heft_dict_s / heft_array_s,
        "heft_makespans_match": bool(heft_match),
    }
    OUT.write_text(json.dumps(result, indent=2))
    print(f"predict: scalar {scalar_s:.2f}s vs batched {batched_s*1e3:.1f}ms "
          f"for {pairs} pairs -> {speedup:.0f}x "
          f"(max rel diff mean={rel_mean:.2e}, std={rel_std:.2e})")
    print(f"HEFT {n_tasks}x{n_nodes}: dict {heft_dict_s:.2f}s vs array "
          f"{heft_array_s*1e3:.0f}ms -> {heft_dict_s/heft_array_s:.1f}x "
          f"(makespans match: {heft_match})")
    print(f"wrote {OUT}")
    return [("bench_predict.matrix_speedup", batched_s * 1e6,
             f"speedup={speedup:.0f}x;rel={rel_mean:.1e}"),
            ("bench_predict.heft_speedup", heft_array_s * 1e6,
             f"speedup={heft_dict_s/heft_array_s:.1f}x;match={heft_match}")]


if __name__ == "__main__":
    enable_compile_cache()
    run()
