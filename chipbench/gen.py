"""Inputs made from the seed: profiling benches, local runs, ground-truth
runtimes, and the workflow's instance graph.

The ground truth follows the nf-core task model of the configuration file:
a task's runtime on a node is its CPU part scaled by the node's CPU score
plus its I/O part scaled by the node's I/O rate, times a fixed
per-(task, node) efficiency and a per-run lognormal jitter.  Benches are
the node's true rates with measurement noise.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    """An independent stream per (seed, purpose)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2 ** 64,
                                *(t % 2 ** 32 for t in tags)]))


def partition_sizes(x: float, n: int) -> list[float]:
    """The paper's geometric downsampling ladder X/2, X/4, ..., X/2^n."""
    out, s = [], x / 2.0
    for _ in range(n):
        out.append(s)
        s /= 2.0
    return out


# ---- the nf-core deployment ------------------------------------------------
def _effective(kind: str, size: float) -> float:
    return 0.0 if kind == "flat" else size ** 0.5 if kind == "sqrt" else size


def task_runtime(task, node, size, ref, cpu_factor=1.0) -> float:
    """Noise-free runtime of ``task`` (name, cpu_unit, io_unit, kind, base)
    on ``node`` at input ``size`` GB; ``ref`` is the reference machine."""
    _, cpu_u, io_u, kind, base = task
    share = cpu_u / max(cpu_u + io_u, 1e-9)
    s = _effective(kind, size)
    cpu_t = (base * share + cpu_u * s) * (ref["cpu"] / node["cpu"]) \
        / cpu_factor
    io_t = (base * (1 - share) + io_u * s) * (ref["io"] / node["io"])
    return cpu_t + io_t


def bench(node, rng, noise) -> dict:
    """A microbenchmark result of ``node``: its rates times lognormal
    measurement noise (field names of the program's ``BenchResult``)."""
    def meas(x):
        return float(x * rng.lognormal(0.0, noise))
    return {"node": node["name"], "cpu_events_s": meas(node["cpu"]),
            "matmul_gflops": meas(node["gflops"]),
            "mem_gbps": meas(node["mem_gbps"]),
            "io_read_mbps": meas(node["io"]),
            "io_write_mbps": meas(node["io"] * 0.98),
            "link_gbps": meas(node["link_gbps"])}


def eager_data(cfg: dict, seed: int) -> dict:
    """Benches, per-pair efficiencies and the local profiling runs of one
    deployment: ``runs[(task, size, cpu_factor)]`` for every partition at
    full speed and the first ``slow_partitions`` throttled."""
    rng = rng_for(seed, 1)
    local, types = cfg["local"], cfg["node_types"]
    b_local = bench(local, rng, cfg["bench_noise"])
    b_types = [bench(t, rng, cfg["bench_noise"]) for t in types]
    eff = {(t[0], n["name"]): float(np.exp(rng.normal(0.0, cfg["systematic"])))
           for t in cfg["tasks"] for n in [local] + types}
    sizes = partition_sizes(cfg["input_gb"], cfg["partitions"])
    slow_cf = 1.0 - cfg["freq_reduction"]
    runs = {}
    for t in cfg["tasks"]:
        for k, s in enumerate(sizes):
            for cf in ((1.0, slow_cf) if k < cfg["slow_partitions"]
                       else (1.0,)):
                runs[(t[0], s, cf)] = (
                    task_runtime(t, local, s, local, cf)
                    * eff[(t[0], local["name"])]
                    * float(rng.lognormal(0.0, cfg["noise"])))
    return {"local": b_local, "types": b_types, "eff": eff,
            "sizes": sizes, "slow_cf": slow_cf, "runs": runs}


def instances(deps: dict, n_samples: int):
    """The workflow's instance graph: ``n_samples`` samples, each through
    every task of ``deps`` (task -> the tasks whose output it reads), in a
    topological order: instance id ``s<k>.<task>`` -> (task, predecessor
    ids)."""
    order, done = [], set()
    while len(order) < len(deps):
        ready = [t for t in deps if t not in done
                 and all(p in done for p in deps[t])]
        if not ready:
            raise ValueError("the task graph has a cycle")
        order.extend(ready)
        done.update(ready)
    return {f"s{s}.{name}": (name, [f"s{s}.{p}" for p in deps[name]])
            for s in range(n_samples) for name in order}


def eager_truth(cfg: dict, data: dict, dag: dict, seed: int, run: int):
    """Ground-truth runtime of every (instance, node type) for one run."""
    rng = rng_for(seed, 2, run)
    tasks = {t[0]: t for t in cfg["tasks"]}
    local = cfg["local"]
    out = {}
    for tid, (name, _) in dag.items():
        for n in cfg["node_types"]:
            out[(tid, n["name"])] = (
                task_runtime(tasks[name], n, cfg["input_gb"], local)
                * data["eff"][(name, n["name"])]
                * float(rng.lognormal(0.0, cfg["noise"])))
    return out
