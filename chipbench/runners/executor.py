"""Closed-loop execution of whole workflows through ``OnlineExecutor``.

Set-up fits the estimator once on the local profiling runs and runs a few
warm-up workflows (the configuration's ``warmup_runs``), which compile
every program a run uses.  The window then runs workflows one after
another, each from a copy of the fitted estimator and with ground truth
drawn from the seed.  A scheduling tick is one pass of the executor's
event loop, read from the starts of the program's own ``tick_step``
spans.  The run in flight at the close is finished untimed when it is the
only one, so there is always a whole run to check; otherwise it is
stopped at its next tick.

A closed-loop traffic file may carry a ``faults`` block::

    "faults": {"crash": [[0, 0.25], [-1, 0.5]], "p_fail": 0.05,
               "p_spread": 1.0}

Each ``crash`` entry is (index into the grid's node names, fraction of
the fault-free makespan M): that node crashes at that share of M and does
not return.  M is the median makespan of set-up's fault-free warm-up
runs; set-up then runs one more warm-up under faults.  Every run r gets
the program's ``FaultInjector`` with those crash times, the per-attempt
failure probability ``p_fail`` spread by ``p_spread``, and a seed drawn
from the cell's seed and r.  The executor's own fault-tolerance settings
(``rel_k``, ``max_attempts``, ``backoff_*``, ``strict``) are keywords of
the configuration's ``executor`` block, which a faults traffic has to give
``rel_k``, ``max_attempts``, ``backoff_base`` and ``backoff_cap``, since
the reference reads them there.  The check then also holds every run to
the fault semantics of ``reference_faults``.  Without the block no fault
process exists and the runs are as before.
"""
from __future__ import annotations

import copy
import math
import statistics
import sys

import numpy as np

import gen
import reference_faults as rf
from harness import BenchError, WindowClosed
from reference import (Reference, cpu_weight, rel_gap, runtime_factors,
                       schedule_errors, tick_index)

#: ``gen.rng_for`` tag of the fault injectors' seeds (1 and 2 are the
#: deployment's data and each run's ground truth)
FAULT_TAG = 3
#: what a faults traffic needs the configuration's executor block to state
FAULT_KEYS = {"rel_k", "max_attempts", "backoff_base", "backoff_cap"}


class Runner:
    kind = "executor"

    def __init__(self, cfg: dict, traffic: dict, seed: int, rec):
        from repro.core import LotaruEstimator
        from repro.core.nodes import get_node
        from repro.core.profiler import BenchResult
        if traffic.get("arrivals") != "closed":
            raise BenchError("the executor runner runs closed-loop "
                             "workflow traffic only")
        samples = int(traffic["samples_per_run"])
        self.cfg, self.seed, self.rec = cfg, seed, rec
        self.data = gen.eager_data(cfg, seed)
        self.names = [t[0] for t in cfg["tasks"]]
        self.types = [get_node(n["name"]) for n in cfg["node_types"]]
        est = LotaruEstimator(
            BenchResult(**self.data["local"]),
            {b["node"]: BenchResult(**b) for b in self.data["types"]},
            freq_reduction=cfg["freq_reduction"], **cfg["estimator"])
        est.fit_tasks(self.names, cfg["input_gb"], self._run_local,
                      n_partitions=cfg["partitions"],
                      slow_partitions=cfg["slow_partitions"])
        self.est0 = est
        self.dag = gen.instances(cfg["deps"], samples)
        self.tasks = self._sched_tasks(self.dag)
        self.truths = {}
        makespans = []
        for k in range(1, cfg["warmup_runs"] + 1):
            ex = self._warm_up(k)
            makespans.append(ex.run().makespan)
        self.faults = traffic.get("faults")
        if self.faults is not None:
            if not makespans:
                raise BenchError("a faults traffic needs warmup_runs >= 1 "
                                 "for its fault-free makespan")
            self._fault_setup(ex.grid.names(), statistics.median(makespans))
            k = cfg["warmup_runs"] + 1
            self._warm_up(k, self.injector(-k)).run()
        self.runs: list[tuple[int, object, dict]] = []
        # a faults run's final reliability state (host counts), for check
        self.reliability: dict[int, object] = {}
        for r in range(2):      # the first runs' ground truth, before the window
            self._truth(r)
        self.shape = {"T": len(self.names), "N": len(self.types),
                      "Nb": len(self.types) + 1, "B": 1}

    def _warm_up(self, k: int, faults=None):
        """The executor of warm-up run ``-k``."""
        self.rec.run = -k
        truth = gen.eager_truth(self.cfg, self.data, self.dag, self.seed, -k)
        return self._executor(self._sched_tasks(self.dag), self.dag, truth,
                              self.rec, faults)

    def _fault_setup(self, names: list[str], makespan: float) -> None:
        """Crash times from the traffic's ``crash`` list and the fault-free
        makespan."""
        missing = FAULT_KEYS - set(self.cfg["executor"])
        if missing:
            raise BenchError("a faults traffic needs the configuration's "
                             f"executor block to set {sorted(missing)}")
        self.node_names = names
        self.crash_at = {names[i]: frac * makespan
                         for i, frac in self.faults["crash"]}
        print(f"chipbench: fault-free makespan {makespan!r} s (median of "
              f"{self.cfg['warmup_runs']} warm-up runs); crashes "
              f"{self.crash_at!r}", file=sys.stderr, flush=True)

    def fault_seed(self, run: int) -> int:
        return int(gen.rng_for(self.seed, FAULT_TAG, run).integers(2 ** 31))

    def injector(self, run: int):
        """The program's fault process for run ``run``."""
        from repro.sched.simulator import FaultInjector
        f = self.faults
        return FaultInjector(crash_at=self.crash_at, p_fail=f["p_fail"],
                             p_spread=f["p_spread"],
                             seed=self.fault_seed(run))

    def _run_local(self, name: str, size: float, cpu_factor: float) -> float:
        """The recorded local run of ``name`` at the partition ``size``."""
        d = self.data
        s = next(v for v in d["sizes"] if math.isclose(v, size, rel_tol=1e-9))
        cf = next(v for v in (1.0, d["slow_cf"])
                  if math.isclose(v, cpu_factor, rel_tol=1e-9))
        return d["runs"][(name, s, cf)]

    @staticmethod
    def _sched_tasks(dag):
        from repro.sched.heft import SchedTask
        tasks = {tid: SchedTask(id=tid) for tid in dag}
        for tid, (_, preds) in dag.items():
            for p in preds:
                tasks[tid].pred.append(p)
                tasks[p].succ.append(tid)
        return tasks

    def _executor(self, tasks, dag, truth, rec, faults=None):
        from repro.online import OnlineExecutor
        from repro.sched.simulator import GridEngine
        cfg = self.cfg
        grid = GridEngine.from_types(nodes_per_type=cfg["nodes_per_type"],
                                     types=self.types)
        return OnlineExecutor(
            copy.deepcopy(self.est0), tasks,
            {tid: name for tid, (name, _) in dag.items()}, cfg["input_gb"],
            grid, lambda tid, node: truth[(tid, grid.type_of(node).name)],
            tracer=rec, faults=faults, **cfg["executor"])

    def _truth(self, run: int) -> dict:
        if run not in self.truths:
            self.truths[run] = gen.eager_truth(self.cfg, self.data, self.dag,
                                               self.seed, run)
        return self.truths[run]

    def run_window(self, win) -> None:
        rec = self.rec
        state = {"closed": False}

        def hook(phase, t0, _data):
            if phase == "tick_step" and t0 >= win.t_close:
                if not state["closed"]:
                    state["closed"] = True
                    win.end(t0)
                if self.runs:
                    raise WindowClosed

        rec.hook = hook
        r = 0
        try:
            while not win.closed():
                rec.run = r
                truth = self._truth(r)
                faults = (self.injector(r) if self.faults is not None
                          else None)
                with rec.span("run"):
                    with rec.span("run_setup"):
                        ex = self._executor(self.tasks, self.dag, truth, rec,
                                            faults)
                    trace = ex.run()
                self.runs.append((r, trace, truth))
                if faults is not None:
                    self.reliability[r] = ex.est.reliability
                r += 1
        except WindowClosed:
            pass
        finally:
            rec.hook = None
        if not state["closed"]:
            win.end()

    def ticks(self, win) -> tuple[list[float], int]:
        """Tick latencies (s) inside the window — between consecutive
        ``tick_step`` starts of one run — and the observations absorbed."""
        spans = self.rec.of("tick_step", win.t_open, win.t_end)
        lat = [b[1] - a[1] for a, b in zip(spans, spans[1:]) if a[3] == b[3]]
        return lat, sum(s[4] for s in spans)

    def release(self) -> None:
        self.est0 = None

    # ---- correctness ----------------------------------------------------
    def _reference(self, dtype):
        d, cfg = self.data, self.cfg
        samples, ws = [], []
        for name in self.names:
            normal = [d["runs"][(name, s, 1.0)] for s in d["sizes"]]
            slow = [d["runs"][(name, s, d["slow_cf"])]
                    for s in d["sizes"][:cfg["slow_partitions"]]]
            samples.append((np.array(d["sizes"]), np.array(normal)))
            ws.append(cpu_weight(normal, slow, d["slow_cf"]))

        def score(b):
            return {"cpu": b["cpu_events_s"],
                    "io": 0.5 * (b["io_read_mbps"] + b["io_write_mbps"])}
        factors = runtime_factors(ws, score(d["local"]),
                                  [score(b) for b in d["types"]])
        n = len(self.types)
        return Reference(samples, factors, np.arange(1, n + 1), n + 1,
                         cfg["input_gb"], dtype)

    def _replay(self, trace, dtype):
        """Dispatch-time estimates and de-adjusted runtimes of one run,
        from the reference absorbing the run's own ticks."""
        ref = self._reference(dtype)
        row = {nm: i for i, nm in enumerate(self.names)}
        col = {t.name: j for j, t in enumerate(self.types)}
        times, ests, y_local = [], [ref.estimate()], []
        for t, obs in trace.observations.by_tick():
            y_local.extend(ref.tick([(row[o.task], col[o.node], o.size,
                                      o.runtime) for o in obs]))
            times.append(t)
            ests.append(ref.estimate())
        rows, mean, std, alt_m, alt_s, tie, skip = ([] for _ in range(7))
        amb = ref.ambiguous_cells()
        for rec in trace.records:
            (m, s), alt, tie_rows = ests[tick_index(times, rec.start)]
            i, j = row[rec.name], col[rec.node_type]
            rows.append((rec.pred_mean, rec.pred_std))
            mean.append(m[i, j])
            std.append(s[i, j])
            alt_m.append(alt[0][i, j] if alt is not None else m[i, j])
            alt_s.append(alt[1][i, j] if alt is not None else s[i, j])
            tie.append(bool(tie_rows[i]))
            skip.append(bool(amb[i, j]))
        return {"prog": np.array(rows).reshape(-1, 2),
                "mean": np.array(mean, np.float64),
                "std": np.array(std, np.float64),
                "alt": np.stack([alt_m, alt_s], -1).astype(np.float64),
                "tie": np.array(tie), "skip": np.array(skip),
                "y_local": np.array(y_local, np.float64),
                "y_prog": np.array([o.local_runtime
                                    for o in trace.observations])}

    def check(self, control: bool = False) -> dict:
        """The numbers compared, by name (their limits are in the
        configuration).  With ``control`` the reference computed in
        bfloat16 takes the program's place: the estimates and de-adjusted
        runtimes compared are the control's, at the same ticks."""
        sched, est_gap, dadj_gap = 0, 0.0, 0.0
        preds = {tid: p for tid, (_, p) in self.dag.items()}
        for _, trace, truth in self.runs:
            sched += schedule_errors(
                [{"id": r.id, "node": r.node, "node_type": r.node_type,
                  "start": r.start, "end": r.end, "runtime": r.runtime}
                 for r in trace.records], preds, truth)
            sched += trace.total - trace.completed
            rp = self._replay(trace, np.float64)
            prog, y_prog = rp["prog"], rp["y_prog"]
            if control:
                import ml_dtypes
                cp = self._replay(trace, ml_dtypes.bfloat16)
                prog = np.stack([cp["mean"], cp["std"]], -1)
                y_prog = cp["y_local"]
            est_gap = max(est_gap, _est_gap(prog, rp))
            dadj_gap = max(dadj_gap, rel_gap(y_prog, rp["y_local"]))
        out = {"runs_checked": len(self.runs), "schedule_errors": sched,
               "estimate_gap": est_gap, "deadjust_gap": dadj_gap}
        if self.faults is not None:
            out.update(self._check_faults())
        return out

    def _check_faults(self) -> dict:
        """The fault semantics of every checked run, against
        ``reference_faults``: schedule errors under the crashes and the
        backoff, the gap of the run's final reliability factors, and the
        runs that lost fewer nodes than the traffic crashes, plus one when
        no attempt of the window failed by itself."""
        ex = self.cfg["executor"]
        errors, gap, short, attempt_failed = 0, 0.0, 0, False
        for r, trace, _ in self.runs:
            recs = [{"id": x.id, "node": x.node, "start": x.start,
                     "end": x.end} for x in trace.records]
            cens = [{"id": c.id, "node": c.node, "start": c.start,
                     "lost_at": c.lost_at, "reason": c.reason}
                    for c in trace.censored]
            errors += rf.fault_schedule_errors(
                recs, cens, self.crash_at, ex["max_attempts"],
                len(trace.observations),
                (ex["backoff_base"], ex["backoff_cap"]))
            succ, fail = rf.attempt_counts(recs, cens)
            rel = self.reliability[r]
            prog = (np.ones(len(self.node_names)) if rel is None
                    else rel.factors(self.node_names, ex["rel_k"]))
            gap = max(gap, rel_gap(prog, rf.reliability_factors(
                self.node_names, succ, fail, ex["rel_k"])))
            attempt_failed |= any(c["reason"] == "attempt" for c in cens)
            short += trace.lost_nodes < len(self.crash_at)
        short += self.faults["p_fail"] > 0 and not attempt_failed
        runs = [t for _, t, _ in self.runs]
        print("chipbench: per run failures "
              f"{[t.failures for t in runs]}, retries "
              f"{[t.retries for t in runs]}, lost nodes "
              f"{[t.lost_nodes for t in runs]}, re-plans "
              f"{[t.replans for t in runs]}", file=sys.stderr)
        return {"fault_schedule_errors": errors, "reliability_gap": gap,
                "fault_shortfall": int(short)}


def _est_gap(prog, rp) -> float:
    """Widest gap of dispatch-time (mean, std) against the reference."""
    ref = np.stack([rp["mean"], rp["std"]], -1)
    return max(rel_gap(prog[:, k:k + 1], ref[:, k:k + 1],
                       rp["alt"][:, k:k + 1], rp["tie"],
                       rp["skip"][:, None]) for k in range(2))
