"""Online estimation subsystem benchmark.  Writes ``BENCH_online.json``.

Three measurements:

1. incremental-update throughput — one ``update_task_batch`` observation
   vs a full ``fit_task_batch`` refit at ~1000 tasks (the re-prediction
   hot path during execution), plus the ``lax.scan`` stream rate;
2. incremental-vs-refit equivalence — max relative difference of the
   predictive means/stds after a shuffled stream (x64, so the gap is
   algorithmic, not float32);
3. static-plan vs online re-scheduling — makespan and cumulative MPE
   trajectory of the event-driven executor across the paper's five
   workflows on the heterogeneous cluster (ground truth carries the
   simulator's systematic per-(task, node) efficiency the initial factor
   adjustment cannot see — exactly what streaming observations recover).
   Four arms per workflow: static (frozen predictions), online without
   the bias layer (the PR 2 loop), online with the per-(task, node)
   bias posterior + same-tick batching + bias-coupled straggler copies
   (the PR 3 loop), and the risk-aware arm — bias + empirical-Bayes
   sigma_r pooling + uncertainty-priced HEFT (effective cost
   mean + risk_k * widened sigma) + tail-mass speculative admission.
   The bias arm must beat the PR 2 arm's final MPE on most workflows
   (the systematic efficiency IS a per-pair multiplicative bias), and
   the risk arm must win or tie the bias arm's final makespan on most
   workflows (pricing posterior width steers work off jittery pairs).

A fourth section (``faults``) sweeps the default crash scenario — two
nodes dying mid-run plus a ~5% per-attempt failure probability — and
checks that the fault-tolerant loop (retries with capped backoff,
censored observations, Beta-Binomial reliability pricing) completes
100% of every workflow within a committed makespan-inflation bound,
while the frozen static plan strands the dead nodes' work.

A fifth section (``scale``) sweeps the (T, N) estimate-matrix size to
~1M cells and the stacked workflow axis W to 64: steady-state per-tick
wall time of the fused ``tick_step`` engine vs the legacy
observe → update → bias scatter → dirty-row re-predict sequence (same
observation batches, per-phase spans through the ``repro.obs`` lanes),
plus the vmapped/sharded fleet tick's cell throughput.  The gate
asserts the fused tick beats legacy by ``SCALE_MIN_SPEEDUP``x at the
100k-cell point.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro.core import LotaruEstimator, TickEngine, blr, build_state, \
    get_node, profile_cluster, profile_node, target_nodes
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_fleet_mesh
from repro.obs import (EventLog, calibration_summary, observe_records,
                       tick_latency_summary)
from repro.data.synthetic import (scale_estimator, synthetic_dag,
                                  synthetic_samples)
from repro.online import OnlineExecutor, fanout_chain_dag
from repro.online.fleet import fleet_tick_step, shard_fleet, stack_states
from repro.sched.heft import (CommCosts, heft_schedule_array,
                              realized_makespan)
from repro.sched.simulator import (ClusterSimulator, FaultInjector,
                                   GridEngine, Topology)
from repro.sched.workflows import INPUTS, WORKFLOWS, dag_edge_gb

OUT = Path(__file__).resolve().parents[1] / "BENCH_online.json"
TRACES = Path(__file__).resolve().parents[1] / "traces"

#: calibration gate inputs: coverage of the 90% predictive interval must
#: land in CAL_BAND once CAL_MIN_OBS observations have streamed in (the
#: warm-up reflects the near-prior posterior, not the online estimator)
CAL_MIN_OBS = 20
CAL_BAND = (0.80, 0.98)
Z90 = 1.6448536269514722     # Phi^-1(0.95): the 90% two-sided z quantile


def bench_update_throughput(n_tasks: int = 1000, n_updates: int = 500):
    sizes_list, runtimes_list = synthetic_samples(n_tasks)
    model = blr.fit_task_batch(sizes_list, runtimes_list)

    # full-refit steady state (the seed's only way to absorb a sample)
    reps = 3
    blr.fit_task_batch(sizes_list, runtimes_list)        # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        blr.fit_task_batch(sizes_list, runtimes_list)
    refit_s = (time.perf_counter() - t0) / reps

    # single-observation updates: warm the jit, then time row-scattered
    # updates (repeats allowed — log growth is host-side and amortised)
    rng = np.random.default_rng(1)
    model = blr.update_task_batch(model, 0, 300.0, 400.0)   # compile
    rows = rng.integers(0, n_tasks, n_updates)
    xs = rng.uniform(1, 300, n_updates)
    ys = rng.uniform(1, 500, n_updates)
    jax.block_until_ready(model.post.mu)
    t0 = time.perf_counter()
    for r, x, y in zip(rows, xs, ys):
        model = blr.update_task_batch(model, int(r), float(x), float(y))
    jax.block_until_ready(model.post.mu)
    update_s = (time.perf_counter() - t0) / n_updates

    # scanned stream (no per-observation Python dispatch); the stream
    # consumes its model (shared sample log), so warm and timed runs each
    # get a fresh fit — the scan jit cache is shared between them
    stream_n = 4 * n_updates
    idx = rng.integers(0, n_tasks, stream_n)
    sx = rng.uniform(1, 300, stream_n)
    sy = rng.uniform(1, 500, stream_n)
    warm = blr.fit_task_batch(sizes_list, runtimes_list)
    m = blr.update_task_batch_stream(warm, idx, sx, sy)      # warm scan
    jax.block_until_ready(m.post.mu)
    model2 = blr.fit_task_batch(sizes_list, runtimes_list)
    t0 = time.perf_counter()
    m = blr.update_task_batch_stream(model2, idx, sx, sy)
    jax.block_until_ready(m.post.mu)
    stream_s = (time.perf_counter() - t0) / stream_n

    return {
        "n_tasks": n_tasks,
        "refit_s": refit_s,
        "update_s": update_s,
        "stream_update_s": stream_s,
        "update_speedup_vs_refit": refit_s / update_s,
        "stream_speedup_vs_refit": refit_s / stream_s,
        "stream_obs_per_s": 1.0 / stream_s,
    }


def bench_equivalence(n_tasks: int = 200, per_task: int = 5, seed: int = 2):
    rng = np.random.default_rng(seed)
    sizes_list, runtimes_list = synthetic_samples(n_tasks, seed=seed)
    model = blr.fit_task_batch(sizes_list, runtimes_list)
    stream = [(int(rng.integers(0, n_tasks)), float(rng.uniform(1, 400)),
               float(rng.uniform(1, 600)))
              for _ in range(per_task * n_tasks)]
    m_inc = blr.update_task_batch_stream(
        model, [s[0] for s in stream], [s[1] for s in stream],
        [s[2] for s in stream])
    concat_s = [np.concatenate([sizes_list[i],
                                [s[1] for s in stream if s[0] == i]])
                for i in range(n_tasks)]
    concat_r = [np.concatenate([runtimes_list[i],
                                [s[2] for s in stream if s[0] == i]])
                for i in range(n_tasks)]
    m_ref = blr.fit_task_batch(concat_s, concat_r)
    worst_mean = worst_std = 0.0
    for xq in (2.0, 64.0, 350.0):
        mi, si = blr.predict_task_batch(m_inc, xq)
        mr, sr = blr.predict_task_batch(m_ref, xq)
        worst_mean = max(worst_mean, float(np.max(
            np.abs(np.asarray(mi) - np.asarray(mr))
            / np.maximum(np.abs(np.asarray(mr)), 1e-12))))
        worst_std = max(worst_std, float(np.max(
            np.abs(np.asarray(si) - np.asarray(sr))
            / np.maximum(np.abs(np.asarray(sr)), 1e-12))))
    gate_equal = bool((np.asarray(m_inc.correlated)
                       == np.asarray(m_ref.correlated)).all())
    return {"n_tasks": n_tasks, "stream_len": len(stream),
            "max_rel_diff_mean": worst_mean, "max_rel_diff_std": worst_std,
            "pearson_gate_equal": gate_equal}


RISK_K = 1.0        # risk-aware arm: effective cost = mean + RISK_K * sigma
SPEC_TAIL = 0.8     # tail-mass admission: P(bias > drift) >= 0.8


def _calibration(events) -> dict:
    """Per-workflow calibration record for the gate: both coverage forms
    of the 90% predictive interval, post-warm-up.  ``coverage90`` scores
    the executor's own t-intervals (the surprise-gate bounds);
    ``coverage90_z`` scores ``pred_mean ± Z90 * pred_std`` — the Gaussian
    interval implied by the σ that ``risk_k`` pricing and tail-mass
    speculation actually consume, which is what the gate checks."""
    cal = calibration_summary(events, min_obs=CAL_MIN_OBS)
    recs = observe_records(events)[CAL_MIN_OBS:]
    if recs:
        cov_z = float(np.mean([
            abs(r["runtime"] - r["pred_mean"]) <= Z90 * r["pred_std"]
            for r in recs]))
    else:
        cov_z = float("nan")
    return {"n_obs": cal["n_obs"], "min_obs": CAL_MIN_OBS,
            "coverage90": cal["coverage"], "coverage90_z": cov_z,
            "coverage90_all": cal["coverage_all"],
            "sharpness_rel": cal["sharpness_rel"],
            "pit_tv": cal["pit_tv"]}


def _in_band(r: dict) -> bool:
    return (r["calibration_n_obs"] >= CAL_MIN_OBS
            and CAL_BAND[0] <= r["coverage90_z"] <= CAL_BAND[1])


def bench_workflows(n_samples: int = 8, nodes_per_type: int = 2,
                    seed: int = 0, trace_dir: Path | None = TRACES):
    local = get_node("local-cpu")
    local_bench = profile_node(local, np.random.default_rng(seed + 7))
    tbenches = profile_cluster(target_nodes(), seed=seed + 13)
    truth = ClusterSimulator(seed=seed + 2000)
    results = {}
    observability: dict = {}
    overhead = None
    for wf in WORKFLOWS:
        size = INPUTS[(wf, 1)]
        by_name = {t.name: t for t in WORKFLOWS[wf]}
        tasks, task_name = fanout_chain_dag(list(by_name), n_samples)
        # deterministic ground truth per (instance, node type): realised
        # runtimes carry noise + the hidden systematic efficiency
        truth_tab = {(tid, nt.name): truth.run_task(by_name[task_name[tid]],
                                                    nt, size)
                     for tid in tasks for nt in target_nodes()}

        def make_executor(online: bool, bias_correction: bool = True,
                          risk: bool = False, tracer=None):
            sim = ClusterSimulator(seed=seed)     # same local runs each time
            est = LotaruEstimator(local_bench, tbenches,
                                  bias_correction=bias_correction,
                                  bias_empirical_bayes=risk)
            est.fit_tasks(list(by_name), size,
                          lambda n, s, cf: sim.run_task(by_name[n], local, s,
                                                        cpu_factor=cf))
            grid = GridEngine.from_types(nodes_per_type=nodes_per_type)
            return OnlineExecutor(
                est, tasks, task_name, size, grid,
                lambda tid, node: truth_tab[(tid, grid.type_of(node).name)],
                online=online, confidence=0.9,
                risk_k=RISK_K if risk else 0.0,
                spec_tail=SPEC_TAIL if risk else None, tracer=tracer)

        # clear the jit cache between arms: every arm compiles its own
        # spread of XLA executables (one scan per distinct tick batch
        # size, one HEFT solve per frontier shape) and the leftover
        # modules exhaust the kernel's vm.max_map_count long before
        # they exhaust memory
        static = make_executor(online=False).run()
        jax.clear_caches()
        nobias = make_executor(online=True, bias_correction=False).run()
        jax.clear_caches()
        if overhead is None:
            # tracing overhead, measured once: the same online arm with
            # no tracer attached, timed cold (fresh jit cache) like the
            # traced run below — the delta is what the EventLog costs
            t0 = time.perf_counter()
            make_executor(online=True).run()
            wall_plain = time.perf_counter() - t0
            jax.clear_caches()
        log = EventLog()
        t0 = time.perf_counter()
        online = make_executor(online=True, tracer=log).run()
        wall_traced = time.perf_counter() - t0
        if overhead is None:
            overhead = {"workflow": wf, "wall_untraced_s": wall_plain,
                        "wall_traced_s": wall_traced,
                        "overhead_frac": wall_traced / wall_plain - 1.0,
                        "n_events": len(log.events),
                        "per_event_us": (wall_traced - wall_plain)
                        / max(len(log.events), 1) * 1e6}
        jax.clear_caches()
        risk = make_executor(online=True, risk=True).run()
        if trace_dir is not None:
            trace_dir.mkdir(parents=True, exist_ok=True)
            log.to_jsonl(trace_dir / f"{wf}.jsonl")
            log.to_chrome(trace_dir / f"{wf}.chrome.json")
        cal = _calibration(log.events)
        lat = tick_latency_summary(log.events)
        observability[wf] = {"n_events": len(log.events),
                             "tick_latency": lat}
        traj_s = static.cumulative_mpe()
        traj_o = online.cumulative_mpe()
        results[wf] = {
            "instances": len(tasks),
            "makespan_static": static.makespan,
            "makespan_online_nobias": nobias.makespan,
            "makespan_online": online.makespan,
            "makespan_online_risk": risk.makespan,
            "mpe_static": static.final_mpe(),
            "mpe_online_nobias": nobias.final_mpe(),
            "mpe_online": online.final_mpe(),
            "mpe_online_risk": risk.final_mpe(),
            "mpe_traj_static_first_last": [float(traj_s[0]),
                                           float(traj_s[-1])],
            "mpe_traj_online_first_last": [float(traj_o[0]),
                                           float(traj_o[-1])],
            "replans": online.replans,
            "surprises": online.surprises,
            "speculations": online.speculations,
            "spec_wins": online.spec_wins,
            "risk_replans": risk.replans,
            "risk_speculations": risk.speculations,
            "risk_spec_wins": risk.spec_wins,
            "calibration_n_obs": cal["n_obs"],
            "coverage90": cal["coverage90"],
            "coverage90_z": cal["coverage90_z"],
            "calibration": cal,
        }
        # every workflow/arm combination compiles its own set of XLA
        # executables (frontier sizes vary per re-plan); left to
        # accumulate across the sweep they exhaust the kernel's
        # vm.max_map_count before they exhaust memory
        jax.clear_caches()
    wins = sum(1 for r in results.values()
               if r["mpe_online"] < r["mpe_static"])
    bias_wins = sum(1 for r in results.values()
                    if r["mpe_online"] < r["mpe_online_nobias"])
    makespan_wins = sum(1 for r in results.values()
                        if r["makespan_online"] <= r["makespan_static"])
    # win-or-tie: risk pricing may leave a placement unchanged (same
    # argmin), which is success, not failure — ties count
    risk_makespan_wins = sum(
        1 for r in results.values()
        if r["makespan_online_risk"] <= r["makespan_online"] * (1 + 1e-9))
    calibration_in_band = sum(1 for r in results.values() if _in_band(r))
    return {"workflows": results, "n_samples": n_samples,
            "nodes_per_type": nodes_per_type,
            "risk_k": RISK_K, "spec_tail": SPEC_TAIL,
            "online_mpe_wins": wins, "bias_mpe_wins": bias_wins,
            "online_makespan_wins": makespan_wins,
            "risk_makespan_wins": risk_makespan_wins,
            "calibration_in_band": calibration_in_band,
            "cal_min_obs": CAL_MIN_OBS, "cal_band": list(CAL_BAND),
            "n_workflows": len(results),
            "observability": {"per_workflow": observability,
                              "overhead": overhead,
                              "trace_dir": (str(trace_dir)
                                            if trace_dir else None)}}


FAULT_P = 0.05           # base per-attempt failure probability
FAULT_REL_K = 1.0        # reliability pricing: 1/(E[p] - k*sd)
FAULT_MAX_ATTEMPTS = 6   # per-task attempt budget
INFLATION_BOUND = 2.5    # FT makespan <= bound * fault-free makespan


def bench_fault_tolerance(n_samples: int = 8, nodes_per_type: int = 2,
                          seed: int = 0):
    """Fifth arm: the default crash sweep — two nodes die mid-run and
    every attempt carries a ~5% failure probability.  The fault-tolerant
    loop (retry + backoff + censored observations + reliability-priced
    HEFT) must complete 100% of every workflow with bounded makespan
    inflation over its own fault-free run, while the static plan strands
    whatever its dead nodes owned."""
    local = get_node("local-cpu")
    local_bench = profile_node(local, np.random.default_rng(seed + 7))
    tbenches = profile_cluster(target_nodes(), seed=seed + 13)
    truth = ClusterSimulator(seed=seed + 2000)
    results = {}
    for wf in WORKFLOWS:
        size = INPUTS[(wf, 1)]
        by_name = {t.name: t for t in WORKFLOWS[wf]}
        tasks, task_name = fanout_chain_dag(list(by_name), n_samples)
        truth_tab = {(tid, nt.name): truth.run_task(by_name[task_name[tid]],
                                                    nt, size)
                     for tid in tasks for nt in target_nodes()}

        def make_executor(online: bool, faults=None, strict: bool = True):
            sim = ClusterSimulator(seed=seed)     # same local runs each time
            est = LotaruEstimator(local_bench, tbenches,
                                  bias_correction=True,
                                  bias_empirical_bayes=True)
            est.fit_tasks(list(by_name), size,
                          lambda n, s, cf: sim.run_task(by_name[n], local, s,
                                                        cpu_factor=cf))
            grid = GridEngine.from_types(nodes_per_type=nodes_per_type)
            return OnlineExecutor(
                est, tasks, task_name, size, grid,
                lambda tid, node: truth_tab[(tid, grid.type_of(node).name)],
                online=online, confidence=0.9,
                risk_k=RISK_K, spec_tail=SPEC_TAIL,
                faults=faults, rel_k=FAULT_REL_K,
                max_attempts=FAULT_MAX_ATTEMPTS, strict=strict)

        ref = make_executor(online=True).run()    # fault-free reference
        jax.clear_caches()   # see bench_workflows: bounds mmap growth
        names = list(GridEngine.from_types(
            nodes_per_type=nodes_per_type).nodes)
        crash = {names[0]: 0.25 * ref.makespan,
                 names[-1]: 0.5 * ref.makespan}

        def faults():
            return FaultInjector(crash_at=crash, p_fail=FAULT_P,
                                 seed=seed + 31)

        ft = make_executor(online=True, faults=faults()).run()
        jax.clear_caches()
        static = make_executor(online=False, faults=faults(),
                               strict=False).run()
        results[wf] = {
            "instances": len(tasks),
            "makespan_ref": ref.makespan,
            "makespan_ft": ft.makespan,
            "inflation": ft.makespan / ref.makespan,
            "ft_completed_fraction": ft.completed_fraction(),
            "static_completed_fraction": static.completed_fraction(),
            "failures": ft.failures,
            "retries": ft.retries,
            "lost_nodes": ft.lost_nodes,
            "censored": len(ft.censored),
            "ft_replans": ft.replans,
        }
        jax.clear_caches()   # see bench_workflows: bounds mmap growth
    complete = sum(1 for r in results.values()
                   if r["ft_completed_fraction"] >= 1.0)
    max_inflation = max(r["inflation"] for r in results.values())
    static_strands = sum(1 for r in results.values()
                         if r["static_completed_fraction"] < 1.0)
    return {"workflows": results, "n_samples": n_samples,
            "nodes_per_type": nodes_per_type,
            "p_fail": FAULT_P, "rel_k": FAULT_REL_K,
            "max_attempts": FAULT_MAX_ATTEMPTS,
            "inflation_bound": INFLATION_BOUND,
            "ft_complete": complete, "max_inflation": max_inflation,
            "static_strands": static_strands,
            "n_workflows": len(results)}


# ---------------------------------------------------------------------------
# data-locality arm (PR 10): comm-aware vs comm-blind HEFT on a cross-rack
# cluster, judged by REALIZED makespan; plus the 10k-task scheduling smoke
# ---------------------------------------------------------------------------
LOC_INTRA_GBPS = 10.0    # same-rack bandwidth
LOC_CROSS_GBPS = 0.05    # oversubscribed cross-rack uplink (200x slower)
LOC_DATA_SCALE = 64.0    # edge-volume multiplier: a heavy-data regime
LOC_N_ZONES = 2
LOC_SCALE_MIN_TASKS = 10_000   # the synthetic stress DAG's size floor
LOC_LATENCY_BOUND_S = 30.0     # ... and its schedule-latency ceiling


def _scatter_gather_dag(chain: list[str], n_samples: int):
    """Per-sample scatter/gather instances: the first abstract task is
    the sample's source (QC/staging on the raw input), every middle task
    consumes ITS output in parallel, and the last task (the multiqc-like
    report) gathers them all.  Unlike ``fanout_chain_dag`` — where each
    chain happily serialises on one node and no data ever moves — the
    parallel middle stage MUST spread across nodes, so the source's
    output gets copied and placement faces the real locality trade."""
    from repro.sched.heft import SchedTask
    tasks: dict[str, SchedTask] = {}
    task_name: dict[str, str] = {}
    for s in range(n_samples):
        src, snk = f"s{s}.{chain[0]}", f"s{s}.{chain[-1]}"
        tasks[src] = SchedTask(id=src)
        task_name[src] = chain[0]
        for nm in chain[1:-1]:
            tid = f"s{s}.{nm}"
            tasks[tid] = SchedTask(id=tid, pred=[src])
            tasks[src].succ.append(tid)
            task_name[tid] = nm
        tasks[snk] = SchedTask(id=snk,
                               pred=[f"s{s}.{nm}" for nm in chain[1:-1]])
        for nm in chain[1:-1]:
            tasks[f"s{s}.{nm}"].succ.append(snk)
        task_name[snk] = chain[-1]
    return tasks, task_name


def bench_locality(n_samples: int = 6, nodes_per_type: int = 2,
                   seed: int = 0) -> dict:
    """Sixth arm: data-aware placement on a two-rack cluster.

    Both planners see the SAME noise-free runtime truth; the comm-aware
    one additionally prices per-edge transfer costs (``CommCosts`` over
    the rack topology's secs-per-GB matrix).  Neither plan's own
    optimistic makespan is trusted — both are replayed through
    ``realized_makespan`` under the true transfer prices, so the
    cross-rack copies the blind planner ignored show up in its number.
    The gate: comm-aware realized makespan must win on >= 3/5 workflows
    and never lose by more than 2% (greedy EFT with a transfer term can
    make myopic calls; a bigger regression means mispricing).  A second
    record schedules a >= 10k-task synthetic
    DAG (the WfCommons-style generator) comm-aware and reports the
    latency, bounding the O(T·N + E·N) claim."""
    truth = ClusterSimulator(seed=seed + 2000)
    results = {}
    for wf in WORKFLOWS:
        size = INPUTS[(wf, 1)]
        by_name = {t.name: t for t in WORKFLOWS[wf]}
        tasks, task_name = _scatter_gather_dag(list(by_name), n_samples)
        grid = GridEngine.from_types(nodes_per_type=nodes_per_type)
        names = grid.names()
        # contiguous blocks: each node TYPE lives in one rack, so the
        # fastest hardware is concentrated — chasing speed rack-blind
        # means dragging data across the slow link
        topo = Topology.blocks(names, LOC_N_ZONES,
                               intra_gbps=LOC_INTRA_GBPS,
                               cross_gbps=LOC_CROSS_GBPS)
        spg = topo.secs_per_gb(names)
        ids = list(tasks)
        idx = {tid: i for i, tid in enumerate(ids)}
        succ = [[idx[s] for s in tasks[t].succ] for t in ids]
        pred = [[idx[p] for p in tasks[t].pred] for t in ids]
        cost = np.array([[truth.expected_task_runtime(
            by_name[task_name[tid]], grid.type_of(n), size)
            for n in names] for tid in ids])
        eg = {(idx[p], idx[s]): g * LOC_DATA_SCALE
              for (p, s), g in dag_edge_gb(tasks, task_name, by_name,
                                           size).items()}
        comm = CommCosts(pred, eg, spg)
        blind = heft_schedule_array(succ, pred, cost)
        aware = heft_schedule_array(succ, pred, cost, comm=comm)
        T = len(ids)
        mk = {}
        cross = {}
        for label, s in (("blind", blind), ("aware", aware)):
            dur = cost[np.arange(T), s["assignment"]]
            mk[label] = realized_makespan(succ, pred, dur, s["assignment"],
                                          s["order"], comm=comm)
            cross[label] = sum(
                1 for t in range(T) for p in pred[t]
                if topo.zone(names[s["assignment"][p]])
                != topo.zone(names[s["assignment"][t]]))
        results[wf] = {
            "instances": T,
            "makespan_blind": mk["blind"],
            "makespan_aware": mk["aware"],
            "plan_makespan_blind": blind["makespan"],
            "plan_makespan_aware": aware["makespan"],
            "cross_rack_edges_blind": cross["blind"],
            "cross_rack_edges_aware": cross["aware"],
            "win": mk["aware"] < mk["blind"],
        }
    wins = sum(1 for r in results.values() if r["win"])
    return {"workflows": results, "n_samples": n_samples,
            "nodes_per_type": nodes_per_type, "n_zones": LOC_N_ZONES,
            "intra_gbps": LOC_INTRA_GBPS, "cross_gbps": LOC_CROSS_GBPS,
            "data_scale": LOC_DATA_SCALE,
            "locality_wins": wins, "n_workflows": len(results),
            "scale": locality_scale(seed=seed)}


def locality_scale(seed: int = 0, n_nodes: int = 16,
                   width: int = 100, depth: int = 140) -> dict:
    """Schedule a >= 10k-task synthetic DAG comm-aware and time the
    solve — the time-bounded scaling smoke CI runs standalone."""
    dag = synthetic_dag(width=width, depth=depth, fanout=2.0, seed=seed)
    rng = np.random.default_rng(seed + 5)
    speeds = rng.uniform(0.5, 2.0, n_nodes)
    cost = dag.cost_matrix(speeds)
    names = [f"n{j}" for j in range(n_nodes)]
    topo = Topology.split(names, 4, intra_gbps=LOC_INTRA_GBPS,
                          cross_gbps=LOC_CROSS_GBPS)
    comm = CommCosts(dag.pred, dag.edge_dict(), topo.secs_per_gb(names))
    t0 = time.perf_counter()
    sched = heft_schedule_array(dag.succ, dag.pred, cost, comm=comm)
    schedule_s = time.perf_counter() - t0
    return {"n_tasks": dag.n_tasks, "n_edges": dag.n_edges,
            "n_nodes": n_nodes, "min_tasks": LOC_SCALE_MIN_TASKS,
            "schedule_s": schedule_s,
            "latency_bound_s": LOC_LATENCY_BOUND_S,
            "makespan": sched["makespan"]}


# ---------------------------------------------------------------------------
# scale arm (PR 9): fused tick vs the legacy four-dispatch tick at (T, N),
# plus the vmapped (W, T, N) fleet sweep
# ---------------------------------------------------------------------------
SCALE_BATCH = 64         # observations per tick — both arms see the SAME ones
SCALE_WARM = 2           # warm-up ticks (compile + cache priming), untimed
SCALE_TICKS = 5          # timed steady-state ticks per point
SCALE_SIZE = 64.0        # shared input size of the sweep
SCALE_GATE_CELLS = 100_000   # gate point: fused must win here
SCALE_MIN_SPEEDUP = 5.0      # ... by at least this factor

#: gate-mode (T, N) points — (2048, 50) is the 102 400-cell gate point
SCALE_POINTS_GATE = [(256, 16), (2048, 50)]
#: the full sweep adds the ~1M-cell ceiling
SCALE_POINTS_FULL = SCALE_POINTS_GATE + [(1024, 64), (4096, 256)]


def _scale_obs(names, nodes, rng, batch: int):
    """One tick's worth of (task, node, size, runtime) observations.

    Tasks are drawn WITHOUT replacement so every tick dirties the same
    number of distinct rows — the legacy dirty-row re-predict compiles
    one executable per distinct-row count, and a steady-state comparison
    must not charge it a recompile per tick."""
    rows = rng.choice(len(names), size=min(batch, len(names)),
                      replace=False)
    return [(names[int(r)],
             nodes[int(rng.integers(0, len(nodes)))],
             SCALE_SIZE, float(rng.uniform(5.0, 120.0)))
            for r in rows]


def _scale_point(t: int, n: int, seed: int = 0) -> dict:
    """Steady-state per-tick wall time of both tick implementations at
    (T, N): legacy = ``observe_batch`` + dirty-row ``predict_matrix``
    (four dispatches stitched by Python), fused = ``TickEngine`` (one
    donated ``tick_step``).  Same observation batches, per-phase spans
    through the ``repro.obs`` lanes."""
    rng = np.random.default_rng(seed + 17)
    batches = [_scale_obs([f"t{i}" for i in range(t)],
                          [f"n{j}" for j in range(n)], rng, SCALE_BATCH)
               for _ in range(SCALE_WARM + SCALE_TICKS)]

    def drive(tick):
        for b in batches[:SCALE_WARM]:
            tick(b)
        t0 = time.perf_counter()
        for b in batches[SCALE_WARM:]:
            tick(b)
        return (time.perf_counter() - t0) / SCALE_TICKS

    est, _names, nodes = scale_estimator(t, n, seed=seed)
    log_l = EventLog()
    est.set_tracer(log_l)
    est.predict_matrix(nodes, SCALE_SIZE)          # prime cache + compile

    def legacy_tick(b):
        est.observe_batch(b)
        m, _s = est.predict_matrix(nodes, SCALE_SIZE)
        return m

    legacy_s = drive(legacy_tick)
    jax.clear_caches()

    est2, _names, nodes = scale_estimator(t, n, seed=seed)
    log_f = EventLog()
    engine = TickEngine(est2, nodes, size=SCALE_SIZE, tracer=log_f)

    def fused_tick(b):
        engine.observe_batch(b)
        m, _s = engine.predict_matrix(nodes, SCALE_SIZE)
        return m

    fused_s = drive(fused_tick)
    jax.clear_caches()
    return {"t": t, "n": n, "cells": t * n, "batch": SCALE_BATCH,
            "legacy_tick_s": legacy_s, "fused_tick_s": fused_s,
            "speedup": legacy_s / fused_s,
            "phases_legacy": tick_latency_summary(log_l.events),
            "phases_fused": tick_latency_summary(log_f.events)}


def _fleet_point(w: int, t: int, n: int, seed: int = 0) -> dict:
    """Throughput of the vmapped fleet tick over W stacked workflows,
    sharded across whatever devices the mesh exposes when the W axis
    divides (a single device replicates — today's layout)."""
    est, _names, nodes = scale_estimator(t, n, seed=seed)
    state, _sn = build_state(est, nodes)
    fleet = stack_states([state] * w)
    mesh = make_fleet_mesh(task=1)
    wf_axis = dict(mesh.shape)["wf"]
    sharded = w % wf_axis == 0
    if sharded:
        fleet = shard_fleet(fleet, mesh)
    rng = np.random.default_rng(seed + 23)
    sizes = np.full(w, SCALE_SIZE)

    def tick_obs():
        rows = rng.integers(0, t, (w, SCALE_BATCH))
        cols = rng.integers(0, n, (w, SCALE_BATCH))
        y = rng.uniform(5.0, 120.0, (w, SCALE_BATCH))
        obs = np.zeros((w, SCALE_BATCH, 8))
        obs[..., 0] = rows
        obs[..., 1] = cols
        obs[..., 2] = SCALE_SIZE
        obs[..., 3] = y
        obs[..., 5] = y                  # med/spr: any consistent history
        obs[..., 6] = 1.0
        obs[..., 7] = 1.0
        return obs

    for _ in range(SCALE_WARM):
        fleet, mean, _std = fleet_tick_step(fleet, tick_obs(), sizes)
    jax.block_until_ready(mean)
    t0 = time.perf_counter()
    for _ in range(SCALE_TICKS):
        fleet, mean, _std = fleet_tick_step(fleet, tick_obs(), sizes)
    jax.block_until_ready(mean)
    tick_s = (time.perf_counter() - t0) / SCALE_TICKS
    jax.clear_caches()
    return {"w": w, "t": t, "n": n, "cells": w * t * n,
            "devices": len(jax.devices()), "sharded": sharded,
            "mesh_wf": wf_axis, "tick_s": tick_s,
            "cells_per_s": w * t * n / tick_s}


def bench_scale(points=None, fleet_ws=None, *, fleet_t: int = 128,
                fleet_n: int = 16, seed: int = 0) -> dict:
    points = SCALE_POINTS_FULL if points is None else points
    fleet_ws = [4, 16, 64] if fleet_ws is None else fleet_ws
    pts = [_scale_point(t, n, seed=seed) for t, n in points]
    fleets = [_fleet_point(w, fleet_t, fleet_n, seed=seed)
              for w in fleet_ws]
    gate_pts = [p for p in pts if p["cells"] >= SCALE_GATE_CELLS]
    gate_speedup = min((p["speedup"] for p in gate_pts),
                       default=float("nan"))
    return {"batch": SCALE_BATCH, "warm_ticks": SCALE_WARM,
            "timed_ticks": SCALE_TICKS, "size": SCALE_SIZE,
            "gate_cells": SCALE_GATE_CELLS,
            "min_speedup": SCALE_MIN_SPEEDUP,
            "points": pts, "fleet": fleets,
            "gate_speedup": gate_speedup}


def run(n_tasks: int = 1000, n_samples: int = 8,
        nodes_per_type: int = 2, scale_points=None,
        fleet_ws=None) -> list[tuple]:
    thr = bench_update_throughput(n_tasks=n_tasks)
    eq = bench_equivalence(n_tasks=max(50, n_tasks // 5))
    wf = bench_workflows(n_samples=n_samples, nodes_per_type=nodes_per_type)
    fl = bench_fault_tolerance(n_samples=n_samples,
                               nodes_per_type=nodes_per_type)
    jax.clear_caches()
    loc = bench_locality(n_samples=max(n_samples, 4),
                         nodes_per_type=nodes_per_type)
    sc = bench_scale(points=scale_points, fleet_ws=fleet_ws)
    result = {"config": {"n_tasks": n_tasks, "x64": True},
              "throughput": thr, "equivalence": eq, "execution": wf,
              "faults": fl, "locality": loc, "scale": sc}
    OUT.write_text(json.dumps(result, indent=2))
    print(f"update: {thr['update_s']*1e6:.0f}us/obs vs refit "
          f"{thr['refit_s']*1e3:.1f}ms -> "
          f"{thr['update_speedup_vs_refit']:.0f}x "
          f"(scan stream: {thr['stream_obs_per_s']:.0f} obs/s, "
          f"{thr['stream_speedup_vs_refit']:.0f}x)")
    print(f"equivalence: max rel mean={eq['max_rel_diff_mean']:.2e} "
          f"std={eq['max_rel_diff_std']:.2e} "
          f"gate_equal={eq['pearson_gate_equal']}")
    for name, r in wf["workflows"].items():
        print(f"  {name:10s} MPE {r['mpe_static']:.3f} -> "
              f"{r['mpe_online_nobias']:.3f} (PR2) -> "
              f"{r['mpe_online']:.3f} (bias) -> "
              f"{r['mpe_online_risk']:.3f} (risk)  "
              f"makespan {r['makespan_static']:.0f} "
              f"-> {r['makespan_online']:.0f} "
              f"-> {r['makespan_online_risk']:.0f} (risk)  "
              f"(replans {r['replans']}/{r['surprises']} surprises, "
              f"{r['speculations']} spec/{r['spec_wins']} won; risk "
              f"{r['risk_speculations']} spec)")
    print(f"online MPE wins: {wf['online_mpe_wins']}/{wf['n_workflows']}  "
          f"bias-vs-PR2 wins: {wf['bias_mpe_wins']}/{wf['n_workflows']}  "
          f"risk makespan win-or-tie: "
          f"{wf['risk_makespan_wins']}/{wf['n_workflows']}")
    for name, r in wf["workflows"].items():
        c = r["calibration"]
        print(f"  {name:10s} calibration: coverage90 t={c['coverage90']:.3f}"
              f" z={c['coverage90_z']:.3f} (n={c['n_obs']}, "
              f"warm-up {c['min_obs']})  sharpness_rel="
              f"{c['sharpness_rel']:.2f}  pit_tv={c['pit_tv']:.2f}")
    ov = wf["observability"]["overhead"]
    print(f"calibration in band {wf['cal_band']}: "
          f"{wf['calibration_in_band']}/{wf['n_workflows']}  tracing "
          f"overhead ({ov['workflow']}): {ov['overhead_frac']:+.1%} "
          f"({ov['n_events']} events, {ov['per_event_us']:.1f}us/event)"
          if ov else "calibration: no overhead sample (tracing off?)")
    for name, r in fl["workflows"].items():
        print(f"  {name:10s} faults: FT {r['ft_completed_fraction']:.0%} "
              f"complete @ {r['inflation']:.2f}x makespan "
              f"(static {r['static_completed_fraction']:.0%}; "
              f"{r['failures']} failures/{r['retries']} retries/"
              f"{r['lost_nodes']} lost nodes/{r['censored']} censored)")
    print(f"fault arm: {fl['ft_complete']}/{fl['n_workflows']} complete, "
          f"max inflation {fl['max_inflation']:.2f}x "
          f"(bound {fl['inflation_bound']}x), static strands on "
          f"{fl['static_strands']}/{fl['n_workflows']}")
    for name, r in loc["workflows"].items():
        print(f"  {name:10s} locality: realized makespan blind "
              f"{r['makespan_blind']:.0f} -> aware {r['makespan_aware']:.0f} "
              f"({'win' if r['win'] else 'no win'}; cross-rack edges "
              f"{r['cross_rack_edges_blind']} -> "
              f"{r['cross_rack_edges_aware']})")
    ls = loc["scale"]
    print(f"locality: aware wins {loc['locality_wins']}/"
          f"{loc['n_workflows']}  10k smoke: {ls['n_tasks']} tasks "
          f"({ls['n_edges']} edges) scheduled comm-aware in "
          f"{ls['schedule_s']:.2f}s (bound {ls['latency_bound_s']}s)")
    for p in sc["points"]:
        print(f"  scale ({p['t']:5d}x{p['n']:3d} = {p['cells']:7d} cells) "
              f"tick {p['legacy_tick_s']*1e3:.2f}ms legacy -> "
              f"{p['fused_tick_s']*1e3:.2f}ms fused "
              f"({p['speedup']:.1f}x)")
    for p in sc["fleet"]:
        print(f"  fleet W={p['w']:2d} ({p['cells']:7d} cells, "
              f"{p['devices']} device(s), "
              f"{'sharded' if p['sharded'] else 'unsharded'}) "
              f"tick {p['tick_s']*1e3:.2f}ms = "
              f"{p['cells_per_s']/1e6:.1f}M cells/s")
    print(f"scale gate: {sc['gate_speedup']:.1f}x fused-over-legacy at "
          f">= {sc['gate_cells']} cells (need >= {sc['min_speedup']}x)")
    print(f"wrote {OUT}")
    return [("bench_online.update_throughput", thr["update_s"] * 1e6,
             f"speedup={thr['update_speedup_vs_refit']:.0f}x"),
            ("bench_online.equivalence", 0.0,
             f"rel={eq['max_rel_diff_mean']:.1e};"
             f"gate={eq['pearson_gate_equal']}"),
            ("bench_online.mpe_wins", 0.0,
             f"{wf['online_mpe_wins']}/{wf['n_workflows']}"),
            ("bench_online.bias_mpe_wins", 0.0,
             f"{wf['bias_mpe_wins']}/{wf['n_workflows']}"),
            ("bench_online.risk_makespan_wins", 0.0,
             f"{wf['risk_makespan_wins']}/{wf['n_workflows']}"),
            ("bench_online.calibration_in_band", 0.0,
             f"{wf['calibration_in_band']}/{wf['n_workflows']}"),
            ("bench_online.fault_completion", 0.0,
             f"{fl['ft_complete']}/{fl['n_workflows']};"
             f"inflation={fl['max_inflation']:.2f}x"),
            ("bench_online.locality_wins", 0.0,
             f"{loc['locality_wins']}/{loc['n_workflows']};"
             f"10k={ls['schedule_s']:.2f}s"),
            ("bench_online.scale_speedup", sc["gate_speedup"],
             f"{sc['gate_speedup']:.1f}x@>={sc['gate_cells']}cells")]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes (CI smoke)")
    ap.add_argument("--gate", action="store_true",
                    help="small throughput shapes but FULL-size workflow "
                         "arms — the CI perf gate asserts the online and "
                         "bias MPE wins on these numbers")
    ap.add_argument("--scale-smoke", action="store_true",
                    help="tiny (W=4, T=64, N=8) scale arm only, no "
                         "BENCH_online.json write — the CI multi-device "
                         "sharding smoke")
    ap.add_argument("--locality-smoke", action="store_true",
                    help="schedule the >= 10k-task synthetic DAG "
                         "comm-aware and enforce the latency bound; no "
                         "BENCH_online.json write — the CI scheduling "
                         "smoke")
    a = ap.parse_args()
    enable_compile_cache()
    if a.locality_smoke:
        ls = locality_scale()
        ok = (ls["n_tasks"] >= ls["min_tasks"]
              and ls["schedule_s"] <= ls["latency_bound_s"])
        print(f"locality smoke: {ls['n_tasks']} tasks ({ls['n_edges']} "
              f"edges) on {ls['n_nodes']} nodes scheduled comm-aware in "
              f"{ls['schedule_s']:.2f}s (need >= {ls['min_tasks']} tasks "
              f"within {ls['latency_bound_s']}s) -> "
              f"{'ok' if ok else 'FAIL'}")
        raise SystemExit(0 if ok else 1)
    if a.scale_smoke:
        sc = bench_scale(points=[(64, 8)], fleet_ws=[4],
                         fleet_t=64, fleet_n=8)
        p = sc["points"][0]
        print(f"scale smoke ({p['t']}x{p['n']}): legacy "
              f"{p['legacy_tick_s']*1e3:.2f}ms fused "
              f"{p['fused_tick_s']*1e3:.2f}ms ({p['speedup']:.1f}x)")
        f = sc["fleet"][0]
        print(f"fleet smoke W={f['w']} on {f['devices']} device(s) "
              f"({'sharded' if f['sharded'] else 'unsharded'}): "
              f"{f['tick_s']*1e3:.2f}ms/tick")
    elif a.quick:
        run(n_tasks=64, n_samples=2, nodes_per_type=1,
            scale_points=[(128, 16)], fleet_ws=[2])
    elif a.gate:
        run(n_tasks=64, n_samples=8, nodes_per_type=2,
            scale_points=SCALE_POINTS_GATE, fleet_ws=[4])
    else:
        run()
