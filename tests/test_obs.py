"""Observability plane: tracer no-op bit-exactness, event emission,
JSONL / Chrome export, ExecutionTrace schema round-trip, running-median
equivalence, calibration diagnostics and latency profiling."""
import json
import math

import numpy as np
import pytest

from repro.obs import (EVENT_KINDS, Event, EventLog, MetricsRegistry,
                       NULL_TRACER, RunningMedian, calibration_summary,
                       chrome_trace_events, load_jsonl, phase_breakdown,
                       pit_uniformity, render_report, report_dict,
                       running_median, slowest_spans, tick_latency_summary)
from repro.online.buffer import ObservationBuffer
from repro.online.executor import ExecutionTrace, TaskRun
from repro.sched.simulator import FaultInjector

from tests.test_faults import _scenario


def _faulty(tracer=None, **kw):
    fi = FaultInjector(p_fail=0.15, seed=3,
                       outages={"tpu-v2/0": (20.0, 120.0)})
    return _scenario(online=True, faults=fi, rel_k=0.5, strict=False,
                     tracer=tracer, noise_seed=7, slow=2.5,
                     spec_tail=0.8, **kw)


# ---------------------------------------------------------------------------
# tracing is read-only: attaching a tracer never perturbs the loop
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fused", [False, True])
def test_tracer_disabled_is_bit_exact(fused):
    """The PR 5 contract, extended: the executor's full output — every
    counter, record, censored run and observation, via ``to_dict`` — is
    bit-identical whether no tracer, the NULL_TRACER, or a collecting
    ``EventLog`` is attached, on the legacy and the fused tick alike.
    Tracing observes; it never steers."""
    base = _faulty(tracer=None, fused=fused).run().to_dict()
    for tracer in (NULL_TRACER, EventLog()):
        got = _faulty(tracer=tracer, fused=fused).run().to_dict()
        assert json.dumps(got, sort_keys=True) == \
            json.dumps(base, sort_keys=True)


@pytest.mark.parametrize("fused", [False, True])
def test_tracer_bit_exact_fault_free(fused):
    a = _scenario(online=True, tracer=None, fused=fused).run().to_dict()
    b = _scenario(online=True, tracer=EventLog(), fused=fused).run().to_dict()
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


# ---------------------------------------------------------------------------
# event emission: the loop's lifecycle lands in the log, typed
# ---------------------------------------------------------------------------
def test_traced_run_emits_lifecycle_events():
    log = EventLog()
    trace = _faulty(tracer=log).run()
    c = log.counters()
    assert c["run_start"] == 1 and c["run_end"] == 1
    # every emitted kind is in the closed taxonomy
    assert {e.kind for e in log.events} <= EVENT_KINDS
    # one observe per completion, coverage flag consistent with the
    # trace's surprise counter, PIT in [0, 1]
    obs = log.filter("observe")
    assert len(obs) == trace.completed
    assert sum(not e.data["covered"] for e in obs) == trace.surprises
    assert c.get("surprise", 0) == trace.surprises
    for e in obs:
        assert 0.0 <= e.data["pit"] <= 1.0
        assert e.data["lo"] <= e.data["hi"]
    # fault machinery shows up under injected churn
    assert c["fault"] == trace.failures
    assert c["retry"] == trace.retries
    assert c["speculation"] == trace.speculations
    assert c["finish"] == trace.completed
    assert c.get("node_down", 0) >= 1 and c.get("node_up", 0) >= 1
    # estimator + plan spans were recorded
    assert log.spans("predict_matrix") and log.spans("plan")
    assert log.spans("update_stream") and log.spans("bias_update")
    # sim clock on events is monotone within the heap's pop order
    ticks = [e.t_sim for e in log.filter("tick")]
    assert all(a <= b + 1e-9 for a, b in zip(ticks, ticks[1:]))


def test_fused_run_spans_inside_the_tick():
    """A traced fused run splits each completion tick into its surprise
    gate, the engine's tick (with the estimates' fetch nested in it) and
    the bias statistics' fetch; every span records its parent and the
    thread's CPU time over it."""
    log = EventLog()
    _faulty(tracer=log, fused=True).run()
    ticks = len(log.filter("predict"))          # one per completion tick
    assert ticks > 0
    for phase in ("surprise_gate", "tick_step", "tick_fetch", "bias_fetch"):
        assert len(log.spans(phase)) == ticks, phase
    parents = {e.data["phase"]: set() for e in log.spans()}
    for e in log.spans():
        parents[e.data["phase"]].add(e.data["parent"])
        assert 0.0 <= e.data["cpu_s"]
    assert parents.pop("tick_fetch") == {"tick_step"}
    assert all(p == {None} for p in parents.values()), parents
    # the nested fetch lies inside its tick_step on the wall clock
    steps = log.spans("tick_step")
    for f, st in zip(log.spans("tick_fetch"), steps):
        assert st.t_wall <= f.t_wall
        assert f.t_wall + f.data["dur_s"] <= \
            st.t_wall + st.data["dur_s"] + 1e-9
    gates = log.spans("surprise_gate")
    assert sum(e.data["n"] for e in gates) == len(log.filter("observe"))


def test_span_parent_stack_and_cpu_time():
    log = EventLog()
    with log.span("outer"):
        with log.span("inner", n=2):
            sum(range(20000))
        with log.span("inner"):
            pass
    with log.span("after"):
        pass
    got = [(e.data["phase"], e.data["parent"]) for e in log.spans()]
    assert got == [("inner", "outer"), ("inner", "outer"),
                   ("outer", None), ("after", None)]
    assert log.spans("inner")[0].data["n"] == 2
    for e in log.spans():
        assert 0.0 <= e.data["cpu_s"]
    # a span that waits records wall time but (almost) no CPU time
    import time
    with log.span("sleep"):
        time.sleep(0.05)
    slept = log.spans("sleep")[0].data
    assert slept["dur_s"] >= 0.05 and slept["cpu_s"] < 0.5 * slept["dur_s"]


def test_unknown_event_kind_warns_not_raises():
    log = EventLog()
    with pytest.warns(UserWarning, match="unknown trace event kind"):
        log.emit("not_a_kind", t_sim=1.0)
    assert len(log.events) == 1     # still recorded


# ---------------------------------------------------------------------------
# export: JSONL round-trip and Chrome trace_event shape
# ---------------------------------------------------------------------------
def test_jsonl_round_trip(tmp_path):
    log = EventLog()
    _faulty(tracer=log).run()
    p = log.to_jsonl(tmp_path / "t.jsonl")
    header = json.loads(p.read_text().splitlines()[0])
    assert header["trace_format"] == 1
    assert header["events"] == len(log.events)
    back = load_jsonl(p)
    assert back == log.events


def test_jsonl_rejects_newer_format(tmp_path):
    p = tmp_path / "future.jsonl"
    p.write_text(json.dumps({"trace_format": 99, "events": 0}) + "\n")
    with pytest.raises(ValueError, match="newer"):
        load_jsonl(p)


def test_chrome_trace_shape(tmp_path):
    log = EventLog()
    trace = _faulty(tracer=log).run()
    p = log.to_chrome(tmp_path / "t.chrome.json")
    doc = json.loads(p.read_text())
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert all({"ph", "pid"} <= set(e) for e in evs)
    # every finish is a sim-clock duration slice whose length is the
    # realised runtime (in microseconds)
    slices = [e for e in evs if e["ph"] == "X" and e["pid"] == 2]
    assert len(slices) == trace.completed
    for s in slices:
        assert s["dur"] == pytest.approx(s["args"]["runtime"] * 1e6)
    # both processes and their thread lanes are named
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["pid"] for m in meta} == {1, 2}


# ---------------------------------------------------------------------------
# ExecutionTrace / ObservationBuffer: versioned schema round-trip
# ---------------------------------------------------------------------------
def test_execution_trace_dict_round_trip():
    trace = _faulty(tracer=None).run()
    d = json.loads(json.dumps(trace.to_dict()))   # through real JSON
    back = ExecutionTrace.from_dict(d)
    assert back.to_dict() == trace.to_dict()
    assert back.records == trace.records
    assert back.censored == trace.censored
    assert list(back.observations) == list(trace.observations)
    np.testing.assert_allclose(back.cumulative_mpe(),
                               trace.cumulative_mpe())


def test_execution_trace_rejects_newer_schema():
    trace = _scenario(online=True).run()
    d = trace.to_dict()
    d["version"] = 99
    with pytest.raises(ValueError, match="newer"):
        ExecutionTrace.from_dict(d)


def test_observation_buffer_round_trip():
    buf = ObservationBuffer()
    buf.record("t0", "tpu-v2", 32.0, 5.0, 4.2, time=1.5)
    buf.record("t1", "tpu-v3", 32.0, 7.0, 6.1, time=2.5)
    back = ObservationBuffer.from_dict(
        json.loads(json.dumps(buf.to_dict())))
    assert list(back) == list(buf)


# ---------------------------------------------------------------------------
# running median: O(log n) two-heap == naive prefix re-median
# ---------------------------------------------------------------------------
def test_running_median_matches_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 10, 101):
        xs = rng.normal(size=n)
        naive = np.array([np.median(xs[:k + 1]) for k in range(n)])
        np.testing.assert_array_equal(running_median(xs), naive)
    # duplicates and integer plateaus hit the heap rebalance edges
    xs = np.array([3.0, 3.0, 3.0, 1.0, 1.0, 5.0, 5.0, 3.0])
    naive = np.array([np.median(xs[:k + 1]) for k in range(len(xs))])
    np.testing.assert_array_equal(running_median(xs), naive)


def test_running_median_empty_raises():
    with pytest.raises(ValueError):
        RunningMedian().median()
    assert running_median([]).size == 0


def test_cumulative_mpe_incremental_equals_naive():
    """The satellite fix: ``cumulative_mpe`` used to recompute
    ``np.median`` over every prefix (O(n²)); the two-heap running median
    must reproduce it exactly."""
    rng = np.random.default_rng(7)
    records = [TaskRun(id=f"s{i}", name="t", node="n0", node_type="nt",
                       start=0.0, end=1.0,
                       runtime=float(rng.uniform(1.0, 10.0)),
                       pred_mean=float(rng.uniform(1.0, 10.0)),
                       pred_std=1.0)
               for i in range(73)]
    trace = ExecutionTrace(records=records)
    errs = trace.errors()
    naive = np.array([np.median(errs[:k + 1]) for k in range(len(errs))])
    np.testing.assert_array_equal(trace.cumulative_mpe(), naive)


# ---------------------------------------------------------------------------
# calibration diagnostics
# ---------------------------------------------------------------------------
def _obs_event(runtime, lo, hi, pit, pred_mean=1.0):
    return Event(kind="observe", t_sim=0.0, t_wall=0.0,
                 data={"runtime": runtime, "lo": lo, "hi": hi,
                       "covered": lo <= runtime <= hi, "pit": pit,
                       "pred_mean": pred_mean})


def test_calibration_summary_synthetic():
    # 8 covered + 2 not, uniform-ish PITs, unit widths
    events = [_obs_event(0.5 if i < 8 else 2.0, 0.0, 1.0,
                         (i + 0.5) / 10.0) for i in range(10)]
    s = calibration_summary(events, min_obs=0, bins=10)
    assert s["n_obs"] == 10 and s["n_post_warmup"] == 10
    assert s["coverage"] == pytest.approx(0.8)
    assert s["sharpness"] == pytest.approx(1.0)
    assert s["pit_tv"] == pytest.approx(0.0)     # exactly one PIT per bin
    assert s["coverage_timeline_first_last"] == [1.0, 0.8]


def test_calibration_warm_up_exclusion():
    # warm-up half all missed, second half all covered
    events = ([_obs_event(5.0, 0.0, 1.0, 0.99) for _ in range(10)]
              + [_obs_event(0.5, 0.0, 1.0, 0.5) for _ in range(10)])
    s = calibration_summary(events, min_obs=10)
    assert s["coverage_all"] == pytest.approx(0.5)
    assert s["coverage"] == pytest.approx(1.0)   # warm-up excluded
    short = calibration_summary(events[:5], min_obs=10)
    assert short["n_post_warmup"] == 0
    assert math.isnan(short["coverage"])


def test_pit_uniformity_extremes():
    assert pit_uniformity((np.arange(100) + 0.5) / 100.0) == 0.0
    assert pit_uniformity(np.full(100, 0.5)) == pytest.approx(0.9)


def test_predict_pit_node_matches_interval():
    """PIT and interval come from the same predictive distribution: the
    PIT of each interval endpoint must be the corresponding quantile."""
    from tests.test_faults import _make_est
    est, chain = _make_est()
    conf = 0.2
    for task in chain:
        lo, hi = est.predict_interval_node(task, "tpu-v2", 32.0, conf)
        plo = est.predict_pit_node(task, "tpu-v2", 32.0, lo)
        phi = est.predict_pit_node(task, "tpu-v2", 32.0, hi)
        assert plo == pytest.approx((1 - conf) / 2, abs=1e-6)
        assert phi == pytest.approx(1 - (1 - conf) / 2, abs=1e-6)
        # monotone in the runtime
        assert (est.predict_pit_node(task, "tpu-v2", 32.0, lo * 0.5)
                < plo < phi
                < est.predict_pit_node(task, "tpu-v2", 32.0, hi * 2.0))


# ---------------------------------------------------------------------------
# latency profiling: first-call (compile) vs steady state
# ---------------------------------------------------------------------------
def _span(phase, dur, t_wall=0.0):
    return Event(kind="span", t_sim=0.0, t_wall=t_wall,
                 data={"phase": phase, "dur_s": dur})


def test_phase_breakdown_splits_compile():
    events = [_span("predict", 1.0, 0.0), _span("predict", 0.1, 1.0),
              _span("predict", 0.3, 2.0), _span("plan", 0.05, 3.0)]
    pb = phase_breakdown(events)
    assert pb["predict"]["count"] == 3
    assert pb["predict"]["first_s"] == pytest.approx(1.0)
    assert pb["predict"]["steady_mean_s"] == pytest.approx(0.2)
    assert pb["predict"]["steady_max_s"] == pytest.approx(0.3)
    assert pb["predict"]["total_s"] == pytest.approx(1.4)
    assert pb["plan"]["count"] == 1
    assert math.isnan(pb["plan"]["steady_mean_s"])   # no steady sample yet
    s = tick_latency_summary(events)
    assert s["compile_total_s"] == pytest.approx(1.05)
    assert s["traced_total_s"] == pytest.approx(1.45)


def test_nested_spans_counted_once():
    """``tick_latency_summary`` sums top-level spans only, so a nested
    span's time is not counted twice; ``phase_breakdown`` keeps every
    phase; ``slowest_spans`` and the report carry ``cpu_s`` beside
    ``dur_s``.  Events without ``parent`` (older traces) are top level."""
    def span(phase, dur, cpu, parent, t_wall):
        return Event(kind="span", t_sim=0.0, t_wall=t_wall,
                     data={"phase": phase, "dur_s": dur, "cpu_s": cpu,
                           "parent": parent})
    events = [span("gate", 0.4, 0.35, None, 0.0),
              span("fetch", 0.25, 0.01, "step", 1.0),
              span("step", 1.0, 0.2, None, 0.9),
              span("gate", 0.3, 0.29, None, 3.0),
              span("fetch", 0.125, 0.01, "step", 4.0),
              span("step", 0.5, 0.1, None, 3.9),
              _span("plan", 0.25, 5.0)]
    pb = phase_breakdown(events)
    assert set(pb) == {"gate", "fetch", "step", "plan"}
    assert pb["fetch"]["count"] == 2
    assert pb["fetch"]["total_s"] == pytest.approx(0.375)
    s = tick_latency_summary(events)
    assert set(s["phases"]) == set(pb)
    assert s["traced_total_s"] == pytest.approx(0.7 + 1.5 + 0.25)
    assert s["compile_total_s"] == pytest.approx(0.4 + 1.0 + 0.25)
    assert s["steady_tick_s"] == pytest.approx(0.3 + 0.5)
    # the same from a JSONL round trip (plain dicts)
    dicts = [e.to_json() for e in events]
    assert tick_latency_summary(dicts)["traced_total_s"] == \
        pytest.approx(s["traced_total_s"])
    slow = slowest_spans(events, 2)
    assert [(d["phase"], d["dur_s"], d["cpu_s"]) for d in slow] == \
        [("step", 1.0, 0.2), ("step", 0.5, 0.1)]
    text = render_report(events)
    assert "slowest spans (wall, cpu)" in text
    row = next(ln for ln in text.splitlines() if "fetch" in ln
               and "t_sim=" in ln)
    assert "250.00ms" in row and "10.00ms" in row and "'step'" in row


def test_profiling_on_real_trace():
    log = EventLog()
    _scenario(online=True, tracer=log).run()
    s = tick_latency_summary(log.events)
    assert set(s["phases"]) >= {"predict_matrix", "update_stream",
                                "bias_update"}
    assert 0.0 < s["compile_frac"] <= 1.0
    pm = s["phases"]["predict_matrix"]
    # the first/steady split is present and self-consistent (whether the
    # first call actually compiled depends on the process's jit cache —
    # under `pytest -x` earlier tests may already have warmed it)
    assert pm["first_s"] > 0.0 and pm["count"] >= 2
    assert pm["steady_p50_s"] <= pm["steady_max_s"]
    assert s["traced_total_s"] >= s["compile_total_s"]


# ---------------------------------------------------------------------------
# registry + report
# ---------------------------------------------------------------------------
def test_metrics_registry_from_events():
    log = EventLog()
    _faulty(tracer=log).run()
    m = MetricsRegistry.from_events(log.events).to_dict()
    assert m["counters"]["events.observe"] == len(log.filter("observe"))
    assert any(k.startswith("span_s.") for k in m["histograms"])
    assert any(k.startswith("final.") for k in m["gauges"])


def test_report_renders(tmp_path):
    log = EventLog()
    _faulty(tracer=log).run()
    text = render_report(log.events, min_obs=5)
    for needle in ("TRACE REPORT", "calibration", "coverage",
                   "PIT histogram", "latency", "fault / retry"):
        assert needle in text
    d = json.loads(json.dumps(report_dict(log.events, min_obs=5),
                              default=float))
    assert {"metrics", "calibration", "latency",
            "slowest_spans", "fault_narrative"} <= set(d)


def test_report_trace_cli(tmp_path):
    import subprocess
    import sys
    from pathlib import Path
    log = EventLog()
    _scenario(online=True, tracer=log).run()
    p = log.to_jsonl(tmp_path / "t.jsonl")
    out_json = tmp_path / "report.json"
    repo = Path(__file__).resolve().parents[1]
    r = subprocess.run(
        [sys.executable, str(repo / "scripts" / "report_trace.py"),
         str(p), "--json", str(out_json), "--min-obs", "5"],
        capture_output=True, text=True, cwd=repo)
    assert r.returncode == 0, r.stderr
    assert "TRACE REPORT" in r.stdout
    assert "t.jsonl" in json.loads(out_json.read_text())
