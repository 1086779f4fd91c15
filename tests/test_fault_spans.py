"""Spans on the executor's fault path: ``fault`` around the handling of a
lost node or a failed attempt, ``replan`` around the frontier re-plan it
causes.  They are read-only (the run is bit-identical with the
benchmark's span recorder, a collecting log and none), one ``fault`` span per lost node or
attempt failure whose ``n`` adds up to the censored runs, one ``replan``
span per fault re-plan and none for a surprise re-plan, and a fault-free
run enters neither."""
import importlib.util
import inspect
import json
from collections import Counter
from pathlib import Path

import pytest

from repro.obs import NULL_TRACER, EventLog
from repro.online import OnlineExecutor
from repro.sched.simulator import FaultInjector

from tests.test_faults import _scenario

CRASH = {"tpu-v2/0": 60.0, "tpu-v3/1": 140.0}


def _recorder():
    """The benchmark's span recorder (``chipbench/harness.py``): a tracer
    with ``enabled`` False, so the program builds no event payloads, that
    still times every span."""
    path = Path(__file__).resolve().parents[1] / "chipbench" / "harness.py"
    spec = importlib.util.spec_from_file_location("chipbench_harness", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Recorder()


def _faulted(tracer=None, online=True, **kw):
    """The crash scenario; the static loop (``online=False``) cannot
    re-assign the crashed nodes' work, so it runs non-strict."""
    fi = FaultInjector(crash_at=CRASH, p_fail=0.15, seed=4)
    return _scenario(faults=fi, rel_k=1.0, max_attempts=8, tracer=tracer,
                     online=online, strict=online, noise_seed=3, **kw)


def _dump(trace) -> str:
    return json.dumps(trace.to_dict(), sort_keys=True)


@pytest.mark.parametrize("online", [False, True])
def test_fault_spans_leave_the_run_bit_identical(online):
    """Schedule, observations, censored runs, counters and makespan are
    the same with no tracer, the null tracer, the benchmark's span
    recorder and a collecting log."""
    base = _faulted(None, online).run()
    assert base.failures > base.lost_nodes > 0     # the path was taken
    for tracer in (NULL_TRACER, _recorder(), EventLog()):
        got = _faulted(tracer, online).run()
        assert _dump(got) == _dump(base), type(tracer).__name__


class _PlanCallers(OnlineExecutor):
    """Counts ``_plan`` calls by the function that made them."""

    def _plan(self, *args, **kw):
        self.callers[inspect.stack()[1].function] += 1
        return super()._plan(*args, **kw)


def _counted(online=True):
    """(plan calls by caller, the run's trace, its span events)."""
    log = EventLog()
    ex = _faulted(log, online)
    ex.__class__ = _PlanCallers
    ex.callers = Counter()
    return ex.callers, ex.run(), log


def _wall(e):
    return e.t_wall, e.t_wall + e.data["dur_s"]


@pytest.mark.parametrize("online", [False, True])
def test_fault_spans_count_the_lost_nodes_and_attempts(online):
    _, trace, log = _counted(online)
    faults = log.spans("fault")
    attempts = sum(c.reason == "attempt" for c in trace.censored)
    assert attempts > 0 and trace.lost_nodes == len(CRASH)
    assert len(faults) == trace.lost_nodes + attempts
    assert sum(e.data["n"] for e in faults) == len(trace.censored) == \
        trace.failures
    assert all(e.data["parent"] is None for e in faults)


def test_replan_spans_count_fault_replans_only():
    """One ``replan`` span per re-plan of the fault path, none for the
    initial plan or a surprise re-plan, which keep their ``plan`` span
    alone; each holds its ``plan`` and lies inside its ``fault``."""
    callers, trace, log = _counted()
    fault_replans = callers["replan_frontier"]
    surprise_replans = callers["run"] - 1           # less the initial plan
    assert fault_replans > 0 and surprise_replans > 0
    assert trace.replans == fault_replans + surprise_replans
    replans = log.spans("replan")
    assert len(replans) == fault_replans
    assert all(e.data["parent"] == "fault" and e.data["n"] > 0
               for e in replans)
    plans = log.spans("plan")
    assert sum(e.data["parent"] == "replan" for e in plans) == fault_replans
    assert sum(e.data["parent"] is None for e in plans) == \
        surprise_replans + 1
    faults = [_wall(e) for e in log.spans("fault")]
    for e in replans:
        t0, t1 = _wall(e)
        assert any(f0 <= t0 and t1 <= f1 + 1e-9 for f0, f1 in faults)
        assert sum(t0 <= p0 and p1 <= t1 + 1e-9
                   for p0, p1 in map(_wall, plans)) == 1


@pytest.mark.parametrize("online", [False, True])
def test_fault_free_run_enters_no_fault_span(online):
    for kw in ({}, {"rel_k": 1.0}):
        rec = _recorder()
        trace = _scenario(tracer=rec, online=online, **kw).run()
        assert trace.failures == trace.lost_nodes == 0
        phases = {s[0] for s in rec.spans}
        assert "plan" in phases
        assert not phases & {"fault", "replan"}
