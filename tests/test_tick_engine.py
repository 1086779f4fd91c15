"""Equivalence spine of the fused tick (PR 9).

The array-native tick engine (``repro.core.tick``) must be a pure
refactor of the legacy observe → update → bias scatter → re-predict
sequence: every number the executor consumes (estimate matrices,
surprise intervals, PIT values, bias points, writeback posteriors) has
to match the OO path to <= 1e-12.  These tests run under x64 — the bar
is algorithmic identity, not float32 noise — via a module fixture that
flips ``jax_enable_x64`` and clears every jit cache on both edges.

The executor-level spine drives all five paper workflows, faults off
AND on, through the executor as it runs (on a ``TickEngine``) and through
the same executor handed the estimator's host path in the engine's
place, built from identical seeds, and requires the full trace
signatures (assignment, start/end, dispatch-time predictions,
replan/surprise counters) to agree.
"""
import jax
import numpy as np
import pytest

from repro.core import (LotaruEstimator, build_state, get_node,
                        profile_cluster, profile_node, target_nodes)
from repro.core.tick import TickEngine, predict_state, tick_step
from repro.online import OnlineExecutor, fanout_chain_dag
from repro.online import executor as executor_mod
from repro.sched.simulator import (ClusterSimulator, FaultInjector,
                                   GridEngine)
from repro.sched.workflows import INPUTS, WORKFLOWS

TOL = 1e-12


@pytest.fixture(scope="module", autouse=True)
def _x64():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    jax.clear_caches()
    yield
    jax.config.update("jax_enable_x64", prev)
    jax.clear_caches()


@pytest.fixture(scope="module")
def cluster():
    local = get_node("local-cpu")
    local_bench = profile_node(local, np.random.default_rng(7))
    tbenches = profile_cluster(target_nodes(), seed=13)
    return local, local_bench, tbenches


def _fitted(cluster, wf: str, size: float, *, seed=0):
    local, local_bench, tbenches = cluster
    by_name = {t.name: t for t in WORKFLOWS[wf]}
    sim = ClusterSimulator(seed=seed)
    est = LotaruEstimator(local_bench, tbenches, bias_correction=True,
                          bias_empirical_bayes=True)
    est.fit_tasks(list(by_name), size,
                  lambda n, s, cf: sim.run_task(by_name[n], local, s,
                                                cpu_factor=cf))
    return est, by_name


# ---------------------------------------------------------------------------
# tick-level: TickEngine vs the legacy estimator, one surface at a time
# ---------------------------------------------------------------------------
def test_tick_engine_matches_legacy_observe_batch(cluster):
    wf, size = "eager", INPUTS[("eager", 1)]
    est_a, by_name = _fitted(cluster, wf, size)
    est_b, _ = _fitted(cluster, wf, size)
    nodes = [nt.name for nt in target_nodes()]
    engine = TickEngine(est_b, nodes, size=size)

    m0a, s0a = est_a.predict_matrix(nodes, size)
    m0b, s0b = engine.predict_matrix(nodes, size)
    np.testing.assert_allclose(m0b, m0a, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(s0b, s0a, rtol=TOL, atol=TOL)

    rng = np.random.default_rng(3)
    names = list(by_name)
    for _ in range(6):
        k = int(rng.integers(1, 4))
        batch = [(names[int(rng.integers(0, len(names)))],
                  nodes[int(rng.integers(0, len(nodes)))],
                  size, float(rng.uniform(5.0, 80.0)))
                 for _ in range(k)]
        ys_a = est_a.observe_batch(batch)
        ys_b = engine.observe_batch(batch)
        np.testing.assert_allclose(ys_b, ys_a, rtol=TOL, atol=TOL)
        ma, sa = est_a.predict_matrix(nodes, size)
        mb, sb = engine.predict_matrix(nodes, size)
        np.testing.assert_allclose(mb, ma, rtol=TOL, atol=TOL)
        np.testing.assert_allclose(sb, sa, rtol=TOL, atol=TOL)
        for name in names[:3]:
            for nt in nodes[:2]:
                lo_a, hi_a = est_a.predict_interval_node(name, nt, size, 0.9)
                lo_b, hi_b = engine.predict_interval_node(name, nt, size, 0.9)
                assert lo_b == pytest.approx(lo_a, rel=TOL, abs=TOL)
                assert hi_b == pytest.approx(hi_a, rel=TOL, abs=TOL)
                pit_a = est_a.predict_pit_node(name, nt, size, 30.0)
                pit_b = engine.predict_pit_node(name, nt, size, 30.0)
                assert pit_b == pytest.approx(pit_a, rel=TOL, abs=TOL)
                assert engine.bias_point(name, nt) == pytest.approx(
                    est_a.bias_point(name, nt), rel=TOL, abs=TOL)

    # finalize folds the device state back: the OO surface continues
    engine.finalize()
    for name in names:
        for nt in nodes:
            pa = est_a.predict(name, nt, size)
            pb = est_b.predict(name, nt, size)
            np.testing.assert_allclose(pb, pa, rtol=TOL, atol=TOL)


def test_tick_engine_spans(cluster):
    """Each tick enters ``tick_step`` with the estimates' fetch nested in
    it, then fetches the bias statistics outside it; both carry the
    batch size.  Tracing leaves the engine's numbers as they were."""
    from repro.obs import EventLog
    wf, size = "eager", INPUTS[("eager", 1)]
    est_a, by_name = _fitted(cluster, wf, size)
    est_b, _ = _fitted(cluster, wf, size)
    nodes = [nt.name for nt in target_nodes()]
    log = EventLog()
    plain = TickEngine(est_a, nodes, size=size)
    traced = TickEngine(est_b, nodes, size=size, tracer=log)
    names = list(by_name)
    batches = [[(names[0], nodes[0], size, 30.0)],
               [(names[1], nodes[1], size, 12.0),
                (names[2], nodes[2], size, 55.0)]]
    for batch in batches:
        assert traced.observe_batch(batch) == plain.observe_batch(batch)
        np.testing.assert_array_equal(traced.predict_matrix(nodes, size),
                                      plain.predict_matrix(nodes, size))
    got = [(e.data["phase"], e.data["parent"], e.data["n"])
           for e in log.spans()]
    assert got == [("tick_fetch", "tick_step", 1), ("tick_step", None, 1),
                   ("bias_fetch", None, 1),
                   ("tick_fetch", "tick_step", 2), ("tick_step", None, 2),
                   ("bias_fetch", None, 2)]
    assert all(e.data["cpu_s"] >= 0.0 for e in log.spans())


@pytest.fixture
def _f32():
    """The device dtype of a TPU run: x64 off for one test, back on after."""
    jax.config.update("jax_enable_x64", False)
    jax.clear_caches()
    yield
    jax.config.update("jax_enable_x64", True)
    jax.clear_caches()


#: engine vs legacy in float32: the same formulas in another program
#: order, so a few float32 ulps apart (about 4e-6 measured on the CPU)
F32_TOL = 5e-5


@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("confidence", [0.5, 0.9, 0.99])
def test_tick_engine_gate_matches_legacy(cluster, request, confidence,
                                         dtype):
    """The surprise gate's interval and the PIT, which the engine answers
    on the host from the gate table its tick copied, match the legacy
    estimator's on every (task, node) pair: Student-t rows and the
    median fallback, bias pairs observed and not.  To ``TOL`` under x64,
    and in float32, the dtype the chip runs, to ``F32_TOL``."""
    tol = TOL
    if dtype == "float32":
        request.getfixturevalue("_f32")
        tol = F32_TOL
    wf, size = "eager", INPUTS[("eager", 1)]
    est_a, by_name = _fitted(cluster, wf, size)
    est_b, _ = _fitted(cluster, wf, size)
    names = list(by_name)
    assert est_a.tasks[names[0]].model.post.mu.dtype == np.dtype(dtype)
    nodes = [nt.name for nt in target_nodes()]
    engine = TickEngine(est_b, nodes, size=size)
    assert not all(est_a.tasks[n].model.correlated for n in names)
    batches = [[(names[0], nodes[0], size, 30.0),
                (names[-1], nodes[1], size, 12.0)],
               [(names[-1], nodes[1], size, 16.0),
                (names[4], nodes[2], size, 55.0)]]
    for tick in range(len(batches) + 1):
        for name in names:
            for nt in nodes:
                lo_a, hi_a = est_a.predict_interval_node(name, nt, size,
                                                         confidence)
                lo_b, hi_b = engine.predict_interval_node(name, nt, size,
                                                          confidence)
                assert lo_b == pytest.approx(lo_a, rel=tol, abs=tol)
                assert hi_b == pytest.approx(hi_a, rel=tol, abs=tol)
                for rt in (0.5 * hi_a, hi_a):
                    assert engine.predict_pit_node(
                        name, nt, size, rt) == pytest.approx(
                        est_a.predict_pit_node(name, nt, size, rt),
                        rel=tol, abs=tol)
        if tick < len(batches):
            est_a.observe_batch(batches[tick])
            engine.observe_batch(batches[tick])
    assert est_a.bias_point(names[-1], nodes[1]) != 1.0


def test_tick_engine_gate_refuses_other_size(cluster):
    """The engine answers the gate at its own size only, as
    ``predict_matrix`` does."""
    wf, size = "bacass", INPUTS[("bacass", 1)]
    est, by_name = _fitted(cluster, wf, size)
    nodes = [nt.name for nt in target_nodes()]
    engine = TickEngine(est, nodes, size=size)
    name = list(by_name)[0]
    with pytest.raises(ValueError, match="its own size"):
        engine.predict_interval_node(name, nodes[0], 0.5 * size)
    with pytest.raises(ValueError, match="its own size"):
        engine.predict_pit_node(name, nodes[0], 0.5 * size, 30.0)
    assert engine.predict_interval_node(name, nodes[0], size)[1] > 0.0


def test_gate_dispatches_nothing_after_a_tick(cluster, monkeypatch):
    """After a tick the engine answers the surprise gate and the PIT at
    its size from host copies: with the state's device buffers deleted,
    implicit transfers disallowed and eager primitives counted, every
    pair still gets an answer and no primitive runs."""
    from jax._src import dispatch
    wf, size = "eager", INPUTS[("eager", 1)]
    est, by_name = _fitted(cluster, wf, size)
    nodes = [nt.name for nt in target_nodes()]
    engine = TickEngine(est, nodes, size=size)
    names = list(by_name)
    engine.observe_batch([(names[0], nodes[0], size, 30.0),
                          (names[-1], nodes[1], size, 12.0)])
    for leaf in jax.tree.leaves(engine.state):
        leaf.delete()
    ran = []
    real = dispatch.apply_primitive

    def counted(prim, *args, **kw):
        ran.append(prim)
        return real(prim, *args, **kw)

    monkeypatch.setattr(dispatch, "apply_primitive", counted)
    with jax.transfer_guard("disallow"):
        for name in names:
            for nt in nodes:
                lo, hi = engine.predict_interval_node(name, nt, size, 0.9)
                assert 0.0 <= lo <= hi
                pit = engine.predict_pit_node(name, nt, size, 30.0)
                assert 0.0 <= pit <= 1.0
    assert ran == []


def test_engine_program_names(cluster, monkeypatch):
    """The benchmark's device-time readers find the tick's program by the
    fragment ``tick_core`` of its name: the tick the engine runs has it,
    and the host view's program, run at build and after each tick, has
    not."""
    import re
    from repro.core import tick
    wf, size = "bacass", INPUTS[("bacass", 1)]
    est, by_name = _fitted(cluster, wf, size)
    nodes = [nt.name for nt in target_nodes()]
    ran = []
    for attr in ("tick_step", "host_view"):
        jitted = getattr(tick, attr)

        def spy(*args, _jitted=jitted, _attr=attr, **kw):
            text = _jitted.lower(*args, **kw).as_text()
            ran.append((_attr, re.search(r"module @(\S+)", text).group(1)))
            return _jitted(*args, **kw)

        monkeypatch.setattr(tick, attr, spy)
    engine = TickEngine(est, nodes, size=size)
    engine.observe_batch([(list(by_name)[0], nodes[0], size, 30.0)])
    assert [a for a, _ in ran] == ["host_view", "tick_step", "host_view"]
    for attr, program in ran:
        assert ("tick_core" in program) == (attr == "tick_step"), program


def _bits(a):
    return a.shape, a.dtype, a.tobytes()


@pytest.mark.parametrize("dtype", ["float64", "float32"])
def test_host_view_is_bitwise_the_tick_outputs(cluster, request,
                                               monkeypatch, dtype):
    """At build and after every tick, the engine's estimates, gate table
    and bias statistics, all views of one copied buffer, are bitwise the
    float64 widening of what ``tick_step`` returned, of the gate table of
    the model it left, and of the bias leaves of the state it left; the
    six views do not overlap."""
    from repro.core import tick
    if dtype == "float32":
        request.getfixturevalue("_f32")
    wf, size = "eager", INPUTS[("eager", 1)]
    est, by_name = _fitted(cluster, wf, size)
    nodes = [nt.name for nt in target_nodes()]
    engine = TickEngine(est, nodes, size=size)
    assert engine.state.factors.dtype == np.dtype(dtype)
    want = []

    def widened(state, mean, std):
        # copies: the next tick consumes (donates) this state
        return [np.array(a, np.float64) for a in (
            mean, std, tick._gate_table(state.model, size),
            state.bias_counts, state.bias_log_sum, state.bias_log_sq)]

    def spy(state, obs, size_, host_deadjust):
        out = real(state, obs, size_, host_deadjust=host_deadjust)
        want.append(widened(out[0], out[1], out[2]))
        return out

    real = tick.tick_step
    monkeypatch.setattr(tick, "tick_step", spy)
    want.append(widened(engine.state,
                        *predict_state(engine.state, size)))
    names = list(by_name)
    batches = [[(names[0], nodes[0], size, 30.0)],
               [(names[-1], nodes[1], size, 12.0),
                (names[4], nodes[2], size, 55.0)],
               [(names[-1], nodes[1], size, 16.0)]]
    for tick_no in range(len(batches) + 1):
        b = engine._bias
        got = [engine._mean, engine._std, engine._gate, b.counts,
               b.log_sum, b.log_sq]
        assert [_bits(g) for g in got] == [_bits(w) for w in want[-1]]
        for k, g in enumerate(got):
            assert g.flags.c_contiguous
            assert not any(np.shares_memory(g, h) for h in got[k + 1:])
        if tick_no < len(batches):
            engine.observe_batch(batches[tick_no])
    assert len(want) == len(batches) + 1
    assert np.any(engine._bias.counts > 0)


def test_one_copy_per_tick(cluster, monkeypatch):
    """One ``observe_batch`` dispatches the host view once and makes one
    copy to the host, of that buffer, whose byte count the ``tick_fetch``
    span carries.  The CPU backend does not enforce the transfer guard,
    so the explicit copies are counted, and numpy, as the engine sees it,
    refuses a device array (an implicit copy)."""
    from repro.core import tick
    from repro.obs import EventLog
    wf, size = "eager", INPUTS[("eager", 1)]
    est, by_name = _fitted(cluster, wf, size)
    nodes = [nt.name for nt in target_nodes()]
    log = EventLog()
    engine = TickEngine(est, nodes, size=size, tracer=log)
    views, copied = [], []
    real_view, real_get = tick.host_view, jax.device_get

    def view(*args, **kw):
        views.append(real_view(*args, **kw))
        return views[-1]

    def device_get(x):
        copied.append(x)
        return real_get(x)

    class HostOnlyNumpy:
        def __getattr__(self, name):
            f = getattr(np, name)
            if name not in ("asarray", "array"):
                return f

            def host_only(a, *args, **kw):
                assert not isinstance(a, jax.Array), "implicit copy"
                return f(a, *args, **kw)
            return host_only

    monkeypatch.setattr(tick, "host_view", view)
    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(tick, "np", HostOnlyNumpy())
    names = list(by_name)
    with jax.transfer_guard_device_to_host("disallow"):
        engine.observe_batch([(names[0], nodes[0], size, 30.0),
                              (names[-1], nodes[1], size, 12.0)])
    monkeypatch.undo()
    assert len(views) == 1
    assert len(copied) == 1 and copied[0] is views[0]
    t, n = engine._mean.shape
    nb = engine._bias.counts.shape[1]
    fetch, = log.spans("tick_fetch")
    assert fetch.data["nbytes"] == views[0].nbytes == \
        (2 * t * n + 6 * t + 3 * t * nb) * 8


def test_tick_step_donates_and_predict_state_matches(cluster):
    wf, size = "bacass", INPUTS[("bacass", 1)]
    est, _ = _fitted(cluster, wf, size)
    nodes = [nt.name for nt in target_nodes()]
    state, _names = build_state(est, nodes)
    m0, s0 = predict_state(state, size)
    m1, s1 = est.predict_matrix(nodes, size)
    np.testing.assert_allclose(np.asarray(m0), m1, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(np.asarray(s0), s1, rtol=TOL, atol=TOL)
    before = np.asarray(state.model.stats.moments).copy()
    obs = np.zeros((2, 8))
    obs[0] = [0, 0, size, 25.0, 25.0, 25.0, 1.0, 1.0]
    obs[1] = [1, 1, size, 40.0, 40.0, 40.0, 1.0, 0.0]   # masked row
    new_state, mean, std, y = tick_step(
        state, np.asarray(obs), size, host_deadjust=True)
    assert np.all(np.isfinite(np.asarray(mean)))
    # donation: the input state's buffers are consumed
    with pytest.raises((RuntimeError, ValueError)):
        jax.block_until_ready(state.model.stats.moments) + 0
    after = np.asarray(new_state.model.stats.moments)
    assert not np.array_equal(after[0], before[0])   # live row absorbed
    assert np.array_equal(after[1], before[1])       # masked row untouched


# ---------------------------------------------------------------------------
# executor-level: full traces agree on all five workflows, faults on/off
# ---------------------------------------------------------------------------
def _trace_sig(trace):
    recs = sorted((r.id, r.node, r.start, r.end, r.pred_mean, r.pred_std)
                  for r in trace.records)
    return recs, (trace.makespan, trace.replans, trace.surprises,
                  trace.completed, trace.failures, trace.retries)


def _reference_engine(est, nodes, *, size, tracer=None):
    """Test-local stand-in for ``TickEngine``: hands the executor the
    estimator itself, so every tick takes the estimator's host path
    (``observe_batch`` + dirty-row ``predict_matrix`` + the scalar
    interval/PIT consumers) — the reference the engine is held to."""
    est.finalize = lambda: None
    return est


def _run_workflow(cluster, wf, *, reference, with_faults, n_samples=2,
                  nodes_per_type=2, seed=0, executor_cls=OnlineExecutor):
    local, local_bench, tbenches = cluster
    size = INPUTS[(wf, 1)]
    by_name = {t.name: t for t in WORKFLOWS[wf]}
    tasks, task_name = fanout_chain_dag(list(by_name), n_samples)
    truth = ClusterSimulator(seed=seed + 2000)
    truth_tab = {(tid, nt.name): truth.run_task(by_name[task_name[tid]],
                                                nt, size)
                 for tid in tasks for nt in target_nodes()}
    est, _ = _fitted(cluster, wf, size, seed=seed)
    grid = GridEngine.from_types(nodes_per_type=nodes_per_type)
    faults = (FaultInjector(p_fail=0.08, seed=seed + 31)
              if with_faults else None)
    with pytest.MonkeyPatch.context() as mp:
        if reference:
            mp.setattr(executor_mod, "TickEngine", _reference_engine)
        ex = executor_cls(
            est, tasks, task_name, size, grid,
            lambda tid, node: truth_tab[(tid, grid.type_of(node).name)],
            online=True, confidence=0.9, risk_k=0.5, spec_tail=0.6,
            faults=faults, rel_k=1.0 if with_faults else None,
            max_attempts=6, strict=False)
    return ex.run()


@pytest.mark.parametrize("wf", list(WORKFLOWS))
@pytest.mark.parametrize("with_faults", [False, True])
def test_fused_executor_matches_legacy(cluster, wf, with_faults):
    """The engine executor against the same executor driving the
    estimator's host reference path."""
    legacy = _run_workflow(cluster, wf, reference=True,
                           with_faults=with_faults)
    jax.clear_caches()
    fused = _run_workflow(cluster, wf, reference=False,
                          with_faults=with_faults)
    jax.clear_caches()
    recs_l, tail_l = _trace_sig(legacy)
    recs_f, tail_f = _trace_sig(fused)
    assert tail_f[1:] == tail_l[1:]          # counters identical
    assert tail_f[0] == pytest.approx(tail_l[0], rel=TOL, abs=TOL)
    assert len(recs_f) == len(recs_l)
    for a, b in zip(recs_l, recs_f):
        assert b[:2] == a[:2]                # same task -> node assignment
        np.testing.assert_allclose(b[2:], a[2:], rtol=TOL, atol=TOL)


class _FromScratchRank(OnlineExecutor):
    """Drops the rank cache before every re-plan, so each rank is built
    from scratch over the full instance graph."""

    def _incremental_rank(self, *args, **kw):
        self._rank_cache = None
        return super()._incremental_rank(*args, **kw)


def test_incremental_replan_alone_is_bitwise(cluster):
    """Incremental rank reuse against from-scratch ranking, both on the
    estimator's reference path: the traces must be BITWISE equal, not
    just 1e-12-close."""
    inc = _run_workflow(cluster, "methylseq", reference=True,
                        with_faults=False)
    jax.clear_caches()
    scratch = _run_workflow(cluster, "methylseq", reference=True,
                            with_faults=False,
                            executor_cls=_FromScratchRank)
    assert inc.replans > 0
    assert _trace_sig(scratch) == _trace_sig(inc)
