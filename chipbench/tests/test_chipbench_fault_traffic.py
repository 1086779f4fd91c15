"""A configuration and a fault scenario enter the benchmark as files: a
configuration the tests have no entry for brings its own test sizes, a
traffic file's ``faults`` block reaches the program's fault process, and
the check holds the runs to the plain reference of the fault semantics."""
import copy
import json
import shutil

import pytest

import harness
import reference_faults as rf
from conftest import BENCH, REPO, load_bench, make_tree

# names of the test's own, apart from any the benchmark commits
FT = "eager-ft-test"
CELL = "eager-ft-test.faults"
FAULTS = {"arrivals": "closed", "samples_per_run": 8,
          "faults": {"crash": [[0, 0.25], [-1, 0.5]], "p_fail": 0.05,
                     "p_spread": 1.0}}
EXECUTOR = {"rel_k": 1.0, "max_attempts": 6, "backoff_base": 1.0,
            "backoff_cap": 30.0}
LIMITS = {"fault_schedule_errors": 0, "reliability_gap": 1e-9,
          "fault_shortfall": 0}


def _stage(tmp_path):
    """A checkout whose benchmark gains a configuration, a traffic file and
    a workload as new files and entries, no existing file edited; and the
    test tree ``make_tree`` builds from it."""
    src = tmp_path / "src"
    shutil.copytree(BENCH, src / BENCH.name, ignore=shutil.ignore_patterns(
        "out", "tests", "__pycache__"))
    bench = load_bench()
    eager = _eager(bench)
    cfg = json.loads((REPO / eager["file"]).read_text())
    cfg["executor"] = {**cfg["executor"], **EXECUTOR}
    cfg["limits"] = {**cfg["limits"], **LIMITS}
    cfg["warmup_runs"] = 3
    cfg["test_sizes"] = {"warmup_runs": 1}
    (src / f"chipbench/configs/{FT}.json").write_text(json.dumps(cfg))
    (src / "chipbench/traffic/faults-test.json").write_text(
        json.dumps(FAULTS))
    bench["configs"].append({**eager, "name": FT,
                             "file": f"chipbench/configs/{FT}.json"})
    bench["workloads"].append({"name": CELL, "config": FT,
                               "traffic": "faults-test", "chips": 1,
                               "why": "test"})
    (src / "BENCHMARK.json").write_text(json.dumps(bench))
    return make_tree(tmp_path / "dst", src)


def _eager(bench):
    return next(c for c in bench["configs"]
                if c["name"] == "nfcore-eager-ds1-5n")


@pytest.fixture
def ft_tree(tmp_path):
    return _stage(tmp_path)


def test_make_tree_takes_test_sizes_from_each_file(ft_tree):
    cfg = json.loads((ft_tree / f"chipbench/configs/{FT}.json").read_text())
    assert cfg["warmup_runs"] == 1
    eager = _eager(load_bench())["file"]
    assert json.loads((ft_tree / eager).read_text()) == \
        json.loads((REPO / eager).read_text())


def test_faults_cell_is_correct(ft_tree, run_cell):
    rc, line, err = run_cell(ft_tree, "--workload", CELL, "--seed",
                             3_000_000_101, "--seconds", 1, "--trace", 0)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert set(LIMITS) < set(line["checks"])
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
    assert "chipbench: fault-free makespan" in err
    assert " 0 programs prepared" in err


def _grid_fail_ignored(monkeypatch):
    """A crashed node stays alive and keeps taking work."""
    from repro.sched.simulator import GridEngine
    monkeypatch.setattr(GridEngine, "fail", lambda self, name, at: None)


def _failures_as_successes(monkeypatch):
    """Every attempt outcome reaches the reliability posterior as a
    success."""
    from repro.core.estimator import LotaruEstimator
    orig = LotaruEstimator.record_attempt
    monkeypatch.setattr(LotaruEstimator, "record_attempt",
                        lambda self, node, success: orig(self, node, True))


def _no_backoff(monkeypatch):
    """A lost attempt is retried at once."""
    from repro.online import OnlineExecutor
    monkeypatch.setattr(OnlineExecutor, "_backoff", lambda self, n: 0.0)


BROKEN = [(_grid_fail_ignored, "fault_schedule_errors"),
          (_failures_as_successes, "reliability_gap"),
          (_no_backoff, "fault_schedule_errors")]


@pytest.mark.parametrize("fault,number", BROKEN,
                         ids=[f.__name__[1:] for f, _ in BROKEN])
def test_broken_fault_path_fails(ft_tree, run_cell, monkeypatch, fault,
                                 number):
    fault(monkeypatch)
    rc, line, err = run_cell(ft_tree, "--workload", CELL, "--seed",
                             3_000_000_102, "--seconds", 1, "--trace", 0)
    assert rc == 0, err
    assert line["correct"] is False, err
    assert line["checks"][number]["value"] > line["checks"][number]["limit"]


def test_same_seed_same_faults(ft_tree):
    """Crash times and injector seeds follow from the seed alone."""
    cfg = json.loads((ft_tree / f"chipbench/configs/{FT}.json").read_text())
    runner = harness.load_module(ft_tree / "chipbench/runners/executor.py")

    def draw(seed):
        r = runner.Runner(cfg, FAULTS, seed, harness.Recorder())
        inj = r.injector(0)
        assert inj.crash_at == r.crash_at and inj.p_fail == 0.05
        return r.crash_at, [r.fault_seed(k) for k in range(3)]

    seed = 2 ** 31 + 77
    a = draw(seed)
    assert a == draw(seed)
    b = draw(seed + 1)
    assert a[0] != b[0] and a[1] != b[1]
    assert list(a[0]) == ["tpu-v2/0", "tpu-v5p/0"]
    assert len(set(a[1])) == 3


def test_reliability_reference_by_hand():
    """Beta(8, 1) prior, factor 1 / max(E[p] - k sd, 0.05):
    a, 3 successes: Beta(11, 1), E 0.916667, sd 0.076665 -> 1.190460;
    b, 1 failure: Beta(8, 2), E 0.8, sd 0.120605 -> 1.471897;
    c, 300 failures: E 0.025890, under the floor -> 20;
    d, nothing: Beta(8, 1), E 0.888889, sd 0.099381 -> 1.266611."""
    nodes = ["a", "b", "c", "d"]
    succ, fail = {"a": 3}, {"b": 1, "c": 300}
    got = rf.reliability_factors(nodes, succ, fail, 1.0)
    assert got == pytest.approx([1.190460389831765, 1.4718967901369417,
                                 20.0, 1.2666114670727728], rel=1e-12)
    # k = 0: the factor is (a + b) / a
    assert rf.reliability_factors(nodes, succ, fail, 0.0)[:1] == \
        pytest.approx([12 / 11], rel=1e-15)
    # no attempt anywhere: the layer is inert
    assert list(rf.reliability_factors(nodes, {}, {}, 1.0)) == [1.0] * 4


# a clean run: n0 crashes at 10 and loses s0.d there; s0.c's first
# attempt fails on n2
RECORDS = [{"id": "s0.a", "node": "n0", "start": 0.0, "end": 4.0},
           {"id": "s0.b", "node": "n1", "start": 0.0, "end": 12.0},
           {"id": "s0.d", "node": "n1", "start": 12.0, "end": 20.0},
           {"id": "s0.c", "node": "n1", "start": 20.0, "end": 25.0}]
CENSORED = [{"id": "s0.d", "node": "n0", "start": 4.0, "lost_at": 10.0,
             "reason": "node"},
            {"id": "s0.c", "node": "n2", "start": 0.0, "lost_at": 3.0,
             "reason": "attempt"}]


def _starts_after_crash(recs, cens):
    cens.append({"id": "s0.e", "node": "n0", "start": 10.0, "lost_at": 10.5,
                 "reason": "attempt"})


def _ends_after_crash(recs, cens):
    recs[0]["end"] = 10.5
    cens[0].update(node="n2", reason="attempt")


def _lost_before_crash(recs, cens):
    cens[0]["lost_at"] = 9.5


def _overlap(recs, cens):
    recs[2]["start"] = 11.5


def _over_budget(recs, cens):
    cens.append({"id": "s0.c", "node": "n2", "start": 4.0, "lost_at": 4.5,
                 "reason": "attempt"})


def _retry_before_backoff(recs, cens):
    cens[1]["lost_at"] = 19.5     # s0.c's retry at 20, its backoff ends 20.5


def _observation_missing(recs, cens):
    recs.append({"id": "s0.f", "node": "n2", "start": 4.0, "end": 5.0})


ERRORS = [_starts_after_crash, _ends_after_crash, _lost_before_crash,
          _overlap, _over_budget, _retry_before_backoff,
          _observation_missing]


@pytest.mark.parametrize("case", [None] + ERRORS,
                         ids=["clean"] + [f.__name__[1:] for f in ERRORS])
def test_fault_schedule_errors_by_case(case):
    recs, cens = copy.deepcopy(RECORDS), copy.deepcopy(CENSORED)
    if case is not None:
        case(recs, cens)
    got = rf.fault_schedule_errors(recs, cens, {"n0": 10.0}, 2, n_obs=4,
                                   backoff=(1.0, 30.0))
    assert got == (0 if case is None else 1)


# (start, end, lost) of one instance's attempts; backoff base 1, cap 3
BACKOFF = {
    "retries_wait": ([(0, 2, True), (3, 4, True), (6, 9, False)], 0),
    "first_retry_early": ([(0, 2, True), (2.5, 9, False)], 1),
    "second_retry_early": ([(0, 2, True), (3, 4, True), (5.5, 9, False)], 1),
    "capped": ([(0, 1, True), (2, 3, True), (5, 6, True), (9, 10, True),
                (13, 20, False)], 0),
    "speculative_copy": ([(0, 5, True), (2, 9, False)], 0),
    "retry_after_both_lost": ([(0, 5, True), (2, 6, True), (6.5, 9, False)],
                              1),
}


@pytest.mark.parametrize("case", BACKOFF)
def test_backoff_errors(case):
    """A retry waits min(base 2^(n-1), cap) after the last loss; a copy
    started beside a live attempt is no retry."""
    attempts, want = BACKOFF[case]
    assert rf.backoff_errors(attempts, (1.0, 3.0)) == want
