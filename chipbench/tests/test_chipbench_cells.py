"""Each cell runs end to end on the CPU and prints the result line the
benchmark's contract asks for; its inputs follow from the seed."""
import json

import pytest

from conftest import load_bench

CELLS = [w["name"] for w in load_bench()["workloads"]]
EAGER = "chipbench/configs/nfcore-eager-ds1-5n.json"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_prints_result_line(tree, run_cell, cell):
    rc, line, err = run_cell(tree, "--workload", cell, "--seed",
                             3_000_000_007, "--seconds", 1, "--trace", 0)
    assert rc == 0, err
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"tick_ms_p50", "tick_ms_p95", "obs_per_s",
            "setup_s"} == set(line["metrics"])
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
    # the numbers compared are the last lines of standard error
    assert err.strip().splitlines()[-1] == "chipbench: correct True (program)"


def test_same_seed_same_inputs(tree):
    """Two draws from one large seed give the same benches, local runs and
    ground truth; another seed gives others."""
    import gen
    cfg = json.loads((tree / EAGER).read_text())
    dag = gen.instances(cfg["deps"], 8)
    seed = 2 ** 31 + 5
    a, b = gen.eager_data(cfg, seed), gen.eager_data(cfg, seed)
    assert a["runs"] == b["runs"] and a["types"] == b["types"]
    truth = gen.eager_truth(cfg, a, dag, seed, 3)
    assert truth == gen.eager_truth(cfg, b, dag, seed, 3)
    assert truth != gen.eager_truth(cfg, a, dag, seed, 4)
    assert gen.eager_data(cfg, seed + 1)["runs"] != a["runs"]


def test_instance_graph_follows_deps(tree):
    """Every task of the configuration runs once per sample, after the tasks
    whose output it reads."""
    import gen
    cfg = json.loads((tree / EAGER).read_text())
    dag = gen.instances(cfg["deps"], 2)
    assert len(dag) == 2 * len(cfg["tasks"])
    assert {name for name, _ in dag.values()} == {t[0] for t in cfg["tasks"]}
    seen = set()
    for tid, (name, preds) in dag.items():
        assert all(p in seen for p in preds), tid
        assert sorted(p.split(".", 1)[1] for p in preds) == \
            sorted(cfg["deps"][name])
        seen.add(tid)
    roots = {tid for tid, (_, preds) in dag.items() if not preds}
    assert roots == {"s0.fastqc", "s0.adapter_removal", "s1.fastqc",
                     "s1.adapter_removal"}
    with pytest.raises(ValueError, match="cycle"):
        gen.instances({"a": ["b"], "b": ["a"]}, 1)
