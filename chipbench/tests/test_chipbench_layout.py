"""A configuration, a traffic mix and a per-layer metric added as files,
with entries in BENCHMARK.json, are found by name: no code is edited."""
import json

READER = '''"""Prediction nodes of the cell's cluster."""


def read(ctx):
    return ctx.shape["N"] if ctx.lat else None
'''


def test_added_files_are_found(tree, run_cell):
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    base = json.loads((tree / bench["configs"][0]["file"]).read_text())
    # a smaller cluster, two of the five machine types; two samples a run
    base.update(node_types=base["node_types"][:2])
    (tree / "chipbench/configs/eager-tiny.json").write_text(json.dumps(base))
    (tree / "chipbench/traffic/runs.json").write_text(
        json.dumps({"arrivals": "closed", "samples_per_run": 2}))
    (tree / "chipbench/metrics/cluster.nodes.py").write_text(READER)
    bench["configs"].append({"name": "eager-tiny", "source": "test",
                             "file": "chipbench/configs/eager-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "eagertiny.runs",
                               "config": "eager-tiny", "traffic": "runs",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "cluster.nodes", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "obs_per_s",
                               "workloads": ["eagertiny.runs"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))

    rc, line, err = run_cell(tree, "--workload", "eagertiny.runs", "--seed",
                             11, "--seconds", 1, "--trace", 1)
    assert rc == 0, err
    assert line["correct"] is True, err
    # the new cell reports the one per-layer metric that lists it
    assert set(line["metrics"]) == {"cluster.nodes"}
    # the new configuration's cluster reached the program
    assert line["metrics"]["cluster.nodes"]["value"] == 2

    rc, line, err = run_cell(tree, "--workload", "eagertiny.runs", "--seed",
                             12, "--seconds", 1, "--trace", 0)
    assert rc == 0 and line["correct"] is True, err
    assert "cluster.nodes" not in line["metrics"]


def test_unknown_names_are_errors(tree, run_cell):
    rc, line, err = run_cell(tree, "--workload", "no.such", "--seed", 1,
                             "--seconds", 1, "--trace", 0)
    assert rc != 0 and line is None
    assert "unknown workload" in err
