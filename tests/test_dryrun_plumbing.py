"""Distribution plumbing: steps lower+compile on a real (small) SPMD mesh.

The production 512-device dry-run runs via ``repro.launch.dryrun`` (its own
process sets XLA_FLAGS before jax init).  Here we exercise the identical
code path on a subprocess-local 8-device mesh so the test env keeps its
single default device.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.launch.mesh import make_mesh, make_rules
    from repro.launch.shapes import ShapeSpec, input_specs
    from repro.launch.steps import make_train_step, make_decode_step
    from repro.models import build_model
    from repro.models.common import tree_defs_to_abstract
    from repro.optim import AdamWConfig, state_defs
    from repro.analysis.hlo_stats import analyze_hlo
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    rules = make_rules(mesh)
    out = {}
    for arch in ["qwen2-7b", "mamba2-1.3b"]:
        cfg = smoke_config(arch).with_(moe_groups=4)
        model = build_model(cfg)
        opt = AdamWConfig()
        with jax.set_mesh(mesh):
            pa = model.abstract_params(mesh, rules)
            oa = tree_defs_to_abstract(state_defs(model.param_defs, opt), mesh, rules)
            batch = input_specs(cfg, ShapeSpec("t", "train", 64, 8), mesh, rules)
            step = make_train_step(model, rules, opt)
            c = jax.jit(step, donate_argnums=(0, 1)).lower(pa, oa, batch).compile()
            stats = analyze_hlo(c.as_text(), default_group=8)
            mem = c.memory_analysis()
            out[arch] = {
                "flops": stats.flops,
                "coll": stats.collective_bytes,
                "whiles": stats.n_while_loops,
                "temp": mem.temp_size_in_bytes,
            }
            # decode path must also compile on the mesh
            caches = model.abstract_caches(mesh, rules, 8, max_len=64)
            dbatch = input_specs(cfg, ShapeSpec("d", "decode", 64, 8), mesh, rules)
            idx = jax.ShapeDtypeStruct((), jnp.int32,
                                       sharding=NamedSharding(mesh, P()))
            dstep = make_decode_step(model, rules)
            jax.jit(dstep, donate_argnums=(2,)).lower(pa, dbatch, caches, idx).compile()
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_small_mesh_spmd_compile():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for arch, rec in out.items():
        assert rec["flops"] > 0, arch
        assert rec["coll"] > 0, arch          # SPMD inserted collectives
        assert rec["whiles"] >= 1, arch       # scan-over-layers survived
        assert rec["temp"] < 4e9, arch


def test_cell_applicability_rules():
    from repro.configs import get_config
    from repro.launch.shapes import SHAPES, cell_applicable
    ok, _ = cell_applicable(get_config("qwen2-7b"), SHAPES["long_500k"])
    assert not ok
    ok, _ = cell_applicable(get_config("mamba2-1.3b"), SHAPES["long_500k"])
    assert ok
    ok, _ = cell_applicable(get_config("zamba2-1.2b"), SHAPES["long_500k"])
    assert ok
    for arch in ("qwen2-7b", "zamba2-1.2b"):
        for shape in ("train_4k", "prefill_32k", "decode_32k"):
            ok, _ = cell_applicable(get_config(arch), SHAPES[shape])
            assert ok


def test_dryrun_artifacts_complete_if_present():
    art = ROOT / "experiments" / "artifacts" / "dryrun"
    files = list(art.glob("*.json"))
    if not files:
        pytest.skip("dry-run artifacts not generated yet")
    recs = [json.loads(f.read_text()) for f in files]
    assert len(recs) == 80                      # 10 archs x 4 shapes x 2 meshes
    by_status = {}
    for r in recs:
        by_status.setdefault(r["status"], []).append(r)
    assert not by_status.get("error"), [r["arch"] for r in by_status["error"]]
    assert len(by_status.get("skip", [])) == 16  # 8 full-attn archs x long_500k x 2
    for r in by_status["ok"]:
        assert r["roofline"]["flops_per_device"] > 0, r["arch"]
        assert r["memory"]["hbm_estimate_bytes"] > 0
