"""Dump the top collectives (trip-aware) of one dry-run cell, attributed by
op_name metadata — the §Perf profiling tool.

  PYTHONPATH=src python scripts/probe_collectives.py qwen2-7b train_4k single
"""
import collections
import os
import re
import sys

import jax
import jax.numpy as jnp

from repro.analysis.hlo_stats import _DEF_RE, _shape_bytes, _split_blocks, analyze_hlo
from repro.configs import get_config
from repro.launch.dryrun import ARCH_DIST, _moe_groups_for
from repro.launch.mesh import make_production_mesh, make_rules
from repro.launch.shapes import SHAPES, input_specs
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
from repro.models import build_model
from repro.models.common import tree_defs_to_abstract
from repro.optim import AdamWConfig, state_defs
from jax.sharding import NamedSharding, PartitionSpec as P


def compile_cell(arch, shape_name, multi_pod):
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    dist = ARCH_DIST.get(arch, {})
    mesh = make_production_mesh(multi_pod=multi_pod)
    overrides = dict(dist.get("overrides", {}))
    if cfg.n_kv_heads % int(mesh.shape["model"]) != 0:
        overrides.setdefault("kv_seq", "model")
    rules = make_rules(mesh, fsdp_over_pod=dist.get("fsdp_over_pod", False),
                       overrides=overrides)
    cfg = cfg.with_(moe_groups=_moe_groups_for(cfg, mesh, rules))
    if dist.get("param_dtype") == "bf16":
        cfg = cfg.with_(param_dtype=jnp.bfloat16)
    model = build_model(cfg)
    opt = AdamWConfig(state_dtype=dist.get("opt_state_dtype", "fp32"),
                      master_fp32=dist.get("master_fp32", False))
    with jax.set_mesh(mesh):
        pa = model.abstract_params(mesh, rules)
        batch = input_specs(cfg, shape, mesh, rules)
        if shape.kind == "train":
            oa = tree_defs_to_abstract(state_defs(model.param_defs, opt),
                                       mesh, rules)
            gd = dist.get("grad_dtype")
            step = make_train_step(model, rules, opt,
                                   microbatches=dist.get("microbatches", 1),
                                   grad_dtype=jnp.bfloat16 if gd == "bf16" else None)
            c = jax.jit(step, donate_argnums=(0, 1)).lower(pa, oa, batch).compile()
        elif shape.kind == "prefill":
            caches = model.abstract_caches(mesh, rules, shape.global_batch,
                                           max_len=shape.seq, cross_len=shape.seq)
            c = jax.jit(make_prefill_step(model, rules),
                        donate_argnums=(2,)).lower(pa, batch, caches).compile()
        else:
            caches = model.abstract_caches(mesh, rules, shape.global_batch,
                                           max_len=shape.seq, cross_len=shape.seq)
            idx = jax.ShapeDtypeStruct((), jnp.int32,
                                       sharding=NamedSharding(mesh, P()))
            c = jax.jit(make_decode_step(model, rules),
                        donate_argnums=(2,)).lower(pa, batch, caches, idx).compile()
    return c, mesh


def main():
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    arch, shape_name, mesh_kind = sys.argv[1], sys.argv[2], sys.argv[3]
    top = int(sys.argv[4]) if len(sys.argv) > 4 else 20
    c, mesh = compile_cell(arch, shape_name, mesh_kind == "multi")
    txt = c.as_text()
    blocks = _split_blocks(txt)
    stats = analyze_hlo(txt, default_group=mesh.size)
    print(f"flops/dev {stats.flops:.3e}  hbm_adj {stats.hbm_bytes_kernel_adj/1e12:.2f}TB  "
          f"coll {stats.collective_bytes/1e9:.1f}GB  "
          f"{stats.collective_bytes_by_op}")

    # trip-aware multipliers: re-derive by re-running the fixpoint
    from repro.analysis import hlo_stats as H
    # approximate: every while body named wide.* executes its trip count;
    # use static counts weighted by known trip counts from the while lines
    trips = {}
    for bname, lines in blocks.items():
        for line in lines:
            if " while(" in line:
                b = H._BODY_RE.search(line)
                t = H._TRIP_RE.search(line)
                if b and t:
                    trips[b.group(1)] = int(t.group(1))
    agg = collections.Counter()
    for bname, lines in blocks.items():
        mult = trips.get(bname, 1 if bname.startswith("main") else 0)
        if mult == 0 and not bname.startswith("main"):
            # nested: approximate with product if parent known
            mult = trips.get(bname, 0)
        for line in lines:
            m = _DEF_RE.match(line)
            if not m:
                continue
            _, shp, opc = m.groups()
            if opc in ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute"):
                mm = re.search(r'op_name="([^"]*)"', line)
                opname = re.sub(r"\d+", "", mm.group(1))[:80] if mm else "?"
                agg[(opc, shp[:44], opname)] += max(mult, 1)
    rows = sorted(agg.items(), key=lambda kv: -_shape_bytes(kv[0][1]) * kv[1])
    for (opc, shp, opname), n in rows[:top]:
        print(f"{n:5d}x {opc:12s} {_shape_bytes(shp)/1e6:9.1f}MB {shp:44s} {opname[:78]}")


if __name__ == "__main__":
    main()
