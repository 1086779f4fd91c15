"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: for each cell
we build abstract (ShapeDtypeStruct + NamedSharding) params / optimizer
state / caches / batch, lower the right step function, compile it, and
record memory_analysis(), cost_analysis() and the collective-bytes census
of the compiled HLO into experiments/artifacts/dryrun/<cell>.json.

Usage:
  python -m repro.launch.dryrun                        # all cells, both meshes
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh multi
"""
import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo_stats import analyze_hlo
from repro.analysis.roofline import Roofline, model_flops
from repro.configs import get_config, list_archs
from repro.launch.mesh import make_production_mesh, make_rules
from repro.launch.shapes import SHAPES, cell_applicable, input_specs
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
from repro.models import build_model
from repro.models.common import tree_defs_to_abstract
from repro.optim import AdamWConfig, state_defs

ART_DIR = Path(__file__).resolve().parents[3] / "experiments" / "artifacts" / "dryrun"

# Per-arch distribution overrides (the hillclimb ledger lives in
# EXPERIMENTS.md §Perf; these are the production defaults).
ARCH_DIST = {
    # 400B params on 16GB chips: mixed precision (bf16 params + fp32 master
    # in optimizer state), bf16 optimizer moments, bf16 gradient wire,
    # ZeRO over the pod axis, and 4-way gradient-accumulation microbatching
    # to bound activation temps.
    # §Perf iterations: mb=1 (microbatching multiplied FSDP weight gathers
    # 4x — refuted as a default; memory handled by the 1024-chip recipe),
    # capacity factor 2.0 -> 1.25 (top-1 dispatch waste)
    "llama4-maverick-400b-a17b": dict(fsdp_over_pod=True,
                                      opt_state_dtype="bf16",
                                      param_dtype="bf16",
                                      master_fp32=True,
                                      microbatches=1,
                                      capacity_factor=1.25),
    # §Perf iteration: bf16 params halve every FSDP weight all-gather
    # (fp32 master lives in the optimizer state).  Validated on the
    # hillclimb cells, then promoted to the fleet-wide production default:
    "qwen2-7b": dict(param_dtype="bf16", master_fp32=True),
    "qwen2-vl-7b": dict(param_dtype="bf16", master_fp32=True),
    "stablelm-12b": dict(param_dtype="bf16", master_fp32=True),
    "stablelm-1.6b": dict(param_dtype="bf16", master_fp32=True),
    "starcoder2-15b": dict(param_dtype="bf16", master_fp32=True),
    "seamless-m4t-large-v2": dict(param_dtype="bf16", master_fp32=True),
    "qwen3-moe-30b-a3b": dict(param_dtype="bf16", master_fp32=True),
    "mamba2-1.3b": dict(param_dtype="bf16", master_fp32=True),
    # §Perf iterations: ssd_chunk 256 REFUTED (+46% collective — bigger
    # per-chunk tensors at the seq-shard boundary); seq_shard off CONFIRMED
    # (mamba blocks are channel-parallel: sequence sharding forced per-layer
    # seq<->channel reshards); microbatches=2 BLOCKED by an XLA SPMD
    # verifier bug (dynamic-slice of the partitioned embedding gather
    # inside the accumulation loop) — see EXPERIMENTS.md §Perf.
    "zamba2-1.2b": dict(param_dtype="bf16", master_fp32=True,
                        seq_shard=False),
}


def _cell_name(arch: str, shape: str, mesh: str) -> str:
    return f"{arch}__{shape}__{mesh}"


def _moe_groups_for(cfg, mesh, rules):
    dp = 1
    for a in rules.dp_axes:
        dp *= mesh.shape[a]
    return dp


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opt_cfg: AdamWConfig | None = None) -> dict:
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cfg = get_config(arch)
    ok, why = cell_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "family": cfg.family,
           "status": "skip" if not ok else "pending", "reason": why}
    if not ok:
        return rec

    dist = ARCH_DIST.get(arch, {})
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    overrides = dict(dist.get("overrides", {}))
    tp = int(mesh.shape["model"])
    if cfg.n_kv_heads % tp != 0:
        # GQA with kv_heads < tp: shard caches along the sequence instead
        # (kv_heads/act_kv_heads fall back to replication automatically via
        # dimension-aware AxisRules).
        overrides.setdefault("kv_seq", "model")
    rules = make_rules(mesh, fsdp_over_pod=dist.get("fsdp_over_pod", False),
                       overrides=overrides)
    cfg = cfg.with_(moe_groups=_moe_groups_for(cfg, mesh, rules))
    if dist.get("param_dtype") == "bf16":
        cfg = cfg.with_(param_dtype=jnp.bfloat16)
    if "ssd_chunk" in dist and cfg.ssm is not None:
        import dataclasses as _dc
        cfg = cfg.with_(ssm=_dc.replace(cfg.ssm, chunk=dist["ssd_chunk"]))
    if "capacity_factor" in dist and cfg.moe is not None:
        import dataclasses as _dc
        cfg = cfg.with_(moe=_dc.replace(cfg.moe,
                                        capacity_factor=dist["capacity_factor"]))
    if "seq_shard" in dist:
        cfg = cfg.with_(seq_shard=dist["seq_shard"])
    model = build_model(cfg)
    opt_cfg = opt_cfg or AdamWConfig(
        state_dtype=dist.get("opt_state_dtype", "fp32"),
        master_fp32=dist.get("master_fp32", False))

    chips = mesh.size
    params_abs = model.abstract_params(mesh, rules)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_abs = tree_defs_to_abstract(state_defs(model.param_defs, opt_cfg),
                                            mesh, rules)
            batch = input_specs(cfg, shape, mesh, rules)
            gd = dist.get("grad_dtype")
            step = make_train_step(model, rules, opt_cfg,
                                   microbatches=dist.get("microbatches", 1),
                                   grad_dtype=jnp.bfloat16 if gd == "bf16" else None)
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                params_abs, opt_abs, batch)
        elif shape.kind == "prefill":
            caches = model.abstract_caches(mesh, rules, shape.global_batch,
                                           max_len=shape.seq, cross_len=shape.seq)
            batch = input_specs(cfg, shape, mesh, rules)
            step = make_prefill_step(model, rules)
            lowered = jax.jit(step, donate_argnums=(2,)).lower(
                params_abs, batch, caches)
        else:  # decode
            caches = model.abstract_caches(mesh, rules, shape.global_batch,
                                           max_len=shape.seq, cross_len=shape.seq)
            batch = input_specs(cfg, shape, mesh, rules)
            index = jax.ShapeDtypeStruct((), jnp.int32,
                                         sharding=NamedSharding(mesh, P()))
            step = make_decode_step(model, rules)
            lowered = jax.jit(step, donate_argnums=(2,)).lower(
                params_abs, batch, caches, index)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    stats = analyze_hlo(hlo, default_group=chips)

    mflops, tokens = model_flops(cfg, shape.kind, shape.seq, shape.global_batch)
    # memory term uses the Pallas-kernel-aware accounting: the production
    # TPU path runs attention/SSD as fused kernels whose loop-internal
    # tensors are VMEM-resident (raw XLA-path bytes kept for the ablation)
    roof = Roofline(arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
                    flops_per_device=stats.flops,
                    bytes_per_device=stats.hbm_bytes_kernel_adj,
                    coll_bytes_per_device=float(stats.collective_bytes),
                    model_flops_total=mflops, step_tokens=tokens)

    rec.update(
        status="ok",
        chips=chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", 0),
            output_bytes=getattr(mem, "output_size_in_bytes", 0),
            temp_bytes=getattr(mem, "temp_size_in_bytes", 0),
            alias_bytes=getattr(mem, "alias_size_in_bytes", 0),
            generated_code_bytes=getattr(mem, "generated_code_size_in_bytes", 0),
        ),
        cost={k: float(v) for k, v in cost.items()
              if isinstance(v, (int, float)) and "{" not in k},
        collectives=dict(bytes_per_device=stats.collective_bytes,
                         counts=stats.collective_counts,
                         bytes_by_op=stats.collective_bytes_by_op),
        hlo_census=dict(n_while_loops=stats.n_while_loops,
                        static_collectives=stats.static_collectives,
                        kernel_blocks=stats.kernel_blocks,
                        hbm_bytes_raw=stats.hbm_bytes,
                        hbm_bytes_naive=stats.hbm_bytes_naive,
                        flops_by_block=stats.dot_flops_by_block,
                        xla_cost_flops=float(cost.get("flops", 0.0)),
                        xla_bytes_accessed=float(cost.get("bytes accessed", 0.0))),
        roofline=roof.to_dict(),
        params_total=cfg.param_count(),
        params_active=cfg.active_param_count(),
    )
    # per-device HBM pressure (args include donated params/opt/caches)
    hbm = (rec["memory"]["argument_bytes"] + rec["memory"]["temp_bytes"]
           + rec["memory"]["output_bytes"] - rec["memory"]["alias_bytes"])
    rec["memory"]["hbm_estimate_bytes"] = hbm
    rec["memory"]["fits_16gb"] = bool(hbm < 16e9)
    return rec


def main() -> None:
    # 512 host devices stand in for the chips; set before the backend
    # starts, which nothing before this point does
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=str(ART_DIR))
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi_pod in meshes:
                name = _cell_name(arch, shape, "pod2x16x16" if multi_pod else "pod16x16")
                path = out_dir / f"{name}.json"
                if path.exists():
                    print(f"[cached] {name}")
                    continue
                t0 = time.time()
                try:
                    rec = run_cell(arch, shape, multi_pod)
                except (ValueError, TypeError, KeyError, RuntimeError,
                        NotImplementedError) as e:
                    # record the failure, keep sweeping: shape/sharding
                    # mismatches (ValueError/TypeError), unknown arch or
                    # missing config key (KeyError), XLA compile errors
                    # (XlaRuntimeError is a RuntimeError), unimplemented
                    # lowerings (NotImplementedError)
                    failures += 1
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "pod2x16x16" if multi_pod else "pod16x16",
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()[-4000:]}
                path.write_text(json.dumps(rec, indent=1))
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" bound={r['bound']} roofline_frac={r['roofline_fraction']:.3f}"
                             f" hbm={rec['memory']['hbm_estimate_bytes']/1e9:.2f}GB"
                             f" compile={rec['compile_s']:.0f}s")
                print(f"[{status}] {name}{extra} ({time.time()-t0:.0f}s)", flush=True)
    print(f"done; failures={failures}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
