"""Shared model configuration and sharding vocabulary.

Models are plain pytrees of jnp arrays; every parameter leaf has a parallel
``PartitionSpec`` leaf built from *logical axis names* resolved against the
active mesh through ``AxisRules``.  No flax/haiku — the framework owns its
parameter system so that dry-run abstract lowering (ShapeDtypeStruct with
NamedSharding) and real initialization share one code path.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# Logical axis vocabulary
# ---------------------------------------------------------------------------
# layers    : scan-stacked layer dimension (never sharded; must stay local)
# vocab     : embedding / output-projection vocabulary dim     -> "model"
# embed     : d_model dim of weights                           -> fsdp axes
# heads     : query heads                                      -> "model"
# kv_heads  : KV heads (GQA)                                   -> "model"
# mlp       : feed-forward hidden dim                          -> "model"
# experts   : MoE expert dim                                   -> "model"
# batch     : activation batch dim                             -> data axes
# act_embed : activation d_model dim (usually unsharded)
# act_heads : activation heads dim                             -> "model"
# act_mlp   : activation ffn dim                               -> "model"
# act_vocab : activation vocab dim (chunked-xent logits)       -> "model"
# ssm_*     : mamba2 state dims (unsharded by default)

DEFAULT_RULES: dict[str, Any] = {
    "layers": None,
    "vocab": "model",
    "embed": "__fsdp__",      # resolved to ("data",) / ("pod","data") at mesh time
    "embed_noshard": None,
    "heads": "model",
    "kv_heads": "model",
    "kv_seq": None,           # cache seq dim; sharded when kv_heads % tp != 0
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "expert_mlp": None,
    "batch": "__dp__",        # resolved to data axes
    "groups": "__dp__",
    "seq": None,
    "act_embed": None,
    "act_seq": "model",      # sequence-parallel activations between blocks
    "act_heads": "model",
    "act_kv_heads": "model",
    "act_mlp": "model",
    "act_vocab": "model",
    "embed_gather": "model",  # bf16 embed-table copy layout for the gather:
                              # d over "model" keeps the lookup collective-free
    "ssm_heads": "model",
    "ssm_state": None,
    "ssm_inner": "model",
    "conv_dim": "model",
}


@dataclass(frozen=True)
class AxisRules:
    """Resolves logical axis names to mesh axes for a given mesh layout.

    ``axis_sizes`` enables dimension-aware resolution: a sharded dim whose
    size does not divide the mesh-axis product is resolved to None (JAX
    rejects uneven input shardings).  The dropped sharding is compensated
    elsewhere (e.g. GQA caches shard ``kv_seq`` when kv_heads %% tp != 0).
    """

    fsdp_axes: tuple[str, ...] = ("data",)
    dp_axes: tuple[str, ...] = ("data",)
    overrides: Mapping[str, Any] = field(default_factory=dict)
    axis_sizes: Mapping[str, int] = field(default_factory=dict)

    def _mesh_axes(self, name: str):
        table = dict(DEFAULT_RULES)
        table.update(self.overrides)
        mesh_axis = table.get(name, None)
        if mesh_axis == "__fsdp__":
            mesh_axis = self.fsdp_axes if len(self.fsdp_axes) > 1 else (
                self.fsdp_axes[0] if self.fsdp_axes else None)
        elif mesh_axis == "__dp__":
            mesh_axis = self.dp_axes if len(self.dp_axes) > 1 else (
                self.dp_axes[0] if self.dp_axes else None)
        return mesh_axis

    def _shard_count(self, mesh_axis) -> int:
        if mesh_axis is None or not self.axis_sizes:
            return 1
        axes = mesh_axis if isinstance(mesh_axis, tuple) else (mesh_axis,)
        n = 1
        for a in axes:
            n *= self.axis_sizes.get(a, 1)
        return n

    def resolve(self, *logical: str | None,
                dims: Sequence[int] | None = None) -> P:
        out = []
        for i, name in enumerate(logical):
            if name is None:
                out.append(None)
                continue
            mesh_axis = self._mesh_axes(name)
            if dims is not None and mesh_axis is not None:
                n = self._shard_count(mesh_axis)
                if n > 1 and dims[i] % n != 0:
                    mesh_axis = None     # uneven: fall back to replication
            out.append(mesh_axis)
        return P(*out)


def mesh_axis_sizes(mesh) -> dict:
    return {name: int(mesh.shape[name]) for name in mesh.axis_names}


def rules_for_mesh(mesh) -> AxisRules:
    names = mesh.axis_names
    sizes = mesh_axis_sizes(mesh)
    if "pod" in names:
        return AxisRules(fsdp_axes=("pod", "data"), dp_axes=("pod", "data"),
                         axis_sizes=sizes)
    if "data" in names:
        return AxisRules(fsdp_axes=("data",), dp_axes=("data",),
                         axis_sizes=sizes)
    # single-device / test mesh
    return AxisRules(fsdp_axes=(), dp_axes=(), axis_sizes=sizes)


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    every: int = 1                 # MoE block every N layers (llama4: 2)
    shared_expert: bool = False    # additional always-on expert (llama4)
    capacity_factor: float = 1.25
    router_jitter: float = 0.0


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2                # d_inner = expand * d_model
    head_dim: int = 64             # mamba2 P
    chunk: int = 128               # SSD chunk length
    n_groups: int = 1              # B/C groups

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_ssm_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class ModelConfig:
    arch: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0              # 0 -> d_model // n_heads
    qkv_bias: bool = False
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    act: str = "swiglu"            # swiglu | gelu
    rope_theta: float = 10_000.0
    mrope: bool = False            # qwen2-vl multimodal RoPE
    tie_embeddings: bool = False
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    hybrid_attn_every: int = 0     # zamba2: shared attn block every N ssm blocks
    enc_layers: int = 0            # encdec only
    dec_layers: int = 0
    # numerics / execution
    dtype: Any = jnp.bfloat16      # activation/compute dtype
    param_dtype: Any = jnp.float32
    attn_chunk: int = 512          # KV block for chunked flash-style attention
    xent_chunk: int = 2048         # token block for chunked cross entropy
    remat: str = "full"            # none | full | dots
    moe_groups: int = 0            # 0 -> infer from mesh dp size
    kernel_mode: str = "xla"       # xla | pallas (pallas only on real TPU)
    seq_shard: bool = True         # sequence-parallel activations (Megatron-SP)

    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ----- parameter counting (analytic; used by roofline + Lotaru) -------
    def param_count(self) -> int:
        return _param_count(self)

    def active_param_count(self) -> int:
        return _param_count(self, active_only=True)


def _attn_params(cfg: ModelConfig) -> int:
    hd = cfg.resolved_head_dim()
    q = cfg.d_model * cfg.n_heads * hd
    kv = 2 * cfg.d_model * cfg.n_kv_heads * hd
    o = cfg.n_heads * hd * cfg.d_model
    b = (cfg.n_heads * hd + 2 * cfg.n_kv_heads * hd) if cfg.qkv_bias else 0
    return q + kv + o + b


def _mlp_params(d_model: int, d_ff: int, act: str) -> int:
    return (3 if act == "swiglu" else 2) * d_model * d_ff


def _param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    emb = cfg.vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    if cfg.family == "encdec":
        enc = cfg.enc_layers * (_attn_params(cfg) + _mlp_params(cfg.d_model, cfg.d_ff, cfg.act))
        dec = cfg.dec_layers * (2 * _attn_params(cfg) + _mlp_params(cfg.d_model, cfg.d_ff, cfg.act))
        return emb + enc + dec
    if cfg.family == "ssm":
        s = cfg.ssm
        di = s.d_inner(cfg.d_model)
        nh = s.n_ssm_heads(cfg.d_model)
        per = (cfg.d_model * (2 * di + 2 * s.n_groups * s.d_state + nh)   # in_proj
               + s.d_conv * (di + 2 * s.n_groups * s.d_state)             # conv
               + nh * 2                                                   # A_log, D
               + di                                                       # norm gate
               + di * cfg.d_model)                                        # out_proj
        return emb + cfg.n_layers * per
    if cfg.family == "hybrid":
        ssm_cfg = cfg.with_(family="ssm")
        base = _param_count(ssm_cfg, active_only)
        shared = _attn_params(cfg) + _mlp_params(cfg.d_model, cfg.d_ff, cfg.act)
        return base + shared
    # dense / moe / vlm
    per_attn = _attn_params(cfg)
    total = emb
    for layer in range(cfg.n_layers):
        total += per_attn
        if cfg.moe is not None and layer % cfg.moe.every == cfg.moe.every - 1:
            n_active = cfg.moe.top_k + (1 if cfg.moe.shared_expert else 0)
            n_count = n_active if active_only else (
                cfg.moe.n_experts + (1 if cfg.moe.shared_expert else 0))
            total += n_count * _mlp_params(cfg.d_model, cfg.moe.d_ff_expert, cfg.act)
            total += cfg.d_model * cfg.moe.n_experts  # router
        else:
            total += _mlp_params(cfg.d_model, cfg.d_ff, cfg.act)
    return total


# ---------------------------------------------------------------------------
# Parameter/spec tree construction
# ---------------------------------------------------------------------------
@dataclass
class ParamDef:
    """Deferred parameter: shape + init + logical axes.

    Materialised either abstractly (ShapeDtypeStruct for the dry-run) or
    concretely (real arrays for training/examples).
    """
    shape: tuple[int, ...]
    logical_axes: tuple[str | None, ...]
    init: str = "normal"           # normal | zeros | ones | scaled
    scale: float = 1.0
    dtype: Any = jnp.float32

    def spec(self, rules: AxisRules) -> P:
        return rules.resolve(*self.logical_axes, dims=self.shape)


def init_leaf(key, d: ParamDef):
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    fan_in = d.shape[-2] if len(d.shape) >= 2 else max(d.shape[-1], 1)
    std = d.scale / (fan_in ** 0.5)
    return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(d.dtype)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def tree_defs_to_specs(defs, rules: AxisRules):
    return jax.tree.map(lambda d: d.spec(rules), defs, is_leaf=is_def)


def tree_defs_to_abstract(defs, mesh, rules: AxisRules):
    from jax.sharding import NamedSharding
    def mk(d: ParamDef):
        return jax.ShapeDtypeStruct(d.shape, d.dtype,
                                    sharding=NamedSharding(mesh, d.spec(rules)))
    return jax.tree.map(mk, defs, is_leaf=is_def)


def tree_defs_init(defs, key):
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(key, len(leaves))
    vals = [init_leaf(k, d) for k, d in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, vals)


def logical_constraint(x, rules: AxisRules, *logical: str | None):
    """Sharding constraint by logical axes under the mesh that
    ``jax.set_mesh`` made current; a no-op when no mesh is set (single
    device).  A spec the mesh cannot take raises."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, rules.resolve(*logical, dims=x.shape))
