"""Blocked (flash) attention forward, TPU Pallas.

TPU-native design (not a CUDA port): the grid is (batch, q_heads, q_blocks,
kv_blocks) with the kv dimension iterated minor-most — TPU grids execute
sequentially per core, so the online-softmax running state lives in VMEM
scratch across kv iterations (no warp semantics, no shared-memory banking).
Block shapes default to 128x128 (MXU tile aligned); GQA is handled in the
*index map* (q head h reads kv head h // group), so grouped KV is never
materialised in HBM.

Softmax statistics and the output accumulator are fp32; QK^T and PV run
with bf16 inputs + fp32 accumulation (MXU-native mixed precision).
Fully-masked causal blocks are skipped via ``pl.when`` (the compute —
though not the prefetch — of the upper triangle vanishes).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                      sm_scale: float, causal: bool, block_q: int,
                      block_k: int, n_k: int, kv_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if causal:
        run = ik * block_k <= iq * block_q + block_q - 1
    else:
        run = True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]                                   # (bq, d)
        k = k_ref[0, 0]                                   # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < kv_len
        if causal:
            valid = valid & (k_pos <= q_pos)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret", "kv_len"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, kv_len: int | None = None,
                    interpret: bool) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D); Hq % Hkv == 0.

    Returns (B, Hq, Sq, D) in q.dtype.  ``kv_len`` masks a padded KV tail
    (decode caches).  ``interpret=True`` runs the kernel body on CPU for
    validation; ``False`` targets real TPU.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    kv_len = Sk if kv_len is None else min(kv_len, Sk)

    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    pq = -Sq % bq
    pk = -Sk % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    n_q = q.shape[2] // bq
    n_k = k.shape[2] // bk

    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=1.0 / math.sqrt(D), causal=causal,
        block_q=bq, block_k=bk, n_k=n_k, kv_len=kv_len)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    if pq:
        out = out[:, :, :Sq]
    return out
