"""jit'd public wrapper: layout adaptation + kernel/XLA-path dispatch."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import flash_attention
from .ref import attention_ref


def mha(q, k, v, *, causal: bool = True, kv_len=None, mode: str = "pallas",
        interpret: bool, block_q: int = 128, block_k: int = 128):
    """Layout (B, S, H, D) — the model-stack convention.

    mode="pallas": blocked kernel (interpret=True on CPU, False on TPU);
    mode="xla": pure-jnp oracle (used by the dry-run path).
    """
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if mode == "pallas":
        out = flash_attention(qt, kt, vt, causal=causal, kv_len=kv_len,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
    else:
        out = attention_ref(qt, kt, vt, causal=causal, kv_len=kv_len)
    return jnp.swapaxes(out, 1, 2)
