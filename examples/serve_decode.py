"""Serving example: prefill a prompt batch, then step the decode loop with
a KV cache — then cross-check the serving attention against the Pallas
flash kernel.

    PYTHONPATH=src python examples/serve_decode.py              # on a TPU
    PYTHONPATH=src python examples/serve_decode.py --interpret  # on a CPU
"""
import argparse

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.launch.shapes import concrete_batch
from repro.models import AxisRules, build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernel in interpret mode (CPU)")
    args = ap.parse_args()

    rules = AxisRules(fsdp_axes=(), dp_axes=())
    cfg = smoke_config("stablelm-1.6b").with_(n_layers=4, d_model=64,
                                              d_ff=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    B, T_prompt, T_gen = 4, 24, 16
    batch = concrete_batch(cfg, "prefill", B, T_prompt)
    caches = model.init_caches(B, max_len=T_prompt + T_gen)

    prefill = jax.jit(lambda p, b, c: model.prefill(p, b, c, rules))
    decode = jax.jit(lambda p, b, c, i: model.decode(p, b, c, i, rules))

    logits, caches = prefill(params, batch, caches)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    for step in range(T_gen - 1):
        logits, caches = decode(params, {"tokens": tok}, caches,
                                jnp.asarray(T_prompt + step, jnp.int32))
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)

    gen = jnp.concatenate(out_tokens, axis=1)
    print(f"prompt batch {B} x {T_prompt} tokens -> generated {gen.shape[1]} "
          f"tokens per sequence")
    print("sample generations:", np.asarray(gen[:2]))

    q = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 64, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 64, 16))
    v = jax.random.normal(jax.random.PRNGKey(3), (2, 2, 64, 16))
    err = jnp.max(jnp.abs(flash_attention(q, k, v, causal=True, block_q=32,
                                          block_k=32,
                                          interpret=args.interpret)
                          - attention_ref(q, k, v, causal=True)))
    print(f"pallas flash kernel vs oracle: max err {float(err):.2e}")
    print("serve_decode OK")


if __name__ == "__main__":
    main()
