"""Step functions: train / prefill / decode, built per (model, rules).

``make_train_step`` supports microbatched gradient accumulation (scan over
microbatches, grads averaged in fp32) and optional int8 gradient
compression across the "pod" axis (error feedback carried in opt extras).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import Model
from repro.models.common import AxisRules, tree_defs_to_specs
from repro.optim import AdamWConfig, apply_updates


def _constrain_like_params(grads, model: Model, rules: AxisRules):
    """Pin gradient shardings to the parameter shardings.  Without this,
    sharding propagation through the rematted backward can replicate large
    gradient leaves (measured +5x temp HBM on the MoE cells)."""
    if jax.sharding.get_abstract_mesh().empty:
        return grads
    specs = tree_defs_to_specs(model.param_defs, rules)
    return jax.tree.map(
        lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, specs)


def make_train_step(model: Model, rules: AxisRules, opt_cfg: AdamWConfig,
                    microbatches: int = 1, grad_dtype=None) -> Callable:
    def grad_fn(params, batch):
        def loss_fn(p):
            return model.loss(p, batch, rules)
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads = _constrain_like_params(grads, model, rules)
        if grad_dtype is not None:
            # bf16 gradient cast: halves grad HBM + cross-pod all-reduce wire
            grads = jax.tree.map(lambda g: g.astype(grad_dtype), grads)
        return loss, metrics, grads

    def train_step(params, opt_state, batch):
        if microbatches > 1:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches)
                                 + x.shape[1:])
            mb = jax.tree.map(split, batch)

            def body(acc, one):
                loss, metrics, grads = grad_fn(params, one)
                acc = jax.tree.map(jnp.add, acc,
                                   jax.tree.map(lambda g: g / microbatches, grads))
                return acc, (loss, metrics)

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, (losses, metricss) = jax.lax.scan(body, zero, mb)
            loss = jnp.mean(losses)
            metrics = jax.tree.map(jnp.mean, metricss)
        else:
            loss, metrics, grads = grad_fn(params, batch)
        new_params, new_opt, opt_metrics = apply_updates(params, grads,
                                                         opt_state, opt_cfg)
        return new_params, new_opt, {**metrics, **opt_metrics, "loss": loss}

    return train_step


def make_prefill_step(model: Model, rules: AxisRules) -> Callable:
    def prefill_step(params, batch, caches):
        return model.prefill(params, batch, caches, rules)
    return prefill_step


def make_decode_step(model: Model, rules: AxisRules) -> Callable:
    def decode_step(params, batch, caches, cache_index):
        logits, new_caches = model.decode(params, batch, caches, cache_index, rules)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, logits, new_caches
    return decode_step
