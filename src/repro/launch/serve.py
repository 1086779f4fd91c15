"""Batched serving driver: continuous prefill/decode over a request queue.

Single-host reference implementation of the serving loop the decode cells
model: requests arrive with prompts, are batched up to ``max_batch``,
prefetched through ``prefill_step`` and stepped with ``decode_step``
against a shared KV cache.  Per-step wall time is checked against the
LotaruML predictive envelope (mean + k*sigma) when an estimator is given —
a breach marks the node a straggler candidate for the fleet controller.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --smoke
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import AxisRules, build_model


@dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int = 16
    out: list = field(default_factory=list)


class ServeLoop:
    def __init__(self, cfg, *, max_batch: int = 4, max_len: int = 128,
                 rules: AxisRules | None = None, envelope=None,
                 straggler_k: float = 3.0):
        self.cfg = cfg
        self.rules = rules or AxisRules(fsdp_axes=(), dp_axes=())
        self.model = build_model(cfg)
        self.params = self.model.init(jax.random.PRNGKey(0))
        self.max_batch = max_batch
        self.max_len = max_len
        self.prefill = jax.jit(make_prefill_step(self.model, self.rules))
        self.decode = jax.jit(make_decode_step(self.model, self.rules))
        self.envelope = envelope            # (mean_s, sigma_s) or None
        self.straggler_k = straggler_k
        self.straggler_steps = 0
        self.step_times: list[float] = []

    def pack(self, requests: list[Request]):
        """Left-pad the prompts into one (B, T) token batch and allocate
        caches for the longest generation.  Returns ``(batch, caches)``."""
        B = len(requests)
        T = max(len(r.prompt) for r in requests)
        toks = np.zeros((B, T), np.int32)
        for i, r in enumerate(requests):
            toks[i, T - len(r.prompt):] = r.prompt      # left-pad
        caches = self.model.init_caches(B, max_len=T + max(
            r.max_new for r in requests), cross_len=T)
        return {"tokens": jnp.asarray(toks)}, caches

    def run_batch(self, requests: list[Request]) -> list[Request]:
        assert len(requests) <= self.max_batch
        batch, caches = self.pack(requests)
        T = batch["tokens"].shape[1]
        logits, caches = self.prefill(self.params, batch, caches)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        n_steps = max(r.max_new for r in requests)
        for step in range(n_steps):
            t0 = time.perf_counter()
            tok, logits, caches = self.decode(
                self.params, {"tokens": tok[:, None]}, caches,
                jnp.asarray(T + step, jnp.int32))
            tok.block_until_ready()
            dt = time.perf_counter() - t0
            self.step_times.append(dt)
            if self.envelope is not None and step > 0:
                mean, sigma = self.envelope
                if dt > mean + self.straggler_k * sigma:
                    self.straggler_steps += 1
            for i, r in enumerate(requests):
                if step < r.max_new:
                    r.out.append(int(tok[i]))
        return requests

    def serve(self, requests: list[Request]) -> list[Request]:
        """Answer a queue of requests, ``max_batch`` at a time."""
        done = []
        for i in range(0, len(requests), self.max_batch):
            done.extend(self.run_batch(requests[i:i + self.max_batch]))
        return done


def make_requests(cfg, n: int, max_new: int, seed: int = 0) -> list[Request]:
    """``n`` requests with random 4-16 token prompts."""
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, rng.integers(4, 17)),
                    max_new=max_new)
            for i in range(n)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    loop = ServeLoop(cfg)
    queue = make_requests(cfg, args.requests, args.max_new)
    t0 = time.time()
    done = loop.serve(queue)
    dt = time.time() - t0
    toks = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s); median decode step "
          f"{1e3*np.median(loop.step_times):.1f} ms")


if __name__ == "__main__":
    main()
