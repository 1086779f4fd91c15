"""Drive the system's main paths once on a TPU and check each against a
reference on the CPU backend of the same process.

    python chip_smoke.py                # one chip: phases 1-4
    python chip_smoke.py --four-chips   # four chips: the sharded fleet only

Phases (one chip):

1. device gate — no TPU, no run: the script exits non-zero instead of
   falling back to the CPU;
2. the closed estimator loop of ``examples/online_reestimation.py``
   (eager x 8 samples, bias + risk pricing) through ``OnlineExecutor``
   with the fused tick, against the same run on the CPU backend;
3. ``TickEngine`` ticks at the full-sweep ceiling (T, N) = (4096, 256),
   B = 64 observations per tick, against the same ticks on the CPU;
4. ``ServeLoop`` on stablelm-1.6b at published widths (random weights)
   answering 8 requests x 12 new tokens; one batch's prefill logits are
   checked against the chip's cache-free forward, and every layer of that
   forward (and the head) against the CPU replaying it from the chip's
   input to the layer.

With ``--four-chips`` only phase 5 runs: ``fleet_tick_step`` at W = 64,
(T, N) = (128, 16) sharded over a (4, 1) ("wf", "task") mesh, against the
same fleet unsharded on device 0.

Every phase prints its comparisons on lines of their own; any failed check
exits non-zero.  The last line of standard output is one JSON object
naming the device.  The script starts no subprocess and runs in float32
(the default dtype policy).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# --- tolerances, each with its reason ---------------------------------------
#: phase 2: final MPE, absolute.  Both runs are float32 but on two backends
#: (the TPU rounds f32 matmul inputs to bf16 at default precision, the CPU
#: does not), and HEFT breaks near-ties by tiny cost differences, so one
#: flipped placement moves a few task errors; MPE is a median of ~0.1.
LOOP_MPE_ATOL = 0.02
#: phase 2: makespan, relative — the same near-tie flips move the critical
#: path by at most a few tasks' runtimes out of 104 task instances.
LOOP_MAKESPAN_RTOL = 0.10
#: phase 3: (T, N) mean/std, per-cell relative difference (max over cells).
#: bf16 rounding of matmul inputs (2^-8) through the 2x2 normal-equation
#: solves of the posterior, whose conditioning amplifies it a few-fold.
TICK_RTOL = 5e-2
#: phase 4.  With random weights the 24-layer residual stream grows to
#: ~1e4 and each layer amplifies rounding about threefold, so two full
#: bf16 forwards decorrelate (CPU bf16 vs CPU f32: rel L2 0.67 at 24
#: layers, 0.02 at one).  The CPU therefore replays each layer from the
#: chip's own input to that layer.  Per layer, rel L2 of the residual
#: stream: one layer's bf16 rounding on two backends (CPU bf16 vs f32 is
#: 0.011-0.017 per layer at these widths); a layer that drops its
#: attention or MLP scores > 0.3.  The same bound holds for the final
#: norm + unembedding, and for the chip's cached prefill vs its own
#: cache-free forward (same backend, cache layout only).
SERVE_REL_L2 = 5e-2
#: phase 5: sharded vs unsharded fleet, per-cell relative difference.  The
#: same chip type and per-workflow program; only the W split and fusion
#: choices differ, so float32 reassociation is all that may show.
FLEET_RTOL = 1e-5

TICK_SHAPE = (4096, 256)      # the bench's ~1M-cell ceiling
TICK_BATCH = 64
TICK_COUNT = 4
TICK_SIZE = 64.0
FLEET_SHAPE = (64, 128, 16)   # (W, T, N): the bench's fleet shape
FLEET_TICKS = 3


class SmokeFailure(Exception):
    """A check failed; the message says which and by how much."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def max_rel(a, b, floor: float = 1e-6) -> float:
    """Largest per-cell |a - b| / max(|b|, floor); non-finite values
    count as infinite difference."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        return float("inf")
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), floor)))


def rel_l2(a, b) -> float:
    """||a - b|| / ||b||; non-finite values count as infinite."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        return float("inf")
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@contextmanager
def count_compiles():
    """Count XLA backend compiles inside the block: ``box[0]``."""
    import jax
    box = [0]

    def listener(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            box[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield box
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


# --- phase 1 ----------------------------------------------------------------
def device_gate(count: int):
    """The TPU devices, or SmokeFailure when JAX finds fewer than
    ``count`` TPU chips."""
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU chip found: JAX sees {len(devs)} "
          f"{devs[0].platform} device(s)")
    check(len(devs) >= count,
          f"need {count} TPU chips, JAX sees {len(devs)}")
    say(f"[device] platform={devs[0].platform} "
        f"kind={devs[0].device_kind} count={len(devs)}")
    return devs


# --- phase 2 ----------------------------------------------------------------
def run_loop(device, n_samples: int = 8):
    """The eager closed loop (bias + risk pricing, fused tick) with every
    array on ``device``.  Returns the ``ExecutionTrace``."""
    import jax
    from repro.core import (LotaruEstimator, get_node, profile_cluster,
                            profile_node, target_nodes)
    from repro.online import OnlineExecutor, fanout_chain_dag
    from repro.sched.simulator import ClusterSimulator, GridEngine
    from repro.sched.workflows import INPUTS, WORKFLOWS

    with jax.default_device(device):
        local = get_node("local-cpu")
        local_bench = profile_node(local, np.random.default_rng(7))
        tbenches = profile_cluster(target_nodes(), seed=13)
        size = INPUTS[("eager", 1)]
        by_name = {t.name: t for t in WORKFLOWS["eager"]}
        tasks, task_name = fanout_chain_dag(list(by_name), n_samples)
        truth = ClusterSimulator(seed=2000)
        truth_tab = {(tid, nt.name): truth.run_task(by_name[task_name[tid]],
                                                    nt, size)
                     for tid in tasks for nt in target_nodes()}
        sim = ClusterSimulator(seed=0)
        est = LotaruEstimator(local_bench, tbenches, bias_correction=True,
                              bias_empirical_bayes=True)
        est.fit_tasks(list(by_name), size,
                      lambda n, s, cf: sim.run_task(by_name[n], local, s,
                                                    cpu_factor=cf))
        grid = GridEngine.from_types(nodes_per_type=2)
        ex = OnlineExecutor(
            est, tasks, task_name, size, grid,
            lambda tid, node: truth_tab[(tid, grid.type_of(node).name)],
            online=True, confidence=0.9, speculate=True, risk_k=1.0,
            spec_tail=0.8)
        return ex.run()


def phase_loop(device, ref_device, n_samples: int = 8) -> None:
    t0 = time.perf_counter()
    chip = run_loop(device, n_samples)
    ref = run_loop(ref_device, n_samples)
    for label, tr in (("chip", chip), ("cpu", ref)):
        say(f"[loop] {label}: completed {tr.completed}/{tr.total}, "
            f"makespan {tr.makespan!r}, final MPE {tr.final_mpe()!r}, "
            f"replans {tr.replans}, speculations {tr.speculations}")
        check(tr.total > 0 and tr.completed == tr.total,
              f"loop on {label}: completed {tr.completed}/{tr.total}")
    same = sum(a.node == b.node for a, b in
               zip(sorted(chip.records, key=lambda r: r.id),
                   sorted(ref.records, key=lambda r: r.id)))
    d_mpe = abs(chip.final_mpe() - ref.final_mpe())
    d_ms = abs(chip.makespan - ref.makespan) / ref.makespan
    say(f"[loop] same node for {same}/{len(ref.records)} task instances; "
        f"|dMPE| = {d_mpe!r} (tol {LOOP_MPE_ATOL}), "
        f"|dmakespan|/makespan = {d_ms!r} (tol {LOOP_MAKESPAN_RTOL}); "
        f"{time.perf_counter() - t0:.1f} s incl. compile")
    check(d_mpe <= LOOP_MPE_ATOL, f"loop MPE differs by {d_mpe}")
    check(d_ms <= LOOP_MAKESPAN_RTOL, f"loop makespan differs by {d_ms}")


# --- phase 3 ----------------------------------------------------------------
def tick_batches(names, nodes, n_ticks: int, batch: int, seed: int = 17):
    """Per tick, ``batch`` (task, node, size, runtime) observations on
    distinct tasks (as the bench's scale arm draws them)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_ticks):
        rows = rng.choice(len(names), size=min(batch, len(names)),
                          replace=False)
        out.append([(names[int(r)], nodes[int(rng.integers(0, len(nodes)))],
                     TICK_SIZE, float(rng.uniform(5.0, 120.0)))
                    for r in rows])
    return out


def run_ticks(device, t: int, n: int, batches):
    """(mean, std) host copies after each ``TickEngine`` tick, and the
    number of backend compiles in the ticks after the first."""
    import jax
    from repro.core import TickEngine
    from repro.data.synthetic import scale_estimator
    with jax.default_device(device):
        est, _names, nodes = scale_estimator(t, n, seed=0)
        engine = TickEngine(est, nodes, size=TICK_SIZE)
        outs = []
        engine.observe_batch(batches[0])
        outs.append(engine.predict_matrix(nodes, TICK_SIZE))
        with count_compiles() as compiles:
            for b in batches[1:]:
                engine.observe_batch(b)
                outs.append(engine.predict_matrix(nodes, TICK_SIZE))
    return outs, compiles[0]


def phase_ticks(device, ref_device, shape=TICK_SHAPE, batch=TICK_BATCH,
                n_ticks=TICK_COUNT) -> None:
    t, n = shape
    t0 = time.perf_counter()
    names = [f"t{i}" for i in range(t)]
    nodes = [f"n{j}" for j in range(n)]
    batches = tick_batches(names, nodes, n_ticks, batch)
    chip, compiles = run_ticks(device, t, n, batches)
    ref, _ = run_ticks(ref_device, t, n, batches)
    for k, ((m, s), (mr, sr)) in enumerate(zip(chip, ref)):
        check(m.shape == (t, n) and s.shape == (t, n),
              f"tick {k}: estimate shape {m.shape}, want {(t, n)}")
        dm, ds = max_rel(m, mr), max_rel(s, sr)
        say(f"[tick] ({t}, {n}) B={batch} tick {k}: max rel diff "
            f"mean {dm!r} std {ds!r} (tol {TICK_RTOL})")
        check(dm <= TICK_RTOL and ds <= TICK_RTOL,
              f"tick {k}: chip vs cpu mean {dm}, std {ds}")
    say(f"[tick] backend compiles in ticks 2..{n_ticks}: {compiles}; "
        f"{time.perf_counter() - t0:.1f} s incl. compile")
    check(compiles == 0, f"{compiles} compiles after the first tick")


# --- phase 4 ----------------------------------------------------------------
def residual_states(cfg, rules, params, tokens):
    """Cache-free forward keeping the residual stream entering each layer
    and leaving the last, ``(L + 1, B, T, d)``, and the last-position
    logits."""
    from jax import lax
    from repro.models import transformer as tf
    batch = {"tokens": tokens}
    h0 = tf._embed_inputs(params, cfg, batch, rules)
    pos = tf._positions_for(cfg, batch, *tokens.shape)

    def body(h, p):
        h, _, _ = tf._apply_unit(p, h, cfg, rules, pos)
        return h, h

    _, hs = lax.scan(body, h0, params["blocks"])
    states = lax.concatenate([h0[None], hs], 0)
    return states, lm_head(cfg, params, hs[-1])


def lm_head(cfg, params, h):
    """Final norm and unembedding at the last position: (B, vocab) f32."""
    import jax.numpy as jnp
    from repro.models import transformer as tf
    from repro.models.layers import apply_norm
    h = apply_norm(params["ln_f"], h, cfg.norm)
    w = tf.unembed_matrix(params, cfg).astype(cfg.dtype)
    return jnp.einsum("bd,dv->bv", h[:, -1].astype(cfg.dtype), w,
                      preferred_element_type=jnp.float32)


def replay_layers(cfg, rules, blocks, h_in, tokens):
    """Layer l applied to ``h_in[l]``, for every layer at once."""
    from jax import lax
    from repro.models import transformer as tf
    pos = tf._positions_for(cfg, {"tokens": tokens}, *tokens.shape)
    return lax.map(lambda x: tf._apply_unit(x[0], x[1], cfg, rules, pos)[0],
                   (blocks, h_in))


def phase_serve(ref_device, cfg, n_requests: int = 8, max_new: int = 12):
    import jax
    from repro.launch.serve import ServeLoop, make_requests

    t0 = time.perf_counter()
    loop = ServeLoop(cfg)
    requests = make_requests(cfg, n_requests, max_new)
    first = requests[:loop.max_batch]
    batch, caches = loop.pack(first)
    tokens = batch["tokens"]
    logits, _ = loop.prefill(loop.params, batch, caches)
    states, fwd_logits = jax.jit(
        lambda p, t: residual_states(cfg, loop.rules, p, t))(loop.params,
                                                             tokens)

    done = loop.serve(requests)
    n_tok = sum(len(r.out) for r in done)
    say(f"[serve] {cfg.arch}: {len(done)} requests, {n_tok} tokens "
        f"({cfg.param_count()} parameters, float32)")
    check(len(done) == n_requests, f"served {len(done)}/{n_requests}")
    for r in done:
        check(len(r.out) == max_new and all(0 <= v < cfg.vocab
                                            for v in r.out),
              f"request {r.rid}: {len(r.out)} tokens {r.out}")
    check(logits.shape == (len(first), 1, cfg.vocab),
          f"prefill logits shape {logits.shape}")
    path = rel_l2(logits[:, -1], fwd_logits)
    say(f"[serve] chip prefill vs chip cache-free forward, last-position "
        f"logits: rel L2 {path!r} (tol {SERVE_REL_L2})")

    params_ref = jax.device_put(loop.params, ref_device)
    h_in = jax.device_put(states, ref_device)
    tok_ref = jax.device_put(tokens, ref_device)
    replay = jax.jit(lambda b, h, t: replay_layers(cfg, loop.rules, b, h, t))(
        params_ref["blocks"], h_in[:-1], tok_ref)
    per_layer = [rel_l2(states[l + 1], replay[l])
                 for l in range(replay.shape[0])]
    head = rel_l2(fwd_logits, jax.jit(
        lambda p, h: lm_head(cfg, p, h))(params_ref, h_in[-1]))
    worst = int(np.argmax(per_layer))
    say(f"[serve] CPU replay of each of {len(per_layer)} layers from the "
        f"chip's input: rel L2 max {per_layer[worst]!r} (layer {worst}), "
        f"median {float(np.median(per_layer))!r}; head {head!r} "
        f"(tol {SERVE_REL_L2}); {time.perf_counter() - t0:.1f} s incl. "
        f"compile")
    check(path <= SERVE_REL_L2, f"cached prefill vs forward rel L2 {path}")
    check(per_layer[worst] <= SERVE_REL_L2,
          f"layer {worst} rel L2 {per_layer[worst]}")
    check(head <= SERVE_REL_L2, f"head rel L2 {head}")


# --- phase 5 ----------------------------------------------------------------
def fleet_obs(rng, w: int, t: int, n: int, batch: int) -> np.ndarray:
    obs = np.zeros((w, batch, 8))
    y = rng.uniform(5.0, 120.0, (w, batch))
    obs[..., 0] = rng.integers(0, t, (w, batch))
    obs[..., 1] = rng.integers(0, n, (w, batch))
    obs[..., 2] = TICK_SIZE
    obs[..., 3] = y
    obs[..., 5] = y                 # med/spr: any consistent history
    obs[..., 6] = 1.0
    obs[..., 7] = 1.0
    return obs


def phase_fleet(devices, shape=FLEET_SHAPE, batch=TICK_BATCH,
                n_ticks=FLEET_TICKS) -> None:
    import jax
    from repro.core import build_state
    from repro.data.synthetic import scale_estimator
    from repro.launch.mesh import make_fleet_mesh
    from repro.online.fleet import fleet_tick_step, shard_fleet, stack_states

    w, t, n = shape
    t0 = time.perf_counter()
    with jax.default_device(devices[0]):
        est, _names, nodes = scale_estimator(t, n, seed=0)
        state, _ = build_state(est, nodes)
        mesh = make_fleet_mesh()
        check(dict(mesh.shape) == {"wf": len(devices), "task": 1},
              f"fleet mesh {dict(mesh.shape)}")
        sharded = shard_fleet(stack_states([state] * w), mesh)
        single = stack_states([state] * w)
        sizes = np.full(w, TICK_SIZE)
        rng = np.random.default_rng(23)
        for k in range(n_ticks):
            obs = fleet_obs(rng, w, t, n, batch)
            sharded, m_s, s_s = fleet_tick_step(sharded, obs, sizes)
            single, m_1, s_1 = fleet_tick_step(single, obs, sizes)
            dm, ds = max_rel(m_s, m_1), max_rel(s_s, s_1)
            say(f"[fleet] W={w} ({t}, {n}) tick {k}: sharded vs device-0 "
                f"max rel diff mean {dm!r} std {ds!r} (tol {FLEET_RTOL})")
            check(m_s.shape == (w, t, n), f"fleet mean shape {m_s.shape}")
            check(dm <= FLEET_RTOL and ds <= FLEET_RTOL,
                  f"fleet tick {k}: mean {dm}, std {ds}")
    held = m_s.sharding.device_set
    say(f"[fleet] mesh {dict(mesh.shape)}; mean held on {len(held)} "
        f"device(s), reference on {len(m_1.sharding.device_set)}; "
        f"{time.perf_counter() - t0:.1f} s incl. compile")
    check(held == set(devices), f"mean sits on {sorted(map(str, held))}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet tick sharded over four chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package at {SRC}; run this script "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    from repro.configs import get_config
    from repro.launch.cache import enable_compile_cache

    try:
        devs = device_gate(4 if args.four_chips else 1)
        say(f"[cache] {enable_compile_cache()}")
        if args.four_chips:
            phase_fleet(devs[:4])
        else:
            cpu = jax.devices("cpu")[0]
            phase_loop(devs[0], cpu)
            phase_ticks(devs[0], cpu)
            phase_serve(cpu, get_config("stablelm-1.6b"))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
