"""Device-resident estimator state — one registered pytree for the tick.

PR 9 consolidates everything the online loop mutates per tick — the
NIG ``(T, 8)`` streamed moments and batched posterior (``blr``), the
per-(task, node) bias sufficient statistics (``BiasModel``), the
per-node reliability counts (``ReliabilityModel``) and the static
``(T, N)`` runtime-factor matrix — into a single ``EstimatorState``
pytree, so the whole observe → update → bias scatter → re-predict
sequence can run as ONE jitted, donated-buffer dispatch
(``repro.core.tick.tick_step``) and gain a leading workflow axis under
``vmap`` (``repro.online.fleet``).

Design split, mirroring ``BatchedTaskModel``'s data/meta convention:

* array leaves — everything jit/vmap/shard-able;
* ``StateMeta`` — the frozen, hashable hyperparameter record (bias
  prior scales, decay, NIG priors...). Meta, not data: python branches
  on it specialise the compiled tick (``decay == 1.0`` skips the
  forgetting multiply entirely, exactly like ``BiasModel.update``);
* ``StateNames`` — host-side row/column labels (task order, prediction
  node order, bias-column universe).  Deliberately OUTSIDE the pytree:
  strings never cross the device boundary.

The OO classes stay the public API over this state —
``reliability_view`` rebuilds a bit-exact ``ReliabilityModel`` from the
leaves, and ``write_back`` returns a mutated state into a live
``LotaruEstimator``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax import numpy as jnp

from .blr import (BatchedTaskModel, BiasModel, ReliabilityModel,
                  _default_dtype, slice_task_model)


@dataclass(frozen=True)
class StateMeta:
    """Static hyperparameters of one estimator — hashable, so it rides
    the pytree as a meta field and jit specialises on it."""
    bias_correction: bool
    tau0: float
    sigma_r: float
    decay: float
    empirical_bayes: bool
    prior_scale: float = 10.0
    a0: float = 1.0
    b0: float = 1.0
    threshold: float = 0.8
    rel_a0: float = 8.0
    rel_b0: float = 1.0


@dataclass(frozen=True)
class StateNames:
    """Host-side label universe of an ``EstimatorState`` (not a pytree)."""
    tasks: tuple[str, ...]          # row order (estimator task_names())
    nodes: tuple[str, ...]          # prediction-column order (N axis)
    bias_nodes: tuple[str, ...]     # bias-column universe (Nb axis)
    rel_nodes: tuple[str, ...]      # reliability slot order (R axis)


@dataclass(frozen=True)
class EstimatorState:
    """All per-tick mutable estimator state as one pytree.

    Leaves (T tasks, N prediction nodes, Nb bias columns, R rel slots):

    * ``model``      — nested ``BatchedTaskModel`` (moments, posterior,
      Pearson gate, median/spread);
    * ``factors``    — (T, N) static runtime-factor matrix;
    * ``node_cols``  — (N,) int32 bias column of each prediction node,
      ``-1`` outside the bias universe;
    * ``bias_counts`` / ``bias_log_sum`` / ``bias_log_sq`` — (T, Nb)
      ``BiasModel`` sufficient statistics;
    * ``rel_succ`` / ``rel_fail`` — (R,) Beta-Binomial attempt counts.
    """
    model: BatchedTaskModel
    factors: jnp.ndarray
    node_cols: jnp.ndarray
    bias_counts: jnp.ndarray
    bias_log_sum: jnp.ndarray
    bias_log_sq: jnp.ndarray
    rel_succ: jnp.ndarray
    rel_fail: jnp.ndarray
    meta: StateMeta


jax.tree_util.register_dataclass(
    EstimatorState,
    data_fields=["model", "factors", "node_cols", "bias_counts",
                 "bias_log_sum", "bias_log_sq", "rel_succ", "rel_fail"],
    meta_fields=["meta"])


def build_state(est, nodes, rel_nodes=()) -> tuple[EstimatorState,
                                                   StateNames]:
    """Snapshot a fitted ``LotaruEstimator`` into an ``EstimatorState``.

    ``nodes`` fixes the prediction-column order (the executor's node
    *type* universe); ``rel_nodes`` the reliability slots (node
    *instances* — availability is a machine property).  The batched
    model is shared, not copied: its ``SampleLog`` stays the live
    host-side raw-sample history, exactly as in the legacy path.
    """
    names, model, _w = est._batched()
    dt = _default_dtype()
    nodes = tuple(nodes)
    rel_nodes = tuple(rel_nodes)
    factors = jnp.asarray(est.factor_matrix(list(nodes)), dt)
    if est.bias_correction:
        bias = est._ensure_bias()
        tau0, sigma_r = bias.tau0, bias.sigma_r
        decay, eb = bias.decay, bias.empirical_bayes
        counts = jnp.asarray(bias.counts, dt)
        log_sum = jnp.asarray(bias.log_sum, dt)
        log_sq = jnp.asarray(bias.log_sq, dt)
    else:
        opts = est._bias_opts
        tau0, sigma_r = 0.5, opts["sigma_r"]
        decay, eb = opts["decay"], opts["empirical_bayes"]
        counts = jnp.zeros((len(names), len(est.bias_nodes)), dt)
        log_sum = jnp.zeros_like(counts)
        log_sq = jnp.zeros_like(counts)
    node_cols = jnp.asarray([est._bias_col.get(n, -1) for n in nodes],
                            jnp.int32)
    rel = est.reliability
    rel_a0 = rel.a0 if rel is not None else 8.0
    rel_b0 = rel.b0 if rel is not None else 1.0
    succ = np.zeros(len(rel_nodes), np.float64)
    fail = np.zeros(len(rel_nodes), np.float64)
    if rel is not None:
        for k, n in enumerate(rel_nodes):
            succ[k], fail[k] = rel.counts(n)
    meta = StateMeta(bias_correction=bool(est.bias_correction),
                     tau0=float(tau0), sigma_r=float(sigma_r),
                     decay=float(decay), empirical_bayes=bool(eb),
                     rel_a0=float(rel_a0), rel_b0=float(rel_b0))
    state = EstimatorState(
        model=model, factors=factors, node_cols=node_cols,
        bias_counts=counts, bias_log_sum=log_sum, bias_log_sq=log_sq,
        rel_succ=jnp.asarray(succ, dt), rel_fail=jnp.asarray(fail, dt),
        meta=meta)
    return state, StateNames(tasks=tuple(names), nodes=nodes,
                             bias_nodes=tuple(est.bias_nodes),
                             rel_nodes=rel_nodes)


def reliability_view(state: EstimatorState,
                     names: StateNames) -> ReliabilityModel | None:
    """Rebuild the host ``ReliabilityModel`` view (``None`` while no
    attempt was ever recorded, matching the estimator's lazy layer)."""
    succ = np.asarray(state.rel_succ, np.float64)
    fail = np.asarray(state.rel_fail, np.float64)
    if not np.any(succ + fail > 0):
        return None
    seen = {n: [float(succ[k]), float(fail[k])]
            for k, n in enumerate(names.rel_nodes) if succ[k] + fail[k] > 0}
    return ReliabilityModel(a0=state.meta.rel_a0, b0=state.meta.rel_b0,
                            state=seen)


def scalar_part(model: BatchedTaskModel) -> BatchedTaskModel:
    """The leaves a scalar ``TaskModel`` is sliced from — the Pearson
    gate, the posterior and the median fallback — without the moments."""
    return BatchedTaskModel(correlated=model.correlated, post=model.post,
                            median=model.median, spread=model.spread)


def write_back(state: EstimatorState, names: StateNames, est,
               bias: BiasModel | None, rows=None) -> None:
    """Fold a mutated state back into a live ``LotaruEstimator`` so the
    legacy OO surface (scalar predicts, save/load, further
    ``observe_batch`` calls) continues from exactly where the fused tick
    left off.  ``rows`` limits the per-task scalar-model writeback to
    the rows the tick actually touched (the batch cache itself is always
    swapped whole).

    The state crosses to the host once: one ``jax.device_get`` of
    ``scalar_part(state.model)``, whose rows ``slice_task_model`` then
    slices on the host — bitwise the rows it slices on the device, the
    posterior leaves host arrays of the state's dtype.  ``bias`` is a
    host ``BiasModel`` already holding the state's bias statistics at
    float64 (``TickEngine``'s, from its last host view; ``None`` without
    bias correction): the estimator takes copies of its arrays, and the
    bias leaves are not read.  The reliability leaves are read only when
    the state has reliability slots."""
    fts = [est.tasks[n] for n in names.tasks]
    w = np.array([ft.w for ft in fts], np.float64)
    est._batch_cache = (list(names.tasks), fts, state.model, w)
    touched = range(len(names.tasks)) if rows is None else sorted(rows)
    if touched:
        host = jax.device_get(scalar_part(state.model))
        for i in touched:
            est.tasks[names.tasks[i]].model = slice_task_model(host, i)
    est._mat_cache = None
    est._dirty_rows.clear()
    if est.bias_correction:
        into = est._ensure_bias()
        into.counts = bias.counts.copy()
        into.log_sum = bias.log_sum.copy()
        into.log_sq = bias.log_sq.copy()
        into._sigma_r_cache = None
    if names.rel_nodes:
        rel = reliability_view(state, names)
        if rel is not None:
            est.reliability = rel
