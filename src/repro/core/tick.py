"""The fused tick: observe → update → bias scatter → re-predict in ONE
jitted, donated-buffer dispatch over ``EstimatorState``.

The legacy online path is four separate dispatches per simulation tick
(``update_task_batch_stream`` scan, per-row ``slice_task_model``
writebacks, a host-side ``BiasModel.update`` scatter, and a dirty-row
``predict_matrix`` re-predict), stitched together by Python in
``LotaruEstimator.observe_batch``.  ``tick_step`` fuses the whole
sequence into one ``state -> state`` function the scheduler can sit
inside — and, because it is pure over a registered pytree, one that
``vmap``s over a leading workflow axis (``repro.online.fleet``) and
shards under ``jax.sharding.NamedSharding``.

Observation batches are packed as an ``(B, 8)`` array::

    [row, col, x, y_raw, y_local, med, spr, valid]

* ``row``/``col`` — task row and prediction-node column (``state``'s
  ``factors`` axes); ``x`` the input size; ``y_raw`` the measured
  runtime on the node;
* ``y_local`` — the host-de-adjusted local-equivalent runtime.  With
  ``host_deadjust=True`` (the ``TickEngine`` executor path) it is used
  verbatim, keeping the engine bit-compatible with
  ``observe_batch``'s host float64 de-adjust; with ``False`` (the pure
  device / fleet path) it is recomputed on device from ``y_raw`` and
  the tick-start bias, and the packed value is ignored;
* ``med``/``spr`` — the row's refreshed median/MAD (order statistics
  live host-side in the ``SampleLog``, exactly as in the legacy path);
* ``valid`` — padding mask (0 rows are no-ops), so fleet batches can
  pad ragged per-workflow ticks to one shape.

Rows flagged invalid leave every leaf bitwise untouched.
"""
from __future__ import annotations

import numpy as np
from scipy import special as _scipy_special
from scipy import stats as _scipy_stats

import jax
from jax import numpy as jnp

from .blr import (BiasModel, _attach_log, _default_dtype, _predict_core,
                  _update_core_impl, predict_task_batch)
from .state import EstimatorState, build_state, write_back


def _sigma_r(meta, counts, log_sum, log_sq, dt):
    """Device twin of ``BiasModel.effective_sigma_r`` — the fixed
    ``sigma_r``, or the pooled empirical spread of the observed
    log-residuals (floored) once any pair has two observations."""
    if not meta.empirical_bayes:
        return jnp.asarray(meta.sigma_r, dt)
    mask = counts >= 2
    safe_n = jnp.where(mask, counts, 1.0)
    ss = jnp.where(mask, log_sq - log_sum ** 2 / safe_n, 0.0).sum()
    dof = jnp.where(mask, counts - 1.0, 0.0).sum()
    s = jnp.sqrt(jnp.maximum(ss, 0.0) / jnp.maximum(dof, 1.0))
    pooled = jnp.maximum(s, BiasModel.SIGMA_R_FLOOR)
    return jnp.where(mask.any(), pooled, jnp.asarray(meta.sigma_r, dt))


def _fold_predict(model, state, counts, log_sum, log_sq, size):
    """Full (T, N) factor-scaled predictive with the bias posterior
    folded in — device twin of ``_scaled_matrix_core`` +
    ``_fold_bias_matrix`` (point-scale the mean, delta-method-widen the
    std, inert where unobserved or outside the bias universe)."""
    meta = state.meta
    dt = state.factors.dtype
    mean_t, std_t = predict_task_batch(model, size)
    mean = mean_t[:, None] * state.factors
    std = std_t[:, None] * state.factors
    if not meta.bias_correction:
        return mean, std
    sr = _sigma_r(meta, counts, log_sum, log_sq, dt)
    safe_cols = jnp.maximum(state.node_cols, 0)
    lam = 1.0 / meta.tau0 ** 2 + counts / sr ** 2
    mu = log_sum / (sr ** 2 * lam)
    v = 1.0 / lam
    mu_g, v_g = mu[:, safe_cols], v[:, safe_cols]
    n_g = counts[:, safe_cols]
    active = (state.node_cols >= 0)[None, :] & (n_g > 0)
    point = jnp.exp(mu_g)
    out_mean = jnp.where(active, mean * point, mean)
    widened = point * jnp.sqrt(std ** 2 + mean ** 2 * jnp.expm1(v_g))
    out_std = jnp.where(active, widened, std)
    return out_mean, out_std


def _tick_core(state: EstimatorState, obs, size, host_deadjust):
    """One fused tick.  Returns ``(state', mean, std, y_local)`` where
    ``mean``/``std`` are the refreshed post-tick (T, N) estimate matrix
    and ``y_local`` the (B,) local-equivalent runtimes that entered the
    model (input order)."""
    meta = state.meta
    dt = state.factors.dtype
    rows = obs[:, 0].astype(jnp.int32)
    cols = obs[:, 1].astype(jnp.int32)
    x, y_raw = obs[:, 2], obs[:, 3]
    med, spr, valid = obs[:, 5], obs[:, 6], obs[:, 7] > 0
    bcol = state.node_cols[cols]
    safe_b = jnp.maximum(bcol, 0)
    f = jnp.maximum(state.factors[rows, cols], 1e-12)
    if meta.bias_correction:
        # tick-START bias point estimates (the same values the legacy
        # path reads via ``BiasModel.point`` before updating anything)
        sr0 = _sigma_r(meta, state.bias_counts, state.bias_log_sum,
                       state.bias_log_sq, dt)
        n0 = state.bias_counts[rows, safe_b]
        lam0 = 1.0 / meta.tau0 ** 2 + n0 / sr0 ** 2
        mu0 = state.bias_log_sum[rows, safe_b] / (sr0 ** 2 * lam0)
        b_pt = jnp.where((bcol >= 0) & (n0 > 0), jnp.exp(mu0), 1.0)
    else:
        b_pt = jnp.ones_like(y_raw)
    if host_deadjust:
        y = obs[:, 4]
    else:
        y = y_raw / (f * jnp.maximum(b_pt, 1e-12))

    # --- streamed NIG moment/posterior update (masked scan) -------------
    packed = jnp.stack([rows.astype(dt), x, y, med, spr,
                        valid.astype(dt)], axis=-1)

    def step(m, o):
        upd = _update_core_impl(m, o[:5], meta.prior_scale, meta.a0,
                                meta.b0, meta.threshold)
        keep = o[5] > 0
        return jax.tree_util.tree_map(
            lambda new, old: jnp.where(keep, new, old), upd, m), None

    model, _ = jax.lax.scan(step, state.model, packed)

    counts = state.bias_counts
    log_sum = state.bias_log_sum
    log_sq = state.bias_log_sq
    if meta.bias_correction:
        # --- bias residuals vs the POST-update means (one scatter) ------
        p = model.post
        mean_b, _ = jax.vmap(_predict_core)(
            p.mu[rows], p.V[rows], p.a[rows], p.b[rows],
            p.x_scale[rows], p.y_scale[rows], x)
        m_post = jnp.where(model.correlated[rows],
                           jnp.maximum(mean_b, 0.0), model.median[rows])
        scaled = f * m_post
        resid_ok = valid & (bcol >= 0) & (y_raw > 0.0) & (scaled > 1e-12)
        ratio = jnp.where(resid_ok,
                          y_raw / jnp.where(resid_ok, scaled, 1.0), 1.0)
        lr = jnp.log(ratio)
        if meta.decay != 1.0:
            # one update is one forgetting step: decay fires iff the tick
            # contributes any residual, exactly like ``BiasModel.update``
            mult = jnp.where(resid_ok.any(), jnp.asarray(meta.decay, dt),
                             jnp.asarray(1.0, dt))
            counts, log_sum, log_sq = (counts * mult, log_sum * mult,
                                       log_sq * mult)
        zero = jnp.zeros_like(lr)
        counts = counts.at[rows, safe_b].add(
            jnp.where(resid_ok, jnp.ones_like(lr), zero))
        log_sum = log_sum.at[rows, safe_b].add(
            jnp.where(resid_ok, lr, zero))
        log_sq = log_sq.at[rows, safe_b].add(
            jnp.where(resid_ok, lr * lr, zero))

    mean, std = _fold_predict(model, state, counts, log_sum, log_sq, size)
    new_state = EstimatorState(
        model=model, factors=state.factors, node_cols=state.node_cols,
        bias_counts=counts, bias_log_sum=log_sum, bias_log_sq=log_sq,
        rel_succ=state.rel_succ, rel_fail=state.rel_fail, meta=meta)
    return new_state, mean, std, y


def _predict_state_core(state: EstimatorState, size):
    """Estimate matrix of a state without absorbing anything — the
    tick-zero twin of ``tick_step``'s (mean, std) outputs."""
    return _fold_predict(state.model, state, state.bias_counts,
                         state.bias_log_sum, state.bias_log_sq, size)


def _gate_table(model, size):
    """(T, 6) per-task predictive at ``size``, the numbers the surprise
    gate needs: ``[correlated, centre, scale, dof, median, spread]``.

    ``centre`` (``mean * y_scale``), ``scale``
    (``sqrt((b/a)(1 + X V X^T)) * y_scale``) and ``dof`` (``2a``) are the
    Student-t of ``predict_interval``'s batched branch and
    ``predict_cdf``; ``median``/``spread`` the Pearson fallback's."""
    p = model.post
    dt = p.mu.dtype
    x = jnp.broadcast_to(jnp.asarray(size, dt), p.a.shape)
    X = jnp.stack([jnp.ones_like(x), x / p.x_scale], axis=-1)
    centre = jnp.einsum("ti,ti->t", X, p.mu) * p.y_scale
    quad = jnp.einsum("ti,tij,tj->t", X, p.V, X)
    scale = jnp.sqrt((p.b / p.a) * (1.0 + quad)) * p.y_scale
    return jnp.stack([model.correlated.astype(dt), centre, scale,
                      2.0 * p.a, model.median, model.spread], axis=-1)


#: the fused tick entry point: donated state buffers (the input state is
#: consumed, like an optimiser state), one compile per (B, T, N) shape
tick_step = jax.jit(_tick_core, static_argnames=("host_deadjust",),
                    donate_argnums=(0,))

predict_state = jax.jit(_predict_state_core)


def _host_view(mean, std, model, counts, log_sum, log_sq, size):
    """Everything the engine reads on the host after a tick, as ONE flat
    buffer: ``mean | std | gate | counts | log_sum | log_sq``, each
    raveled, ``gate`` the (T, 6) ``_gate_table`` of ``model`` at
    ``size``.  The leaves share the state's dtype, so packing them
    changes no bit."""
    gate = _gate_table(model, size)
    return jnp.concatenate([a.ravel() for a in (mean, std, gate, counts,
                                                log_sum, log_sq)])


#: the engine's one crossing to the host per tick, a program of its own
#: beside ``tick_step``: it packs what the tick returned, so the outputs
#: reach the host in one round trip, not six (a copy costs about 0.4 ms
#: on one TPU v5e at the paper's (13, 5), whatever its size).  Its name
#: must not hold ``tick_core``, by which the benchmark's device-time
#: readers find the tick's program.
host_view = jax.jit(_host_view)


def _layout(shapes):
    """(slice, shape) of each part of a flat buffer holding ``shapes``
    back to back."""
    out, at = [], 0
    for shape in shapes:
        n = int(np.prod(shape))
        out.append((slice(at, at + n), shape))
        at += n
    return out


class TickEngine:
    """Executor-facing driver of the fused tick.

    Owns an ``EstimatorState`` snapshot of a fitted estimator and
    replaces the estimator's per-tick surface (``observe_batch`` +
    ``predict_matrix`` + the scalar interval/PIT/bias consumers) with
    ``tick_step`` outputs, while keeping the host-side pieces the legacy
    path keeps host-side: the raw-sample ``SampleLog`` (order
    statistics), the de-adjust of measured runtimes (bit-compatible
    float64, ``host_deadjust=True``) and the Beta-Binomial reliability
    plane (consumed by the scheduler, not the tick).  Each tick's
    estimates, gate table and bias statistics reach the host in one copy
    of the ``host_view`` buffer; the scalar interval/PIT consumers read
    views of it and dispatch nothing.

    The wrapped estimator is NOT updated per tick — call ``finalize()``
    when the run ends to write the final state back through the thin
    views, after which the estimator continues (scalar predicts,
    save/load, further ``observe_batch`` ticks) from exactly where the
    engine left off.
    """

    def __init__(self, est, nodes, *, size: float, tracer=None):
        from ..obs.trace import NULL_TRACER
        self.est = est
        self.size = float(size)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.state, self.names = build_state(est, nodes)
        self._rowmap = {n: i for i, n in enumerate(self.names.tasks)}
        self._colmap = {n: j for j, n in enumerate(self.names.nodes)}
        self._log = self.state.model.stats.log
        self._model = self.state.model
        self._bias_col = dict(est._bias_col)
        self._touched: set[int] = set()
        self._rel_dirty = False
        t, n = self.state.factors.shape
        nb = self.state.bias_counts.shape[1]
        self._parts = _layout([(t, n), (t, n), (t, 6)] + [(t, nb)] * 3)
        mean, std = predict_state(self.state, self.size)
        flat = np.asarray(jax.device_get(self._view(self.state, mean, std)),
                          np.float64)
        self._take_estimates(flat)
        self._bias = None
        if est.bias_correction:
            m = self.state.meta
            self._bias = BiasModel(t, nb, tau0=m.tau0, sigma_r=m.sigma_r,
                                   decay=m.decay,
                                   empirical_bayes=m.empirical_bayes)
            self._take_bias(flat)

    # ---- estimator-compatible per-tick surface ------------------------
    def predict_matrix(self, nodes, size, with_std: bool = True):
        if list(nodes) != self.names_nodes or float(size) != self.size:
            raise ValueError(
                "TickEngine serves one (nodes, size) configuration; got "
                f"{list(nodes)}/{size}, engine holds "
                f"{self.names_nodes}/{self.size}")
        return self._mean, (self._std if with_std else None)

    @property
    def names_nodes(self) -> list[str]:
        return list(self.names.nodes)

    def observe_batch(self, observations) -> list[float]:
        """One fused tick: de-adjust host-side (bitwise the legacy
        float64 math), append the raw history, then dispatch ONE
        ``tick_step`` that absorbs the stream, scatters the bias
        residuals and re-predicts the full (T, N) matrix, then ONE
        ``host_view`` and its one copy to the host."""
        obs = list(observations)
        if not obs:
            return []
        est = self.est
        dt = _default_dtype()
        packed = np.zeros((len(obs), 8), np.float64)
        ys = np.empty(len(obs), np.float64)
        for k, o in enumerate(obs):
            task, node, size, runtime = (o if isinstance(o, (tuple, list))
                                         else (o.task, o.node, o.size,
                                               o.runtime))
            task, node = str(task), str(node)
            size, runtime = float(size), float(runtime)
            i = self._rowmap[task]
            f = max(float(est.factor(task, node)), 1e-12)
            b = 1.0
            if self._bias is not None and node in self._bias_col:
                b = self._bias.point(i, self._bias_col[node])
            y = runtime / (f * max(b, 1e-12))
            self._log.append(i, size, y)
            med, spr = self._log.median_spread(i)
            packed[k] = (i, self._colmap[node], size, runtime, y, med,
                         spr, 1.0)
            ys[k] = y
            ft = est.tasks[task]
            ft.sizes = np.append(ft.sizes, size)
            ft.runtimes = np.append(ft.runtimes, y)
            self._touched.add(i)
        if self._rel_dirty:
            self._sync_reliability()
        tr = self.tracer
        with tr.span("tick_step", n=len(obs)):
            state, mean, std, _y = tick_step(self.state,
                                             jnp.asarray(packed, dt),
                                             self.size, host_deadjust=True)
            # the estimates, the next tick-start belief for the surprise
            # gate and the bias statistics, packed on the device
            buf = self._view(state, mean, std)
            # its one copy to the host waits for the device; widened once
            with tr.span("tick_fetch", n=len(obs), nbytes=buf.nbytes):
                flat = np.asarray(jax.device_get(buf), np.float64)
                self._take_estimates(flat)
        self.state = state
        self._model = _attach_log(state.model, self._log)
        if self._bias is not None:
            with tr.span("bias_fetch", n=len(obs)):
                self._take_bias(flat)
        return [float(v) for v in ys]

    # ---- the host view: one copy, split into views --------------------
    def _view(self, state, mean, std):
        """Dispatch the ``host_view`` of ``mean``/``std`` and ``state``."""
        return host_view(mean, std, state.model, state.bias_counts,
                         state.bias_log_sum, state.bias_log_sq, self.size)

    def _take_estimates(self, flat) -> None:
        self._mean, self._std, self._gate = (
            flat[sl].reshape(shape) for sl, shape in self._parts[:3])

    def _take_bias(self, flat) -> None:
        b = self._bias
        b.counts, b.log_sum, b.log_sq = (
            flat[sl].reshape(shape) for sl, shape in self._parts[3:])
        b._sigma_r_cache = None

    # ---- scalar consumers (tick-start belief) -------------------------
    def _gate_row(self, i: int, size: float) -> list[float]:
        """Row ``i`` of the gate table the last tick copied; the engine
        answers at its own size only, like ``predict_matrix``."""
        if float(size) != self.size:
            raise ValueError(
                "TickEngine answers at its own size; got "
                f"{size}, engine holds {self.size}")
        return self._gate[i].tolist()

    def predict_interval_node(self, task_name: str, node: str, size: float,
                              confidence: float = 0.9):
        """``LotaruEstimator.predict_interval_node`` of the engine's state,
        answered on the host from the gate table the last tick copied."""
        i = self._rowmap[task_name]
        corr, centre, scale, dof, med, spr = self._gate_row(i, size)
        f = self.est.factor(task_name, node)
        # the quantile functions ``norm.ppf`` / ``t.ppf`` call, without
        # their argument checks (a hundred µs each)
        z = float(_scipy_special.ndtri(0.5 + confidence / 2.0))
        if corr:
            half = float(_scipy_special.stdtrit(
                dof, 0.5 + confidence / 2.0)) * scale
            lo, hi = centre - half, centre + half
        else:
            lo, hi = med - z * spr, med + z * spr
        s_lo = s_hi = 1.0
        if self._bias is not None:
            j = self._bias_col.get(node)
            if j is not None:
                s_lo, s_hi = self._bias.interval_scale(i, j, z)
        return max(lo * f * s_lo, 0.0), hi * f * s_hi

    def predict_pit_node(self, task_name: str, node: str, size: float,
                         runtime: float) -> float:
        """``LotaruEstimator.predict_pit_node`` of the engine's state, read
        like ``predict_interval_node``."""
        i = self._rowmap[task_name]
        corr, centre, scale, dof, med, spr = self._gate_row(i, size)
        f = max(float(self.est.factor(task_name, node)), 1e-12)
        b = 1.0
        if self._bias is not None:
            j = self._bias_col.get(node)
            if j is not None:
                b = self._bias.point(i, j)
        y_local = float(runtime) / (f * max(b, 1e-12))
        if corr:
            return float(_scipy_stats.t.cdf(
                (y_local - centre) / max(scale, 1e-300), dof))
        z = (y_local - med) / max(spr, 1e-300)
        return float(_scipy_stats.norm.cdf(z))

    def bias_point(self, name: str, node: str) -> float:
        if self._bias is None:
            return 1.0
        j = self._bias_col.get(node)
        if j is None:
            return 1.0
        return self._bias.point(self._rowmap[name], j)

    def bias_tail_mass(self, name: str, node: str,
                       threshold: float) -> float:
        if self._bias is None:
            return 0.0
        j = self._bias_col.get(node)
        if j is None:
            return 0.0
        return self._bias.tail_mass(self._rowmap[name], j, threshold)

    # ---- reliability plane (host, scheduler-consumed) -----------------
    def record_attempt(self, node: str, success: bool) -> None:
        self.est.record_attempt(node, success)
        self._rel_dirty = True

    def reliability_factors(self, nodes, k: float = 1.0):
        return self.est.reliability_factors(nodes, k)

    def _sync_reliability(self) -> None:
        """Mirror the host reliability counts into the state leaves so
        the consolidated pytree stays authoritative for save/fleet
        consumers (the tick itself never reads them)."""
        import dataclasses as _dc
        rel = self.est.reliability
        names = self.names
        if rel is None or not names.rel_nodes:
            self._rel_dirty = False
            return
        dt = self.state.rel_succ.dtype
        succ = np.zeros(len(names.rel_nodes), np.float64)
        fail = np.zeros(len(names.rel_nodes), np.float64)
        for kk, n in enumerate(names.rel_nodes):
            succ[kk], fail[kk] = rel.counts(n)
        self.state = _dc.replace(self.state,
                                 rel_succ=jnp.asarray(succ, dt),
                                 rel_fail=jnp.asarray(fail, dt))
        self._rel_dirty = False

    # ---- writeback ----------------------------------------------------
    def finalize(self) -> None:
        """Fold the final state back into the wrapped estimator (batch
        cache, touched scalar models, bias posterior) — after this the
        legacy OO surface continues bit-compatibly."""
        if self._rel_dirty:
            self._sync_reliability()
        state = EstimatorState(
            model=self._model, factors=self.state.factors,
            node_cols=self.state.node_cols,
            bias_counts=self.state.bias_counts,
            bias_log_sum=self.state.bias_log_sum,
            bias_log_sq=self.state.bias_log_sq,
            rel_succ=self.state.rel_succ, rel_fail=self.state.rel_fail,
            meta=self.state.meta)
        write_back(state, self.names, self.est, rows=self._touched)
        self._touched.clear()
