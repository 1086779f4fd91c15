"""Plain reference of the estimator's semantics, and the schedule checks.

Written from the method's equations, with nothing imported from the
program under test: the Normal-Inverse-Gamma regression of each task's
runtime on its input size (from streamed moments), the Pearson gate with
the median/MAD fallback, the per-node factor adjustment, and the
per-(task, node) log-residual bias posterior with its empirical-Bayes
noise scale.  ``Reference`` absorbs the same ticks of observations the
program absorbed and gives the (task x node) estimate after each.

Every array is held and computed in ``dtype``: float64 for the reference,
``ml_dtypes.bfloat16`` for the lower-precision control.  The raw-sample
history (median/MAD) is float64 in both, as the program keeps it on the
host.
"""
from __future__ import annotations

import bisect

import numpy as np

PRIOR_SCALE = 10.0      # weight prior: b ~ N(0, sigma^2 * 100 I)
A0, B0 = 1.0, 1.0       # noise prior: sigma^2 ~ InvGamma(1, 1)
GATE = 0.8              # Pearson threshold of the BLR-vs-median choice
TAU0 = 0.5              # log-bias prior sd
SIGMA_R = 0.25          # log-residual sd until two residuals of a pair exist
SIGMA_R_FLOOR = 0.02    # floor of the pooled empirical-Bayes sd
#: a Pearson coefficient this close to the gate is a tie that float32
#: rounding may break either way; both branches are then accepted
GATE_TIE = 1e-4


def runtime_factors(w, local, nodes) -> np.ndarray:
    """(T, N) factors of the paper's eq. 6:
    w * cpu_local / cpu_node + (1 - w) * io_local / io_node, where a
    bench's io score is the mean of its read and write rates."""
    w = np.asarray(w, np.float64)[:, None]
    cpu = np.array([local["cpu"] / max(n["cpu"], 1e-9) for n in nodes])
    io = np.array([local["io"] / max(n["io"], 1e-9) for n in nodes])
    return w * cpu[None, :] + (1.0 - w) * io[None, :]


def cpu_weight(normal, slow, slow_factor) -> float:
    """Paper eq. 5: the median relative slow-down of the throttled runs
    over the expected one, clamped to [0, 1]."""
    dev = np.median([(s - n) / n for s, n in zip(slow, normal)])
    denom = 1.0 / slow_factor - 1.0
    return float(np.clip(dev / denom, 0.0, 1.0)) if denom > 0 else 0.0


def median_spread(ys) -> tuple[float, float]:
    med = float(np.median(ys))
    return med, float(1.4826 * np.median(np.abs(np.asarray(ys) - med))
                      + 1e-12)


class Reference:
    """The estimate of T tasks on N prediction nodes, tick by tick.

    ``samples``: per task ``(sizes, local runtimes)``; ``factors`` (T, N);
    ``bias_col`` (N,) the bias column of each prediction node (-1 outside
    the bias universe); ``n_bias`` the number of bias columns; ``size``
    the input size the estimate is made at.
    """

    def __init__(self, samples, factors, bias_col, n_bias, size,
                 dtype=np.float64):
        self.dt = dtype
        c = self.c
        self.size = float(size)
        self.T = len(samples)
        self.factors64 = np.asarray(factors, np.float64)
        self.factors = c(self.factors64)
        self.bias_col = np.asarray(bias_col, np.int64)
        self.hist = [list(map(float, r)) for _, r in samples]
        m = np.zeros((self.T, 8))
        corr = np.zeros(self.T, bool)
        med = np.zeros(self.T)
        spr = np.zeros(self.T)
        for i, (x, y) in enumerate(samples):
            x = np.asarray(x, np.float64)
            y = np.asarray(y, np.float64)
            m[i] = [len(x), x.sum(), y.sum(), (x * x).sum(), (y * y).sum(),
                    (x * y).sum(), np.abs(x).max(), np.abs(y).max()]
            xd, yd = x - x.mean(), y - y.mean()
            den = np.sqrt((xd ** 2).sum() * (yd ** 2).sum())
            p = 0.0 if den == 0 else (xd * yd).sum() / den
            corr[i] = p > GATE and len(x) >= 2
            med[i], spr[i] = median_spread(y)
        self.pear_tie = np.zeros(self.T, bool)   # gate within GATE_TIE
        self.m = c(m)
        self.corr = corr
        self.med = c(med)
        self.spr = c(spr)
        self.post = {k: c(np.zeros(self.T)) for k in
                     ("mu1", "mu2", "v11", "v12", "v22", "a", "b", "xs", "ys")}
        self._posterior(np.arange(self.T))
        nb = int(n_bias)
        self.counts = c(np.zeros((self.T, nb)))
        self.log_sum = c(np.zeros((self.T, nb)))
        self.log_sq = c(np.zeros((self.T, nb)))
        # the pooled-sd sums over pairs with two or more residuals,
        # kept up to date pair by pair (float64 accumulators)
        self._ss = 0.0
        self._dof = 0.0
        self.ambiguous = np.zeros((self.T, nb), bool)

    def c(self, x):
        return np.asarray(x, self.dt)

    # ---- the NIG posterior and its predictive -------------------------
    def _posterior(self, rows) -> None:
        c = self.c
        m = self.m[rows]
        n, sx, sy, sxx, syy, sxy, mx, my = (m[:, k] for k in range(8))
        xs = c(np.maximum(mx, 1e-12))
        ys = c(np.maximum(my, 1e-12))
        p0 = 1.0 / PRIOR_SCALE ** 2
        a11 = c(n + p0)
        a12 = c(sx / xs)
        a22 = c(c(sxx / c(xs * xs)) + p0)
        det = c(c(a11 * a22) - c(a12 * a12))
        b1 = c(sy / ys)
        b2 = c(c(sxy / xs) / ys)
        mu1 = c(c(c(a22 * b1) - c(a12 * b2)) / det)
        mu2 = c(c(c(a11 * b2) - c(a12 * b1)) / det)
        an = c(A0 + c(n / 2.0))
        fit = c(c(mu1 * b1) + c(mu2 * b2))
        bn = c(np.maximum(c(B0 + c(0.5 * c(c(syy / c(ys * ys)) - fit))),
                          1e-12))
        p = self.post
        p["mu1"][rows], p["mu2"][rows] = mu1, mu2
        p["v11"][rows] = c(a22 / det)
        p["v12"][rows] = c(-a12 / det)
        p["v22"][rows] = c(a11 / det)
        p["a"][rows], p["b"][rows] = an, bn
        p["xs"][rows], p["ys"][rows] = xs, ys

    def _pearson(self, rows) -> np.ndarray:
        m = self.m[rows].astype(np.float64)
        n, sx, sy, sxx, syy, sxy = (m[:, k] for k in range(6))
        num = sxy - sx * sy / n
        den = np.sqrt(np.maximum((sxx - sx ** 2 / n) * (syy - sy ** 2 / n),
                                 0.0))
        return np.where(den > 0, num / np.where(den > 0, den, 1.0), 0.0)

    def _blr(self, rows, x):
        """Student-t predictive mean/std of ``rows`` at inputs ``x``."""
        c = self.c
        p = {k: v[rows] for k, v in self.post.items()}
        z = c(c(x) / p["xs"])
        mean = c(c(p["mu1"] + c(p["mu2"] * z)) * p["ys"])
        quad = c(c(p["v11"] + c(c(2.0 * p["v12"]) * z))
                 + c(c(p["v22"] * z) * z))
        s2 = c(c(p["b"] / p["a"]) * c(1.0 + quad))
        dof = c(2.0 * p["a"])
        var = c(c(s2 * dof) / c(np.maximum(c(dof - 2.0), 1e-6)))
        std = c(c(np.sqrt(c(np.maximum(var, 0.0)))) * p["ys"])
        return mean, std

    def _task_estimate(self, rows, x, corr):
        mean_b, std_b = self._blr(rows, x)
        mean = np.where(corr, self.c(np.maximum(mean_b, 0.0)), self.med[rows])
        std = np.where(corr, std_b, self.spr[rows])
        return self.c(mean), self.c(std)

    # ---- the bias posterior -------------------------------------------
    def sigma_r(self) -> float:
        if self._dof <= 0:
            return SIGMA_R
        s = np.sqrt(max(self._ss, 0.0) / max(self._dof, 1.0))
        return float(self.c(max(s, SIGMA_R_FLOOR)))

    def _pair_terms(self, i, j) -> tuple[float, float]:
        n = float(self.counts[i, j])
        if n < 2:
            return 0.0, 0.0
        ls, lq = float(self.log_sum[i, j]), float(self.log_sq[i, j])
        return lq - ls * ls / n, n - 1.0

    def _bias_post(self, counts, log_sum):
        c = self.c
        sr2 = c(self.sigma_r() ** 2)
        lam = c(c(1.0 / TAU0 ** 2) + c(counts / sr2))
        mu = c(log_sum / c(sr2 * lam))
        return mu, c(1.0 / lam)

    # ---- one tick -----------------------------------------------------
    def tick(self, obs) -> list[float]:
        """Absorb one tick: ``obs`` is a list of (row, col, x, y_raw) in
        the order the program absorbed them.  Returns the de-adjusted
        local-equivalent runtimes (tick-start bias)."""
        c = self.c
        rows = np.array([o[0] for o in obs], np.int64)
        cols = np.array([o[1] for o in obs], np.int64)
        xs = np.array([o[2] for o in obs], np.float64)
        ys = np.array([o[3] for o in obs], np.float64)
        bcol = self.bias_col[cols]
        sb = np.maximum(bcol, 0)
        # tick-start bias point, float64 as the host de-adjust computes it
        n0 = self.counts[rows, sb].astype(np.float64)
        sr2 = self.sigma_r() ** 2
        lam0 = 1.0 / TAU0 ** 2 + n0 / sr2
        mu0 = self.log_sum[rows, sb].astype(np.float64) / (sr2 * lam0)
        b = np.where((bcol >= 0) & (n0 > 0), np.exp(mu0), 1.0)
        f = np.maximum(self.factors64[rows, cols], 1e-12)
        y_local = ys / (f * np.maximum(b, 1e-12))
        # sequential absorption: each round takes the next occurrence of
        # every row, so a row seen twice in a tick is updated in order
        seen: dict[int, int] = {}
        rounds: list[list[int]] = []
        for k, r in enumerate(rows):
            q = seen.get(int(r), 0)
            seen[int(r)] = q + 1
            if q == len(rounds):
                rounds.append([])
            rounds[q].append(k)
        for ks in rounds:
            ks = np.asarray(ks)
            rr = rows[ks]
            meds, sprs = [], []
            for k in ks:
                h = self.hist[int(rows[k])]
                h.append(float(y_local[k]))
                md, sp = median_spread(h)
                meds.append(md)
                sprs.append(sp)
            x, y = c(xs[ks]), c(y_local[ks])
            m = self.m[rr]
            add = np.stack([c(np.ones_like(x)), x, y, c(x * x), c(y * y),
                            c(x * y)], axis=-1)
            self.m[rr, :6] = c(m[:, :6] + add)
            self.m[rr, 6] = c(np.maximum(m[:, 6], np.abs(x)))
            self.m[rr, 7] = c(np.maximum(m[:, 7], np.abs(y)))
            self._posterior(rr)
            p = self._pearson(rr)
            self.pear_tie[rr] = np.abs(p - GATE) < GATE_TIE
            self.corr[rr] = (p > GATE) & (self.m[rr, 0] >= 2)
            self.med[rr] = c(meds)
            self.spr[rr] = c(sprs)
        # residuals against the post-update means, one scatter
        m_post, _ = self._task_estimate(rows, xs, self.corr[rows])
        alt, _ = self._task_estimate(rows, xs, ~self.corr[rows])
        for k in range(len(rows)):
            i, j = int(rows[k]), int(bcol[k])
            if j < 0:
                continue
            lr = self._log_resid(ys[k], f[k], m_post[k])
            if self.pear_tie[i] and self._log_resid(ys[k], f[k],
                                                    alt[k]) != lr:
                self.ambiguous[i, j] = True
            if lr is None:
                continue
            ss0, d0 = self._pair_terms(i, j)
            self.counts[i, j] = c(self.counts[i, j] + 1.0)
            self.log_sum[i, j] = c(self.log_sum[i, j] + lr)
            self.log_sq[i, j] = c(self.log_sq[i, j] + c(lr * lr))
            ss1, d1 = self._pair_terms(i, j)
            self._ss += ss1 - ss0
            self._dof += d1 - d0
        return [float(v) for v in y_local]

    def _log_resid(self, y_raw, f, m_post):
        scaled = self.c(self.c(f) * m_post)
        if not (y_raw > 0.0 and scaled > 1e-12):
            return None
        return self.c(np.log(self.c(self.c(y_raw) / scaled)))

    # ---- the estimate -------------------------------------------------
    def estimate(self, rows=None):
        """(mean, std) of ``rows`` (all by default) on every prediction
        node, and the same with the Pearson gate flipped for rows whose
        coefficient ties with it (None where no row ties)."""
        rows = np.arange(self.T) if rows is None else np.asarray(rows)
        x = np.full(len(rows), self.size)
        corr = self.corr[rows]
        out = self._fold(rows, *self._task_estimate(rows, x, corr))
        tie = self.pear_tie[rows]
        if not tie.any():
            return out, None, tie
        alt = self._fold(rows, *self._task_estimate(rows, x, corr ^ tie))
        return out, alt, tie

    def _fold(self, rows, mean_t, std_t):
        c = self.c
        mean = c(mean_t[:, None] * self.factors[rows])
        std = c(std_t[:, None] * self.factors[rows])
        sb = np.maximum(self.bias_col, 0)
        counts = self.counts[rows][:, sb]
        mu, v = self._bias_post(counts, self.log_sum[rows][:, sb])
        active = (self.bias_col >= 0)[None, :] & (counts > 0)
        point = c(np.exp(mu))
        wide = c(point * c(np.sqrt(c(c(std * std)
                                     + c(c(mean * mean) * c(np.expm1(v)))))))
        return (c(np.where(active, c(mean * point), mean)),
                c(np.where(active, wide, std)))

    def ambiguous_cells(self, rows=None) -> np.ndarray:
        """(R, N) cells whose bias pair absorbed a residual that a Pearson
        tie made ambiguous."""
        rows = np.arange(self.T) if rows is None else np.asarray(rows)
        sb = np.maximum(self.bias_col, 0)
        return self.ambiguous[rows][:, sb] & (self.bias_col >= 0)[None, :]


# ---- comparisons -----------------------------------------------------------
def rel_gap(prog, ref, alt=None, tie_rows=None, skip=None) -> float:
    """Widest ``|prog - ref|`` over the cells, each against ``|ref|`` or the
    median ``|ref|``, whichever is larger.  Rows in ``tie_rows`` take the
    nearer of ``ref`` and ``alt``; cells in ``skip`` are left out.  A
    non-finite program value counts as an infinite gap."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.isfinite(prog).all():
        return float("inf")
    scale = max(float(np.median(np.abs(ref))), 1e-300)
    gap = np.abs(prog - ref) / np.maximum(np.abs(ref), scale)
    if alt is not None and tie_rows is not None and np.any(tie_rows):
        alt = np.asarray(alt, np.float64)
        gap_alt = np.abs(prog - alt) / np.maximum(np.abs(alt), scale)
        gap = np.where(np.asarray(tie_rows)[:, None], np.minimum(gap, gap_alt),
                       gap)
    if skip is not None:
        gap = np.where(skip, 0.0, gap)
    return float(gap.max()) if gap.size else 0.0


def schedule_errors(records, preds, truth) -> int:
    """Faults in one run's schedule: an instance missing or run twice, a
    start before a predecessor's end, two runs overlapping on one node, a
    runtime other than the ground truth, or an end other than start plus
    runtime.  ``records``: dicts with id, node, node_type, start, end,
    runtime; ``preds``: instance id -> predecessor ids; ``truth``:
    (id, node type) -> runtime."""
    errors = 0
    by_id: dict[str, dict] = {}
    for r in records:
        if r["id"] in by_id:
            errors += 1
        by_id[r["id"]] = r
    errors += len(set(preds) - set(by_id)) + len(set(by_id) - set(preds))
    for tid, r in by_id.items():
        if truth.get((tid, r["node_type"])) != r["runtime"]:
            errors += 1
        if r["end"] != r["start"] + r["runtime"]:
            errors += 1
        for p in preds.get(tid, ()):
            if p not in by_id or by_id[p]["end"] > r["start"]:
                errors += 1
    per_node: dict[str, list] = {}
    for r in by_id.values():
        per_node.setdefault(r["node"], []).append((r["start"], r["end"]))
    for spans in per_node.values():
        spans.sort()
        for (_, e0), (s1, _) in zip(spans, spans[1:]):
            if s1 < e0:
                errors += 1
    return errors


def tick_index(tick_times, t) -> int:
    """How many ticks (at sorted ``tick_times``) had happened by ``t``."""
    return bisect.bisect_right(tick_times, t)
