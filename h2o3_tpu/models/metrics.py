"""Model metrics — successor of the ``hex.ModelMetrics*`` hierarchy
(``ModelMetricsRegression/Binomial/Multinomial/Clustering``; AUC machinery in
``hex.AUC2``) [UNVERIFIED upstream paths, SURVEY.md §2.2].

Two computation paths behind the same entry points:

- **host (CPU mesh / numpy inputs)**: exact float64 summaries on the pulled
  prediction column(s) — exact rank-statistic AUC, 400-point threshold table.
- **device (accelerator + jax-array inputs)**: the prediction column(s)
  stay where they are — the O(n) sufficient statistics are reduced ON DEVICE
  (weighted sums + a 1024-bucket score histogram — exactly H2O ``AUC2``'s
  400-bin design, finer) and only KBs come down; the criterion surface is
  assembled from buckets on host.
"""

from __future__ import annotations

import numpy as np

from h2o3_tpu.utils import metrics as _mx

_EPS = 1e-15
_NBUCKETS = 1024


def _on_device(*arrays) -> bool:
    """True when we should take the device-stats path: an accelerator
    backend and at least one jax array among the inputs."""
    try:
        import jax

        if jax.default_backend() == "cpu":
            return False
        return any(isinstance(a, jax.Array) for a in arrays)
    except Exception:
        return False


class ModelMetrics:
    def __init__(self, kind: str, values: dict, domain=None):
        self.kind = kind
        self._v = dict(values)
        self.domain = domain

    def __getattr__(self, item):
        v = self.__dict__.get("_v", {})
        if item in v:
            return v[item]
        raise AttributeError(item)

    def gains_lift(self):
        """Gains/lift table rows (binomial metrics only; else None)."""
        return self._v.get("gains_lift_table")

    def kolmogorov_smirnov(self) -> float:
        return self.value("ks")

    def value(self, name: str) -> float:
        """Look up a scalar criterion by name (nan if absent) — the lookup
        used by grid ranking / early stopping / leaderboards."""
        v = self._v.get(name)
        if v is None and name == "mean_residual_deviance":
            v = self._v.get("mse")
        try:
            return float(v)
        except (TypeError, ValueError):
            return float("nan")

    def to_dict(self) -> dict:
        out = {"kind": self.kind}
        for k, v in self._v.items():
            out[k] = v.tolist() if isinstance(v, np.ndarray) else v
        return out

    def __repr__(self):
        keys = [
            k
            for k in (
                "rmse",
                "mae",
                "r2",
                "mean_residual_deviance",
                "auc",
                "pr_auc",
                "logloss",
                "mean_per_class_error",
                "gini",
            )
            if k in self._v
        ]
        body = ", ".join(f"{k}={self._v[k]:.6g}" for k in keys)
        return f"<ModelMetrics{self.kind.capitalize()} {body}>"


# --------------------------------------------------------------------------
# regression


@_mx.span("metrics.regression")
def regression_metrics(
    actual: np.ndarray,
    pred: np.ndarray,
    weights: np.ndarray | None = None,
    distribution: str = "gaussian",
) -> ModelMetrics:
    if _on_device(actual, pred):
        return _regression_metrics_device(actual, pred, weights, distribution)
    a = np.asarray(actual, np.float64)
    p = np.asarray(pred, np.float64)
    w = np.ones_like(a) if weights is None else np.asarray(weights, np.float64)
    ok = ~np.isnan(a) & ~np.isnan(p) & (w > 0)
    a, p, w = a[ok], p[ok], w[ok]
    sw = w.sum()
    err = a - p
    mse = float((w * err**2).sum() / sw)
    mae = float((w * np.abs(err)).sum() / sw)
    mean_a = (w * a).sum() / sw
    ss_tot = float((w * (a - mean_a) ** 2).sum() / sw)
    rmsle = float("nan")
    if (a > -1).all() and (p > -1).all():
        rmsle = float(
            np.sqrt((w * (np.log1p(a) - np.log1p(p)) ** 2).sum() / sw)
        )
    dev = _mean_deviance(a, p, w, distribution)
    return ModelMetrics(
        "regression",
        {
            "mse": mse,
            "rmse": float(np.sqrt(mse)),
            "mae": mae,
            "rmsle": rmsle,
            "r2": float(1.0 - mse / ss_tot) if ss_tot > 0 else float("nan"),
            "mean_residual_deviance": dev,
            "nobs": int(ok.sum()),
        },
    )


def _mean_deviance(a, p, w, distribution: str) -> float:
    sw = w.sum()
    if distribution == "poisson":
        p = np.maximum(p, _EPS)
        with np.errstate(divide="ignore", invalid="ignore"):
            t = np.where(a > 0, a * np.log(a / p), 0.0)
        return float((2 * w * (t - (a - p))).sum() / sw)
    if distribution == "gamma":
        p = np.maximum(p, _EPS)
        a_ = np.maximum(a, _EPS)
        return float((2 * w * (-np.log(a_ / p) + (a_ - p) / p)).sum() / sw)
    if distribution == "laplace":
        return float((w * np.abs(a - p)).sum() / sw)
    return float((w * (a - p) ** 2).sum() / sw)  # gaussian & default


# --------------------------------------------------------------------------
# binomial


@_mx.span("metrics.binomial")
def binomial_metrics(
    actual: np.ndarray,
    prob: np.ndarray,
    weights: np.ndarray | None = None,
    domain: tuple[str, str] = ("0", "1"),
) -> ModelMetrics:
    """``actual`` is {0,1} int; ``prob`` is P(class 1)."""
    if _on_device(actual, prob):
        return _binomial_metrics_device(actual, prob, weights, domain)
    y = np.asarray(actual, np.float64)
    p = np.clip(np.asarray(prob, np.float64), _EPS, 1 - _EPS)
    w = np.ones_like(y) if weights is None else np.asarray(weights, np.float64)
    ok = ~np.isnan(y) & ~np.isnan(p) & (w > 0)
    y, p, w = y[ok], p[ok], w[ok]
    sw = w.sum()

    logloss = float(-(w * (y * np.log(p) + (1 - y) * np.log(1 - p))).sum() / sw)
    mse = float((w * (y - p) ** 2).sum() / sw)
    auc = _weighted_auc(y, p, w)
    pr_auc = _pr_auc(y, p, w)

    # threshold table (the AUC2 criterion surface)
    thresholds = np.unique(np.quantile(p, np.linspace(0, 1, 400)))
    table = _threshold_table(y, p, w, thresholds)
    f1 = table["f1"]
    best = int(np.nanargmax(f1)) if not np.all(np.isnan(f1)) else 0
    best_thr = float(thresholds[best])
    # The table's thresholds are quantiles of the scores, so for a model
    # with tied scores (every tree ensemble) the max-F1 one IS a score, and
    # ``p >= thr`` then turns on the last bit of p for that whole tie group:
    # a scorer in another precision (the exported MOJO's float64, the native
    # runtime, another backend) labelled the group the other way. The labels'
    # threshold is therefore carried half-way down to the next score below:
    # the same rows of this frame at or over it, and none within rounding.
    below = p[p < best_thr]
    if below.size:
        best_thr = float(0.5 * (best_thr + below.max()))
    cm = _confusion(y, p, w, best_thr)

    mx = {}
    for name in ("f1", "f2", "f0point5", "accuracy", "precision", "recall",
                 "specificity", "mcc", "min_per_class_accuracy",
                 "mean_per_class_accuracy"):
        vals = table[name]
        if np.all(np.isnan(vals)):  # degenerate (e.g. constant predictions)
            mx[f"max_{name}"] = {"threshold": 0.5, "value": float("nan")}
        else:
            mx[f"max_{name}"] = {
                "threshold": float(thresholds[int(np.nanargmax(vals))]),
                "value": float(np.nanmax(vals)),
            }

    order = np.argsort(-p, kind="mergesort")
    ps = p[order]
    # collapse tied scores to one mass each: KS/gains are defined over
    # realizable thresholds — per-row cumulatives through a tie group would
    # make both depend on arbitrary input row order (a constant predictor
    # must give KS 0, not 1)
    first = np.concatenate([[0], np.nonzero(np.diff(ps))[0] + 1])
    gl_rows, ks = _gains_lift(
        np.add.reduceat((w * y)[order], first),
        np.add.reduceat((w * (1 - y))[order], first),
    )

    return ModelMetrics(
        "binomial",
        {
            "auc": auc,
            "pr_auc": pr_auc,
            "gini": 2 * auc - 1,
            "logloss": logloss,
            "mse": mse,
            "rmse": float(np.sqrt(mse)),
            "mean_per_class_error": float(
                1.0 - mx["max_mean_per_class_accuracy"]["value"]
            ),
            "default_threshold": best_thr,
            "confusion_matrix": cm,
            "max_criteria": mx,
            "nobs": int(ok.sum()),
            "gains_lift_table": gl_rows,
            "ks": ks,
        },
        domain=domain,
    )


def _gains_lift(wpos_desc, wneg_desc, groups: int = 16):
    """Gains/lift table + Kolmogorov-Smirnov from positive/negative weight
    mass ordered by DESCENDING score (per row on host, per score bucket on
    device) — the ModelMetricsBinomial GainsLift analog [UNVERIFIED
    upstream hex/GainsLift.java]. Returns (rows, ks)."""
    wpos = np.asarray(wpos_desc, np.float64)
    wneg = np.asarray(wneg_desc, np.float64)
    w = wpos + wneg
    cum_w = np.cumsum(w)
    cum_pos = np.cumsum(wpos)
    cum_neg = np.cumsum(wneg)
    tot, tot_pos, tot_neg = cum_w[-1], cum_pos[-1], cum_neg[-1]
    if tot <= 0 or tot_pos <= 0 or tot_neg <= 0:
        return [], float("nan")
    ks = float(np.max(np.abs(cum_pos / tot_pos - cum_neg / tot_neg)))
    overall = tot_pos / tot
    rows = []
    prev_i = -1
    prev_pos = prev_w = 0.0
    for g in range(1, groups + 1):
        i = int(np.searchsorted(cum_w, tot * g / groups - 1e-12))
        i = min(i, len(w) - 1)
        if i <= prev_i:
            continue  # degenerate tiny group (ties/few rows): merge forward
        grp_w = cum_w[i] - prev_w
        grp_pos = cum_pos[i] - prev_pos
        rows.append({
            "group": len(rows) + 1,
            "cumulative_data_fraction": float(cum_w[i] / tot),
            "lower_threshold_index": int(i),
            "response_rate": float(grp_pos / grp_w) if grp_w > 0 else float("nan"),
            "lift": float((grp_pos / grp_w) / overall) if grp_w > 0 else float("nan"),
            "cumulative_response_rate": float(cum_pos[i] / cum_w[i]),
            "cumulative_lift": float((cum_pos[i] / cum_w[i]) / overall),
            "capture_rate": float(grp_pos / tot_pos),
            "cumulative_capture_rate": float(cum_pos[i] / tot_pos),
            "gain": float(100.0 * ((grp_pos / grp_w) / overall - 1.0)) if grp_w > 0 else float("nan"),
            "cumulative_gain": float(100.0 * ((cum_pos[i] / cum_w[i]) / overall - 1.0)),
        })
        prev_i, prev_pos, prev_w = i, cum_pos[i], cum_w[i]
    return rows, ks


def _weighted_auc(y, p, w) -> float:
    order = np.argsort(p, kind="mergesort")
    y, p, w = y[order], p[order], w[order]
    wpos = w * (y == 1)
    wneg = w * (y == 0)
    tot_pos, tot_neg = wpos.sum(), wneg.sum()
    if tot_pos == 0 or tot_neg == 0:
        return float("nan")
    # rank-sum with tie handling: group equal scores
    cum_neg = np.cumsum(wneg)
    # for ties, positives at a tied score see half the tied negatives
    _, idx, inv = np.unique(p, return_index=True, return_inverse=True)
    grp_neg = np.add.reduceat(wneg, idx)
    below = np.concatenate([[0.0], np.cumsum(grp_neg)[:-1]])
    frac = below[inv] + 0.5 * grp_neg[inv]
    return float((wpos * frac).sum() / (tot_pos * tot_neg))


def _pr_auc(y, p, w) -> float:
    order = np.argsort(-p, kind="mergesort")
    y, w = y[order], w[order]
    tp = np.cumsum(w * (y == 1))
    fp = np.cumsum(w * (y == 0))
    tot_pos = tp[-1]
    if tot_pos == 0:
        return float("nan")
    precision = tp / np.maximum(tp + fp, _EPS)
    recall = tp / tot_pos
    return float(np.trapezoid(precision, recall))


def _threshold_table(y, p, w, thresholds):
    pred = p[None, :] >= thresholds[:, None]  # (T, n)
    wpos = (w * (y == 1))[None, :]
    wneg = (w * (y == 0))[None, :]
    tp = (pred * wpos).sum(1)
    fp = (pred * wneg).sum(1)
    fn = wpos.sum() - tp
    tn = wneg.sum() - fp
    with np.errstate(divide="ignore", invalid="ignore"):
        precision = tp / (tp + fp)
        recall = tp / (tp + fn)
        specificity = tn / (tn + fp)
        accuracy = (tp + tn) / (tp + fp + fn + tn)
        f1 = 2 * precision * recall / (precision + recall)
        f2 = 5 * precision * recall / (4 * precision + recall)
        f05 = 1.25 * precision * recall / (0.25 * precision + recall)
        mcc = (tp * tn - fp * fn) / np.sqrt((tp + fp) * (tp + fn) * (tn + fp) * (tn + fn))
        min_pca = np.minimum(recall, specificity)
        mean_pca = 0.5 * (recall + specificity)
    return {
        "f1": f1,
        "f2": f2,
        "f0point5": f05,
        "accuracy": accuracy,
        "precision": precision,
        "recall": recall,
        "specificity": specificity,
        "mcc": np.abs(mcc),
        "min_per_class_accuracy": min_pca,
        "mean_per_class_accuracy": mean_pca,
    }


def _confusion(y, p, w, thr) -> list[list[float]]:
    pred = (p >= thr).astype(np.float64)
    tp = float((w * ((y == 1) & (pred == 1))).sum())
    fp = float((w * ((y == 0) & (pred == 1))).sum())
    fn = float((w * ((y == 1) & (pred == 0))).sum())
    tn = float((w * ((y == 0) & (pred == 0))).sum())
    return [[tn, fp], [fn, tp]]


# --------------------------------------------------------------------------
# multinomial


@_mx.span("metrics.multinomial")
def multinomial_metrics(
    actual: np.ndarray,
    probs: np.ndarray,
    weights: np.ndarray | None = None,
    domain: tuple[str, ...] = (),
) -> ModelMetrics:
    """``actual`` int class ids; ``probs`` (n, K)."""
    if _on_device(actual, probs):
        return _multinomial_metrics_device(actual, probs, weights, domain)
    y = np.asarray(actual)
    P = np.clip(np.asarray(probs, np.float64), _EPS, 1.0)
    w = np.ones(len(y), np.float64) if weights is None else np.asarray(weights, np.float64)
    ok = (y >= 0) & (w > 0) & ~np.isnan(P).any(axis=1)
    y, P, w = y[ok], P[ok], w[ok]
    sw = w.sum()
    K = P.shape[1]

    logloss = float(-(w * np.log(P[np.arange(len(y)), y])).sum() / sw)
    pred = P.argmax(axis=1)
    err = float((w * (pred != y)).sum() / sw)

    cm = np.zeros((K, K))
    np.add.at(cm, (y, pred), w)
    with np.errstate(divide="ignore", invalid="ignore"):
        per_class_err = 1.0 - np.diag(cm) / cm.sum(axis=1)
    mean_pce = float(np.nanmean(per_class_err))

    # top-k hit ratios (h2o reports up to 10)
    order = np.argsort(-P, axis=1)
    ranks = np.argmax(order == y[:, None], axis=1)
    topk = [float((w * (ranks <= k)).sum() / sw) for k in range(min(10, K))]

    onehot = np.zeros_like(P)
    onehot[np.arange(len(y)), y] = 1.0
    mse = float((w[:, None] * (onehot - P) ** 2).sum() / (sw))

    return ModelMetrics(
        "multinomial",
        {
            "logloss": logloss,
            "classification_error": err,
            "mean_per_class_error": mean_pce,
            "per_class_error": per_class_err,
            "confusion_matrix": cm,
            "hit_ratios": topk,
            "mse": mse,
            "rmse": float(np.sqrt(mse)),
            "nobs": int(ok.sum()),
        },
        domain=domain,
    )


# --------------------------------------------------------------------------
# device-stats path (accelerator backends; see module docstring)


def _to_dev(x, dtype=None):
    import jax.numpy as jnp

    return jnp.asarray(np.asarray(x) if not hasattr(x, "devices") else x, dtype)


def _bucket_hist(b, stats):
    """(n,) int32 buckets + (n, S) stats → (NBUCKETS, S) via chunked one-hot
    matmuls (scatter-add is pathological on TPU; this is MXU work)."""
    import jax
    import jax.numpy as jnp

    n, S = stats.shape
    chunk = 8192
    nchunks = -(-n // chunk)
    pad = nchunks * chunk - n
    if pad:
        b = jnp.pad(b, (0, pad))
        stats = jnp.pad(stats, ((0, pad), (0, 0)))
    b_c = b.reshape(nchunks, chunk)
    s_c = stats.reshape(nchunks, chunk, S)
    iota = jnp.arange(_NBUCKETS, dtype=jnp.int32)

    def body(acc, xs):
        bb, ss = xs
        oh = (bb[:, None] == iota[None, :]).astype(jnp.float32)
        return acc + jax.lax.dot_general(
            ss, oh, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ), None

    acc0 = jnp.zeros((S, _NBUCKETS), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (b_c, s_c))
    return acc.T  # (NBUCKETS, S)


def _log_f32(x):
    """Natural log of a positive (normal) float32 array from additions and
    multiplications alone: Cephes ``logf`` (x = m 2^e with m in [sqrt(1/2),
    sqrt(2)), a degree-8 polynomial in m - 1), good to an ulp or two. The
    TPU's own ``log`` reads LOW by 2.0e-6 in the mean over a column of
    probabilities (v5e, 6M rows, measured in PR 26; this form: 2e-11), and a
    logloss is nothing but that mean: it came out 4e-6 low, relative, where
    the benchmark holds a reported logloss to 1e-5. The float32 sum is not
    the problem (4e-8 relative)."""
    import jax.numpy as jnp

    m, e = jnp.frexp(x)
    low = m < np.float32(np.sqrt(0.5))
    e = jnp.where(low, e - 1, e).astype(jnp.float32)
    f = jnp.where(low, m + m, m) - 1.0
    z = f * f
    r = jnp.float32(7.0376836292e-2)
    for c in (-1.1514610310e-1, 1.1676998740e-1, -1.2420140846e-1,
              1.4249322787e-1, -1.6668057665e-1, 2.0000714765e-1,
              -2.4999993993e-1, 3.3333331174e-1):
        r = r * f + np.float32(c)
    # ln 2 in two parts, so that e * ln 2 loses nothing
    r = r * f * z + np.float32(-2.12194440e-4) * e - 0.5 * z
    return f + r + np.float32(0.693359375) * e


def _binom_device_stats():
    import jax
    import jax.numpy as jnp

    @jax.jit
    @jax.named_scope("ph_metric")
    def stats(y, p, w):
        ok = (~jnp.isnan(y)) & (~jnp.isnan(p)) & (w > 0)
        wok = jnp.where(ok, w, 0.0).astype(jnp.float32)
        # zero masked values BEFORE arithmetic: 0 * NaN = NaN would poison
        # the weighted sums the ok-mask is meant to exclude
        y = jnp.where(ok, y, 0.0)
        p = jnp.where(ok, p, 0.5)
        pc = jnp.clip(p, _EPS, 1 - _EPS)
        ypos = y == 1
        # the probability of the row's own class, clipped at 1e-15 as the
        # host path clips it: in float32 the bound 1 - 1e-15 rounds to 1.0,
        # so a saturated sigmoid (p == 1.0f, y == 0) has to be caught here
        q = jnp.where(ypos, pc, jnp.maximum(1 - pc, _EPS))
        logloss_sum = -(wok * _log_f32(q)).sum()
        mse_sum = (wok * (y - pc) ** 2).sum()
        sw = wok.sum()
        nobs = ok.sum()
        b = jnp.clip((pc * _NBUCKETS).astype(jnp.int32), 0, _NBUCKETS - 1)
        table = _bucket_hist(
            b, jnp.stack([wok * ypos, wok * (~ypos)], axis=1)
        )  # (B, 2): wpos, wneg
        # ONE packed output array = ONE device→host transfer (a 5-leaf tuple
        # would be 5 sequential host round-trips). nobs is
        # bitcast, not value-cast: int32 counts past 2^24 don't fit f32.
        nobs_bits = jax.lax.bitcast_convert_type(nobs.astype(jnp.int32), jnp.float32)
        head = jnp.stack([logloss_sum, mse_sum, sw, nobs_bits])
        return jnp.concatenate([head, table.reshape(-1)])

    return stats


_BINOM_STATS = None


def _binomial_metrics_device(actual, prob, weights, domain) -> ModelMetrics:
    global _BINOM_STATS
    if _BINOM_STATS is None:
        _BINOM_STATS = _binom_device_stats()
    import jax.numpy as jnp

    y = _to_dev(actual, jnp.float32)
    p = _to_dev(prob, jnp.float32)
    w = jnp.ones_like(p) if weights is None else _to_dev(weights, jnp.float32)
    packed32 = np.asarray(_BINOM_STATS(y, p, w))  # float32; [3] is int32 bits
    ll_s, mse_s, sw_ = packed32[:3].astype(np.float64)
    nobs_ = int(packed32[3:4].view(np.int32)[0])
    table = packed32[4:].astype(np.float64).reshape(_NBUCKETS, 2)
    sw = float(sw_)
    logloss = float(ll_s) / sw
    mse = float(mse_s) / sw
    wpos_b, wneg_b = table[:, 0], table[:, 1]
    tot_pos, tot_neg = wpos_b.sum(), wneg_b.sum()

    # AUC with the bucket-as-tie-group rank statistic (H2O AUC2 semantics)
    below_neg = np.concatenate([[0.0], np.cumsum(wneg_b)[:-1]])
    auc = (
        float((wpos_b * (below_neg + 0.5 * wneg_b)).sum() / (tot_pos * tot_neg))
        if tot_pos > 0 and tot_neg > 0
        else float("nan")
    )

    # threshold surface from bucket cumulatives: thr_b = b / NBUCKETS,
    # predicted-positive = buckets >= b
    tp = np.cumsum(wpos_b[::-1])[::-1]
    fp = np.cumsum(wneg_b[::-1])[::-1]
    fn = tot_pos - tp
    tn = tot_neg - fp
    thresholds = np.arange(_NBUCKETS) / _NBUCKETS
    with np.errstate(divide="ignore", invalid="ignore"):
        precision = tp / (tp + fp)
        recall = tp / np.maximum(tot_pos, _EPS)
        specificity = tn / np.maximum(tot_neg, _EPS)
        accuracy = (tp + tn) / sw
        f1 = 2 * precision * recall / (precision + recall)
        f2 = 5 * precision * recall / (4 * precision + recall)
        f05 = 1.25 * precision * recall / (0.25 * precision + recall)
        mcc = (tp * tn - fp * fn) / np.sqrt(
            (tp + fp) * (tp + fn) * (tn + fp) * (tn + fn)
        )
        min_pca = np.minimum(recall, specificity)
        mean_pca = 0.5 * (recall + specificity)
    tbl = {
        "f1": f1, "f2": f2, "f0point5": f05, "accuracy": accuracy,
        "precision": precision, "recall": recall, "specificity": specificity,
        "mcc": np.abs(mcc), "min_per_class_accuracy": min_pca,
        "mean_per_class_accuracy": mean_pca,
    }
    # PR-AUC over descending-threshold sweep
    order = np.argsort(-thresholds, kind="mergesort")
    pr = precision[order]
    rc = recall[order]
    okm = ~np.isnan(pr)
    pr_auc = float(np.trapezoid(pr[okm], rc[okm])) if okm.any() else float("nan")

    mx = {}
    for name, vals in tbl.items():
        if np.all(np.isnan(vals)):
            mx[f"max_{name}"] = {"threshold": 0.5, "value": float("nan")}
        else:
            i = int(np.nanargmax(vals))
            mx[f"max_{name}"] = {
                "threshold": float(thresholds[i]),
                "value": float(vals[i]),
            }
    bi = (
        int(np.nanargmax(tbl["f1"])) if not np.all(np.isnan(tbl["f1"])) else 0
    )
    best_thr = float(thresholds[bi])
    cm = [[float(tn[bi]), float(fp[bi])], [float(fn[bi]), float(tp[bi])]]
    gl_rows, ks = _gains_lift(wpos_b[::-1], wneg_b[::-1])

    return ModelMetrics(
        "binomial",
        {
            "auc": auc,
            "pr_auc": pr_auc,
            "gini": 2 * auc - 1,
            "logloss": logloss,
            "mse": mse,
            "rmse": float(np.sqrt(mse)),
            "mean_per_class_error": float(
                1.0 - mx["max_mean_per_class_accuracy"]["value"]
            ),
            "default_threshold": best_thr,
            "confusion_matrix": cm,
            "max_criteria": mx,
            "nobs": int(nobs_),
            "gains_lift_table": gl_rows,
            "ks": ks,
        },
        domain=domain,
    )


_REG_STATS = None


def _regression_metrics_device(actual, pred, weights, distribution) -> ModelMetrics:
    global _REG_STATS
    import jax
    import jax.numpy as jnp

    if _REG_STATS is None:

        @jax.jit
        @jax.named_scope("ph_metric")
        def stats(a, p, w):
            ok = (~jnp.isnan(a)) & (~jnp.isnan(p)) & (w > 0)
            wok = jnp.where(ok, w, 0.0).astype(jnp.float32)
            a0 = jnp.where(ok, a, 0.0)
            p0 = jnp.where(ok, p, 0.0)
            sw = wok.sum()
            err = a0 - p0
            mse_s = (wok * err**2).sum()
            mae_s = (wok * jnp.abs(err)).sum()
            sa = (wok * a0).sum()
            # CENTERED second moment: E[a²]−E[a]² catastrophically cancels in
            # f32 for large-mean targets (measured r2 0.9999 vs true 0.75);
            # a second pass against the mean costs one more O(n) reduction
            mean_a = sa / jnp.maximum(sw, 1e-30)
            saa = (wok * (a0 - mean_a) ** 2).sum()
            loggable = jnp.all(jnp.where(ok, (a0 > -1) & (p0 > -1), True))
            le = jnp.log1p(jnp.maximum(a0, -1 + 1e-12)) - jnp.log1p(
                jnp.maximum(p0, -1 + 1e-12)
            )
            rmsle_s = (wok * le * le).sum()
            # deviances
            pe = jnp.maximum(p0, _EPS)
            ae = jnp.maximum(a0, _EPS)
            pois = (
                2
                * wok
                * (jnp.where(a0 > 0, a0 * jnp.log(ae / pe), 0.0) - (a0 - p0))
            ).sum()
            gam = (2 * wok * (-jnp.log(ae / pe) + (ae - pe) / pe)).sum()
            return sw, mse_s, mae_s, sa, saa, loggable, rmsle_s, pois, gam, ok.sum()

        _REG_STATS = stats

    a = _to_dev(actual, jnp.float32)
    p = _to_dev(pred, jnp.float32)
    w = jnp.ones_like(a) if weights is None else _to_dev(weights, jnp.float32)
    sw, mse_s, mae_s, sa, saa, loggable, rmsle_s, pois, gam, nobs = (
        np.asarray(v, np.float64) for v in _REG_STATS(a, p, w)
    )
    sw = float(sw)
    mse = float(mse_s) / sw
    mae = float(mae_s) / sw
    ss_tot = float(saa) / sw  # already centered on device
    rmsle = float(np.sqrt(float(rmsle_s) / sw)) if bool(loggable) else float("nan")
    if distribution == "poisson":
        dev = float(pois) / sw
    elif distribution == "gamma":
        dev = float(gam) / sw
    elif distribution == "laplace":
        dev = mae
    else:
        dev = mse
    return ModelMetrics(
        "regression",
        {
            "mse": mse,
            "rmse": float(np.sqrt(mse)),
            "mae": mae,
            "rmsle": rmsle,
            "r2": float(1.0 - mse / ss_tot) if ss_tot > 0 else float("nan"),
            "mean_residual_deviance": dev,
            "nobs": int(nobs),
        },
    )


_MULTI_STATS = {}


def _multinomial_metrics_device(actual, probs, weights, domain) -> ModelMetrics:
    import jax
    import jax.numpy as jnp

    P = _to_dev(probs, jnp.float32)
    K = int(P.shape[1])
    if K not in _MULTI_STATS:

        @jax.jit
        @jax.named_scope("ph_metric")
        def stats(y, P, w):
            ok = (y >= 0) & (w > 0) & (~jnp.isnan(P).any(axis=1))
            wok = jnp.where(ok, w, 0.0).astype(jnp.float32)
            ysafe = jnp.clip(y, 0, K - 1).astype(jnp.int32)
            # zero masked rows before arithmetic (0 * NaN = NaN)
            P = jnp.where(ok[:, None], P, 1.0 / K)
            Pc = jnp.clip(P, _EPS, 1.0)
            p_true = jnp.take_along_axis(Pc, ysafe[:, None], axis=1)[:, 0]
            ll_s = -(wok * _log_f32(p_true)).sum()
            pred = jnp.argmax(Pc, axis=1)
            err_s = (wok * (pred != ysafe)).sum()
            oh_y = (ysafe[:, None] == jnp.arange(K)[None, :]).astype(jnp.float32)
            oh_p = (pred[:, None] == jnp.arange(K)[None, :]).astype(jnp.float32)
            cm = jax.lax.dot_general(
                oh_y * wok[:, None], oh_p, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # rank of the true class (count of strictly-greater probs)
            rank = (Pc > p_true[:, None]).sum(axis=1)
            rank_hist = _bucket_hist(
                jnp.clip(rank, 0, _NBUCKETS - 1).astype(jnp.int32), wok[:, None]
            )[:, 0]
            mse_s = (wok[:, None] * (oh_y - Pc) ** 2).sum()
            return ll_s, err_s, cm, rank_hist, mse_s, wok.sum(), ok.sum()

        _MULTI_STATS[K] = stats

    y = _to_dev(actual, jnp.int32)
    w = (
        jnp.ones(P.shape[0], jnp.float32)
        if weights is None
        else _to_dev(weights, jnp.float32)
    )
    ll_s, err_s, cm, rank_hist, mse_s, sw_, nobs = (
        np.asarray(v, np.float64) for v in _MULTI_STATS[K](y, P, w)
    )
    sw = float(sw_)
    with np.errstate(divide="ignore", invalid="ignore"):
        per_class_err = 1.0 - np.diag(cm) / cm.sum(axis=1)
    topk = list(np.cumsum(rank_hist[: min(10, K)]) / sw)
    mse = float(mse_s) / sw
    return ModelMetrics(
        "multinomial",
        {
            "logloss": float(ll_s) / sw,
            "classification_error": float(err_s) / sw,
            "mean_per_class_error": float(np.nanmean(per_class_err)),
            "per_class_error": per_class_err,
            "confusion_matrix": cm,
            "hit_ratios": [float(t) for t in topk],
            "mse": mse,
            "rmse": float(np.sqrt(mse)),
            "nobs": int(nobs),
        },
        domain=domain,
    )


def make_metrics(predicted, actuals, weights=None, domain=None,
                 distribution: str = "gaussian") -> ModelMetrics:
    """``h2o.make_metrics`` successor [UNVERIFIED upstream
    water/api/ModelMetricsMaker]: ModelMetrics straight from prediction and
    actual vectors, no model required.

    ``predicted``: Vec/array of predictions — P(positive) for binomial,
    (n, K) class probabilities (Frame or array) for multinomial, plain
    numbers for regression. ``actuals``: numeric Vec/array, or a
    categorical Vec / string array for classification. ``domain`` forces
    classification with those labels; otherwise a categorical actuals
    column decides.
    """
    from h2o3_tpu.frame.frame import Frame, Vec

    def _vec_np(x):
        if isinstance(x, Frame):
            assert x.ncol == 1, "expected a single-column frame"
            x = x.vec(0)
        if isinstance(x, Vec):
            if x.is_categorical():
                # hand labels (not raw codes) downstream so a caller-supplied
                # domain in a different level order still maps correctly
                codes = x.to_numpy().astype(np.int64)
                lv = np.asarray(list(x.domain) + [None], dtype=object)
                return lv[np.where(codes < 0, len(lv) - 1, codes)], tuple(x.domain)
            return x.to_numpy(), None
        return np.asarray(x), None

    def _to_codes(y, dom):
        """labels/codes -> int codes in ``dom`` order; unknown/NA -> -1."""
        arr = np.asarray(y)
        if np.issubdtype(arr.dtype, np.number):
            out = np.asarray(arr, np.float64)
            out = np.where(np.isnan(out), -1, out)
            return out.astype(np.int64)
        lut = {str(d): i for i, d in enumerate(dom)}
        return np.array([-1 if v is None else lut.get(str(v), -1) for v in arr],
                        np.int64)

    w = None
    if weights is not None:
        w, _ = _vec_np(weights)

    # multinomial: predicted is (n, K) probabilities — Frame or 2-D array
    P = None
    if isinstance(predicted, Frame) and predicted.ncol > 1:
        P = np.stack([predicted.vec(i).to_numpy() for i in range(predicted.ncol)], axis=1)
    elif not isinstance(predicted, (Frame, Vec)):
        arr = np.asarray(predicted)
        if arr.ndim == 2 and arr.shape[1] > 1:
            P = arr
    if P is not None:
        y, adom = _vec_np(actuals)
        dom = tuple(domain) if domain else (adom or tuple(map(str, range(P.shape[1]))))
        if len(dom) != P.shape[1]:
            raise ValueError(
                f"predicted has {P.shape[1]} probability columns but the "
                f"domain has {len(dom)} labels")
        return multinomial_metrics(_to_codes(y, dom), P, w, dom)

    p, _ = _vec_np(predicted)
    y, adom = _vec_np(actuals)
    dom = tuple(domain) if domain else adom
    if dom and len(dom) == 2:
        yc = _to_codes(y, dom).astype(np.float64)
        # binomial_metrics filters only NaN; NA/unknown labels (-1) must not
        # enter the logloss/AUC sums as y=-1
        yc = np.where(yc < 0, np.nan, yc)
        return binomial_metrics(yc, np.asarray(p, np.float64), w, dom)
    if dom and len(dom) > 2:
        raise ValueError("multinomial make_metrics needs a (n, K) predicted frame")
    return regression_metrics(y, p, w, distribution)
