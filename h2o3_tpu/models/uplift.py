"""Uplift DRF — successor of ``hex.tree.uplift.UpliftDRF`` [UNVERIFIED
upstream path, SURVEY.md §2.2]: random forest for heterogeneous treatment
effect estimation (Rzepakowski & Jaroszewicz divergence splitting).

TPU design: the shared histogram fabric (ops/histogram.histogram_in_jit)
carries 4 stat channels; uplift repurposes them as
{w_treat, w_treat·y, w_ctrl, w_ctrl·y} so ONE histogram pass per level
yields both treatment and control class distributions per (node, col, bin).
A custom split scan computes the divergence gain

    gain = (n_L/n)·D(P_t^L, P_c^L) + (n_R/n)·D(P_t^R, P_c^R) − D(P_t, P_c)

for D ∈ {KL, Euclidean, ChiSquared} over the binary outcome distributions,
with prefix splits in natural bin order (numeric) and observed-uplift-sorted
order (categorical). Leaves carry the uplift estimate p_t − p_c; prediction
replay and tree recording reuse TreeLevel/_partition_update unchanged.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.cluster.job import Job
from h2o3_tpu.cluster.registry import DKV
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.model_base import CommonParams, Model, ModelBuilder
from h2o3_tpu.models.tree.binning import bin_frame, fit_bins, fit_bins_for
from h2o3_tpu.models.tree.shared_tree import (
    Tree,
    TreeLevel,
    _partition_update,
    count_partition_levels,
)
from h2o3_tpu.ops.histogram import histogram_in_jit
from h2o3_tpu.utils.log import Log

_NEG = -1e30


@dataclass
class UpliftDRFParams(CommonParams):
    nbins_cats: int = 1024  # categorical bin cap (shared tree semantics)
    treatment_column: str = "treatment"
    uplift_metric: str = "KL"  # KL | ChiSquared | Euclidean
    ntrees: int = 50
    max_depth: int = 10
    min_rows: float = 10.0
    mtries: int = -2  # -2 -> all columns (h2o uplift default differs from DRF)
    sample_rate: float = 0.632
    nbins: int = 255
    min_split_improvement: float = 1e-5
    score_tree_interval: int = 10


def _divergence(pt, pc, metric: str):
    """D(P_t || P_c) for Bernoulli distributions given success probs."""
    eps = 1e-9
    pt = jnp.clip(pt, eps, 1 - eps)
    pc = jnp.clip(pc, eps, 1 - eps)
    if metric == "kl":
        return pt * jnp.log(pt / pc) + (1 - pt) * jnp.log((1 - pt) / (1 - pc))
    if metric == "chisquared":
        return (pt - pc) ** 2 / pc + ((1 - pt) - (1 - pc)) ** 2 / (1 - pc)
    # euclidean
    return (pt - pc) ** 2 + ((1 - pt) - (1 - pc)) ** 2


def _node_div(s, metric, min_rows):
    """Per-cell divergence + validity from stacked stats (..., 4)."""
    wt, wyt, wc, wyc = s[..., 0], s[..., 1], s[..., 2], s[..., 3]
    pt = jnp.where(wt > 0, wyt / jnp.maximum(wt, 1e-30), 0.0)
    pc = jnp.where(wc > 0, wyc / jnp.maximum(wc, 1e-30), 0.0)
    d = _divergence(pt, pc, metric)
    ok = (wt >= min_rows) & (wc >= min_rows)
    return d, ok, wt + wc


def _uplift_split_scan(hist, is_cat, col_mask, min_rows, min_split_improvement,
                       metric: str):
    """Best divergence-gain split per node from hist (N, C, B, 4).

    Stats axis: 0=w_t, 1=w_t·y, 2=w_c, 3=w_c·y. Bin 0 is the NA bin.
    """
    N, C, B, _ = hist.shape
    total = hist.sum(axis=2)  # (N, C, 4)
    na = hist[:, :, 0, :]
    data = hist[:, :, 1:, :]

    d_parent, _, n_parent = _node_div(total[:, 0, :], metric, 0.0)  # (N,)

    def gain_of(L, R):
        dl, okl, nl = _node_div(L, metric, min_rows)
        dr, okr, nr = _node_div(R, metric, min_rows)
        n = jnp.maximum(nl + nr, 1e-30)
        g = (nl / n) * dl + (nr / n) * dr - d_parent[:, None, None]
        return jnp.where(okl & okr, g, _NEG)

    # numeric prefix
    cum = jnp.cumsum(data, axis=2)
    tot_nonna = cum[:, :, -1:, :]
    left = cum[:, :, :-1, :]
    right = tot_nonna - left
    g_nl = gain_of(left + na[:, :, None, :], right)
    g_nr = gain_of(left, right + na[:, :, None, :])
    g_num = jnp.maximum(g_nl, g_nr)  # (N, C, B-2)
    num_t = jnp.argmax(g_num, axis=2)
    num_gain = jnp.take_along_axis(g_num, num_t[:, :, None], 2).squeeze(2)
    num_na_left = (
        jnp.take_along_axis(g_nl, num_t[:, :, None], 2).squeeze(2)
        >= jnp.take_along_axis(g_nr, num_t[:, :, None], 2).squeeze(2)
    )

    # categorical: prefix in observed-uplift-sorted bin order (all columns —
    # masked to cat columns at selection; B is small enough that the extra
    # argsort on numeric columns is noise at uplift's typical C)
    wt_b, wc_b = data[..., 0], data[..., 2]
    up = jnp.where(wt_b > 0, data[..., 1] / jnp.maximum(wt_b, 1e-30), jnp.inf) - \
        jnp.where(wc_b > 0, data[..., 3] / jnp.maximum(wc_b, 1e-30), 0.0)
    order = jnp.argsort(up, axis=2)
    sdata = jnp.take_along_axis(data, order[..., None], axis=2)
    scum = jnp.cumsum(sdata, axis=2)
    s_tot = scum[:, :, -1:, :]
    s_left = scum[:, :, :-1, :]
    s_right = s_tot - s_left
    gc_nl = gain_of(s_left + na[:, :, None, :], s_right)
    gc_nr = gain_of(s_left, s_right + na[:, :, None, :])
    g_cat = jnp.maximum(gc_nl, gc_nr)
    cat_k = jnp.argmax(g_cat, axis=2)
    cat_gain = jnp.take_along_axis(g_cat, cat_k[:, :, None], 2).squeeze(2)
    cat_na_left = (
        jnp.take_along_axis(gc_nl, cat_k[:, :, None], 2).squeeze(2)
        >= jnp.take_along_axis(gc_nr, cat_k[:, :, None], 2).squeeze(2)
    )

    col_gain = jnp.where(is_cat[None, :], cat_gain, num_gain)
    col_gain = jnp.where(col_mask > 0, col_gain, _NEG)
    best_col = jnp.argmax(col_gain, axis=1)
    best_gain = jnp.take_along_axis(col_gain, best_col[:, None], 1).squeeze(1)

    take = lambda a: jnp.take_along_axis(a, best_col[:, None], 1).squeeze(1)
    split_bin = take(num_t) + 1
    bc_is_cat = is_cat[best_col]
    bc_na_left = jnp.where(bc_is_cat, take(cat_na_left), take(num_na_left))
    ranks = jnp.argsort(order, axis=2)
    idx = jnp.broadcast_to(best_col[:, None, None], (N, 1, ranks.shape[2]))
    best_ranks = jnp.take_along_axis(ranks, idx, axis=1).squeeze(1)
    cat_left = best_ranks <= take(cat_k)[:, None]
    cat_mask = jnp.concatenate([bc_na_left[:, None], cat_left], axis=1)

    wt, wyt, wc, wyc = (total[:, 0, s] for s in range(4))
    uplift = jnp.where(wt > 0, wyt / jnp.maximum(wt, 1e-30), 0.0) - jnp.where(
        wc > 0, wyc / jnp.maximum(wc, 1e-30), 0.0
    )
    ok = best_gain >= min_split_improvement

    return {
        "gain": best_gain, "ok": ok, "col": best_col, "is_cat": bc_is_cat,
        "split_bin": split_bin, "na_left": bc_na_left, "cat_mask": cat_mask,
        "node_w": wt + wc, "uplift": uplift,
    }


def _uplift_level_fn(
    bins_u8, nid, preds, varimp, wt, wyt, wc, wyc, key, is_cat,
    min_rows, min_split_improvement, col_sample_rate,
    *, n_pad: int, n_pad_next: int, n_bins: int, force_leaf: bool, metric: str,
):
    C = bins_u8.shape[1]
    hist = histogram_in_jit(bins_u8, nid, (wt, wyt, wc, wyc), n_pad, n_bins)

    if force_leaf:
        tot = hist[:, 0, :, :].sum(axis=1)
        wt_n, wyt_n, wc_n, wyc_n = (tot[:, s] for s in range(4))
        uplift = jnp.where(wt_n > 0, wyt_n / jnp.maximum(wt_n, 1e-30), 0.0) - \
            jnp.where(wc_n > 0, wyc_n / jnp.maximum(wc_n, 1e-30), 0.0)
        ok = jnp.zeros(n_pad, bool)
        gain = jnp.zeros(n_pad, jnp.float32)
        split_col = jnp.zeros(n_pad, jnp.int32)
        split_bin = jnp.zeros(n_pad, jnp.int32)
        is_cat_n = jnp.zeros(n_pad, bool)
        cat_mask = jnp.zeros((n_pad, n_bins), bool)
        na_left = jnp.zeros(n_pad, bool)
        node_w = wt_n + wc_n
    else:
        col_mask = jnp.ones((n_pad, C), jnp.float32)
        keep = jax.random.uniform(key, (n_pad, C)) < col_sample_rate
        keep = jnp.where(keep.any(axis=1, keepdims=True), keep, True)
        col_mask = col_mask * keep
        sp = _uplift_split_scan(
            hist, is_cat, col_mask, min_rows, min_split_improvement, metric
        )
        ok = sp["ok"]
        fits = 2 * jnp.cumsum(ok.astype(jnp.int32)) <= n_pad_next
        ok = ok & fits
        gain = jnp.where(ok, jnp.maximum(sp["gain"], 0.0), 0.0)
        split_col, split_bin = sp["col"], sp["split_bin"]
        is_cat_n, cat_mask, na_left = sp["is_cat"], sp["cat_mask"], sp["na_left"]
        uplift, node_w = sp["uplift"], sp["node_w"]

    leaf_now = ~ok
    leaf_val = jnp.where(leaf_now, uplift, 0.0).astype(jnp.float32)
    cs = jnp.cumsum(ok.astype(jnp.int32))
    child_base = jnp.where(ok, 2 * (cs - 1), 0).astype(jnp.int32)
    n_split = cs[-1] if n_pad else jnp.int32(0)
    varimp = varimp.at[split_col].add(jnp.where(ok, gain, 0.0).astype(varimp.dtype))

    nid, preds = _partition_update(
        bins_u8, nid, preds, split_col, split_bin, is_cat_n, cat_mask,
        na_left, leaf_now, leaf_val, child_base,
    )
    record = {
        "node_w": node_w.astype(jnp.float32),
        "split_col": split_col.astype(jnp.int32),
        "split_bin": split_bin.astype(jnp.int32),
        "is_cat": is_cat_n, "cat_mask": cat_mask, "na_left": na_left,
        "leaf_now": leaf_now, "leaf_val": leaf_val, "child_base": child_base,
        "gain": gain,
    }
    return nid, preds, varimp, n_split, record


_STEP_CACHE: dict = {}


def _uplift_level(n_pad, n_pad_next, n_bins, force_leaf, metric):
    key = (n_pad, n_pad_next, n_bins, force_leaf, metric, jax.default_backend())
    fn = _STEP_CACHE.get(key)
    if fn is None:
        fn = jax.jit(
            partial(
                _uplift_level_fn,
                n_pad=n_pad, n_pad_next=n_pad_next, n_bins=n_bins,
                force_leaf=force_leaf, metric=metric,
            )
        )
        _STEP_CACHE[key] = fn
    return fn


def _uplift_tree_program(max_depth: int, n_bins: int, node_cap: int,
                         metric: str):
    """Whole-tree uplift program (ISSUE 16).

    All levels of one uplift tree trace into a single jitted dispatch —
    the 4-lane (wt, wyt, wc, wyc) scan runs through the same unrolled
    level structure the GBM/DRF whole-tree programs use. Levels past the
    point where every branch retired produce all-leaf placeholder records
    (zero histograms → no splits) that replay inertly, exactly like the
    fused GBM program's post-exit levels, so the recorded tree is
    bit-equal to the legacy per-level loop's on every REAL level."""
    key = ("uplift_tree", max_depth, n_bins, node_cap, metric,
           jax.default_backend())
    fn = _STEP_CACHE.get(key)
    if fn is None:

        def whole_tree(bins_u8, preds, varimp, wt, wyt, wc, wyc, key_,
                       is_cat, min_rows, msi, col_rate):
            nid = jnp.zeros(bins_u8.shape[0], jnp.int32)
            recs = []
            for depth in range(max_depth + 1):
                n_pad = min(1 << depth, node_cap)
                n_pad_next = min(2 * n_pad, node_cap)
                nid, preds, varimp, _, rec = _uplift_level_fn(
                    bins_u8, nid, preds, varimp, wt, wyt, wc, wyc,
                    jax.random.fold_in(key_, depth), is_cat,
                    min_rows, msi, col_rate,
                    n_pad=n_pad, n_pad_next=n_pad_next, n_bins=n_bins,
                    force_leaf=depth == max_depth, metric=metric,
                )
                recs.append(rec)
            return nid, preds, varimp, tuple(recs)

        fn = jax.jit(whole_tree, donate_argnums=(1, 2))
        _STEP_CACHE[key] = fn
    return fn


def _build_uplift_tree(bins_u8, wt, y, wc, *, n_bins, is_cat_cols, max_depth,
                       min_rows, min_split_improvement, col_sample_rate,
                       preds, key, varimp, metric, node_cap=1024):
    from h2o3_tpu.models.tree.shared_tree import use_fused_trees

    is_cat_dev = jnp.asarray(np.asarray(is_cat_cols, bool))
    wyt = wt * y
    wyc = wc * y
    tree = Tree()
    if use_fused_trees(max_depth):
        prog = _uplift_tree_program(max_depth, n_bins, node_cap, metric)
        count_partition_levels(max_depth + 1)
        _, preds, varimp, records = prog(
            bins_u8, preds, varimp, wt, wyt, wc, wyc, key, is_cat_dev,
            jnp.float32(min_rows), jnp.float32(min_split_improvement),
            jnp.float32(col_sample_rate),
        )
        for rec in records:
            tree.levels.append(TreeLevel(**rec))
        return tree, preds, varimp
    # per-level host loop (H2O3_TPU_WHOLE_TREE=0 / depth cap)
    nid = jnp.zeros(bins_u8.shape[0], jnp.int32)
    for depth in range(max_depth + 1):
        n_pad = min(1 << depth, node_cap)
        n_pad_next = min(2 * n_pad, node_cap)
        force_leaf = depth == max_depth
        step = _uplift_level(n_pad, n_pad_next, n_bins, force_leaf, metric)
        count_partition_levels(1)
        nid, preds, varimp, n_split, rec = step(
            bins_u8, nid, preds, varimp, wt, wyt, wc, wyc,
            jax.random.fold_in(key, depth), is_cat_dev,
            jnp.float32(min_rows), jnp.float32(min_split_improvement),
            jnp.float32(col_sample_rate),
        )
        tree.levels.append(TreeLevel(**rec))
        if force_leaf:
            break
        if jax.default_backend() == "cpu" and int(n_split) == 0:
            break
    return tree, preds, varimp


class UpliftDRFModel(Model):
    algo = "upliftdrf"

    def _predict_raw(self, frame: Frame) -> np.ndarray:
        bins = bin_frame(self.output["bin_spec"], frame)
        preds = jnp.zeros(bins.shape[0], jnp.float32)
        for tree in self.output["trees"]:
            _, preds = tree.replay(
                bins, jnp.zeros(bins.shape[0], jnp.int32), preds
            )
        uplift = np.asarray(preds)[: frame.nrow] / max(
            self.output["ntrees_actual"], 1
        )
        return uplift

    def predict(self, frame: Frame) -> Frame:
        frame = self._apply_preprocessors(frame)
        u = self._predict_raw(frame)
        return Frame.from_arrays({"uplift_predict": u})

    def _score_metrics(self, frame: Frame):
        # AUUC (area under the uplift curve) — the uplift model's metric
        from h2o3_tpu.models import metrics as MM

        u = self._predict_raw(frame)
        y = frame.vec(self.params.response_column).to_numpy()
        t_codes = frame.vec(self.params.treatment_column).to_numpy()
        return _auuc_metrics(u, y, t_codes)


def _auuc_metrics(uplift: np.ndarray, y: np.ndarray, treat: np.ndarray,
                  n_bins: int = 1000):
    """Qini/AUUC from predicted uplift, actual outcome, treatment flag."""
    from h2o3_tpu.models.metrics import ModelMetrics

    order = np.argsort(-uplift)
    y_s = y[order]
    t_s = (treat[order] > 0).astype(np.float64)
    n = len(y_s)
    ct = np.cumsum(t_s)
    cc = np.cumsum(1 - t_s)
    cyt = np.cumsum(y_s * t_s)
    cyc = np.cumsum(y_s * (1 - t_s))
    with np.errstate(divide="ignore", invalid="ignore"):
        # qini-style cumulative uplift at each cut
        lift = cyt - np.where(cc > 0, cyc * ct / np.maximum(cc, 1), 0.0)
    idx = np.linspace(0, n - 1, min(n, n_bins)).astype(np.int64)
    auuc = float(np.trapezoid(lift[idx], idx) / n)
    # random-targeting baseline for qini coefficient
    total = lift[-1]
    rand_area = float(total * (n - 1) / 2.0 / n)
    qini = auuc - rand_area
    ate = float(
        (cyt[-1] / max(ct[-1], 1)) - (cyc[-1] / max(cc[-1], 1))
    )
    return ModelMetrics(
        "uplift",
        {"auuc": auuc, "qini": qini, "ate": ate, "nobs": float(n)},
    )


class UpliftDRF(ModelBuilder):
    algo = "upliftdrf"
    PARAMS_CLS = UpliftDRFParams
    SUPPORTS_REGRESSION = False

    def _build(self, job: Job, train: Frame, valid: Frame | None):
        p: UpliftDRFParams = self.params
        if p.ntrees < 1 or p.max_depth < 1:
            raise ValueError("ntrees and max_depth must be >= 1")
        yv = train.vec(p.response_column)
        if not yv.is_categorical() or yv.cardinality > 2:
            raise ValueError("upliftdrf needs a binary categorical response")
        tv = train.vec(p.treatment_column)
        if not tv.is_categorical() or tv.cardinality > 2:
            raise ValueError("treatment_column must be a 2-level factor")
        metric = p.uplift_metric.lower()
        if metric not in ("kl", "chisquared", "euclidean"):
            raise ValueError(f"unknown uplift_metric {p.uplift_metric!r}")

        feats = [n for n in self._x if n != p.treatment_column]
        spec = fit_bins_for(p, train, feats)
        bins = bin_frame(spec, train)
        npad = train.npad
        C = len(feats)

        y_np = yv.to_numpy().astype(np.float64)
        t_np = tv.to_numpy().astype(np.float64)
        base_w = np.zeros(npad, np.float32)
        base_w[: train.nrow] = 1.0
        if p.weights_column:
            base_w[: train.nrow] *= np.nan_to_num(
                train.vec(p.weights_column).to_numpy()
            ).astype(np.float32)
        base_w[: train.nrow] *= (y_np >= 0) & (t_np >= 0)
        ybuf = np.zeros(npad, np.float32)
        ybuf[: train.nrow] = np.clip(np.nan_to_num(y_np, nan=0.0), 0, 1)
        tbuf = np.zeros(npad, np.float32)
        tbuf[: train.nrow] = np.clip(np.nan_to_num(t_np, nan=0.0), 0, 1)
        w = jnp.asarray(base_w)
        y = jnp.asarray(ybuf)
        tr = jnp.asarray(tbuf)

        mtries = p.mtries
        if mtries in (-1, 0):
            mtries = max(1, int(np.sqrt(C)))
        elif mtries == -2:
            mtries = C
        col_rate = min(1.0, mtries / C)

        rngkey = jax.random.PRNGKey(abs(p.seed) if p.seed and p.seed > 0 else 97)
        preds = jnp.zeros(npad, jnp.float32)
        varimp = jnp.zeros(C, jnp.float32)
        trees: list[Tree] = []
        for m in range(p.ntrees):
            if job.stop_requested:
                break
            rngkey, sk = jax.random.split(rngkey)
            mask = jax.random.bernoulli(sk, p.sample_rate, (npad,)).astype(
                jnp.float32
            )
            w_tree = w * mask
            tree, preds, varimp = _build_uplift_tree(
                bins, w_tree * tr, y, w_tree * (1.0 - tr),
                n_bins=spec.max_bins, is_cat_cols=spec.is_cat,
                max_depth=p.max_depth, min_rows=p.min_rows,
                min_split_improvement=p.min_split_improvement,
                col_sample_rate=col_rate, preds=preds,
                key=jax.random.fold_in(rngkey, m), varimp=varimp,
                metric=metric,
            )
            trees.append(tree)
            job.update(0.05 + 0.9 * (m + 1) / p.ntrees)

        out = {
            "bin_spec": spec,
            "trees": trees,
            "names": feats,
            "varimp": np.asarray(varimp).astype(np.float64),
            "response_domain": tuple(yv.domain),
            "treatment_domain": tuple(tv.domain),
            "ntrees_actual": len(trees),
        }
        model = UpliftDRFModel(DKV.make_key("upliftdrf"), p, out)
        model.training_metrics = model._score_metrics(train)
        if valid is not None:
            model.validation_metrics = model._score_metrics(valid)
        return model
