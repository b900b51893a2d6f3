"""Plain reference for ``gbm_higgs``: H2O's bernoulli GBM on static quantile
bins, in numpy float64 on the host. It imports nothing of the program.

The reference does not grow trees of its own: two float32 sums that differ
in the last bit flip a split, and from there two sound builders grow
different trees. It FOLLOWS the model that the timed window produced, as a
served model's reference follows the served tokens: rows are routed by the
model's own splits (column, threshold value), and at every node the
reference computes from the data, in float64, on its OWN bin edges (the
model's edges are compared, and read only for what its splits mean),

- ``edges_gap``: the model's bin edges against the configuration's rule
  (quantile bins by linear interpolation over an evenly strided sample);
- ``gain_gap``: how far the gain of the model's chosen split (from the rows
  it sends left and right) lies under the best gain over every column, edge
  of the reference's own and NA direction, against the tree's root gain
  (first trees only). Against the node's own best gain the number
  swings: a deep node's gain is a small difference of large float32 terms
  (``diagnostic.gain_node``; PERF.md has both readings);
- ``leaf_gap``: every leaf value against learn_rate * sum(y - p) /
  sum(p (1 - p)) over the rows the model routes there, p from the
  reference's own running prediction;
- ``logloss_gap``: the model's reported training logloss against the logloss
  of the reference's running prediction after the last tree.

``control=True`` also reads the same numbers for the reference computed one
precision below the configuration's float32 statistics: gradients, hessians
and sample values rounded to bfloat16 before they are summed; for the gain,
the split that the rounded histogram puts first.
"""

from __future__ import annotations

import numpy as np

from .rounding import bf16

NA_BIN = 0  # code 0 is the NA bin; data bins are 1..nbins


def quantile_edges(X: np.ndarray, nbins: int, sample: int, rounded=False) -> np.ndarray:
    """(C, nbins-1) right-inclusive edges: the q-quantiles, q = k/nbins, of an
    evenly strided sample of the rows, by linear interpolation."""
    n = X.shape[0]
    ns = min(n, sample)
    idx = np.round(np.linspace(0, n - 1, ns)).astype(np.int64)
    S = X[idx]
    xs = np.sort(bf16(S) if rounded else S.astype(np.float64), axis=0)
    pos = np.linspace(0.0, 1.0, nbins + 1)[1:-1] * (ns - 1)
    lo = np.floor(pos).astype(np.int64)
    frac = (pos - lo)[:, None]
    hi = np.minimum(lo + 1, ns - 1)
    e = xs[lo] * (1 - frac) + xs[hi] * frac
    return (bf16(e) if rounded else e).T


def bin_codes(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """(C, n) uint8 codes: 1 + the number of edges below x (x <= edge k
    falls in bin k + 1); NaN takes the NA bin."""
    from concurrent.futures import ThreadPoolExecutor

    codes = np.empty(X.shape[::-1], np.uint8)

    def one(c):  # searchsorted releases the interpreter lock
        x = np.ascontiguousarray(X[:, c])
        codes[c] = np.where(
            np.isnan(x), NA_BIN, 1 + np.searchsorted(edges[c], x, side="left"))

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(one, range(X.shape[1])))
    return codes


def _rel(a, ref, floor):
    return np.abs(a - ref) / np.maximum(np.abs(ref), floor)


def _fit(w, s):
    """A side's squared-error term, s^2 / w (0 for an empty side)."""
    return np.where(w > 0, s * s / np.maximum(w, 1e-300), 0.0)


def _best_split(cnt, wy, min_rows):
    """Gains of every candidate of one level from per-(node, col, bin) counts
    and residual sums: (N, C, nbins-1, 2) with the NA bin sent left / right.
    DHistogram's squared-error gain with the wy2 terms cancelled:
    wy_L^2/w_L + wy_R^2/w_R - wy_P^2/w_P; a side under min_rows is invalid."""
    fit = _fit
    na_w, na_s = cnt[:, :, :1], wy[:, :, :1]
    cw, cs = np.cumsum(cnt[:, :, 1:], axis=2), np.cumsum(wy[:, :, 1:], axis=2)
    lw, ls = cw[:, :, :-1], cs[:, :, :-1]  # split after data bin t+1
    rw, rs = cw[:, :, -1:] - lw, cs[:, :, -1:] - ls
    parent = fit(cw[:, :, -1:] + na_w, cs[:, :, -1:] + na_s)
    out = np.empty(lw.shape + (2,))
    for d, (aw, as_, bw, bs) in enumerate((
            (lw + na_w, ls + na_s, rw, rs), (lw, ls, rw + na_w, rs + na_s))):
        g = fit(aw, as_) + fit(bw, bs) - parent
        out[..., d] = np.where((aw >= min_rows) & (bw >= min_rows), g, -np.inf)
    return out


def follow(X: np.ndarray, y: np.ndarray, model: dict, cfg: dict, control=False) -> dict:
    """Follow ``model`` (see ``gbm_higgs.outputs``) over the data. Returns
    ``{"program": {name: value}, "control": {...}}`` (control only if asked)."""
    est, ref = cfg["estimator"], cfg["reference"]
    n, C = X.shape
    nbins, lr = int(est["nbins"]), float(est["learn_rate"])
    min_rows, msi = float(est["min_rows"]), float(ref["min_split_improvement"])
    depth = int(est["max_depth"])
    out = {"program": {}, "control": {}}

    # ---- binning: the model's edges against the configuration's rule ----
    want = quantile_edges(X, nbins, int(ref["bin_sample"]))
    edges32 = np.asarray(model["edges"], np.float32)
    if edges32.shape != want.shape:
        out["program"] = dict.fromkeys(
            ("edges_gap", "leaf_gap", "gain_gap", "logloss_gap"), float("inf"))
        return out
    floor = np.median(np.abs(want))
    out["program"]["edges_gap"] = float(_rel(edges32.astype(np.float64), want, floor).max())
    if control:
        out["control"]["edges_gap"] = float(_rel(
            quantile_edges(X, nbins, int(ref["bin_sample"]), rounded=True),
            want, floor).max())
    # The reference bins with its OWN edges: every candidate split it weighs
    # comes from its own table. Of the model's edges it takes only what the
    # model's splits mean: "code <= split_bin" is "x <= the model's edge
    # split_bin - 1" (none below bin 1, all from bin nbins on).
    codes = bin_codes(X, want)
    inf = np.full((C, 1), np.inf, np.float32)
    thr = np.concatenate([-inf, edges32, inf], axis=1)  # (C, nbins + 1)
    rows = np.arange(n)

    fit = _fit

    # ---- the trees ----
    ybar = y.mean()
    f0 = np.log(ybar / (1 - ybar))
    F = np.full(n, f0)
    Fc = F.copy() if control else None
    leaf_prog, leaf_ref, leaf_ctl = [], [], []
    gain_gap, gain_gap_ctl = 0.0, 0.0  # deficits against the tree's root gain
    gain_node, gain_node_ctl = 0.0, 0.0  # against the node's own best gain
    worst_node = None  # the node behind gain_node, for the look by hand
    for ti, levels in enumerate(model["trees"]):
        p = 1.0 / (1.0 + np.exp(-F))
        g, h = y - p, p * (1 - p)
        gb, hb = (bf16(g), bf16(h)) if control else (None, None)
        check_gain = ti < int(ref["gain_trees"])
        nid = np.zeros(n, np.int64)  # a retired row sits in slot N
        root_gain = None
        for li, lv in enumerate(levels):
            N = len(lv["leaf_now"])
            cnt = np.bincount(nid, minlength=N + 1)[:N].astype(np.float64)
            wy = np.bincount(nid, weights=g, minlength=N + 1)[:N]
            wh = np.bincount(nid, weights=h, minlength=N + 1)[:N]
            real = cnt > 0
            leaf = np.asarray(lv["leaf_now"], bool)
            val = np.where(wh > 0, lr * wy / np.maximum(wh, 1e-300), 0.0)
            sel = real & leaf
            leaf_prog.append(np.asarray(lv["leaf_val"], np.float64)[sel])
            leaf_ref.append(val[sel])
            if control:
                wyb = np.bincount(nid, weights=gb, minlength=N + 1)[:N]
                whb = np.bincount(nid, weights=hb, minlength=N + 1)[:N]
                valb = np.where(whb > 0, lr * wyb / np.maximum(whb, 1e-300), 0.0)
                leaf_ctl.append(valb[sel])
                Fc += np.append(np.where(leaf, valb, 0.0), 0.0)[nid]
            F += np.append(np.where(leaf, val, 0.0), 0.0)[nid]

            # ---- every row goes where the model's own split sends it ----
            ext = lambda k, fill: np.append(np.asarray(lv[k]), fill)
            col = ext("split_col", 0)[nid]
            x = X[rows, col]
            left = np.where(
                np.isnan(x), ext("na_left", True)[nid].astype(bool),
                x <= thr[col, np.clip(ext("split_bin", 0), 0, nbins)[nid]])

            if check_gain and li < depth:
                m = (N + 1) * (nbins + 1)
                base = nid * (nbins + 1)
                hc = np.empty((N, C, nbins + 1))
                hs = np.empty_like(hc)
                hsb = np.empty_like(hc) if control else None
                take = lambda v: v.reshape(N + 1, -1)[:N]
                for c in range(C):
                    idx = base + codes[c]
                    hc[:, c] = take(np.bincount(idx, minlength=m))
                    hs[:, c] = take(np.bincount(idx, weights=g, minlength=m))
                    if control:
                        hsb[:, c] = take(np.bincount(idx, weights=gb, minlength=m))
                gains = _best_split(hc, hs, min_rows)  # (N, C, nbins-1, 2)
                flat = gains.reshape(N, -1)
                best = flat.max(axis=1)
                best = np.where(np.isfinite(best), best, 0.0)  # no valid split
                if root_gain is None:
                    root_gain = max(float(best[0]), 1e-300)
                scale = np.maximum(best, 1e-3 * root_gain)
                # the gain of the model's own split, from the rows it sends
                # left and right (no table of the model's enters it)
                lw = np.bincount(nid, weights=left, minlength=N + 1)[:N]
                ls = np.bincount(nid, weights=g * left, minlength=N + 1)[:N]
                chosen = np.where(
                    (lw >= min_rows) & (cnt - lw >= min_rows),
                    fit(lw, ls) + fit(cnt - lw, wy - ls) - fit(cnt, wy), -np.inf)
                # a node the model retired early chose "no split": sound only
                # where no valid candidate clears min_split_improvement; a
                # split the reference finds invalid is a whole gap
                deficit = np.where(
                    leaf, np.where(best > msi, best, 0.0),
                    np.where(np.isfinite(chosen),
                             np.maximum(best - np.maximum(chosen, 0.0), 0.0), scale))
                node_rel = np.where(real, deficit / scale, 0.0)
                k = int(node_rel.argmax())
                if node_rel[k] >= gain_node:
                    gain_node = float(node_rel[k])
                    bc, bt, bd = np.unravel_index(int(flat[k].argmax()), gains.shape[1:])
                    worst_node = {
                        "tree": ti, "level": li, "node": k, "rows": int(cnt[k]),
                        "best_gain_over_root": float(best[k] / root_gain),
                        "chosen_over_best": float(chosen[k] / best[k]) if best[k] else None,
                        "parent_term_over_best": float(fit(cnt, wy)[k] / best[k]) if best[k] else None,
                        "candidates_above_chosen": int((flat[k] > chosen[k]).sum()),
                        "best_at": [int(bc), int(bt) + 1, int(bd)],
                        "chosen_at": [int(lv["split_col"][k]), int(lv["split_bin"][k])]}
                gain_gap = max(gain_gap, float(deficit[real].max()) / root_gain)
                if control:
                    gb_all = _best_split(hc, hsb, min_rows).reshape(N, -1)
                    first = flat[np.arange(N), gb_all.argmax(axis=1)]
                    ok = real & ~leaf & np.isfinite(first)
                    if ok.any():
                        gain_node_ctl = max(gain_node_ctl, float(
                            ((best - first) / scale)[ok].max()))
                        gain_gap_ctl = max(gain_gap_ctl, float(
                            (best - first)[ok].max()) / root_gain)

            nxt = ext("child_base", 0)[nid] + np.where(left, 0, 1)
            n_next = len(levels[li + 1]["leaf_now"]) if li + 1 < len(levels) else 0
            nid = np.where(np.append(leaf, True)[nid], n_next, nxt)

    lp, lr_ = np.concatenate(leaf_prog), np.concatenate(leaf_ref)
    floor = np.median(np.abs(lr_))
    out["program"]["leaf_gap"] = float(_rel(lp, lr_, floor).max())
    out["program"]["gain_gap"] = gain_gap
    out["diagnostic"] = {"gain_node": gain_node, "gain_node_control": gain_node_ctl,
                         "worst_node": worst_node}

    def logloss(Fx):
        # -mean(y log p + (1-y) log(1-p)) = mean(log(1+e^F) - y F)
        return float(np.mean(np.logaddexp(0.0, Fx) - y * Fx))

    ll = logloss(F)
    out["program"]["logloss_gap"] = abs(float(model["logloss"]) - ll) / ll
    out["reference"] = {"logloss": ll, "init_f": float(f0),
                        "leaves": int(lp.size), "trees": len(model["trees"])}
    if control:
        out["control"]["leaf_gap"] = float(
            _rel(np.concatenate(leaf_ctl), lr_, floor).max())
        out["control"]["gain_gap"] = gain_gap_ctl
        out["control"]["logloss_gap"] = abs(logloss(Fc) - ll) / ll
    return out
