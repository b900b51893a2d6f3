"""Plain reference for ``drf_higgs``: H2O's random forest on static quantile
bins, in numpy float64 on the host. It imports nothing of the program (the
quantile edges and the codes are ``gbm_higgs_ref``'s, the same plain rule).

Like GBM's, the reference does not grow a forest of its own: it FOLLOWS the
model the timed window produced. Rows are routed by the model's own splits
(column, threshold value); the model also says which rows each tree had in
its bag and which columns each node was offered, and both are CHECKED before
they are used. Everything else is computed from the data, in float64, on the
reference's own bin edges:

- ``edges_gap``: the model's bin edges against the configuration's rule, as
  ``gbm_higgs``;
- ``offer_gap``: the share of decided nodes whose offered set is not exactly
  ``mtries`` columns, or does not hold the column the node split on;
- ``bag_gap``: how far a tree's in-bag share of the rows lies from
  ``sample_rate``, in binomial standard deviations at the frame's rows
  (sqrt(rate (1 - rate) / rows): 1.97e-4 at 6,000,000), so that one limit
  holds at every size; inf if a pad row is in a bag or the mask is shorter
  than the frame;
- ``leaf_gap``: every leaf's value against the mean response of the IN-BAG
  rows the model routes there;
- ``gain_gap``: how far the gain of a node's chosen split (from the in-bag
  rows it sends left and right) lies under the best gain over the node's
  OFFERED columns, every edge of the reference's own and both NA directions,
  against the first tree's root gain over ALL columns (a root that is offered
  five noise columns has a gain near zero, and a scale that swings with the
  draw is no scale). All nodes of the first ``gain_levels`` levels and a
  seeded sample of ``deep_nodes`` nodes from level ``deep_from`` on, of the
  first tree. A node the frontier cap retired (``node_cap``: at most
  ``node_cap / 2`` nodes split a level, later ones go leaf in node order;
  stated under ``assumed``) is not held to have found no split;
- ``logloss_gap``: the training logloss the model reports against the
  reference's own: each row's probability is the mean over the trees of the
  leaf means it lands in, clipped to [0, 1] and, inside the logarithm, to
  [1e-15, 1 - 1e-15], as the program states. Over ALL rows, in-bag and out:
  H2O reports out-of-bag training metrics and this program does not — a
  departure that is listed (``assumed``), not repaired here.

``control=True`` also reads the numbers for the reference one precision
below the configuration's float32: sample values and edges, leaf means and
the histogram's sums rounded to bfloat16 (the response and the bag are 0/1,
which no rounding moves); for the gain, the split the rounded histogram puts
first.
"""

from __future__ import annotations

import numpy as np

from .gbm_higgs_ref import _best_split, _fit, _rel, bin_codes, quantile_edges
from .rounding import bf16

CHECKS = ("edges_gap", "offer_gap", "bag_gap", "leaf_gap", "gain_gap", "logloss_gap")
P_CLIP = 1e-15


def mtries_of(cfg: dict) -> int:
    """The columns a node is offered: H2O's -1 is sqrt(C) for a classifier."""
    m, C = int(cfg["estimator"]["mtries"]), int(cfg["cols"])
    return max(1, int(np.sqrt(C))) if m in (-1, 0) else (C if m == -2 else m)


def _hist(codes, nid, yv, N, nbins):
    """(N, C, nbins+1) counts and response sums of rows (all in-bag) by node,
    column and code; ``nid`` in 0..N-1."""
    C = codes.shape[0]
    m = N * (nbins + 1)
    base = nid * (nbins + 1)
    hc = np.empty((N, C, nbins + 1))
    hs = np.empty_like(hc)
    for c in range(C):
        idx = base + codes[c]
        hc[:, c] = np.bincount(idx, minlength=m).reshape(N, -1)
        hs[:, c] = np.bincount(idx, weights=yv, minlength=m).reshape(N, -1)
    return hc, hs


def thresholds(edges32: np.ndarray) -> np.ndarray:
    """(C, nbins + 1) float32: what the model's splits mean. "code <=
    split_bin" is "x <= edge split_bin - 1" (none below bin 1, all from bin
    nbins on)."""
    inf = np.full((edges32.shape[0], 1), np.inf, np.float32)
    return np.concatenate([-inf, edges32, inf], axis=1)


def route(X, thr, lv: dict, nid, n_next: int) -> tuple:
    """One level down by the model's own splits: (left, the next node ids).
    ``nid`` holds a node of the level or, for a retired row, its width N; a
    row whose node went leaf retires to ``n_next``."""
    ext = lambda name, fill: np.append(np.asarray(lv[name]), fill)
    col = ext("split_col", 0)[nid]
    x = X[np.arange(len(nid)), col]
    left = np.where(
        np.isnan(x), ext("na_left", True)[nid].astype(bool),
        x <= thr[col, np.clip(ext("split_bin", 0), 0, thr.shape[1] - 1)[nid]])
    nxt = ext("child_base", 0)[nid] + np.where(left, 0, 1)
    return left, np.where(ext("leaf_now", True).astype(bool)[nid], n_next, nxt)


def follow(X: np.ndarray, y: np.ndarray, model: dict, cfg: dict, control=False) -> dict:
    """Follow ``model`` (see ``drf_higgs.outputs``) over the data. Returns
    ``{"program": {name: value}, "control": {...}}`` (control only if asked)."""
    est, ref = cfg["estimator"], cfg["reference"]
    n, C = X.shape
    nbins, depth = int(est["nbins"]), int(est["max_depth"])
    min_rows, msi = float(est["min_rows"]), float(ref["min_split_improvement"])
    rate, k = float(est["sample_rate"]), mtries_of(cfg)
    half_cap = int(ref["node_cap"]) // 2
    out = {"program": {}, "control": {}}

    # ---- binning: the model's edges against the configuration's rule ----
    want = quantile_edges(X, nbins, int(ref["bin_sample"]))
    edges32 = np.asarray(model["edges"], np.float32)
    bags = [np.asarray(b, bool) for b in model["inbag"]]
    if edges32.shape != want.shape or len(bags) != len(model["trees"]):
        out["program"] = dict.fromkeys(CHECKS, float("inf"))
        return out
    floor = np.median(np.abs(want))
    out["program"]["edges_gap"] = float(_rel(edges32.astype(np.float64), want, floor).max())
    if control:
        out["control"]["edges_gap"] = float(_rel(
            quantile_edges(X, nbins, int(ref["bin_sample"]), rounded=True),
            want, floor).max())
    # the reference bins with its OWN edges; of the model's it takes only what
    # the model's splits mean (``thresholds``)
    codes = bin_codes(X, want)
    thr = thresholds(edges32)
    rng = np.random.default_rng(int(ref["sample_seed"]))

    bag_gap = 0.0
    decided = offer_bad = 0
    leaf_prog, leaf_ref = [], []
    gain_gap = gain_gap_ctl = 0.0
    gain_nodes = 0
    worst_node = None
    root_gain = None
    P = np.zeros(n)  # sum over the trees of the leaf mean a row lands in
    Pc = np.zeros(n) if control else None
    for ti, levels in enumerate(model["trees"]):
        bag = bags[ti]
        if bag.size < n or bag[n:].any():
            bag_gap = float("inf")  # a pad row in the bag, or no mask of the frame
            bag = np.resize(bag, n)
        bag = bag[:n]
        bag_gap = max(bag_gap, abs(float(bag.mean()) - rate)
                      / np.sqrt(rate * (1 - rate) / n))
        w = bag.astype(np.float64)
        g = y * w
        in_rows = np.flatnonzero(bag)
        nid = np.zeros(n, np.int64)  # a retired row sits in slot N
        # the deep sample: deep_nodes spread evenly over the levels from deep_from
        deep = range(int(ref["deep_from"]), min(depth, len(levels)))
        each, more = divmod(int(ref["deep_nodes"]), max(len(deep), 1))
        quota = {li: each + (i < more) for i, li in enumerate(deep)}
        for li, lv in enumerate(levels):
            N = len(lv["leaf_now"])
            cnt = np.bincount(nid, weights=w, minlength=N + 1)[:N]
            wy = np.bincount(nid, weights=g, minlength=N + 1)[:N]
            real = cnt > 0
            leaf = np.asarray(lv["leaf_now"], bool)
            val = np.where(real, wy / np.maximum(cnt, 1e-300), 0.0)
            sel = real & leaf
            leaf_prog.append(np.asarray(lv["leaf_val"], np.float64)[sel])
            leaf_ref.append(val[sel])
            P += np.append(np.where(leaf, val, 0.0), 0.0)[nid]
            if control:
                Pc += np.append(np.where(leaf, bf16(val), 0.0), 0.0)[nid]

            # ---- what the node was offered ----
            offer = np.asarray(lv["col_offer"], bool)
            split = real & ~leaf
            if offer.shape != (N, C):
                decided, offer_bad = decided + int(split.sum()), offer_bad + int(split.sum())
                offer = np.ones((N, C), bool)
            else:
                scol = np.clip(np.asarray(lv["split_col"]), 0, C - 1)
                bad = (offer.sum(axis=1) != k) | ~offer[np.arange(N), scol]
                decided += int(split.sum())
                offer_bad += int((bad & split).sum())

            # ---- every row goes where the model's own split sends it ----
            n_next = len(levels[li + 1]["leaf_now"]) if li + 1 < len(levels) else 0
            left, nid_next = route(X, thr, lv, nid, n_next)

            # ---- the gain of the chosen split, first tree only ----
            nodes = None
            if ti == 0 and li < depth:
                # the frontier cap's rule: when half_cap nodes split, every
                # later node went leaf whatever it could have gained
                capped = np.zeros(N, bool)
                if int(split.sum()) >= half_cap:
                    capped[np.flatnonzero(split)[-1] + 1:] = True
                cand = np.flatnonzero(real & ~(leaf & capped))
                if li < int(ref["gain_levels"]):
                    nodes = cand
                elif li in quota and cand.size:
                    nodes = np.sort(rng.choice(cand, min(quota[li], cand.size), replace=False))
            if nodes is not None and nodes.size:
                S = nodes.size
                slot = np.full(N + 1, -1, np.int64)
                slot[nodes] = np.arange(S)
                r = in_rows[slot[nid[in_rows]] >= 0]
                sid = slot[nid[r]]
                hc, hs = _hist(codes[:, r], sid, y[r], S, nbins)
                gains = _best_split(hc, hs, min_rows)  # (S, C, nbins-1, 2)
                if root_gain is None:  # level 0: over ALL columns
                    root_gain = max(float(gains[0].max()), 1e-300)
                offered = np.where(offer[nodes][:, :, None, None], gains, -np.inf)
                flat = offered.reshape(S, -1)
                best = flat.max(axis=1)
                best = np.where(np.isfinite(best), best, 0.0)  # no valid split
                lw = np.bincount(sid, weights=left[r], minlength=S)
                ls = np.bincount(sid, weights=y[r] * left[r], minlength=S)
                cn, sy = cnt[nodes], wy[nodes]
                chosen = np.where(
                    (lw >= min_rows) & (cn - lw >= min_rows),
                    _fit(lw, ls) + _fit(cn - lw, sy - ls) - _fit(cn, sy), -np.inf)
                # a leaf chose "no split": sound only where no valid offered
                # candidate clears min_split_improvement; a split the
                # reference finds invalid is a whole gap
                scale = np.maximum(best, 1e-3 * root_gain)
                deficit = np.where(
                    leaf[nodes], np.where(best > msi, best, 0.0),
                    np.where(np.isfinite(chosen),
                             np.maximum(best - np.maximum(chosen, 0.0), 0.0), scale))
                gain_nodes += S
                j = int(deficit.argmax())
                if deficit[j] / root_gain >= gain_gap:
                    gain_gap = float(deficit[j]) / root_gain
                    worst_node = {
                        "level": li, "node": int(nodes[j]), "rows": int(cn[j]),
                        "leaf": bool(leaf[nodes[j]]),
                        "best_gain_over_root": float(best[j] / root_gain),
                        "chosen_over_best": float(chosen[j] / best[j]) if best[j] else None,
                        "chosen_at": [int(lv["split_col"][nodes[j]]),
                                      int(lv["split_bin"][nodes[j]])]}
                if control:
                    gb = np.where(offer[nodes][:, :, None, None],
                                  _best_split(bf16(hc), bf16(hs), min_rows), -np.inf)
                    first = flat[np.arange(S), gb.reshape(S, -1).argmax(axis=1)]
                    ok = ~leaf[nodes] & np.isfinite(first)
                    if ok.any():
                        gain_gap_ctl = max(gain_gap_ctl, float(
                            (best - first)[ok].max()) / root_gain)

            nid = nid_next

    lp, lr_ = np.concatenate(leaf_prog), np.concatenate(leaf_ref)
    floor = np.median(np.abs(lr_))
    out["program"]["offer_gap"] = offer_bad / max(decided, 1)
    out["program"]["bag_gap"] = bag_gap
    out["program"]["leaf_gap"] = float(_rel(lp, lr_, floor).max())
    out["program"]["gain_gap"] = gain_gap
    pure = (lr_ == 0.0) | (lr_ == 1.0)  # leaves of one class: 0 or 1 exactly
    out["diagnostic"] = {"worst_node": worst_node, "gain_nodes": gain_nodes,
                         "decided_nodes": decided, "root_gain": root_gain,
                         "pure_leaves": int(pure.sum()),
                         "pure_leaves_inexact": int((lp[pure] != lr_[pure]).sum())}

    def logloss(Psum):
        p = np.clip(np.clip(Psum / len(model["trees"]), 0.0, 1.0), P_CLIP, 1 - P_CLIP)
        return float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))

    ll = logloss(P)
    out["program"]["logloss_gap"] = abs(float(model["logloss"]) - ll) / ll
    out["reference"] = {"logloss": ll, "leaves": int(lp.size),
                        "trees": len(model["trees"]),
                        "inbag_share": [float(b[:n].mean()) for b in bags]}
    if control:
        out["control"]["leaf_gap"] = float(_rel(bf16(lr_), lr_, floor).max())
        out["control"]["gain_gap"] = gain_gap_ctl
        out["control"]["logloss_gap"] = abs(logloss(Pc) - ll) / ll
    return out
