"""The faults of the forest cell, beside ``faults.py`` (which is not edited):
each takes what ``drf_higgs.outputs`` gave for a sound model, and the data,
and returns what a program with that fault would have given.

    PLANTED["drf_higgs"][name](model, X, y, cfg) -> model
"""

from __future__ import annotations

import copy

import numpy as np


def bernoulli_offer(model: dict, X, y, cfg) -> dict:
    """The parent's column draw: each column offered with probability
    mtries / C (a node that split always held its own column), so a node
    sees anything from 1 to C columns and rarely exactly ``mtries``."""
    from benchmark.configs.drf_higgs_ref import mtries_of

    out = copy.deepcopy(model)
    rng = np.random.default_rng(5)
    C = int(cfg["cols"])
    for levels in out["trees"]:
        for lv in levels:
            N = len(lv["leaf_now"])
            keep = rng.random((N, C)) < mtries_of(cfg) / C
            keep[np.arange(N), np.clip(lv["split_col"], 0, C - 1)] = True
            lv["col_offer"] = keep & lv["col_offer"].any(axis=1, keepdims=True)
    return out


def bag_ignored(model: dict, X, y, cfg) -> dict:
    """A mask the leaves ignore: every leaf holds the mean response of ALL
    the rows that reach it, in the bag or not, and the reported logloss is
    that forest's, while the model still reports the bag it drew. Rows are
    routed by the model's own splits (the reference's ``route``)."""
    from benchmark.configs.drf_higgs_ref import route, thresholds

    out = copy.deepcopy(model)
    thr = thresholds(np.asarray(model["edges"], np.float32))
    P = np.zeros(len(y))
    for levels in out["trees"]:
        nid = np.zeros(len(y), np.int64)
        for li, lv in enumerate(levels):
            N = len(lv["leaf_now"])
            cnt = np.bincount(nid, minlength=N + 1)[:N]
            sy = np.bincount(nid, weights=y, minlength=N + 1)[:N]
            leaf = np.asarray(lv["leaf_now"], bool)
            lv["leaf_val"] = np.where(
                leaf & (cnt > 0), sy / np.maximum(cnt, 1), lv["leaf_val"]).astype(np.float32)
            P += np.append(np.where(leaf, lv["leaf_val"], 0.0), 0.0)[nid]
            n_next = len(levels[li + 1]["leaf_now"]) if li + 1 < len(levels) else 0
            _, nid = route(X, thr, lv, nid, n_next)
    p = np.clip(P / len(out["trees"]), 1e-15, 1 - 1e-15)
    out["logloss"] = float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))
    return out


def altered(model: dict, X, y, cfg) -> dict:
    """An answer altered where it is produced: one leaf of the first tree is
    10% off."""
    out = copy.deepcopy(model)
    for lv in reversed(out["trees"][0]):
        hit = np.flatnonzero(lv["leaf_now"] & (lv["leaf_val"] != 0))
        if hit.size:
            lv["leaf_val"] = lv["leaf_val"].copy()
            lv["leaf_val"][hit[0]] *= 1.10
            return out
    raise AssertionError("no leaf to alter")


PLANTED = {"drf_higgs": {"bernoulli_offer": bernoulli_offer,
                         "bag_ignored": bag_ignored, "altered": altered}}
