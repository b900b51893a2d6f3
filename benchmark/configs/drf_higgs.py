"""Configuration ``drf_higgs``: H2ORandomForestEstimator at its published
defaults (``max_depth`` 20, ``mtries`` -1, ``sample_rate`` 0.632,
``min_rows`` 1) on the Higgs-shaped frame. Sizes and arguments are in
``drf_higgs.json``; the plain reference is ``drf_higgs_ref.py``."""

from __future__ import annotations

import numpy as np

from . import drf_higgs_ref as ref
from .higgs_data import frame_for, release, train  # noqa: F401

LEVEL_FIELDS = ("split_col", "split_bin", "na_left", "leaf_now", "leaf_val",
                "child_base")


def make_frame(cfg: dict, seed: int):
    """The frame, after a look at the program: a forest can be followed only
    where the fitted model says which rows each tree had in its bag and which
    columns each node was offered. A program without that cannot run this
    configuration, and says so at once instead of after the window."""
    from h2o3_tpu.models.tree.drf import DRFModel

    missing = [m for m in ("inbag_rows", "offered_columns") if not hasattr(DRFModel, m)]
    if missing:
        raise SystemExit(
            f"benchmark: drf_higgs needs DRFModel.{' and DRFModel.'.join(missing)} "
            "(the bags and the offered columns of a fitted forest); this program "
            "has none, so the configuration cannot be run or checked on it")
    return frame_for(cfg, seed)


def build_estimator(cfg: dict):
    from h2o3_tpu.estimators import H2ORandomForestEstimator

    return H2ORandomForestEstimator(**cfg["estimator"])


def passes(cfg: dict, est) -> int:
    """A pass is a tree: the configuration's ``ntrees``, which the fitted
    model has to hold (a call that built another number has failed)."""
    built, want = len(est.model.output["trees"]), int(cfg["estimator"]["ntrees"])
    if built != want:
        raise RuntimeError(f"the model holds {built} trees, the configuration asks for {want}")
    return want


def needed_work(cfg: dict, n_passes: int) -> dict:
    """What any implementation must do for ``n_passes`` trees: each of a
    tree's max_depth levels reads, for every IN-BAG row (``sample_rate`` of
    the rows), C one-byte codes, a four-byte node id and three float32
    statistic lanes, and adds three statistics per in-bag row and column."""
    est = cfg["estimator"]
    n, C = est["sample_rate"] * cfg["rows"], cfg["cols"]
    levels = n_passes * est["max_depth"]
    work = {"flops": 3.0 * n * C * levels, "bytes": float(n) * (C + 4 + 3 * 4) * levels}
    return {**work, "hist_kernel": dict(work)}


def outputs(est) -> dict:
    """What ``correct`` takes from a fitted model: its bin edges, its trees
    (one output class) with the columns each node was offered, each tree's
    in-bag rows (over the frame's padded length) and the training logloss it
    reports."""
    m = est.model
    out = m.output
    trees = []
    for ti, group in enumerate(out["trees"]):
        offered = m.offered_columns(ti)
        trees.append([{**{k: np.asarray(getattr(lv, k)) for k in LEVEL_FIELDS},
                       "col_offer": offered[li]}
                      for li, lv in enumerate(group[0].levels)])
    return {"edges": np.asarray(out["bin_spec"].edges), "trees": trees,
            "inbag": [m.inbag_rows(ti) for ti in range(len(trees))],
            "logloss": float(est.logloss())}


def compare(cfg: dict, X, y, model: dict, control: bool = False) -> dict:
    return ref.follow(X, y, model, cfg, control=control)
