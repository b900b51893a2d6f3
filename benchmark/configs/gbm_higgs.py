"""Configuration ``gbm_higgs``: H2OGradientBoostingEstimator on the
Higgs-shaped frame. Sizes and arguments are in ``gbm_higgs.json``; the plain
reference is ``gbm_higgs_ref.py``."""

from __future__ import annotations

import numpy as np

from . import gbm_higgs_ref as ref
from .higgs_data import frame_for as make_frame  # noqa: F401
from .higgs_data import release, train  # noqa: F401

LEVEL_FIELDS = ("split_col", "split_bin", "na_left", "leaf_now", "leaf_val",
                "child_base")


def build_estimator(cfg: dict):
    from h2o3_tpu.estimators import H2OGradientBoostingEstimator

    return H2OGradientBoostingEstimator(**cfg["estimator"])


def passes(cfg: dict, est) -> int:
    """A pass is a tree: the configuration's ``ntrees``, which the fitted
    model has to hold (a call that built another number has failed)."""
    built, want = len(est.model.output["trees"]), int(cfg["estimator"]["ntrees"])
    if built != want:
        raise RuntimeError(f"the model holds {built} trees, the configuration asks for {want}")
    return want


def needed_work(cfg: dict, n_passes: int) -> dict:
    """What any implementation must do for ``n_passes`` trees: each of a
    tree's max_depth levels reads, for every row, C one-byte codes, a
    four-byte node id and three float32 statistic lanes, and adds three
    statistics per row and column."""
    n, C, d = cfg["rows"], cfg["cols"], cfg["estimator"]["max_depth"]
    levels = n_passes * d
    return {"flops": 3.0 * n * C * levels,
            "bytes": float(n) * (C + 4 + 3 * 4) * levels,
            "hist_kernel": {"flops": 3.0 * n * C * levels,
                            "bytes": float(n) * (C + 4 + 3 * 4) * levels}}


def outputs(est) -> dict:
    """What ``correct`` takes from a fitted model: its bin edges, its trees
    (one output class) and the training logloss it reports."""
    out = est.model.output
    trees = [[{k: np.asarray(getattr(lv, k)) for k in LEVEL_FIELDS}
              for lv in group[0].levels] for group in out["trees"]]
    return {"edges": np.asarray(out["bin_spec"].edges), "trees": trees,
            "init_f": float(out["init_f"]), "logloss": float(est.logloss())}


def compare(cfg: dict, X, y, model: dict, control: bool = False) -> dict:
    return ref.follow(X, y, model, cfg, control=control)

