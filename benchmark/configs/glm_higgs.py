"""Configuration ``glm_higgs``: H2OGeneralizedLinearEstimator on the
Higgs-shaped frame. Sizes and arguments are in ``glm_higgs.json``; the plain
reference is ``glm_higgs_ref.py``."""

from __future__ import annotations

import numpy as np

from . import glm_higgs_ref as ref
from .higgs_data import frame_for as make_frame  # noqa: F401
from .higgs_data import release, train  # noqa: F401


def build_estimator(cfg: dict):
    from h2o3_tpu.estimators import H2OGeneralizedLinearEstimator

    return H2OGeneralizedLinearEstimator(**cfg["estimator"])


def passes(cfg: dict, est) -> int:
    """A pass is one whole fit. How many IRLS iterations a fit takes is the
    program's own choice, so they are not the yardstick's unit of work: a
    fit that converges in fewer is faster, not smaller (the per-layer
    ``glm_irls_iterations_per_fit`` counts them)."""
    if not est.model.output["regularization_path"]:
        raise RuntimeError("the fit holds no solution")
    return 1


def needed_work(cfg: dict, n_passes: int) -> dict:
    """What ``n_passes`` fits need: the configuration's ``irls_iterations``
    (fixed there, whatever the program takes) times an iteration's work:
    read the (n, p) float32 design once and three row lanes (response,
    weight, linear predictor), and 2 n p^2 FLOPs for the Gram."""
    n, p = cfg["rows"], cfg["cols"] + 1
    iters = n_passes * int(cfg["reference"]["irls_iterations"])
    return {"flops": 2.0 * n * p * p * iters,
            "bytes": (float(n) * p * 4 + 3.0 * n * 4) * iters}


def outputs(est) -> dict:
    """What ``correct`` takes from a fitted model: its coefficients on the
    original scale (f0..f27, intercept last) and its training logloss."""
    out = est.model.output
    names = list(out["coef_names"])
    beta = np.asarray(out["beta_orig"], np.float64)
    order = [names.index(f"f{i}") for i in range(len(names) - 1)]
    order.append(next(i for i, nm in enumerate(names) if i not in order))
    return {"coef": beta[order], "logloss": float(est.logloss())}


def compare(cfg: dict, X, y, model: dict, control: bool = False) -> dict:
    return ref.compare(X, y, model, cfg, control=control)

