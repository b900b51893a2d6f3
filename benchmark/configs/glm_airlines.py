"""Configuration ``glm_airlines``: H2OGeneralizedLinearEstimator, binomial,
on the airline-shaped frame: six categorical predictors that expand to 776
design columns, two numerics. Sizes and arguments are in
``glm_airlines.json``; the data is ``airlines_data.py``'s; the plain
reference is ``glm_airlines_ref.py``."""

from __future__ import annotations

import json
import os

import numpy as np

from . import glm_airlines_ref as ref
from .airlines_data import CATS, NUMS, domain, release, train  # noqa: F401
from .airlines_data import make_frame as _make_frame


def make_frame(cfg: dict, seed: int):
    return _make_frame(cfg, seed)


def build_estimator(cfg: dict):
    from h2o3_tpu.estimators import H2OGeneralizedLinearEstimator

    return H2OGeneralizedLinearEstimator(**cfg["estimator"])


def passes(cfg: dict, est) -> int:
    """A pass is one whole fit, as ``glm_higgs``'s: the IRLS iterations a
    fit takes are the program's choice (``glm_irls_iterations_per_fit``)."""
    if not est.model.output["regularization_path"]:
        raise RuntimeError("the fit holds no solution")
    return 1


# bytes a row that any implementation reads an IRLS iteration: the six
# codes (int32), the two numerics (f32), and the row lanes
CODES_BYTES, NUM_BYTES = 4 * len(CATS), 4 * len(NUMS)
NONZEROS = len(CATS) + len(NUMS) + 1  # a row's non-zero design entries


def needed_work(cfg: dict, n_passes: int) -> dict:
    """What ``n_passes`` fits need, from the shapes alone: the
    configuration's ``irls_iterations`` (fixed there, whatever the program
    takes) times an iteration's work. A row has 9 non-zero design entries
    (6 indicators, 2 numerics, the intercept), so its Gram contribution is
    the 45 entries of their upper triangle and its ``b`` contribution 9, a
    multiply and an add each; the linear predictor is 9 more. An iteration
    reads the codes, the numerics and four row lanes (response, weight,
    offset, linear predictor). ``cat_gram`` is the Gram kernel's part: the
    codes, the numerics, the weight and the working response in, the
    Gram's and ``b``'s FLOPs. Not the dense ``2 N p^2`` of a one-hot
    design: that is work an implementation chooses, not work it needs."""
    n = float(cfg["rows"])
    iters = n_passes * int(cfg["reference"]["irls_iterations"])
    tri = NONZEROS * (NONZEROS + 1) // 2
    gram_flops = 2.0 * (tri + NONZEROS)
    return {
        "flops": n * iters * (gram_flops + 2.0 * NONZEROS),
        "bytes": n * iters * (CODES_BYTES + NUM_BYTES + 4 * 4.0),
        "cat_gram": {"flops": n * iters * gram_flops,
                     "bytes": n * iters * (CODES_BYTES + NUM_BYTES + 2 * 4.0)},
    }


def coef_names(cfg: dict) -> list[str]:
    """The program's coefficient names in the reference's order: each
    column's levels but the first, the numerics, the intercept."""
    cols = cfg["data"]["columns"]
    names = [f"{c}.{d}" for c in CATS
             for d in domain(c, int(cols[c]["levels"]))[1:]]
    return names + list(NUMS) + ["Intercept"]


def outputs(est) -> dict:
    """What ``correct`` takes from a fitted model: its coefficients on the
    original scale, in the reference's order, and its training logloss."""
    out = est.model.output
    at = {nm: i for i, nm in enumerate(out["coef_names"])}
    beta = np.asarray(out["beta_orig"], np.float64)
    return {"coef": beta[[at[nm] for nm in coef_names(_LEVELS)]],
            "logloss": float(est.logloss())}


with open(os.path.splitext(__file__)[0] + ".json") as _f:
    _LEVELS = {"data": json.load(_f)["data"]}  # the layout outputs() orders by


def compare(cfg: dict, X, y, model: dict, control: bool = False,
            memo: dict | None = None) -> dict:
    return ref.compare(X, y, model, cfg, control=control, memo=memo)
