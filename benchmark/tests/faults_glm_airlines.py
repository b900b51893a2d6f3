"""The faults of the airline GLM cell, beside ``faults.py`` and
``faults_dl.py`` (which are not edited): each takes what
``glm_airlines.outputs`` gave for a sound model, the data, the
configuration and the comparison's memo (the reference's design, fitted
once), and returns what a program with that fault would have given.

    PLANTED["glm_airlines"][name](model, X, y, cfg, memo) -> model
"""

from __future__ import annotations

import copy

import numpy as np


def half_batch(model: dict, X, y, cfg, memo: dict) -> dict:
    """The fit takes the first half of the rows only: its coefficients are
    the float64 fit of that half, and the logloss it reports is theirs over
    every row."""
    from benchmark.configs import glm_airlines_ref as ref

    h = len(y) // 2
    got = ref.fit({k: v[:h] for k, v in X.items()}, y[:h], cfg,
                  start=model["coef"])
    D = memo.get("D") or ref.Design(X, cfg)
    return {"coef": got["coef"],
            "logloss": ref.logloss(D, y, D.standardised(got["coef"]))}


def _largest(coef) -> int:
    """The categorical or numeric coefficient largest in magnitude (the
    intercept, last, aside)."""
    return int(np.argmax(np.abs(np.asarray(coef)[:-1])))


def dropped_level(model: dict, X, y, cfg, memo: dict) -> dict:
    """A level left out of the design: its coefficient reads 0, its rows
    scored as the reference level's (the largest coefficient's level)."""
    out = copy.deepcopy(model)
    out["coef"][_largest(out["coef"])] = 0.0
    return out


def altered_2pct(model: dict, X, y, cfg, memo: dict) -> dict:
    """An answer altered where it is produced: the largest coefficient 2%
    off."""
    out = copy.deepcopy(model)
    out["coef"][_largest(out["coef"])] *= 1.02
    return out


PLANTED = {"glm_airlines": {"half_batch": half_batch,
                            "dropped_level": dropped_level,
                            "altered_2pct": altered_2pct}}
