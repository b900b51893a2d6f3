"""The faults a training cell can have, planted under the timed path: each
takes what the configuration's ``outputs`` gave for a sound model and returns
what a program with that fault would have given. (Half of the batch left out
needs no function: the program is trained on the first half of the frame and
compared against the whole.)"""

from __future__ import annotations

import copy

import numpy as np


def unchanged_gbm(model: dict) -> dict:
    """A step that returns its state unchanged: the running prediction never
    takes a tree in, so every tree is built on the first tree's gradients."""
    out = copy.deepcopy(model)
    out["trees"] = [copy.deepcopy(out["trees"][0]) for _ in out["trees"]]
    return out


def altered_gbm(model: dict) -> dict:
    """An answer altered where it is produced: one leaf of the third tree is
    10% off."""
    out = copy.deepcopy(model)
    for lv in reversed(out["trees"][2]):
        hit = np.flatnonzero(lv["leaf_now"] & (lv["leaf_val"] != 0))
        if hit.size:
            lv["leaf_val"] = lv["leaf_val"].copy()
            lv["leaf_val"][hit[0]] *= 1.10
            return out
    raise AssertionError("no leaf to alter")


def unchanged_glm(model: dict) -> dict:
    """A step that returns its state unchanged: the coefficients stay where
    IRLS starts, all zero but the intercept."""
    out = copy.deepcopy(model)
    out["coef"] = np.zeros_like(out["coef"])
    out["coef"][-1] = model["coef"][-1]
    return out


def altered_glm(model: dict) -> dict:
    """An answer altered where it is produced: the largest coefficient 2% off."""
    out = copy.deepcopy(model)
    out["coef"] = out["coef"].copy()
    out["coef"][np.argmax(np.abs(out["coef"][:-1]))] *= 1.02
    return out


PLANTED = {
    "gbm_higgs": {"unchanged": unchanged_gbm, "altered": altered_gbm},
    "glm_higgs": {"unchanged": unchanged_glm, "altered": altered_glm},
}
