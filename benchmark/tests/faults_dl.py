"""The faults of the network cell, beside ``faults.py`` (which is not
edited): each takes what ``dl_higgs.outputs`` gave for a sound model, and the
data, and returns what a program with that fault would have given. A fault of
the update rule is planted in the records the probes read: their weights
after the probed steps become what that rule gives from the same records,
replayed in float32 (the program's precision).

    PLANTED["dl_higgs"][name](model, X, y, cfg) -> model
"""

from __future__ import annotations

import copy

import numpy as np


def _replayed(model: dict, X, y, cfg, half_batch=False, **changed) -> dict:
    from benchmark.configs.dl_higgs import hyper
    from benchmark.configs.dl_higgs_ref import replay, standardize

    hp = {**hyper(cfg), **changed}
    Xs = standardize(X)
    out = copy.deepcopy(model)
    rec = out["probe"]
    zero = [(np.zeros_like(W), np.zeros_like(b)) for W, b in rec["initial"]]
    early, late = rec["early"], rec["late"]
    early["after"] = replay(hp, rec["initial"], zero, 0, Xs, y, early["rows"],
                            early["steps"], dtype=np.float32, half_batch=half_batch)
    if late["steps"] and "momentum" in late:
        late["after"] = replay(hp, late["before"], late["momentum"], late["count"],
                               Xs, y, late["rows"], late["steps"], dtype=np.float32,
                               half_batch=half_batch)
    return out


def momentum_held(model: dict, X, y, cfg) -> dict:
    """The momentum never leaves ``momentum_start``: the ramp is dropped."""
    return _replayed(model, X, y, cfg,
                     momentum_stable=cfg["estimator"]["momentum_start"])


def annealing_dropped(model: dict, X, y, cfg) -> dict:
    """The rate stays at ``rate``: ``rate_annealing`` is dropped."""
    return _replayed(model, X, y, cfg, rate_annealing=0.0)


def half_batch(model: dict, X, y, cfg) -> dict:
    """Each update takes the gradient of half of its minibatch's rows."""
    return _replayed(model, X, y, cfg, half_batch=True)


def count_stuck(model: dict, X, y, cfg) -> dict:
    """The SGD state's step count never advances: every update takes the
    schedule at 0 steps (the rate never annealed, the momentum held at
    ``momentum_start``), and the late probe records a count of 0."""
    out = _replayed(model, X, y, cfg, rate_annealing=0.0,
                    momentum_stable=cfg["estimator"]["momentum_start"])
    out["probe"]["late"]["count"] = 0
    return out


def rows_repeated(model: dict, X, y, cfg) -> dict:
    """The epoch's row order is not a permutation: the late probe's steps
    take the first probe's rows again (its weights are what the update
    gives from those rows, so only the records show it)."""
    from benchmark.configs.dl_higgs import hyper
    from benchmark.configs.dl_higgs_ref import replay, standardize

    out = copy.deepcopy(model)
    rec = out["probe"]
    late = rec["late"]
    late["rows"] = rec["early"]["rows"][: len(late["rows"])].copy()
    late["after"] = replay(hyper(cfg), late["before"], late["momentum"], late["count"],
                           standardize(X), y, late["rows"], late["steps"],
                           dtype=np.float32)
    return out


def weight_1pct(model: dict, X, y, cfg) -> dict:
    """An answer altered where it is produced: the third weight matrix the
    model reports is 1% off the one its predictions were made with."""
    out = copy.deepcopy(model)
    W, b = out["weights"][2]
    out["weights"][2] = (W * np.float32(1.01), b)
    return out


PLANTED = {"dl_higgs": {"momentum_held": momentum_held,
                        "annealing_dropped": annealing_dropped,
                        "half_batch": half_batch, "weight_1pct": weight_1pct,
                        "count_stuck": count_stuck, "rows_repeated": rows_repeated}}
