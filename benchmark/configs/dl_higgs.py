"""Configuration ``dl_higgs``: H2ODeepLearningEstimator at the network Baldi,
Sadowski and Whiteson published for the HIGGS data (28 inputs, five hidden
layers of 300 tanh units, a two-class softmax; momentum SGD on minibatches
of 100) on the Higgs-shaped frame. Sizes and arguments are in
``dl_higgs.json``; the plain reference is ``dl_higgs_ref.py``."""

from __future__ import annotations

import math

import numpy as np

from . import dl_higgs_ref as ref
from .higgs_data import frame_for as make_frame, release, train  # noqa: F401


def estimator_args(cfg: dict) -> dict:
    """The estimator's arguments: the file's, with H2O's two schedule
    parameters derived from the published per-epoch schedule at the
    configuration's rows (``schedule``): the momentum ramp is
    ``momentum_ramp_epochs`` epochs of samples, and ``rate_annealing`` makes
    ``rate / (1 + rate_annealing * rows)`` at the end of an epoch equal to
    the paper's ``rate / decay_per_batch ** batches``."""
    est = dict(cfg["estimator"])
    sch = cfg["schedule"]
    rows, batch = int(cfg["rows"]), int(est["mini_batch_size"])
    est["momentum_ramp"] = float(sch["momentum_ramp_epochs"]) * rows
    est["rate_annealing"] = math.expm1(
        (rows // batch) * math.log(sch["decay_per_batch"])) / rows
    return est


def hyper(cfg: dict) -> dict:
    """The update's hyperparameters, as the reference reads them."""
    est = estimator_args(cfg)
    return {"l1": 0.0, "rate_decay": 1.0, **{k: est[k] for k in (
        "rate", "rate_annealing", "momentum_start", "momentum_ramp",
        "momentum_stable", "nesterov_accelerated_gradient", "l2",
        "mini_batch_size")}}


def build_estimator(cfg: dict):
    from h2o3_tpu.estimators import H2ODeepLearningEstimator

    return H2ODeepLearningEstimator(**estimator_args(cfg))


def passes(cfg: dict, est) -> int:
    """A pass is an epoch: the configuration's ``epochs``, which the fitted
    model has to have trained (early stopping is off)."""
    done, want = int(est.model.output["epochs_trained"]), int(cfg["estimator"]["epochs"])
    if done != want:
        raise RuntimeError(f"the model trained {done} epochs, the configuration asks for {want}")
    return want


def widths(cfg: dict) -> list[int]:
    return [int(cfg["cols"]), *(int(h) for h in cfg["estimator"]["hidden"]), 2]


def needed_work(cfg: dict, n_passes: int) -> dict:
    """What any implementation must do for ``n_passes`` epochs, from the
    shapes alone. An epoch is ``rows // batch`` minibatch steps of
    ``6 * batch * M`` FLOPs (forward, and the backward's two products),
    ``M = sum(fan_in * fan_out)``, reading the batch's design rows, label
    and weight; then the training metrics' forward, ``2 * rows * M`` FLOPs
    over the design read once more. ``dl_step`` is one minibatch step: its
    FLOPs and its rows; ``dl_steps`` the steps in ``n_passes``. The parameters and the momentum (2.9 MB) are not
    counted as HBM traffic a step: a program that keeps them on the chip
    across steps does not move them."""
    w = widths(cfg)
    M = float(sum(a * b for a, b in zip(w[:-1], w[1:])))
    rows, C = float(cfg["rows"]), w[0]
    batch = int(cfg["estimator"]["mini_batch_size"])
    steps = float(int(cfg["rows"]) // batch)
    step = {"flops": 6.0 * batch * M, "bytes": batch * (C + 2) * 4.0}
    return {"flops": n_passes * (steps * step["flops"] + 2.0 * rows * M),
            "bytes": n_passes * (steps * step["bytes"] + rows * C * 4.0),
            "dl_step": step, "dl_steps": n_passes * steps}


def outputs(est) -> dict:
    """What ``correct`` takes from a fitted model: its weights, its probe
    records (``sgd_probe``), its device predictions P(class 1) over every
    row of the training frame, and the training logloss and AUC it
    reported."""
    m = est.model
    rec = m.sgd_probe()
    if rec is None:
        raise RuntimeError("the model holds no probe records")
    pred = np.asarray(m._predict_raw_dev(m.params.training_frame), np.float64)
    return {"weights": [(W.copy(), b.copy()) for W, b in rec["late"]["after"]],
            "probe": rec, "pred": pred, "logloss": float(est.logloss()),
            "auc": float(est.auc())}


def probe_plan(cfg: dict) -> dict:
    """The probes a sound training of the configuration records: the first
    probe's steps, the last one's, and the steps taken before the last one
    (its ``count``: a sound SGD state has counted every step before it)."""
    batch = int(cfg["estimator"]["mini_batch_size"])
    nbatch, epochs = int(cfg["rows"]) // batch, int(cfg["estimator"]["epochs"])
    probe = int(cfg["reference"]["probe_steps"])
    early = min(probe, nbatch)
    # one epoch: the last probe starts after the first one ends
    late = nbatch - max(nbatch - probe, early) if epochs == 1 else early
    return {"batch": batch, "early": early, "late": late,
            "late_count": epochs * nbatch - late}


def record_errors(cfg: dict, rec: dict) -> list[str]:
    """What in the model's probe records disagrees with the configuration:
    the steps of each probe, their rows (``steps * batch`` of them, distinct,
    inside the frame; with one epoch the two probes' rows are parts of one
    permutation and share none), and the late probe's step count."""
    plan, n = probe_plan(cfg), int(cfg["rows"])
    errors = []
    for name in ("early", "late"):
        pr, steps = rec[name], plan[name]
        rows = np.asarray(pr["rows"])
        if pr["steps"] != steps or len(rows) != steps * plan["batch"]:
            errors.append(f"{name}: {pr['steps']} steps of {len(rows)} rows, the "
                          f"configuration's {steps} of {steps * plan['batch']}")
        if len(rows) and (rows.min() < 0 or rows.max() >= n):
            errors.append(f"{name}: rows outside [0, {n})")
        if len(np.unique(rows)) != len(rows):
            errors.append(f"{name}: a row taken twice")
    early, late = np.asarray(rec["early"]["rows"]), np.asarray(rec["late"]["rows"])
    if int(cfg["estimator"]["epochs"]) == 1 and len(np.intersect1d(early, late)):
        errors.append("the two probes of one epoch share rows")
    if rec["late"].get("count") != plan["late_count"]:
        errors.append(f"late: the SGD state counted {rec['late'].get('count')} steps "
                      f"before it, the configuration's training {plan['late_count']}")
    return errors


def compare(cfg: dict, X, y, model: dict, control: bool = False,
            memo: dict | None = None) -> dict:
    """The reference's comparison (``dl_higgs_ref.compare``), the late probe
    replayed from the step count the configuration's training has there (not
    the one the model records, so a counter that stopped shows in the
    weights), and ``probe_record_errors``: how many of ``record_errors``'
    checks the records fail (0 for a sound model)."""
    errors = record_errors(cfg, model["probe"])
    out = ref.compare(X, y, model, hyper(cfg), probe_plan(cfg)["late_count"],
                      control=control, memo=memo)
    out["program"]["probe_record_errors"] = float(len(errors))
    if errors:
        out["diagnostic"]["probe_record_errors"] = errors
    return out
