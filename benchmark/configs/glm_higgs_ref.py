"""Plain reference for ``glm_higgs``: H2O's binomial GLM with the elastic-net
penalty, in numpy float64 on the host. It imports nothing of the program.

Minimises, over the standardised design with an unpenalised intercept,

    (1/N) * deviance/2 + lambda * (alpha * |b|_1 + (1 - alpha)/2 * |b|_2^2)

by IRLS: each iteration forms the weighted Gram X'WX and X'Wz and solves the
penalised quadratic by cyclic coordinate descent, both to convergence (the
answer is the optimum, whatever path a solver takes to it). Coefficients
are returned on the original scale, the intercept last.

``rounded=True`` is the control: the same fit one precision below the
configuration's float32, with every matrix product's operands (the design,
the coefficients, the weighted design, the working response) rounded to
bfloat16 first.
"""

from __future__ import annotations

import numpy as np

from .rounding import bf16


def _elastic_net(G, b, l1, l2, beta, tol=1e-13, sweeps=10000):
    """argmin 1/2 b'Gb - b'beta + l2/2 |b|^2 + l1 |b|_1, intercept (last)
    unpenalised, by cyclic coordinate descent from ``beta``."""
    p = len(b)
    beta = beta.copy()
    pen = np.arange(p) < p - 1
    for _ in range(sweeps):
        worst = 0.0
        for j in range(p):
            r = b[j] - G[j] @ beta + G[j, j] * beta[j]
            if pen[j]:
                new = np.sign(r) * max(abs(r) - l1, 0.0) / (G[j, j] + l2)
            else:
                new = r / G[j, j]
            worst = max(worst, abs(new - beta[j]))
            beta[j] = new
        if worst < tol:
            break
    return beta


def fit(X: np.ndarray, y: np.ndarray, cfg: dict, rounded=False) -> dict:
    est = cfg["estimator"]
    lam, alpha = float(est["lambda_"]), float(cfg["reference"]["alpha"])
    n, C = X.shape
    # the big arrays are made once and written in place: fresh pages are slow
    Xs = np.empty((n, C + 1))
    Xs[:, :C] = X
    Xs[:, C] = 1.0
    mean = Xs[:, :C].mean(axis=0)
    Xs[:, :C] -= mean
    sd = np.sqrt(np.einsum("np,np->p", Xs[:, :C], Xs[:, :C]) / (n - 1))
    Xs[:, :C] /= sd
    rnd = bf16 if rounded else (lambda a: a)
    Xm = rnd(Xs)  # the design as the matrix products see it
    Xw = np.empty_like(Xs)
    l1, l2 = lam * alpha * n, lam * (1 - alpha) * n
    beta = np.zeros(C + 1)
    ybar = y.mean()
    beta[-1] = np.log(ybar / (1 - ybar))
    iters = 0
    # rounding leaves a jitter that never settles: the control stops sooner
    tol, most = (1e-6, 12) if rounded else (1e-11, int(cfg["reference"]["max_iterations"]))
    for iters in range(1, most + 1):
        eta = Xm @ rnd(beta)
        mu = np.exp(-eta)
        mu += 1.0
        np.reciprocal(mu, out=mu)
        W = np.maximum(mu * (1 - mu), 1e-10)
        z = eta + (y - mu) / W
        np.multiply(Xs, W[:, None], out=Xw)
        if rounded:
            Xw[:] = bf16(Xw)
        G, b = Xw.T @ Xm, Xw.T @ rnd(z)
        new = _elastic_net(G, b, l1, l2, beta)
        step = np.max(np.abs(new - beta))
        beta = new
        if step < tol:
            break
    eta = Xs @ beta
    coef = beta.copy()
    coef[:-1] = beta[:-1] / sd
    coef[-1] = beta[-1] - np.sum(beta[:-1] * mean / sd)
    return {"coef": coef, "beta_std": beta, "iterations": iters,
            "logloss": float(np.mean(np.logaddexp(0.0, eta) - y * eta))}


def _gap(a, ref):
    return float(np.max(np.abs(a - ref) / np.maximum(
        np.abs(ref), np.median(np.abs(ref)))))


def compare(X, y, model: dict, cfg: dict, control=False) -> dict:
    """``coef_gap``: the worst coefficient's distance from the reference's,
    against that coefficient or the median coefficient, whichever is larger;
    ``logloss_gap``: the reported training logloss against the reference's."""
    want = fit(X, y, cfg)
    out = {"program": {
        "coef_gap": _gap(np.asarray(model["coef"], np.float64), want["coef"]),
        "logloss_gap": abs(model["logloss"] - want["logloss"]) / want["logloss"]},
        "control": {},
        "reference": {"iterations": want["iterations"],
                      "logloss": want["logloss"]}}
    if control:
        low = fit(X, y, cfg, rounded=True)
        out["control"] = {
            "coef_gap": _gap(low["coef"], want["coef"]),
            "logloss_gap": abs(low["logloss"] - want["logloss"]) / want["logloss"]}
    return out
