"""Plain reference for ``glm_airlines``: H2O's binomial GLM with the
elastic-net penalty on six categorical and two numeric predictors, in numpy
float64 on the host. It imports nothing of the program.

The design is the one-hot expansion with each column's first level dropped
(``use_all_factor_levels`` false), the numerics standardised (mean, sample
standard deviation) and an unpenalised intercept, in that column order. It
is never formed: X'WX and X'Wz are computed from the codes by per-level
weighted sums (``np.bincount``), which are exactly the one-hot design's
products (a categorical column's own block is diagonal, holding its levels'
weight sums; two columns' block is their weighted contingency table; a
categorical against a numeric is the numeric's weighted sum by level). The
linear predictor is one lookup of the coefficients a categorical column.

It minimises, over the standardised design,

    (1/N) * deviance/2 + lambda * (alpha * |b|_1 + (1 - alpha)/2 * |b|_2^2)

by IRLS: each iteration forms the weighted Gram and solves the penalised
quadratic by cyclic coordinate descent to 1e-13, finished by the exact
solve on the active set once coordinate descent has found it (the answer is
the optimum, whatever path a solver takes to it). Coefficients are returned
on the original scale, the intercept last.

``rounded=True`` is the control: the same fit one precision below the
configuration's float32 design, Gram and solve. Every product's operands
are rounded to bfloat16 first (the numerics and the coefficients in the
linear predictor; the weights, which are an indicator's weighted entries,
the weighted numerics, the numerics and the working response in the Gram),
and so are the Gram's G and b that the solve takes. (The indicator entries
are exact in bfloat16, and the roundings of the row operands alone make
errors that cancel over millions of rows.)
"""

from __future__ import annotations

import numpy as np

from .rounding import bf16

CATS = ("Month", "DayofMonth", "DayOfWeek", "UniqueCarrier", "Origin", "Dest")


def _same(a):
    return a


def widths(cfg: dict) -> list[int]:
    """Each categorical column's block: its levels but the first."""
    return [int(cfg["data"]["columns"][c]["levels"]) - 1 for c in CATS]


class Design:
    """The codes as block indices (level - 1; the dropped first level and
    NA index the block's spare slot, ``width``), the standardised
    numerics, and the layout: blocks first, numerics, intercept last.
    Row arrays are kept narrow (int16 indices, one column of numerics at a
    time): at the cell's size the reference shares the host with the
    program's process."""

    def __init__(self, X: dict, cfg: dict):
        self.widths = widths(cfg)
        self.offsets = np.concatenate([[0], np.cumsum(self.widths)]).astype(int)
        codes = np.asarray(X["codes"])
        self.idx = []
        for j, wd in enumerate(self.widths):
            c = codes[:, j]
            self.idx.append(np.where(c >= 1, c - 1, wd).astype(np.int16))
        num = np.asarray(X["num"])
        self.n = len(num)
        q = num.shape[1]
        self.mean, self.sd = np.zeros(q), np.zeros(q)
        self.xs = np.empty((self.n, q), order="F")  # a column is contiguous
        for k in range(q):
            self.mean[k] = num[:, k].mean(dtype=np.float64)
            x = self.xs[:, k]
            np.subtract(num[:, k], self.mean[k], out=x)
            self.sd[k] = np.sqrt(np.dot(x, x) / (self.n - 1))
            x /= self.sd[k]
        self.n_cat = int(self.offsets[-1])
        self.p = self.n_cat + q + 1

    def block(self, beta: np.ndarray, j: int) -> np.ndarray:
        lo, wd = self.offsets[j], self.widths[j]
        return np.append(beta[lo:lo + wd], 0.0)

    def eta(self, beta: np.ndarray, rnd=_same) -> np.ndarray:
        eta = rnd(self.xs) @ beta[self.n_cat:-1] + beta[-1]
        for j, idx in enumerate(self.idx):
            eta += self.block(beta, j)[idx]
        return eta

    def gram(self, w: np.ndarray, z: np.ndarray,
             rnd=_same) -> tuple[np.ndarray, np.ndarray]:
        """(X'WX, X'Wz) of the one-hot design, from the codes. ``rnd``
        rounds each product's operands first (the control): the numerics,
        the weights (an indicator's weighted entry), the weighted numerics
        and the working response."""
        p, nc, off = self.p, self.n_cat, self.offsets
        q = self.xs.shape[1]
        G = np.zeros((p, p))
        b = np.zeros(p)
        # the dense block (numerics, then the intercept's column of ones),
        # a column at a time: dw[k] is the weighted column, x[k] the column
        dw = [rnd(self.xs[:, k] * w) for k in range(q)] + [rnd(w)]
        x = [rnd(self.xs[:, k]) for k in range(q)]
        w, z = rnd(w), rnd(z)
        for a in range(q + 1):
            for c in range(q + 1):
                G[nc + a, nc + c] = dw[a].sum() if c == q else np.dot(dw[a], x[c])
            b[nc + a] = np.dot(dw[a], z)
        del dw
        wz = w * z
        wx = [w * x[k] for k in range(q)]  # an indicator's weighted numerics
        del x
        key = np.empty(self.n, np.intp)  # two columns' joint code
        for j, (idx, wd) in enumerate(zip(self.idx, self.widths)):
            idx = idx.astype(np.intp)  # bincount's own index type, cast once
            blk = slice(off[j], off[j] + wd)
            sums = np.bincount(idx, weights=w, minlength=wd + 1)[:wd]
            G[blk, blk] = np.diag(sums)
            G[blk, nc + q] = G[nc + q, blk] = sums
            b[blk] = np.bincount(idx, weights=wz, minlength=wd + 1)[:wd]
            for k, wk in enumerate(wx):
                col = np.bincount(idx, weights=wk, minlength=wd + 1)[:wd]
                G[blk, nc + k] = G[nc + k, blk] = col
            for i in range(j + 1, len(self.idx)):
                wi = self.widths[i]
                np.multiply(idx, wi + 1, out=key)
                np.add(key, self.idx[i], out=key)
                tab = np.bincount(key, weights=w, minlength=(wd + 1) * (wi + 1))
                tab = tab.reshape(wd + 1, wi + 1)[:wd, :wi]
                other = slice(off[i], off[i] + wi)
                G[blk, other] = tab
                G[other, blk] = tab.T
        return G, b

    def coef(self, beta: np.ndarray) -> np.ndarray:
        """Standardised coefficients to the original scale."""
        out = beta.copy()
        nc = self.n_cat
        out[nc:-1] = beta[nc:-1] / self.sd
        out[-1] = beta[-1] - np.sum(beta[nc:-1] * self.mean / self.sd)
        return out

    def standardised(self, coef: np.ndarray) -> np.ndarray:
        """Original-scale coefficients to the standardised scale."""
        out = np.asarray(coef, np.float64).copy()
        nc = self.n_cat
        out[nc:-1] = coef[nc:-1] * self.sd
        out[-1] = coef[-1] + np.sum(coef[nc:-1] * self.mean)
        return out


def _active_solve(G, b, l1, l2, beta, pen):
    """The exact optimum on ``beta``'s active set and signs, or None where
    it fails the optimality conditions (a sign flips, or an inactive
    coordinate's gradient passes the l1 threshold)."""
    act = (beta != 0) | ~pen
    s = np.sign(beta) * pen
    A = np.flatnonzero(act)
    M = G[np.ix_(A, A)] + np.diag(l2 * pen[A])
    x = np.linalg.solve(M, b[A] - l1 * s[A])
    if np.any(pen[A] & (np.sign(x) != s[A])):
        return None
    full = np.zeros_like(beta)
    full[A] = x
    grad = b - G @ full
    if np.any(np.abs(grad[~act]) > l1 * (1 + 1e-9)):
        return None
    return full


def elastic_net(G, b, l1, l2, beta, tol=1e-13, sweeps=20000):
    """argmin 1/2 b'Gb - b'beta + l2/2 |b|^2 + l1 |b|_1, intercept (last)
    unpenalised, by cyclic coordinate descent from ``beta``; every tenth
    sweep tries the exact solve on the active set it has found."""
    p = len(b)
    beta = np.asarray(beta, np.float64).copy()
    pen = np.arange(p) < p - 1
    diag = np.diag(G).copy()
    r = b - G @ beta
    for s in range(sweeps):
        worst = 0.0
        for j in range(p):
            if diag[j] <= 0:
                continue
            rj = r[j] + diag[j] * beta[j]
            if pen[j]:
                new = np.sign(rj) * max(abs(rj) - l1, 0.0) / (diag[j] + l2)
            else:
                new = rj / diag[j]
            d = new - beta[j]
            if d != 0.0:
                r -= G[j] * d  # G is symmetric: its row is its column
                beta[j] = new
                worst = max(worst, abs(d))
        if worst < tol:
            break
        if s % 10 == 9:
            exact = _active_solve(G, b, l1, l2, beta, pen)
            if exact is not None:
                return exact
    return beta


def irls(D: Design, y, cfg, beta, rounded=False) -> tuple[np.ndarray, int, float]:
    """IRLS from ``beta`` (standardised) until a step is under the
    configuration's ``step``; the control stops at 4 iterations or a step
    of 1e-6 (rounding leaves a jitter that never settles; it starts from the
    optimum, so its first Newton step lands on the rounded problem's)."""
    est, ref = cfg["estimator"], cfg["reference"]
    lam, alpha = float(est["lambda_"]), float(ref["alpha"])
    l1, l2 = lam * alpha * D.n, lam * (1 - alpha) * D.n
    tol, most = (1e-6, 4) if rounded else (float(ref["step"]), int(ref["max_iterations"]))
    rnd = bf16 if rounded else _same
    step, it = np.inf, 0
    for it in range(1, most + 1):
        eta = D.eta(rnd(beta), rnd)
        mu = np.negative(eta)  # mu = 1 / (1 + exp(-eta)), in place
        np.exp(mu, out=mu)
        mu += 1.0
        np.reciprocal(mu, out=mu)
        W = np.subtract(1.0, mu)  # W = max(mu (1 - mu), 1e-10)
        W *= mu
        np.maximum(W, 1e-10, out=W)
        z = np.subtract(y, mu)  # z = eta + (y - mu) / W
        del mu
        z /= W
        z += eta
        del eta
        G, b = D.gram(W, z, rnd)
        del W, z
        G, b = rnd(G), rnd(b)
        new = elastic_net(G, b, l1, l2, beta)
        step = float(np.max(np.abs(new - beta)))
        beta = new
        if step < tol:
            break
    return beta, it, step


def logloss(D: Design, y, beta) -> float:
    eta = D.eta(beta)
    return float(np.mean(np.logaddexp(0.0, eta) - y * eta))


def fit(X: dict, y: np.ndarray, cfg: dict, start=None, rounded=False,
        D: Design | None = None) -> dict:
    """The fit from ``start`` (original-scale coefficients; None: zero
    coefficients and the intercept at the mean's logit)."""
    D = D or Design(X, cfg)
    if start is None:
        beta = np.zeros(D.p)
        ybar = y.mean()
        beta[-1] = np.log(ybar / (1 - ybar))
    else:
        beta = D.standardised(np.asarray(start, np.float64))
    beta, iters, step = irls(D, y, cfg, beta, rounded=rounded)
    return {"coef": D.coef(beta), "beta_std": beta, "iterations": iters,
            "step": step, "logloss": logloss(D, y, beta)}


def compare(X, y, model: dict, cfg: dict, control=False,
            memo: dict | None = None) -> dict:
    """``coef_gap``: the largest distance of a coefficient from the
    reference's, on the original scale; ``logloss_gap``: the training
    logloss the program reported against float64's at the reference's
    coefficients, relative. The reference is a full fit from zero below
    ``from_zero_below_rows`` rows, else the float64 IRLS from the
    program's coefficients: a Newton step from a near-optimal point lands
    on the optimum to second order. ``memo``: a dict the caller keeps
    across comparisons on the same data (the planted faults'), so the
    reference is fitted once."""
    memo = {} if memo is None else memo
    if "want" not in memo:
        memo["D"] = Design(X, cfg)
        start = (None if len(y) < int(cfg["reference"]["from_zero_below_rows"])
                 else model["coef"])
        memo["want"] = fit(X, y, cfg, start=start, D=memo["D"])
    D, want = memo["D"], memo["want"]
    out = {"program": {
        "coef_gap": float(np.max(np.abs(np.asarray(model["coef"], np.float64)
                                        - want["coef"]))),
        "logloss_gap": abs(model["logloss"] - want["logloss"]) / want["logloss"]},
        "control": {},
        "reference": {"iterations": want["iterations"], "step": want["step"],
                      "logloss": want["logloss"]}}
    if control:
        low = fit(X, y, cfg, start=want["coef"], rounded=True, D=D)
        out["control"] = {
            "coef_gap": float(np.max(np.abs(low["coef"] - want["coef"]))),
            "logloss_gap": abs(low["logloss"] - want["logloss"]) / want["logloss"]}
    return out
