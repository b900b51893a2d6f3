"""Plain reference for ``dl_higgs``: the network of Baldi, Sadowski and
Whiteson (2014) as H2O's DeepLearning trains it, in numpy float64 on the
host. It imports nothing of the program.

It follows the model, as the tree references do: the trained weights, the
initial weights, and the rows in the order the training took them are the
model's (its seeded draws); everything else is computed here from the data.

- Standardisation: each column's mean and sample standard deviation.
- Forward: ``a_0 = x``, ``a_i = tanh(a_{i-1} W_i + b_i)``, logits
  ``a_5 W_6 + b_6``, ``p = softmax(logits)[:, 1]``.
- Loss of a minibatch: the mean cross-entropy, plus ``l2/2 |w|^2 + l1 |w|_1``
  over every parameter.
- H2O's manual-rate update, for the step after ``n = count * batch``
  samples and a parameter of layer ``l`` (0 for the weights into the first
  hidden layer)::

      rate_n = rate / (1 + rate_annealing * n)
      mu_n   = momentum_start + (momentum_stable - momentum_start)
               * min(n / momentum_ramp, 1)
      d      = -rate_n * rate_decay**l * (1 - mu_n) * grad
      v      = mu_n * v + d
      w      = w + v               (classical; + mu_n * v + d with Nesterov)

``rounded=True`` is the control: the same computation one precision below
the configuration's float32, with every matrix product's operands rounded to
bfloat16 first.
"""

from __future__ import annotations

import os

import numpy as np

from .rounding import bf16

BLOCK = 1 << 13  # rows a block of the forward over the frame
# threads of the forward: the cores this process may run on (not the
# machine's count, which can be many times more), at most 16
THREADS = min(len(os.sched_getaffinity(0)), 16)


def standardize(X: np.ndarray) -> np.ndarray:
    """``(X - mean) / sd`` by column, float64, sd with n - 1."""
    Xs = X.astype(np.float64)
    Xs -= Xs.mean(axis=0)
    Xs /= np.sqrt(np.einsum("nc,nc->c", Xs, Xs) / (len(Xs) - 1))
    return Xs


def _mm(a, b, rnd):
    return rnd(a) @ rnd(b)


def _forward(layers, x, rnd):
    """Every layer's activation (the input first) and the logits."""
    acts = [x]
    for W, b in layers[:-1]:
        acts.append(np.tanh(_mm(acts[-1], W, rnd) + b))
    W, b = layers[-1]
    return acts, _mm(acts[-1], W, rnd) + b


def _prob_block(layers, x, rnd) -> np.ndarray:
    h = x
    for W, b in layers[:-1]:
        h = _mm(h, W, rnd)
        h += b
        np.tanh(h, out=h)
    W, b = layers[-1]
    z = _mm(h, W, rnd) + b
    return 1.0 / (1.0 + np.exp(z[:, 0] - z[:, 1]))


def prob(layers, Xs, rounded=False) -> np.ndarray:
    """P(class 1) for every row, in blocks of ``BLOCK`` rows spread over
    ``THREADS`` threads (one BLAS thread each: the same numbers whatever the
    threads, since a block is the unit of every product)."""
    from concurrent.futures import ThreadPoolExecutor

    from threadpoolctl import threadpool_limits

    rnd = bf16 if rounded else (lambda a: a)
    layers = [(np.asarray(W, np.float64), np.asarray(b, np.float64)) for W, b in layers]
    out = np.empty(len(Xs))

    def block(lo):
        out[lo:lo + BLOCK] = _prob_block(layers, Xs[lo:lo + BLOCK], rnd)

    with threadpool_limits(1), ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(block, range(0, len(Xs), BLOCK)))
    return out


def grad(layers, x, y, l1, l2, rnd):
    """The gradient of the minibatch loss, by back-propagation."""
    acts, z = _forward(layers, x, rnd)
    z = z - z.max(axis=1, keepdims=True)
    delta = np.exp(z)
    delta /= delta.sum(axis=1, keepdims=True)
    delta[np.arange(len(y)), y.astype(np.int64)] -= 1.0
    delta /= len(y)
    out = [None] * len(layers)
    for i in range(len(layers) - 1, -1, -1):
        W, b = layers[i]
        gW = _mm(acts[i].T, delta, rnd)
        gb = delta.sum(axis=0)
        out[i] = (gW + l2 * W + l1 * np.sign(W), gb + l2 * b + l1 * np.sign(b))
        if i:
            delta = _mm(delta, W.T, rnd) * (1.0 - acts[i] ** 2)
    return out


def schedule(hp: dict, count: int) -> tuple[float, float]:
    """(rate, momentum) for the step after ``count`` steps."""
    n = float(count) * hp["mini_batch_size"]
    rate = hp["rate"] / (1.0 + hp["rate_annealing"] * n)
    start, stable, ramp = hp["momentum_start"], hp["momentum_stable"], hp["momentum_ramp"]
    mu = start + (stable - start) * min(n / ramp, 1.0) if ramp > 0 else start
    return rate, mu


def replay(hp: dict, before, velocity, count: int, Xs, y, rows, steps: int,
           rounded=False, dtype=np.float64, half_batch=False):
    """The weights after ``steps`` updates from ``before`` (with momentum
    ``velocity`` after ``count`` steps), taking the rows ``rows`` in order.
    ``dtype``/``half_batch`` are for the planted faults only."""
    rnd = bf16 if rounded else (lambda a: a)
    B = int(hp["mini_batch_size"])
    w = [(np.asarray(W, dtype), np.asarray(b, dtype)) for W, b in before]
    v = [(np.asarray(W, dtype), np.asarray(b, dtype)) for W, b in velocity]
    decay, nesterov = hp["rate_decay"], hp["nesterov_accelerated_gradient"]
    for k in range(steps):
        idx = rows[k * B:(k + 1) * B]
        if half_batch:
            idx = idx[: B // 2]
        g = grad(w, Xs[idx].astype(dtype), y[idx], hp["l1"], hp["l2"], rnd)
        rate, mu = schedule(hp, count + k)
        nw, nv = [], []
        for li, (pw, pv, pg) in enumerate(zip(w, v, g)):
            r = rate * decay ** li * (1.0 - mu)
            pair_w, pair_v = [], []
            for a, m, ga in zip(pw, pv, pg):
                d = (-r * ga).astype(dtype)
                m = (mu * m + d).astype(dtype)
                pair_w.append((a + (mu * m + d if nesterov else m)).astype(dtype))
                pair_v.append(m)
            nw.append(tuple(pair_w))
            nv.append(tuple(pair_v))
        w, v = nw, nv
    return w


def _flat(layers) -> np.ndarray:
    return np.concatenate([np.ravel(np.asarray(q, np.float64))
                           for pair in layers for q in pair])


def probe_gaps(hp: dict, rec: dict, Xs, y, late_count: int,
               control=False) -> tuple[dict, dict]:
    """For each probe with steps: the distance of the program's weights
    after its steps from the reference's, against the distance the
    reference's weights travelled in them; with ``control`` the same for
    the rounded replay's weights in the program's place. The late probe is
    replayed from ``late_count`` steps taken. A probe whose rows are not
    ``steps * batch`` rows of the frame is not replayed."""
    zero = [(np.zeros_like(W, np.float64), np.zeros_like(b, np.float64))
            for W, b in rec["initial"]]
    probes = {"early": (rec["initial"], zero, 0, rec["early"])}
    late = rec["late"]
    if late["steps"] and "momentum" in late:
        probes["late"] = (late["before"], late["momentum"], late_count, late)
    prog, low = {}, {}
    for name, (before, vel, count, pr) in probes.items():
        rows = np.asarray(pr["rows"])
        if (len(rows) != pr["steps"] * hp["mini_batch_size"]
                or (len(rows) and (rows.min() < 0 or rows.max() >= len(Xs)))):
            continue
        args = (hp, before, vel, count, Xs, y, rows, pr["steps"])
        want = _flat(replay(*args))
        moved = np.linalg.norm(want - _flat(before))
        prog[name] = float(np.linalg.norm(_flat(pr["after"]) - want) / moved)
        if control:
            low[name] = float(np.linalg.norm(_flat(replay(*args, rounded=True)) - want)
                              / moved)
    return prog, low


def auc(y: np.ndarray, p: np.ndarray) -> float:
    """The exact AUC: the Mann-Whitney statistic with tied scores halved."""
    order = np.argsort(p, kind="mergesort")
    ps = p[order]
    ranks = np.empty(len(p))
    first = np.concatenate([[0], np.nonzero(np.diff(ps))[0] + 1, [len(p)]])
    mid = 0.5 * (first[:-1] + first[1:] - 1) + 1  # average rank of each tie group
    ranks[order] = np.repeat(mid, np.diff(first))
    pos = y == 1
    n1, n0 = pos.sum(), (~pos).sum()
    return float((ranks[pos].sum() - n1 * (n1 + 1) / 2) / (n1 * n0))


def logloss(y: np.ndarray, p: np.ndarray) -> float:
    p = np.clip(p, 1e-15, 1 - 1e-15)
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log1p(-p)))


def compare(X, y, model: dict, hp: dict, late_count: int, control=False,
            memo: dict | None = None) -> dict:
    """``prob_gap``: the largest distance of the program's device prediction
    for a row from the reference's forward over the model's weights;
    ``logloss_gap`` (relative): the training logloss the program reported
    against the reference's from those weights; ``probe_gap``: the larger of
    the probes' gaps (``probe_gaps``, the late probe replayed from
    ``late_count`` steps). The AUC's gap is a diagnostic, not a check: the
    control (bfloat16) reads under the program on some seeds.
    ``memo``: a dict the caller keeps across comparisons of models trained
    on the same ``X`` (the planted faults'), so that the design and the
    forward over the frame are computed once for a set of weights."""
    memo = {} if memo is None else memo
    if "Xs" not in memo:
        memo["Xs"] = standardize(X)
    Xs = memo["Xs"]
    key = _flat(model["weights"]).tobytes()
    if memo.get("key") != key:
        p = prob(model["weights"], Xs)
        memo.update(key=key, p=p, ll=logloss(y, p), auc=auc(y, p))
    p, ll, a = memo["p"], memo["ll"], memo["auc"]
    probes, low_probes = probe_gaps(hp, model["probe"], Xs, y, late_count,
                                    control=control)
    program = {
        "prob_gap": float(np.max(np.abs(np.asarray(model["pred"], np.float64) - p))),
        "logloss_gap": abs(model["logloss"] - ll) / ll}
    if probes:
        program["probe_gap"] = max(probes.values())
    out = {"program": program, "control": {},
           "reference": {"logloss": ll, "auc": a},
           "diagnostic": {"probes": probes, "auc_gap": abs(model["auc"] - a)}}
    if control:
        low = prob(model["weights"], Xs, rounded=True)
        out["control"] = {
            "prob_gap": float(np.max(np.abs(low - p))),
            "logloss_gap": abs(logloss(y, low) - ll) / ll,
            "probe_gap": max(low_probes.values())}
        out["diagnostic"].update(control_probes=low_probes,
                                 control_auc_gap=abs(auc(y, low) - a))
    return out
