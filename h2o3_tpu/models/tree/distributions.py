"""GBM distribution zoo — successor of H2O's ``DistributionFactory`` /
per-distribution gradient & GammaPass leaf math used by ``hex.tree.gbm.GBM``
[UNVERIFIED upstream paths, SURVEY.md §2.2].

Each distribution yields per-row (target t, hessian h) at the current raw
score F, plus the init score and the response transform for prediction.
Leaf values are Newton steps Σ(w·t)/Σh computed from the same histogram
stats (h2o's GammaPass folded into the histogram pass). Deviations from
h2o's exact leaf formulas (e.g. laplace's median leaves) are noted inline.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-10


@partial(jax.jit, static_argnames=("dist",))
def grad_hess(dist: str, f, y, w, aux: float = 0.0):
    """Per-row pseudo-residual target and hessian for the next tree."""
    if dist == "gaussian":
        return y - f, w
    if dist == "bernoulli":
        p = jax.nn.sigmoid(f)
        return y - p, w * jnp.maximum(p * (1 - p), _EPS)
    if dist == "poisson":
        mu = jnp.exp(f)
        return y - mu, w * jnp.maximum(mu, _EPS)
    if dist == "gamma":
        e = jnp.exp(-f) * y
        return e - 1.0, w * jnp.maximum(e, _EPS)
    if dist == "tweedie":
        p = aux
        a = y * jnp.exp((1.0 - p) * f)
        b = jnp.exp((2.0 - p) * f)
        return a - b, w * jnp.maximum((2.0 - p) * b - (1.0 - p) * a, _EPS)
    if dist == "laplace":
        # gradient step on sign; h2o refits leaf medians [deviation noted]
        return jnp.sign(y - f), w
    if dist == "quantile":
        alpha = aux
        return jnp.where(y > f, alpha, alpha - 1.0), w
    if dist == "huber":
        delta = aux
        r = y - f
        return jnp.clip(r, -delta, delta), w
    raise ValueError(f"unknown distribution {dist}")


@partial(jax.jit, static_argnames=("K",))
def multinomial_grad_hess(F, Y1h, w, K: int):
    """(npad,K) targets/hessians; h scaled so Newton leaves carry the
    (K-1)/K LogitBoost factor h2o applies."""
    P = jax.nn.softmax(F, axis=1)
    T = Y1h - P
    H = w[:, None] * jnp.maximum(P * (1 - P), _EPS) * (K / max(K - 1.0, 1.0))
    return T, H


def init_score(dist: str, y: np.ndarray, w: np.ndarray, aux: float = 0.0) -> float:
    """f0 — the init value (h2o's initial prediction per distribution)."""
    if dist == "laplace":
        return float(_weighted_quantile(y, w, 0.5))
    if dist == "quantile":
        return float(_weighted_quantile(y, w, aux))
    return init_score_from_sums(dist, w.sum(), (w * y).sum())


def init_score_from_sums(dist: str, sw, swy) -> float:
    """f0 from Σw and Σw·y, for every distribution whose init value is a
    function of the weighted mean (all but laplace and quantile, which take
    an order statistic: :func:`init_score`). Float32 sums give the bits
    ``init_score`` gives on float32 lanes with the same sums."""
    mean = float(swy / max(sw, _EPS))
    if dist == "gaussian" or dist == "huber":
        return mean
    if dist == "bernoulli":
        p = min(max(mean, 1e-6), 1 - 1e-6)
        return float(np.log(p / (1 - p)))
    if dist in ("poisson", "gamma", "tweedie"):
        return float(np.log(max(mean, _EPS)))
    raise ValueError(dist)


def _weighted_quantile(y, w, q):
    order = np.argsort(y)
    cw = np.cumsum(w[order])
    return y[order][np.searchsorted(cw, q * cw[-1])]


@partial(jax.jit, static_argnames=("dist",))
def response_transform(dist: str, f):
    """Raw score F -> prediction scale (linkinv)."""
    if dist == "bernoulli":
        return jax.nn.sigmoid(f)
    if dist in ("poisson", "gamma", "tweedie"):
        return jnp.exp(f)
    return f


def resolve_distribution(dist: str, yv, quantile_alpha: float, tweedie_power: float, huber_alpha: float):
    """AUTO resolution + aux parameter, mirroring h2o defaults."""
    d = (dist or "AUTO").lower()
    if d == "auto":
        if yv.is_categorical():
            d = "bernoulli" if yv.cardinality <= 2 else "multinomial"
        else:
            d = "gaussian"
    aux = 0.0
    if d == "tweedie":
        aux = float(tweedie_power)
    elif d == "quantile":
        aux = float(quantile_alpha)
    elif d == "huber":
        aux = float(huber_alpha)  # note: h2o derives delta from this quantile
    return d, aux
