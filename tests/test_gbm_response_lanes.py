"""GBM's response and weight lanes made on the device (``gbm._response_lanes``)
against the host recipe they replace, which lives on here as the reference;
the initial score from the pulled sums; the counter that says where a build's
lanes were made; and the training metrics over the padded device lanes."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import glm
from h2o3_tpu.models import metrics as MM
from h2o3_tpu.models.tree import gbm
from h2o3_tpu.models.tree.distributions import init_score
from h2o3_tpu.utils import metrics as mx

N = 203  # not a multiple of the mesh's row block: the frame has pad rows


@pytest.fixture(scope="module")
def frame():
    rng = np.random.default_rng(11)
    x = rng.normal(size=(N, 3)).astype(np.float32)
    eta = x[:, 0] - 0.5 * x[:, 1]
    df = pd.DataFrame(x, columns=["x0", "x1", "x2"])
    df["yb"] = np.where(eta + 0.3 * rng.normal(size=N) > 0, "s", "b").astype(object)
    df.loc[rng.choice(N, 13, replace=False), "yb"] = None
    # labels on a grid of quarters, so that float32 sums of them are exact
    df["yg"] = (np.round(4 * eta) / 4).astype(np.float32)
    df.loc[rng.choice(N, 11, replace=False), "yg"] = np.nan
    df["ym"] = np.array(["a", "b", "c"], dtype=object)[np.digitize(eta, [-0.5, 0.5])]
    df["yp"] = rng.poisson(np.exp(0.4 * eta)).astype(np.float32)
    df["wt"] = rng.uniform(0.5, 2.0, N).astype(np.float32)
    df.loc[rng.choice(N, 9, replace=False), "wt"] = np.nan
    fr = Frame.from_pandas(df)
    assert fr.npad > fr.nrow
    return fr


def _host_lanes(frame, label, weights, classification, spw=1.0):
    """The lanes as ``GBM._build`` made them on the host before they moved
    to the device: ``(ybuf, w_metric, w_train)``, float32, padded."""
    npad, nrow = frame.npad, frame.nrow
    y_np = frame.vec(label).to_numpy().astype(np.float64)
    w_np = np.zeros(npad, np.float32)
    w_np[:nrow] = 1.0
    if weights:
        w_np[:nrow] *= np.nan_to_num(frame.vec(weights).to_numpy()).astype(np.float32)
    w_np[:nrow] *= ~np.isnan(y_np) if not classification else (y_np >= 0)
    ybuf = np.zeros(npad, np.float32)
    ybuf[:nrow] = np.nan_to_num(y_np, nan=0.0)
    w_train = w_np
    if spw != 1.0:
        w_train = w_np.copy()
        w_train[:nrow] *= np.where(ybuf[:nrow] == 1.0, spw, 1.0).astype(np.float32)
    return ybuf, w_np, w_train


def _host_f0(dist, ybuf, w_np, nrow, aux=0.0, K=0):
    """The initial score as ``GBM._build`` computed it from the host lanes."""
    if dist == "multinomial":
        prior_p = np.array(
            [max((w_np * (ybuf == k)).sum() / max(w_np.sum(), 1e-30), 1e-9) for k in range(K)]
        )
        return np.log(prior_p).astype(np.float32)
    return init_score(dist, ybuf[:nrow], w_np[:nrow], aux)


# case -> (distribution, label, weights column, scale_pos_weight, aux)
CASES = {
    "bernoulli_missing_labels": ("bernoulli", "yb", None, 1.0, 0.0),
    "gaussian_nan_labels": ("gaussian", "yg", None, 1.0, 0.0),
    "weights_with_nan": ("gaussian", "yg", "wt", 1.0, 0.0),
    "scale_pos_weight": ("bernoulli", "yb", None, 3.0, 0.0),
    "multinomial_priors": ("multinomial", "ym", None, 1.0, 0.0),
    "poisson": ("poisson", "yp", None, 1.0, 0.0),
    "laplace": ("laplace", "yg", None, 1.0, 0.0),
    "quantile": ("quantile", "yg", "wt", 1.0, 0.3),
}


@pytest.mark.parametrize("case", list(CASES))
def test_device_lanes_and_f0_equal_the_host_recipe(frame, case):
    dist, label, weights, spw, aux = CASES[case]
    yv = frame.vec(label)
    K = yv.cardinality if dist == "multinomial" else 0
    y, w, w_train, sums = gbm._response_lanes(
        yv.data, frame.vec(weights).data if weights else None, frame.nrow,
        spw=spw, n_classes=K)
    classification = dist in ("bernoulli", "multinomial")
    ybuf, want_w, want_wt = _host_lanes(frame, label, weights, classification, spw)
    y, w = np.asarray(y), np.asarray(w)
    assert y.dtype == w.dtype == np.float32
    np.testing.assert_array_equal(w, want_w)
    # the missing-label rules are GLM's, lane for lane
    valid = jnp.asarray((np.arange(frame.npad) < frame.nrow).astype(np.float32))
    gy, gw, _, _ = glm._response_lanes(
        yv.data, valid, frame.vec(weights).data if weights else None, None)
    np.testing.assert_array_equal(y, np.asarray(gy))
    np.testing.assert_array_equal(w, np.asarray(gw))
    # the host kept a missing categorical label's code (-1) in its 0-weight
    # row; the device lane holds 0 there, as GLM's does
    np.testing.assert_array_equal(
        y, np.where(ybuf < 0, 0, ybuf) if classification else ybuf)
    if spw == 1.0:
        assert w_train is None
    else:
        np.testing.assert_array_equal(np.asarray(w_train), want_wt)
        assert (want_wt != want_w).any()
    want_f0 = _host_f0(dist, ybuf, want_w, frame.nrow, aux, K)
    if dist in ("laplace", "quantile"):  # the build pulls the lanes for these
        f0 = init_score(dist, y[: frame.nrow], w[: frame.nrow], aux)
    else:
        f0 = gbm._initial_score(dist, np.asarray(sums))
    if weights is None:  # 0/1 weights, labels on a grid: exact sums, the same bits
        np.testing.assert_array_equal(np.asarray(f0), np.asarray(want_f0))
    else:  # float32 sums of other weights are summed in another order
        np.testing.assert_allclose(np.asarray(f0), np.asarray(want_f0), rtol=1e-6)


def test_padded_rows_weigh_nothing_whatever_their_label(frame):
    """The row-validity mask, not the label's NA fill, keeps pad rows out: a
    label lane whose pad rows hold a valid code still weighs them 0."""
    nrow = frame.nrow
    codes = np.asarray(frame.vec("yb").data).copy()
    codes[nrow:] = 1
    y, w, _, sums = gbm._response_lanes(jnp.asarray(codes), None, nrow)
    w = np.asarray(w)
    assert (w[nrow:] == 0).all() and (np.asarray(y)[nrow:] == 1).all()
    ybuf, want_w, _ = _host_lanes(frame, "yb", None, True)
    np.testing.assert_array_equal(w, want_w)
    assert gbm._initial_score("bernoulli", np.asarray(sums)) == _host_f0(
        "bernoulli", ybuf, want_w, nrow)


@pytest.mark.parametrize("dist,path", [
    ("bernoulli", "device"), ("quantile", "host"), ("laplace", "host")])
def test_a_build_counts_where_its_lanes_were_made(frame, dist, path):
    from h2o3_tpu import estimators as E

    label = "yb" if dist == "bernoulli" else "yg"
    before = {p: mx.counter_value("tree_response_lanes_total", path=p)
              for p in ("device", "host")}
    est = E.H2OGradientBoostingEstimator(
        ntrees=4, max_depth=3, score_tree_interval=2, seed=1, distribution=dist)
    est.train(x=["x0", "x1", "x2"], y=label, training_frame=frame)
    counts = {p: mx.counter_value("tree_response_lanes_total", path=p) - before[p]
              for p in ("device", "host")}
    assert counts == {path: 1.0, ({"device", "host"} - {path}).pop(): 0.0}
    m = est.model
    ybuf, w_np, _ = _host_lanes(frame, label, None, dist == "bernoulli")
    assert m.output["init_f"] == _host_f0(
        dist, ybuf, w_np, frame.nrow, 0.5 if dist == "quantile" else 0.0)
    if dist != "bernoulli":
        return
    # the training metrics over the padded device lanes against the host
    # metrics over the frame's rows and the model's own predictions
    codes = frame.vec(label).to_numpy().astype(np.float64)
    p1 = m.predict(frame).vec("s").to_numpy().astype(np.float64)
    ref = MM.binomial_metrics(np.where(codes < 0, np.nan, codes), p1)
    assert m.scoring_history[-1]["training_logloss"] == pytest.approx(ref.logloss, abs=1e-6)
    assert m.training_metrics.logloss == pytest.approx(ref.logloss, abs=1e-6)
    assert m.training_metrics.auc == pytest.approx(ref.auc, abs=1e-6)
    assert m.training_metrics.nobs == ref.nobs == int((codes >= 0).sum())


@pytest.mark.parametrize("dist", ["bernoulli", "gaussian", "multinomial"])
def test_device_metrics_take_the_padded_lanes_whole(frame, monkeypatch, dist):
    """On an accelerator the statistics reduce the padded lanes and scores
    as they are; they agree with the statistics of the frame's rows alone,
    as the host lanes and sliced scores gave them."""
    label = {"bernoulli": "yb", "gaussian": "yg", "multinomial": "ym"}[dist]
    yv = frame.vec(label)
    K = yv.cardinality if dist == "multinomial" else 0
    y, w, _, _ = gbm._response_lanes(yv.data, None, frame.nrow, n_classes=K)
    rng = np.random.default_rng(3)
    shape = (frame.npad, K) if K else (frame.npad,)
    F = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    ybuf, w_np, _ = _host_lanes(frame, label, None, K > 0 or dist == "bernoulli")
    monkeypatch.setattr(gbm, "jax", types.SimpleNamespace(
        default_backend=lambda: "tpu", nn=jax.nn))
    monkeypatch.setattr(
        MM, "_on_device", lambda *arrays: any(isinstance(a, jax.Array) for a in arrays))
    got = gbm._metrics_from_F(dist, F, y, w, None)
    want = gbm._metrics_from_F(dist, F, ybuf, w_np, frame.nrow)
    keys = {"bernoulli": ("logloss", "mse", "auc", "pr_auc"),
            "gaussian": ("mse", "mae", "r2"),
            "multinomial": ("logloss", "mse", "classification_error")}[dist]
    for k in keys:
        assert got._v[k] == pytest.approx(want._v[k], rel=1e-6), k
    assert got.nobs == want.nobs
