"""GBM/DRF tests — modeled on upstream ``hex/tree/gbm/GBMTest.java`` scenario
style [UNVERIFIED upstream path]: accuracy pinned against sklearn references,
structural invariants on the recorded trees."""

import numpy as np
import pandas as pd
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.tree import DRF, GBM


def _friedman(n=3000, seed=0, noise=0.3):
    rng = np.random.default_rng(seed)
    X = rng.random((n, 5))
    y = (
        10 * np.sin(np.pi * X[:, 0] * X[:, 1])
        + 20 * (X[:, 2] - 0.5) ** 2
        + 10 * X[:, 3]
        + 5 * X[:, 4]
        + noise * rng.normal(size=n)
    )
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(5)])
    df["y"] = y
    return df


def _binary_df(n=4000, seed=1):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 4))
    eta = X[:, 0] * 2 + X[:, 1] ** 2 - X[:, 2] - 1
    y = (rng.random(n) < 1 / (1 + np.exp(-eta))).astype(int)
    df = pd.DataFrame(X, columns=list("abcd"))
    df["y"] = np.where(y == 1, "Y", "N")
    return df, y


def test_gbm_stump_finds_optimal_split():
    # single depth-1 tree on perfectly separable step data
    x = np.linspace(0, 1, 1000)
    y = np.where(x < 0.5, 1.0, 3.0)
    fr = Frame.from_pandas(pd.DataFrame({"x": x, "y": y}))
    m = GBM(ntrees=1, max_depth=1, learn_rate=1.0, min_rows=1.0).train(
        y="y", training_frame=fr
    )
    pred = m.predict(fr).vec("predict").to_numpy()
    # histogram trees can be off by one bin (~n/nbins rows) at the boundary
    assert np.mean(np.abs(pred - y) > 0.5) < 0.03  # rows on the wrong side
    assert pred[:450] == pytest.approx(1.0, abs=0.05)
    assert pred[550:] == pytest.approx(3.0, abs=0.05)


def test_gbm_regression_beats_baseline_and_tracks_sklearn():
    from sklearn.ensemble import GradientBoostingRegressor

    df = _friedman()
    fr = Frame.from_pandas(df)
    m = GBM(ntrees=30, max_depth=4, learn_rate=0.2, min_rows=5.0, score_tree_interval=100).train(
        y="y", training_frame=fr
    )
    r2 = m.training_metrics.r2
    sk = GradientBoostingRegressor(
        n_estimators=30, max_depth=4, learning_rate=0.2
    ).fit(df.drop(columns="y"), df["y"])
    from sklearn.metrics import r2_score

    sk_r2 = r2_score(df["y"], sk.predict(df.drop(columns="y")))
    assert r2 > 0.9
    assert r2 > sk_r2 - 0.05  # within striking distance of sklearn exact-split GBM


def test_gbm_binomial_auc():
    from sklearn.ensemble import GradientBoostingClassifier
    from sklearn.metrics import roc_auc_score

    df, ybin = _binary_df()
    fr = Frame.from_pandas(df)
    m = GBM(ntrees=30, max_depth=3, learn_rate=0.2, score_tree_interval=100).train(
        y="y", training_frame=fr
    )
    auc = m.training_metrics.auc
    sk = GradientBoostingClassifier(n_estimators=30, max_depth=3, learning_rate=0.2).fit(
        df[list("abcd")], ybin
    )
    sk_auc = roc_auc_score(ybin, sk.predict_proba(df[list("abcd")])[:, 1])
    assert auc > 0.85
    assert auc > sk_auc - 0.03
    # prediction frame layout
    pred = m.predict(fr)
    assert pred.names == ["predict", "N", "Y"]
    p = pred.vec("Y").to_numpy()
    assert 0 <= p.min() and p.max() <= 1


def test_gbm_multinomial():
    rng = np.random.default_rng(3)
    n = 3000
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0.5).astype(int) + (X[:, 2] > 0.8).astype(int)
    df = pd.DataFrame(X, columns=list("abc"))
    df["y"] = np.array(["lo", "mid", "hi"])[y]
    fr = Frame.from_pandas(df)
    m = GBM(ntrees=15, max_depth=3, learn_rate=0.3, score_tree_interval=100).train(
        y="y", training_frame=fr
    )
    assert m.training_metrics.classification_error < 0.1
    pred = m.predict(fr)
    assert pred.names == ["predict", "hi", "lo", "mid"]


def test_gbm_categorical_feature():
    rng = np.random.default_rng(4)
    n = 3000
    g = rng.choice(list("pqrs"), n)
    eff = {"p": 0.0, "q": 5.0, "r": -3.0, "s": 1.0}
    y = np.array([eff[v] for v in g]) + 0.1 * rng.normal(size=n)
    fr = Frame.from_pandas(pd.DataFrame({"g": g, "y": y}))
    m = GBM(ntrees=5, max_depth=2, learn_rate=0.8, min_rows=5.0).train(
        y="y", training_frame=fr
    )
    pred = m.predict(fr).vec("predict").to_numpy()
    for v, e in eff.items():
        sel = g == v
        assert pred[sel].mean() == pytest.approx(e, abs=0.2)


def test_gbm_handles_missing_values():
    rng = np.random.default_rng(5)
    n = 2000
    x = rng.normal(size=n)
    y = np.where(np.isnan(x := np.where(rng.random(n) < 0.2, np.nan, x)), 5.0, 2 * x)
    fr = Frame.from_pandas(pd.DataFrame({"x": x, "y": y}))
    m = GBM(ntrees=10, max_depth=3, learn_rate=0.5, min_rows=5.0).train(
        y="y", training_frame=fr
    )
    assert m.training_metrics.r2 > 0.95  # NA direction must be learned
    pred = m.predict(fr).vec("predict").to_numpy()
    assert np.isnan(pred).sum() == 0


def test_gbm_poisson():
    rng = np.random.default_rng(6)
    n = 3000
    x = rng.normal(size=n)
    y = rng.poisson(np.exp(0.3 + 0.7 * x)).astype(float)
    fr = Frame.from_pandas(pd.DataFrame({"x": x, "y": y}))
    m = GBM(ntrees=20, max_depth=3, distribution="poisson", score_tree_interval=100).train(
        y="y", training_frame=fr
    )
    pred = m.predict(fr).vec("predict").to_numpy()
    assert (pred > 0).all()  # log link keeps predictions positive
    assert m.training_metrics.mean_residual_deviance < 1.5


def test_gbm_early_stopping():
    df = _friedman(n=2000, noise=2.0)
    fr = Frame.from_pandas(df)
    tr, va = fr.split_frame([0.7], seed=3)
    m = GBM(
        ntrees=200,
        max_depth=3,
        learn_rate=0.5,
        stopping_rounds=2,
        stopping_tolerance=1e-3,
        score_tree_interval=5,
    ).train(y="y", training_frame=tr, validation_frame=va)
    assert m.output["ntrees_actual"] < 200
    # scoring history carries both training and validation series
    assert "validation_rmse" in m.scoring_history[0]


def test_gbm_varimp_ranks_informative_feature():
    rng = np.random.default_rng(7)
    n = 2000
    df = pd.DataFrame(
        {
            "signal": rng.normal(size=n),
            "noise1": rng.normal(size=n),
            "noise2": rng.normal(size=n),
        }
    )
    df["y"] = 3 * df["signal"] + 0.1 * rng.normal(size=n)
    fr = Frame.from_pandas(df)
    m = GBM(ntrees=10, max_depth=3).train(y="y", training_frame=fr)
    vi = m.varimp()
    assert vi[0]["variable"] == "signal"
    assert vi[0]["percentage"] > 0.9


def test_gbm_sampling_reproducible():
    df = _friedman(n=1500)
    fr = Frame.from_pandas(df)
    kw = dict(ntrees=10, max_depth=3, sample_rate=0.7, col_sample_rate=0.8, seed=42)
    m1 = GBM(**kw).train(y="y", training_frame=fr)
    m2 = GBM(**kw).train(y="y", training_frame=fr)
    np.testing.assert_allclose(
        m1.predict(fr).vec("predict").to_numpy(),
        m2.predict(fr).vec("predict").to_numpy(),
        rtol=1e-6,
    )


@pytest.mark.slow
def test_drf_classification():
    df, ybin = _binary_df(n=3000)
    fr = Frame.from_pandas(df)
    m = DRF(ntrees=20, max_depth=10, score_tree_interval=100, seed=1).train(
        y="y", training_frame=fr
    )
    assert m.training_metrics.auc > 0.9  # in-bag training AUC is optimistic; sanity bound
    pred = m.predict(fr)
    p1 = pred.vec("Y").to_numpy()
    assert 0 <= p1.min() and p1.max() <= 1


@pytest.mark.slow
def test_drf_regression():
    df = _friedman(n=2500)
    fr = Frame.from_pandas(df)
    m = DRF(ntrees=25, max_depth=12, score_tree_interval=100, seed=2).train(
        y="y", training_frame=fr
    )
    assert m.training_metrics.r2 > 0.85


def test_drf_multinomial():
    rng = np.random.default_rng(9)
    n = 2500
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] > 0).astype(int) + (X[:, 1] > 0.5).astype(int)
    df = pd.DataFrame(X, columns=list("abc"))
    df["y"] = np.array(["A", "B", "C"])[y]
    fr = Frame.from_pandas(df)
    m = DRF(ntrees=15, max_depth=8, score_tree_interval=100, seed=3).train(
        y="y", training_frame=fr
    )
    assert m.training_metrics.classification_error < 0.15
    P = m._predict_raw(fr)
    np.testing.assert_allclose(P.sum(axis=1), 1.0, atol=1e-5)


def test_gbm_predict_on_new_frame_with_unseen_level():
    rng = np.random.default_rng(10)
    n = 1000
    g = rng.choice(["a", "b"], n)
    y = np.where(g == "a", 1.0, 2.0) + 0.01 * rng.normal(size=n)
    fr = Frame.from_pandas(pd.DataFrame({"g": g, "y": y}))
    m = GBM(ntrees=3, max_depth=1, learn_rate=1.0, min_rows=1.0).train(
        y="y", training_frame=fr
    )
    test = Frame.from_pandas(pd.DataFrame({"g": ["a", "b", "zz"], "y": [0.0, 0.0, 0.0]}))
    pred = m.predict(test).vec("predict").to_numpy()
    assert pred[0] == pytest.approx(1.0, abs=0.05)
    assert pred[1] == pytest.approx(2.0, abs=0.05)
    assert np.isfinite(pred[2])  # unseen level routes through the NA path


def test_scanned_chunk_builder_matches_loop_quality():
    """The lax.scan chunked builder (the TPU dispatch-amortization path) must
    produce trees of the same quality as the per-tree loop on CPU."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.models.tree.binning import bin_frame, fit_bins
    from h2o3_tpu.models.tree.shared_tree import (
        build_trees_scanned,
        replay_batch,
        scan_chunk_cap,
        trees_from_stacked,
    )

    df, yarr = _binary_df(n=3000, seed=5)
    fr = Frame.from_pandas(df)
    cols = [c for c in fr.names if c != "y"]
    spec = fit_bins(fr, cols)
    bins = bin_frame(spec, fr)
    npad = bins.shape[0]
    ybuf = np.zeros(npad, np.float32)
    ybuf[: fr.nrow] = yarr
    y01 = jnp.asarray(ybuf)
    w = jnp.asarray((np.arange(npad) < fr.nrow).astype(np.float32))

    from h2o3_tpu.models.tree.distributions import grad_hess, init_score

    f0 = init_score("bernoulli", np.asarray(y01)[: fr.nrow], np.ones(fr.nrow), 0.0)
    F = jnp.full(npad, f0, jnp.float32)
    varimp = jnp.zeros(len(cols), jnp.float32)

    n_trees = 8
    assert scan_chunk_cap(4, spec.max_bins) >= n_trees
    F2, varimp2, stacked = build_trees_scanned(
        bins, w, y01, F, varimp, jax.random.PRNGKey(3), n_trees,
        grad_fn=lambda F_, y_, w_: grad_hess("bernoulli", F_, y_, w_, 0.0),
        grad_key=("gbm", "bernoulli", 0.0),
        sample_rate=0.8,
        n_bins=spec.max_bins,
        is_cat_cols=spec.is_cat,
        max_depth=4,
        min_rows=5.0,
        min_split_improvement=1e-5,
        learn_rates=np.full(n_trees, 0.1, np.float32),
        max_abs_leaf=float("inf"),
        col_sample_rate=1.0,
        col_sample_rate_per_tree=1.0,
    )
    trees = trees_from_stacked(stacked, n_trees)
    assert len(trees) == n_trees and all(len(t.levels) == 5 for t in trees)

    # replay of the stacked records reproduces the carried F exactly
    F_replay = replay_batch(bins, stacked, jnp.full(npad, f0, jnp.float32))
    np.testing.assert_allclose(
        np.asarray(F_replay), np.asarray(F2), rtol=0, atol=1e-5
    )

    # quality: training AUC from the scanned ensemble clearly beats chance
    p1 = 1.0 / (1.0 + np.exp(-np.asarray(F2)[: fr.nrow]))
    from sklearn.metrics import roc_auc_score

    yv = np.asarray(y01)[: fr.nrow]
    assert roc_auc_score(yv, p1) > 0.8


def test_hist_subtraction_matches_direct(monkeypatch):
    """The fused builder's sibling-subtraction scheme (build the lighter
    child's histogram, derive the other as parent − built; terminal level
    from recorded split stats) must reproduce the direct per-node-histogram
    scheme: same splits, same leaf structure, near-identical predictions."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.models.tree.binning import bin_frame, fit_bins
    from h2o3_tpu.models.tree.distributions import grad_hess, init_score
    from h2o3_tpu.models.tree.shared_tree import (
        build_trees_scanned,
        trees_from_stacked,
    )

    rng = np.random.default_rng(11)
    n = 4000
    df = pd.DataFrame(
        {
            "a": rng.normal(size=n),
            "b": rng.normal(size=n),
            "cat": rng.choice(list("uvwxyz"), size=n),
            "c": rng.normal(size=n),
        }
    )
    df.loc[rng.random(n) < 0.05, "a"] = np.nan  # exercise the NA bin
    eta = 2 * df["a"].fillna(0) + (df["cat"].isin(["u", "v"])) * 1.5 - df["c"]
    yarr = (rng.random(n) < 1 / (1 + np.exp(-eta))).astype(np.float32)
    df["y"] = yarr

    fr = Frame.from_pandas(df)
    cols = ["a", "b", "cat", "c"]
    spec = fit_bins(fr, cols)
    bins = bin_frame(spec, fr)
    npad = bins.shape[0]
    ybuf = np.zeros(npad, np.float32)
    ybuf[: fr.nrow] = yarr
    y01 = jnp.asarray(ybuf)
    w = jnp.asarray((np.arange(npad) < fr.nrow).astype(np.float32))
    f0 = init_score("bernoulli", yarr, np.ones(fr.nrow), 0.0)

    def run():
        F = jnp.full(npad, f0, jnp.float32)
        varimp = jnp.zeros(len(cols), jnp.float32)
        F2, vi, stacked = build_trees_scanned(
            bins, w, y01, F, varimp, jax.random.PRNGKey(7), 4,
            grad_fn=lambda F_, y_, w_: grad_hess("bernoulli", F_, y_, w_, 0.0),
            grad_key=("test", "bernoulli"),
            sample_rate=0.9,
            n_bins=spec.max_bins,
            is_cat_cols=spec.is_cat,
            max_depth=4,
            min_rows=5.0,
            min_split_improvement=1e-5,
            learn_rates=np.full(4, 0.2, np.float32),
            max_abs_leaf=float("inf"),
            col_sample_rate=1.0,
            col_sample_rate_per_tree=1.0,
        )
        return np.asarray(F2), np.asarray(vi), trees_from_stacked(stacked, 4)

    monkeypatch.setenv("H2O3_TPU_HIST_SUBTRACT", "1")
    F_sub, vi_sub, trees_sub = run()
    monkeypatch.setenv("H2O3_TPU_HIST_SUBTRACT", "0")
    F_dir, vi_dir, trees_dir = run()

    np.testing.assert_allclose(F_sub, F_dir, rtol=0, atol=2e-4)
    np.testing.assert_allclose(vi_sub, vi_dir, rtol=1e-3, atol=1e-3)
    for ts, td in zip(trees_sub, trees_dir):
        for ls, ld in zip(ts.levels, td.levels):
            np.testing.assert_array_equal(
                np.asarray(ls.split_col), np.asarray(ld.split_col)
            )
            np.testing.assert_array_equal(
                np.asarray(ls.leaf_now), np.asarray(ld.leaf_now)
            )
            np.testing.assert_allclose(
                np.asarray(ls.leaf_val), np.asarray(ld.leaf_val),
                rtol=0, atol=2e-5,
            )


def test_calibrate_model_platt_and_isotonic():
    """calibrate_model/calibration_frame: cal_p columns appear and
    materially fix an overconfident (overfit) GBM's probabilities."""
    from sklearn.metrics import log_loss

    rng = np.random.default_rng(2)
    n = 6000
    X = rng.normal(size=(n, 5))
    eta = 0.8 * X[:, 0] - 0.5 * X[:, 1]
    y = (rng.random(n) < 1 / (1 + np.exp(-eta))).astype(int)
    df = pd.DataFrame(X, columns=list("abcde"))
    df["y"] = np.where(y == 1, "Y", "N")
    tr = Frame.from_pandas(df.iloc[:1500].reset_index(drop=True))
    cal = Frame.from_pandas(df.iloc[1500:3000].reset_index(drop=True))
    te = df.iloc[3000:].reset_index(drop=True)
    tef = Frame.from_pandas(te)
    yte = (te["y"] == "Y").astype(int)

    # deliberately overfit: probabilities pushed toward 0/1
    kw = dict(ntrees=150, max_depth=6, learn_rate=0.3, seed=1)
    raw = GBM(**kw).train(y="y", training_frame=tr).predict(tef).vec("Y").to_numpy()
    m = GBM(**kw, calibrate_model=True, calibration_frame=cal).train(
        y="y", training_frame=tr
    )
    out = m.predict(tef)
    assert out.names[-2:] == ["cal_p0", "cal_p1"]
    cp1 = out.vec("cal_p1").to_numpy()
    cp0 = out.vec("cal_p0").to_numpy()
    np.testing.assert_allclose(cp0 + cp1, 1.0, atol=1e-9)
    assert m.output["calibration"]["a"] < 0.8  # shrinks overconfident scores
    ll_raw = log_loss(yte, np.clip(raw, 1e-9, 1 - 1e-9))
    ll_cal = log_loss(yte, np.clip(cp1, 1e-9, 1 - 1e-9))
    assert ll_cal < ll_raw - 0.1  # material improvement

    iso = GBM(**kw, calibrate_model=True, calibration_frame=cal,
              calibration_method="IsotonicRegression").train(
        y="y", training_frame=tr
    ).predict(tef).vec("cal_p1").to_numpy()
    assert log_loss(yte, np.clip(iso, 1e-9, 1 - 1e-9)) < ll_raw - 0.1

    with pytest.raises(Exception, match="calibration_frame"):
        GBM(**kw, calibrate_model=True).train(y="y", training_frame=tr)


def test_calibration_survives_mojo_export(tmp_path):
    import os

    from h2o3_tpu.genmodel import MojoModel
    from h2o3_tpu.models.export import export_mojo

    rng = np.random.default_rng(4)
    n = 3000
    X = rng.normal(size=(n, 4))
    y = (rng.random(n) < 1 / (1 + np.exp(-X[:, 0]))).astype(int)
    df = pd.DataFrame(X, columns=list("abcd"))
    df["y"] = np.where(y == 1, "Y", "N")
    tr = Frame.from_pandas(df.iloc[:1000].reset_index(drop=True))
    cal = Frame.from_pandas(df.iloc[1000:2000].reset_index(drop=True))
    te = df.iloc[2000:].reset_index(drop=True)
    m = GBM(ntrees=40, max_depth=5, learn_rate=0.3, seed=2,
            calibrate_model=True, calibration_frame=cal).train(
        y="y", training_frame=tr
    )
    p = os.path.join(str(tmp_path), "calm.zip")
    export_mojo(m, p)
    off = MojoModel.load(p).predict(te.drop(columns="y"))
    assert "cal_p1" in off
    live = m.predict(Frame.from_pandas(te)).vec("cal_p1").to_numpy()
    np.testing.assert_allclose(off["cal_p1"], live, atol=1e-6)


@pytest.mark.slow
def test_monotone_constraints_enforced():
    """monotone_constraints: per-tree split rejection + bound propagation
    makes predictions monotone in the constrained feature at any slice."""
    rng = np.random.default_rng(1)
    n = 5000
    x = rng.uniform(-3, 3, n)
    z = rng.normal(size=n)
    y = x + 0.8 * np.sin(3 * x) + 0.5 * z + 0.2 * rng.normal(size=n)
    fr = Frame.from_pandas(pd.DataFrame({"x": x, "z": z, "y": y}))
    kw = dict(ntrees=40, max_depth=4, learn_rate=0.2, seed=1)
    m0 = GBM(**kw).train(y="y", training_frame=fr)
    m1 = GBM(**kw, monotone_constraints={"x": 1}).train(y="y", training_frame=fr)
    xs = np.linspace(-3, 3, 300)
    for zv in (-1.0, 0.0, 1.5):
        gf = Frame.from_pandas(pd.DataFrame({"x": xs, "z": np.full(300, zv)}))
        p0 = m0.predict(gf).vec("predict").to_numpy()
        p1 = m1.predict(gf).vec("predict").to_numpy()
        if zv == 0.0:
            assert (np.diff(p0) < -1e-9).sum() > 0  # wiggles without it
        assert (np.diff(p1) < -1e-9).sum() == 0  # monotone with it
    # quality stays close
    assert m1.training_metrics.value("r2") > m0.training_metrics.value("r2") - 0.05
    # decreasing constraint on -y
    fr2 = Frame.from_pandas(pd.DataFrame({"x": x, "z": z, "y": -y}))
    m2 = GBM(**kw, monotone_constraints={"x": -1}).train(y="y", training_frame=fr2)
    gf = Frame.from_pandas(pd.DataFrame({"x": xs, "z": np.zeros(300)}))
    p2 = m2.predict(gf).vec("predict").to_numpy()
    assert (np.diff(p2) > 1e-9).sum() == 0  # non-increasing

    # binary margin monotonicity (bernoulli)
    yb = (rng.random(n) < 1 / (1 + np.exp(-(x + np.sin(2 * x))))).astype(int)
    frb = Frame.from_pandas(pd.DataFrame(
        {"x": x, "z": z, "y": np.where(yb == 1, "Y", "N")}))
    mb = GBM(ntrees=30, max_depth=3, learn_rate=0.3, seed=2,
             monotone_constraints={"x": 1}).train(y="y", training_frame=frb)
    pb = mb.predict(Frame.from_pandas(
        pd.DataFrame({"x": xs, "z": np.zeros(300)}))).vec("Y").to_numpy()
    assert (np.diff(pb) < -1e-9).sum() == 0

    # validation errors
    with pytest.raises(Exception, match="categorical|unknown"):
        g = rng.choice(["a", "b"], n)
        frc = Frame.from_pandas(pd.DataFrame(
            {"x": x, "g": g, "y": y}))
        GBM(ntrees=5, monotone_constraints={"g": 1}).train(
            y="y", training_frame=frc
        )
    with pytest.raises(Exception, match="distributions"):
        GBM(ntrees=5, distribution="poisson",
            monotone_constraints={"x": 1}).train(
            y="y", training_frame=Frame.from_pandas(
                pd.DataFrame({"x": x, "y": np.abs(y)})))


@pytest.mark.parametrize("k,depth,node_cap", [
    (1, 8, 16), (2, 8, 16), (8, 8, 16),
    pytest.param(1, 13, 2048, marks=pytest.mark.slow),
])
def test_fused_whole_tree_deep_matches_per_level(monkeypatch, k, depth,
                                                 node_cap):
    """The whole-tree program — growth levels unrolled, the node_cap-
    saturated run as a ``lax.while_loop`` — must equal the per-level
    dispatch loop bit-for-bit (same inputs, same keys) on 1-, 2- and
    8-device meshes (sharded scan on >1), and at a depth beyond the old
    12-level fused cap (VERDICT r3 weak #7)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from h2o3_tpu.models.tree import shared_tree as st
    from h2o3_tpu.parallel import mesh as pm

    rng = np.random.default_rng(5)
    n, c = 4096, 5
    key = jax.random.PRNGKey(3)
    assert st._sat_region(depth, node_cap)[1] >= 2  # the loop is in play

    def run(force_per_level: bool):
        bins = pm.shard_rows(jnp.asarray(bins_np))
        w = pm.shard_rows(jnp.ones(n, jnp.float32))
        t = pm.shard_rows(jnp.asarray(t_np))
        h = pm.shard_rows(jnp.ones(n, jnp.float32))
        preds = pm.shard_rows(jnp.zeros(n, jnp.float32))
        vi = jnp.zeros(c, jnp.float32)
        if force_per_level:
            nid = pm.shard_rows(jnp.zeros(n, jnp.int32))
            for d in range(depth + 1):
                n_pad = min(1 << d, node_cap)
                n_pad_next = min(2 * n_pad, node_cap)
                step = st._level_step(n_pad, n_pad_next, 32, d == depth, (),
                                      st._split_shard_on())
                nid, preds, vi, n_split, rec = step(
                    bins, nid, preds, vi, w, w * t, h,
                    jax.random.fold_in(key, d),
                    jnp.ones(c, jnp.float32), jnp.zeros(c, bool),
                    jnp.float32(10.0), jnp.float32(1e-5), jnp.float32(0.1),
                    jnp.float32(np.inf), jnp.float32(1.0), None,
                )
            return preds, vi
        prog = st._tree_program(depth, 32, node_cap, ())
        _, preds, vi, _, counts = prog(
            bins, preds, vi, w, w * t, h, key,
            jnp.ones(c, jnp.float32), jnp.zeros(c, bool),
            jnp.float32(10.0), jnp.float32(1e-5), jnp.float32(0.1),
            jnp.float32(np.inf), jnp.float32(1.0), None,
        )
        assert int(counts[0]) >= 1  # executed saturated levels
        return preds, vi

    bins_np = rng.integers(1, 32, (n, c)).astype(np.uint8)
    t_np = rng.normal(size=n).astype(np.float32)
    # per-level builds every histogram from scratch; the whole-tree program
    # uses sibling subtraction — equality must hold exactly when it is OFF
    monkeypatch.setenv("H2O3_TPU_HIST_SUBTRACT", "0")
    old = pm._mesh
    pm.set_mesh(Mesh(np.array(jax.devices("cpu")[:k]), (pm.ROWS_AXIS,)))
    st._STEP_CACHE.clear()
    try:
        p1, v1 = run(force_per_level=False)
        p2, v2 = run(force_per_level=True)
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    finally:
        pm.set_mesh(old)
        st._STEP_CACHE.clear()  # drop subtract=False programs for later tests


def test_split_scan_tracks_f64_reference():
    """The XLA ``_split_scan`` on a float32 histogram against a numpy
    float64 prefix scan of the same data: the chosen split's child
    statistics within 5e-5 relative, its gain within 5e-4 of the float64
    gain at the SAME candidate (gains subtract nearly-equal numbers), and
    the chosen gain within 5e-4 of the float64 optimum over every column,
    bin and NA direction."""
    import jax.numpy as jnp

    from h2o3_tpu.models.tree.shared_tree import _split_scan

    rng = np.random.default_rng(9)
    n, c, N, B = 4096, 6, 16, 64
    bins = rng.integers(1, B, size=(n, c)).astype(np.uint8)
    bins[rng.random((n, c)) < 0.1] = 0  # NA bin occupied
    nid = rng.integers(0, N, size=n)
    w = rng.random(n).astype(np.float32)
    t = rng.normal(size=n).astype(np.float32)
    stats = np.stack([w, w * t, w], axis=1).astype(np.float32)

    ref = np.zeros((N, c, B, 3), np.float64)
    for col in range(c):
        np.add.at(ref[:, col], (nid, bins[:, col]), stats.astype(np.float64))
    min_rows = 10.0
    sp = _split_scan(
        jnp.asarray(ref.astype(np.float32)), jnp.zeros(c, bool),
        jnp.ones((N, c), jnp.float32), min_rows, 0.0)

    na = ref[:, :, :1, :]
    cum = np.cumsum(ref[:, :, 1:, :], axis=2)
    left = cum[:, :, :-1, :]
    right = cum[:, :, -1:, :] - left
    tot = ref.sum(axis=2)[:, 0, :]

    def fit(s):
        return -np.where(s[..., 0] > 0,
                         s[..., 1] ** 2 / np.maximum(s[..., 0], 1e-300), 0.0)

    def gains(L, R):  # (N, c, B-2)
        ok = (L[..., 0] >= min_rows) & (R[..., 0] >= min_rows)
        return np.where(ok, fit(tot)[:, None, None] - fit(L) - fit(R), -np.inf)

    g_nl, g_nr = gains(left + na, right), gains(left, right + na)
    nodes = np.arange(N)
    col_i, t_i = np.asarray(sp["col"]), np.asarray(sp["split_bin"]) - 1
    nal = np.asarray(sp["na_left"])
    L64 = (left + np.where(nal[:, None, None, None], na, 0.0))[nodes, col_i, t_i]
    R64 = (right + np.where(~nal[:, None, None, None], na, 0.0))[nodes, col_i, t_i]
    for got, want in ((sp["Lst"], L64), (sp["Rst"], R64)):
        err = np.abs(np.asarray(got) - want) / np.maximum(np.abs(want), 1.0)
        assert err.max() < 5e-5, f"child stats rel err {err.max():.2e}"
    got_gain = np.asarray(sp["gain"], np.float64)
    at_same = np.where(nal, g_nl[nodes, col_i, t_i], g_nr[nodes, col_i, t_i])
    best = np.maximum(g_nl, g_nr).reshape(N, -1).max(axis=1)
    assert np.isfinite(best).all()
    scale = np.maximum(np.abs(best), 1.0)
    assert (np.abs(got_gain - at_same) / scale).max() < 5e-4
    assert (np.abs(got_gain - best) / scale).max() < 5e-4


def test_monotone_lossguide_is_refused():
    """Monotone builds run the per-level loop, which carries no leaf
    budget: ``grow_policy=lossguide`` with constraints is refused up front
    (and the message names no removed knob)."""
    rng = np.random.default_rng(3)
    n = 400
    x = rng.normal(size=n)
    fr = Frame.from_pandas(pd.DataFrame(
        {"x": x, "z": rng.normal(size=n), "y": x + 0.1 * rng.normal(size=n)}))
    kw = dict(ntrees=2, max_depth=3, grow_policy="lossguide", max_leaves=4)
    with pytest.raises(Exception, match="monotone_constraints") as e:
        GBM(monotone_constraints={"x": 1}, **kw).train(
            y="y", training_frame=fr)
    assert "SPLIT_FUSE" not in str(e.value)
    m = GBM(**kw).train(y="y", training_frame=fr)  # without: it builds
    assert all(g[0].n_leaves <= 4 for g in m.output["trees"])


def test_efb_skips_monotone_builds(monkeypatch):
    """``H2O3_TPU_TREE_EFB=1`` bundles exclusive columns for the whole-tree
    program only: a monotone build (per-level loop) must skip bundling —
    ``tree_cols_bundled_total`` stays put — and still honour its
    constraint; the same frame without the constraint bundles."""
    from h2o3_tpu.utils import metrics as mx

    rng = np.random.default_rng(8)
    n = 1500
    which = rng.integers(0, 4, n)
    df = pd.DataFrame({f"o{j}": (which == j).astype(np.float64)
                       for j in range(4)})
    df["x"] = rng.normal(size=n)
    df["y"] = df["x"] + which + 0.1 * rng.normal(size=n)
    fr = Frame.from_pandas(df)
    monkeypatch.setenv("H2O3_TPU_TREE_EFB", "1")
    kw = dict(ntrees=3, max_depth=3, seed=4)
    b0 = mx.counter_value("tree_cols_bundled_total")
    m = GBM(monotone_constraints={"x": 1}, **kw).train(
        y="y", training_frame=fr)
    assert mx.counter_value("tree_cols_bundled_total") == b0
    sweep = pd.DataFrame({**{f"o{j}": np.zeros(50) for j in range(4)},
                          "x": np.linspace(-3, 3, 50)})
    p = m.predict(Frame.from_pandas(sweep)).vec("predict").to_numpy()
    assert (np.diff(p) >= -1e-6).all()
    GBM(**kw).train(y="y", training_frame=fr)
    assert mx.counter_value("tree_cols_bundled_total") > b0


def test_gains_lift_and_ks_match_reference():
    """Gains/lift + KS on both metric paths, pinned against a direct
    numpy computation and basic invariants."""
    import numpy as np

    from h2o3_tpu.models.metrics import binomial_metrics

    rng = np.random.default_rng(17)
    n = 4000
    y = rng.integers(0, 2, n).astype(np.float64)
    p = np.clip(rng.normal(0.35 + 0.3 * y, 0.2, n), 0.001, 0.999)
    mm = binomial_metrics(y, p, domain=("n", "p"))
    rows = mm.gains_lift()
    assert rows and len(rows) == 16
    # cumulative columns are monotone; the final row covers everything
    ccr = [r["cumulative_capture_rate"] for r in rows]
    assert all(b >= a - 1e-12 for a, b in zip(ccr, ccr[1:]))
    assert abs(ccr[-1] - 1.0) < 1e-9
    assert abs(rows[-1]["cumulative_data_fraction"] - 1.0) < 1e-9
    assert abs(rows[-1]["cumulative_lift"] - 1.0) < 1e-9
    # top group must beat baseline on this signal
    assert rows[0]["lift"] > 1.2
    # KS == max |TPR - FPR| computed directly
    order = np.argsort(-p, kind="mergesort")
    ys = y[order]
    tpr = np.cumsum(ys) / ys.sum()
    fpr = np.cumsum(1 - ys) / (1 - ys).sum()
    assert abs(mm.kolmogorov_smirnov() - np.max(np.abs(tpr - fpr))) < 1e-9


def test_gains_lift_device_path_close_to_host():
    import jax.numpy as jnp
    import numpy as np

    from h2o3_tpu.models.metrics import binomial_metrics

    rng = np.random.default_rng(3)
    n = 20000
    y = rng.integers(0, 2, n).astype(np.float64)
    p = np.clip(rng.normal(0.35 + 0.3 * y, 0.2, n), 0.001, 0.999)
    host = binomial_metrics(y, p, domain=("n", "p"))
    dev = binomial_metrics(jnp.asarray(y, jnp.float32), jnp.asarray(p, jnp.float32),
                           domain=("n", "p"))
    assert abs(host.kolmogorov_smirnov() - dev.kolmogorov_smirnov()) < 0.02
    hr, dr = host.gains_lift(), dev.gains_lift()
    assert dr and abs(hr[0]["cumulative_lift"] - dr[0]["cumulative_lift"]) < 0.1


def test_ks_zero_for_constant_predictor_any_row_order():
    """Tied scores collapse to one threshold: a constant predictor has
    KS 0 regardless of input row order (was order-dependent up to 1.0)."""
    from h2o3_tpu.models.metrics import binomial_metrics

    y_sorted = np.array([1.0] * 50 + [0.0] * 50)
    p = np.full(100, 0.5)
    mm1 = binomial_metrics(y_sorted, p, domain=("n", "p"))
    rng = np.random.default_rng(0)
    mm2 = binomial_metrics(rng.permutation(y_sorted), p, domain=("n", "p"))
    assert abs(mm1.kolmogorov_smirnov()) < 1e-12
    assert abs(mm2.kolmogorov_smirnov()) < 1e-12


def test_nbins_cats_groups_tail_levels():
    """nbins_cats caps categorical bins: levels past the cap share the last
    bin (upstream's high-cardinality grouping), and the model still trains."""
    from h2o3_tpu.models import GBM
    from h2o3_tpu.models.tree.binning import fit_bins

    rng = np.random.default_rng(2)
    n = 2000
    cat = np.array([f"lvl{i:03d}" for i in rng.integers(0, 50, n)])
    ybin = np.where((rng.random(n) < 0.3) ^ (cat < "lvl025"), "a", "b")
    df = pd.DataFrame({"c": cat, "x": rng.normal(size=n), "y": ybin})
    fr = Frame.from_pandas(df)

    spec = fit_bins(fr, ["c", "x"], nbins_cats=8)
    ci = spec.names.index("c")
    assert spec.nbins[ci] == 8  # 50 levels -> 8 bins, tail grouped
    spec_full = fit_bins(fr, ["c", "x"])
    assert spec_full.nbins[ci] == 50
    # upstream semantics: nbins_cats is INDEPENDENT of the numeric nbins —
    # a low nbins must not silently crush categorical resolution
    spec_low = fit_bins(fr, ["c", "x"], nbins=20)
    assert spec_low.nbins[ci] == 50

    m = GBM(ntrees=3, max_depth=3, nbins_cats=8, seed=1).train(
        y="y", training_frame=fr)
    assert float(m.training_metrics.auc) > 0.5


def test_model_summary_tree_table():
    """model_summary (upstream table): tree counts and depth/leaf ranges."""
    from h2o3_tpu.models import GBM

    rng = np.random.default_rng(6)
    df = pd.DataFrame({"a": rng.normal(size=800), "b": rng.normal(size=800)})
    df["y"] = np.where(df.a - df.b > 0, "p", "q")
    fr = Frame.from_pandas(df)
    m = GBM(ntrees=4, max_depth=3, seed=2).train(y="y", training_frame=fr)
    s = m.model_summary()
    assert s["number_of_trees"] == 4 and s["number_of_internal_trees"] == 4
    assert 1 <= s["min_depth"] <= s["max_depth"] <= 3
    assert 2 <= s["min_leaves"] <= s["max_leaves"] <= 2 ** 3
    assert s["mean_leaves"] >= s["min_leaves"]
