"""GLM tests — modeled on upstream ``hex/glm/GLMBasicTest*.java`` scenarios
[UNVERIFIED upstream path]: fit against known references (sklearn / closed
form) on the 8-device CPU mesh."""

import numpy as np
import pandas as pd
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.glm import GLM


def _reg_data(n=4000, p=5, seed=0, noise=0.1):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, p))
    beta = np.arange(1, p + 1, dtype=np.float64)
    y = X @ beta + 2.5 + noise * rng.normal(size=n)
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(p)])
    df["y"] = y
    return df, beta


def test_gaussian_recovers_coefficients():
    df, beta = _reg_data()
    fr = Frame.from_pandas(df)
    m = GLM(family="gaussian", lambda_=0.0).train(y="y", training_frame=fr)
    coef = m.coef
    for i, b in enumerate(beta):
        assert coef[f"x{i}"] == pytest.approx(b, abs=0.02)
    assert coef["Intercept"] == pytest.approx(2.5, abs=0.02)
    assert m.training_metrics.r2 > 0.99


def test_gaussian_matches_sklearn_ridge():
    from sklearn.linear_model import Ridge

    df, _ = _reg_data(noise=1.0)
    fr = Frame.from_pandas(df)
    lam = 0.1
    m = GLM(family="gaussian", alpha=0.0, lambda_=lam, standardize=False).train(
        y="y", training_frame=fr
    )
    n = len(df)
    sk = Ridge(alpha=lam * n, fit_intercept=True).fit(df.drop(columns="y"), df["y"])
    for i in range(5):
        assert m.coef[f"x{i}"] == pytest.approx(sk.coef_[i], abs=5e-3)


def test_binomial_matches_sklearn():
    from sklearn.linear_model import LogisticRegression

    rng = np.random.default_rng(1)
    n = 6000
    X = rng.normal(size=(n, 4))
    eta = X @ np.array([1.0, -2.0, 0.5, 0.0]) - 0.3
    y = (rng.random(n) < 1 / (1 + np.exp(-eta))).astype(int)
    df = pd.DataFrame(X, columns=list("abcd"))
    df["y"] = np.where(y == 1, "yes", "no")
    fr = Frame.from_pandas(df)
    m = GLM(family="binomial", lambda_=0.0, standardize=False).train(
        y="y", training_frame=fr
    )
    sk = LogisticRegression(penalty=None, max_iter=500).fit(X, y)
    for i, c in enumerate("abcd"):
        assert m.coef[c] == pytest.approx(sk.coef_[0][i], abs=2e-2)
    assert m.training_metrics.auc == pytest.approx(
        _sk_auc(y, sk.predict_proba(X)[:, 1]), abs=2e-3
    )


def _sk_auc(y, p):
    from sklearn.metrics import roc_auc_score

    return roc_auc_score(y, p)


def test_poisson_family():
    rng = np.random.default_rng(2)
    n = 5000
    x = rng.normal(size=n)
    mu = np.exp(0.5 + 0.8 * x)
    y = rng.poisson(mu)
    fr = Frame.from_pandas(pd.DataFrame({"x": x, "y": y.astype(float)}))
    m = GLM(family="poisson", lambda_=0.0, standardize=False).train(
        y="y", training_frame=fr
    )
    assert m.coef["x"] == pytest.approx(0.8, abs=0.05)
    assert m.coef["Intercept"] == pytest.approx(0.5, abs=0.05)


def test_lasso_sparsifies():
    rng = np.random.default_rng(3)
    n, p = 3000, 20
    X = rng.normal(size=(n, p))
    y = X[:, 0] * 3.0 + X[:, 1] * -2.0 + 0.05 * rng.normal(size=n)
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(p)])
    df["y"] = y
    fr = Frame.from_pandas(df)
    m = GLM(family="gaussian", alpha=1.0, lambda_=0.05).train(y="y", training_frame=fr)
    coef = m.coef_norm()
    nz = [k for k, v in coef.items() if abs(v) > 1e-6 and k != "Intercept"]
    assert set(nz) == {"x0", "x1"}


def test_lambda_search_path():
    df, _ = _reg_data(n=2000, noise=0.5)
    fr = Frame.from_pandas(df)
    m = GLM(family="gaussian", lambda_search=True, nlambdas=20, alpha=0.5).train(
        y="y", training_frame=fr
    )
    path = m.output["regularization_path"]
    assert len(path) >= 2
    assert path[0]["lambda"] > path[-1]["lambda"]
    assert m.training_metrics.r2 > 0.95


def test_categorical_predictors():
    rng = np.random.default_rng(4)
    n = 4000
    g = rng.choice(["a", "b", "c"], n)
    eff = {"a": 0.0, "b": 1.0, "c": -2.0}
    y = np.array([eff[v] for v in g]) + 0.1 * rng.normal(size=n)
    fr = Frame.from_pandas(pd.DataFrame({"g": g, "y": y}))
    m = GLM(family="gaussian", lambda_=0.0).train(y="y", training_frame=fr)
    # reference level 'a' dropped; effects relative to it
    assert m.coef["g.b"] == pytest.approx(1.0, abs=0.02)
    assert m.coef["g.c"] == pytest.approx(-2.0, abs=0.02)


def test_multinomial():
    rng = np.random.default_rng(5)
    n = 3000
    X = rng.normal(size=(n, 3))
    logits = X @ rng.normal(size=(3, 3)) * 2
    y = logits.argmax(axis=1)
    df = pd.DataFrame(X, columns=list("abc"))
    df["y"] = np.array(["c0", "c1", "c2"])[y]
    fr = Frame.from_pandas(df)
    m = GLM(family="multinomial", lambda_=1e-4).train(y="y", training_frame=fr)
    mm = m.training_metrics
    assert mm.classification_error < 0.08
    assert mm.logloss < 0.35
    pred = m.predict(fr)
    assert pred.names == ["predict", "c0", "c1", "c2"]


def test_weights_column():
    # duplicate-rows-vs-weight-2 equivalence, an H2O GLM test classic
    rng = np.random.default_rng(6)
    n = 1000
    x = rng.normal(size=n)
    y = 2 * x + rng.normal(size=n) * 0.1
    df1 = pd.DataFrame({"x": np.r_[x, x], "y": np.r_[y, y]})
    df2 = pd.DataFrame({"x": x, "y": y, "w": np.full(n, 2.0)})
    m1 = GLM(family="gaussian", lambda_=0.0).train(y="y", training_frame=Frame.from_pandas(df1))
    m2 = GLM(family="gaussian", lambda_=0.0, weights_column="w").train(
        y="y", training_frame=Frame.from_pandas(df2), x=["x"]
    )
    assert m1.coef["x"] == pytest.approx(m2.coef["x"], abs=1e-4)


def test_p_values():
    df, _ = _reg_data(n=2000, noise=1.0)
    fr = Frame.from_pandas(df)
    m = GLM(family="gaussian", lambda_=0.0, compute_p_values=True, standardize=False).train(
        y="y", training_frame=fr
    )
    pv = m.output["p_values"]
    assert (pv[:5] < 1e-6).all()  # true effects significant


def test_validation_frame_and_predict():
    df, _ = _reg_data(n=3000, noise=0.5)
    fr = Frame.from_pandas(df)
    tr, te = fr.split_frame([0.8], seed=1)
    m = GLM(family="gaussian").train(y="y", training_frame=tr, validation_frame=te)
    assert m.validation_metrics is not None
    assert m.validation_metrics.r2 > 0.9
    pred = m.predict(te)
    assert pred.nrow == te.nrow
    perf = m.model_performance(te)
    assert perf.rmse == pytest.approx(m.validation_metrics.rmse, rel=1e-6)


# ---------------------------------------------------------------------------
# ordinal family + L_BFGS solver (round 3)


def test_glm_ordinal_recovers_proportional_odds():
    from scipy import optimize as spo

    rng = np.random.default_rng(2)
    n = 4000
    x0, x1 = rng.normal(size=(2, n))
    eta = 1.5 * x0 - x1
    lat = eta + rng.logistic(size=n)
    yo = np.digitize(lat, [-1.0, 0.5])  # classes 0 < 1 < 2
    df = pd.DataFrame({"x0": x0, "x1": x1, "y": yo.astype(str)})
    fr = Frame.from_pandas(df, column_types={"y": "enum"})
    m = GLM(family="ordinal", standardize=False).train(y="y", training_frame=fr)
    beta = np.array([m.coef["x0"], m.coef["x1"]])
    theta = np.asarray(m.output["theta"])
    # independent numpy/scipy fit of the same likelihood
    X = np.stack([x0, x1], axis=1)

    def nll(params):
        b, t1, dt = params[:2], params[2], params[3]
        th = np.array([t1, t1 + np.exp(dt)])
        e = X @ b
        cum = 1 / (1 + np.exp(-(th[None, :] - e[:, None])))
        pk = np.diff(
            np.concatenate(
                [np.zeros((n, 1)), cum, np.ones((n, 1))], axis=1
            ), axis=1,
        )
        return -np.log(np.clip(pk[np.arange(n), yo], 1e-12, 1)).sum()

    ref = spo.minimize(nll, np.zeros(4), method="Nelder-Mead",
                       options={"maxiter": 4000, "fatol": 1e-10})
    rb = ref.x[:2]
    rt = np.array([ref.x[2], ref.x[2] + np.exp(ref.x[3])])
    np.testing.assert_allclose(beta, rb, atol=0.05)
    np.testing.assert_allclose(theta, rt, atol=0.05)
    # parameters near the generating truth
    np.testing.assert_allclose(beta, [1.5, -1.0], atol=0.15)
    np.testing.assert_allclose(theta, [-1.0, 0.5], atol=0.15)
    # predicted class probs are proper
    P = m._predict_raw(fr)
    np.testing.assert_allclose(P.sum(axis=1), 1.0, atol=1e-6)


def test_glm_lbfgs_matches_irlsm():
    rng = np.random.default_rng(5)
    n = 3000
    x0, x1 = rng.normal(size=(2, n))
    eta = 1.2 * x0 - 0.7 * x1 + 0.3
    y = (rng.random(n) < 1 / (1 + np.exp(-eta))).astype(int)
    fr = Frame.from_pandas(
        pd.DataFrame({"x0": x0, "x1": x1, "y": y.astype(str)}),
        column_types={"y": "enum"},
    )
    a = GLM(family="binomial", lambda_=0.0).train(y="y", training_frame=fr)
    b = GLM(family="binomial", lambda_=0.0, solver="L_BFGS").train(
        y="y", training_frame=fr
    )
    for k in a.coef:
        np.testing.assert_allclose(a.coef[k], b.coef[k], atol=2e-3)
    # poisson too (different link/deviance path through the same objective)
    lam = np.exp(0.5 * x0)
    yp = rng.poisson(lam)
    frp = Frame.from_pandas(pd.DataFrame({"x0": x0, "y": yp.astype(float)}))
    c = GLM(family="poisson", lambda_=0.0).train(y="y", training_frame=frp)
    d = GLM(family="poisson", lambda_=0.0, solver="L_BFGS").train(
        y="y", training_frame=frp
    )
    np.testing.assert_allclose(c.coef["x0"], d.coef["x0"], atol=2e-3)


def test_hglm_recovers_variance_components():
    from h2o3_tpu.models import HGLM

    rng = np.random.default_rng(7)
    n, q = 8000, 40
    grp = rng.integers(0, q, n)
    u_true = rng.normal(0, 1.5, q)  # sigma_u^2 = 2.25
    x = rng.normal(size=n)
    y = 2.0 + 3.0 * x + u_true[grp] + rng.normal(0, 1.0, n)  # sigma_e^2 = 1
    df = pd.DataFrame({"x": x, "g": [f"g{i:02d}" for i in grp], "y": y})
    fr = Frame.from_pandas(df, column_types={"g": "enum"})
    m = HGLM(random_columns=["g"]).train(y="y", x=["x", "g"], training_frame=fr)
    assert abs(m.coef["x"] - 3.0) < 0.05
    assert abs(m.coef["Intercept"] - 2.0) < 0.6  # absorbs group mean shift
    assert abs(m.output["sigma_e2"] - 1.0) < 0.1
    assert abs(m.output["sigma_u2"]["g"] - 2.25) < 0.8
    blups = m.coefs_random("g")
    corr = np.corrcoef([blups[f"g{i:02d}"] for i in range(q)], u_true)[0, 1]
    assert corr > 0.99  # BLUPs track the true random effects
    # shrinkage: BLUP variance below raw group-mean variance
    assert np.var(list(blups.values())) < np.var(u_true) * 1.5
    # scoring uses the BLUPs: r2 well above the fixed-effect-only fit
    assert m.training_metrics.value("r2") > 0.9


def test_hglm_validation():
    from h2o3_tpu.models import HGLM

    rng = np.random.default_rng(8)
    df = pd.DataFrame({"x": rng.normal(size=100), "y": rng.normal(size=100)})
    fr = Frame.from_pandas(df)
    with pytest.raises(Exception, match="random_columns"):
        HGLM().train(y="y", training_frame=fr)
    with pytest.raises(Exception, match="categorical"):
        HGLM(random_columns=["x"]).train(y="y", training_frame=fr)


def test_glm_ordinal_standardized_coefs_consistent():
    # standardize=True must yield the same class probabilities and the same
    # ORIGINAL-scale slopes as standardize=False (review: the intercept
    # destandardization used to clobber the last coefficient)
    rng = np.random.default_rng(3)
    n = 3000
    x0 = rng.normal(2.0, 3.0, n)  # non-trivial mean/sigma
    x1 = rng.normal(-1.0, 0.5, n)
    lat = 0.8 * x0 + 1.1 * x1 + rng.logistic(size=n)
    yo = np.digitize(lat, [0.0, 2.5])
    df = pd.DataFrame({"x0": x0, "x1": x1, "y": yo.astype(str)})
    fr = Frame.from_pandas(df, column_types={"y": "enum"})
    ms = GLM(family="ordinal", standardize=True).train(y="y", training_frame=fr)
    mu = GLM(family="ordinal", standardize=False).train(y="y", training_frame=fr)
    np.testing.assert_allclose(
        [ms.coef["x0"], ms.coef["x1"]], [mu.coef["x0"], mu.coef["x1"]],
        atol=0.03,
    )
    np.testing.assert_allclose(
        ms.output["theta_orig"], mu.output["theta"], atol=0.08
    )
    Ps = ms._predict_raw(fr)
    Pu = mu._predict_raw(fr)
    np.testing.assert_allclose(Ps, Pu, atol=0.02)


def test_hglm_two_random_columns():
    from h2o3_tpu.models import HGLM

    rng = np.random.default_rng(9)
    n = 6000
    g1 = rng.integers(0, 25, n)
    g2 = rng.integers(0, 8, n)
    u1 = rng.normal(0, 1.0, 25)
    u2 = rng.normal(0, 2.0, 8)
    x = rng.normal(size=n)
    y = 1.0 + 2.0 * x + u1[g1] + u2[g2] + rng.normal(0, 0.7, n)
    df = pd.DataFrame({"x": x, "g1": [f"a{i}" for i in g1],
                       "g2": [f"b{i}" for i in g2], "y": y})
    fr = Frame.from_pandas(df, column_types={"g1": "enum", "g2": "enum"})
    m = HGLM(random_columns=["g1", "g2"]).train(
        y="y", x=["x", "g1", "g2"], training_frame=fr
    )
    assert abs(m.coef["x"] - 2.0) < 0.05
    s = m.output["sigma_u2"]
    assert 0.5 < s["g1"] < 2.0  # true 1.0
    assert 1.5 < s["g2"] < 12.0  # true 4.0, only 8 levels -> wide
    assert abs(m.output["sigma_e2"] - 0.49) < 0.1
    c1 = np.corrcoef([m.coefs_random("g1")[f"a{i}"] for i in range(25)], u1)[0, 1]
    assert c1 > 0.99


def test_glm_interactions_recover_products(tmp_path):
    import os

    from h2o3_tpu.genmodel import MojoModel
    from h2o3_tpu.models.export import export_mojo

    rng = np.random.default_rng(3)
    n = 4000
    x1, x2 = rng.normal(size=(2, n))
    g = rng.choice(["a", "b"], n)
    slope = np.where(g == "a", 1.0, -2.0)
    y = 0.5 * x1 + 3.0 * x1 * x2 + slope * x2 + 0.1 * rng.normal(size=n)
    df = pd.DataFrame({"x1": x1, "x2": x2, "g": g, "y": y})
    fr = Frame.from_pandas(df)
    m0 = GLM(lambda_=0.0).train(y="y", x=["x1", "x2", "g"], training_frame=fr)
    m1 = GLM(lambda_=0.0, interaction_pairs=[("x1", "x2"), ("g", "x2")]).train(
        y="y", x=["x1", "x2", "g"], training_frame=fr
    )
    assert m0.training_metrics.value("r2") < 0.2  # additive model can't fit
    assert m1.training_metrics.value("r2") > 0.99
    c = m1.coef
    assert abs(c["x1:x2"] - 3.0) < 0.05  # product coefficient recovered
    assert abs(c["g.b:x2"] - (-3.0)) < 0.05  # slope delta b vs baseline a
    # export round-trips the interaction design
    p = os.path.join(str(tmp_path), "inter.zip")
    export_mojo(m1, p)
    off = MojoModel.load(p).predict(df.drop(columns="y"))["predict"]
    live = m1.predict(fr).vec("predict").to_numpy()
    np.testing.assert_allclose(off, live, atol=1e-4)
    # `interactions` list form = all pairwise
    m2 = GLM(lambda_=0.0, interactions=["x1", "x2"]).train(
        y="y", x=["x1", "x2"], training_frame=fr
    )
    assert "x1:x2" in m2.coef
    # cat x cat: combined-factor interaction (upstream enum-by-enum)
    g2 = rng.choice(["u", "v"], n)
    bump = np.where((g == "a") & (g2 == "u"), 2.5, 0.0)
    y3 = 0.5 * x1 + bump + 0.1 * rng.normal(size=n)
    df3 = pd.DataFrame({"x1": x1, "g": g, "g2": g2, "y": y3})
    fr3 = Frame.from_pandas(df3)
    # tiny ridge: with main effects present the cross indicators are exactly
    # collinear (a_v+b_v == g2.v), so lambda=0 would leave beta non-unique
    # and the live-vs-offline comparison numerically fragile
    m3 = GLM(lambda_=1e-4, alpha=0.0, interaction_pairs=[("g", "g2")]).train(
        y="y", x=["x1", "g", "g2"], training_frame=fr3
    )
    assert m3.training_metrics.value("r2") > 0.95
    assert any(k.startswith("g:g2.") for k in m3.coef)
    # scoring a fresh frame exercises the combined-code remap path
    pred = m3.predict(fr3).vec("predict").to_numpy()[:n]
    assert float(np.sqrt(np.mean((pred - y3) ** 2))) < 0.2
    # MOJO export must carry the combined-factor spec (offline == live)
    p3 = os.path.join(str(tmp_path), "catcat.zip")
    export_mojo(m3, p3)
    off3 = MojoModel.load(p3).predict(df3.drop(columns="y"))["predict"]
    np.testing.assert_allclose(off3, pred, atol=1e-4)


def test_glm_lbfgs_accepts_explicit_l1():
    """L_BFGS fits elastic net exactly now (bound-constrained split) —
    explicit alpha>0 with lambda>0 trains instead of erroring."""
    rng = np.random.default_rng(7)
    n = 500
    x0 = rng.normal(size=n)
    y = (rng.random(n) < 1 / (1 + np.exp(-x0))).astype(int)
    fr = Frame.from_pandas(
        pd.DataFrame({"x0": x0, "y": y.astype(str)}), column_types={"y": "enum"}
    )
    m = GLM(family="binomial", solver="L_BFGS", alpha=0.5, lambda_=0.01).train(
        y="y", training_frame=fr)
    assert 0.5 < float(m.training_metrics.auc) <= 1.0


def test_lbfgs_elastic_net_matches_irlsm():
    """L_BFGS now honors the L1 part of elastic net (bound-constrained
    split): coefficients track the IRLSM/ADMM solution of the same
    objective, and strong L1 produces the same sparsity pattern."""
    rng = np.random.default_rng(4)
    n, k = 3000, 8
    X = rng.normal(size=(n, k))
    beta_true = np.array([2.0, -1.5, 1.0, 0, 0, 0, 0, 0])
    y = X @ beta_true + rng.normal(size=n) * 0.5
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(k)])
    df["y"] = y
    fr = Frame.from_pandas(df)

    kw = dict(family="gaussian", alpha=0.9, lambda_=0.05)
    m_ir = GLM(solver="IRLSM", **kw).train(y="y", training_frame=fr)
    m_lb = GLM(solver="L_BFGS", **kw).train(y="y", training_frame=fr)
    c_ir = np.array([m_ir.coef[f"x{i}"] for i in range(k)])
    c_lb = np.array([m_lb.coef[f"x{i}"] for i in range(k)])
    np.testing.assert_allclose(c_lb, c_ir, atol=0.02)
    # noise coefficients are driven to (near) zero by the L1 part
    assert np.all(np.abs(c_lb[3:]) < 0.02)
    assert np.all(np.abs(c_lb[:3]) > 0.5)


def test_lbfgs_lambda_search_path():
    """lambda_search now works under L_BFGS: a warm-started geometric path
    with a regularization_path output and a best-lambda pick."""
    rng = np.random.default_rng(11)
    n, k = 1500, 6
    X = rng.normal(size=(n, k))
    y = X[:, 0] * 1.5 - X[:, 1] + rng.normal(size=n) * 0.5
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(k)])
    df["y"] = y
    fr = Frame.from_pandas(df)
    m = GLM(solver="L_BFGS", family="gaussian", alpha=0.95,
            lambda_search=True, nlambdas=20).train(y="y", training_frame=fr)
    path = m.output["regularization_path"]
    assert 2 <= len(path) <= 20
    lams = [r["lambda"] for r in path]
    assert lams == sorted(lams, reverse=True)  # descending sequence
    # deviance improves monotonically-ish down the path; best is recorded
    assert m.output["lambda_best"] == min(
        path, key=lambda r: r["deviance"])["lambda"]
    assert float(m.training_metrics.r2) > 0.6


def test_lbfgs_lambda_search_with_offset_does_not_early_stop():
    """The path early-stop uses an OFFSET-AWARE null deviance: an offset
    explaining most of the response must not terminate the path at
    lambda_max with a maximally-penalized model."""
    rng = np.random.default_rng(13)
    n = 1200
    off = rng.normal(size=n) * 3.0          # dominant known component
    x0 = rng.normal(size=n)
    y = off + 0.8 * x0 + rng.normal(size=n) * 0.3
    fr = Frame.from_pandas(pd.DataFrame({"x0": x0, "off": off, "y": y}))
    m = GLM(solver="L_BFGS", family="gaussian", alpha=1.0,
            lambda_search=True, nlambdas=12, offset_column="off",
            standardize=False).train(y="y", x=["x0"], training_frame=fr)
    path = m.output["regularization_path"]
    assert len(path) > 1, "path stopped at lambda_max (offset-blind null)"
    assert abs(m.coef["x0"] - 0.8) < 0.1


# ---------------------------------------------------------------------------
# the device predictor, and the one Model._score_metrics that routes to it


def _family_frame(family, n=3001, seed=11):
    """3001 rows: the frame's padded length (a multiple of the 8 shards)
    is longer than its rows."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 3))
    eta = X @ np.array([0.8, -0.5, 0.3]) + 0.2
    off = rng.uniform(-0.3, 0.3, n)
    df = pd.DataFrame(X, columns=list("abc"))
    df["off"] = off
    types = None
    if family == "gaussian":
        df["y"] = eta + off + 0.1 * rng.normal(size=n)
    elif family == "poisson":
        df["y"] = rng.poisson(np.exp(0.5 * eta + off)).astype(float)
    elif family == "binomial":
        p = 1 / (1 + np.exp(-(eta + off)))
        df["y"] = np.where(rng.random(n) < p, "yes", "no")
    else:  # multinomial, ordinal: three classes, no offset
        df = df.drop(columns="off")
        cls = np.digitize(eta + rng.logistic(size=n), [-0.5, 0.8])
        df["y"] = cls.astype(str)
        types = {"y": "enum"}
    return df, Frame.from_pandas(df, column_types=types)


@pytest.mark.parametrize("family", ["binomial", "gaussian", "poisson",
                                    "multinomial"])
def test_predict_raw_dev_is_predict_raw_without_the_pull(family):
    import jax

    df, fr = _family_frame(family)
    offset = None if family == "multinomial" else "off"
    m = GLM(family=family, lambda_=0.0, offset_column=offset,
            standardize=family != "multinomial").train(
        y="y", training_frame=fr, x=list("abc"))
    assert fr.npad > fr.nrow
    dev = m._device_predictor()(fr)
    assert isinstance(dev, jax.Array)
    host = m._predict_raw(fr)
    assert isinstance(host, np.ndarray)
    np.testing.assert_array_equal(host, np.asarray(dev))
    # against the formula on the original scale, in numpy
    X = df[list("abc")].to_numpy()
    if family == "multinomial":
        # fitted unstandardised: (P, K) with the intercepts in the last row
        B = np.asarray(m.output["beta_multinomial_std"], np.float64)
        z = X @ B[:-1] + B[-1]
        want = np.exp(z - z.max(axis=1, keepdims=True))
        want /= want.sum(axis=1, keepdims=True)
    else:
        eta = (X @ np.array([m.coef[c] for c in "abc"]) + m.coef["Intercept"]
               + df["off"].to_numpy())
        want = {"gaussian": eta, "poisson": np.exp(eta),
                "binomial": 1 / (1 + np.exp(-eta))}[family]
        if family == "binomial":
            want = np.stack([1 - want, want], axis=1)
    assert host.shape == want.shape
    np.testing.assert_allclose(host, want, rtol=2e-4, atol=2e-5)


def test_ordinal_declines_the_device_predictor():
    _, fr = _family_frame("ordinal")
    m = GLM(family="ordinal").train(y="y", training_frame=fr)
    assert m._device_predictor() is None
    assert m._predict_raw(fr).shape == (fr.nrow, 3)


def _as_accelerator(monkeypatch):
    """Make the routing read the backend as an accelerator, and nothing
    else: the predictors and the statistics still run on the CPU mesh."""
    import types

    import jax

    from h2o3_tpu.models import metrics as MM
    from h2o3_tpu.models import model_base

    monkeypatch.setattr(model_base, "jax",
                        types.SimpleNamespace(default_backend=lambda: "tpu"))
    monkeypatch.setattr(
        MM, "_on_device",
        lambda *arrays: any(isinstance(a, jax.Array) for a in arrays))
    seen = []
    for name in ("binomial_metrics", "multinomial_metrics",
                 "regression_metrics"):
        def spy(actual, pred, *a, _f=getattr(MM, name), **kw):
            seen.append(type(pred))
            return _f(actual, pred, *a, **kw)

        monkeypatch.setattr(MM, name, spy)
    return seen


@pytest.mark.parametrize("algo,path", [
    ("glm", "device"), ("gbm", "device"), ("glm_multinomial", "device"),
    ("glm_ordinal", "host"), ("naivebayes", "host")])
def test_score_metrics_routes_through_the_one_base_method(
        monkeypatch, algo, path):
    import jax

    from h2o3_tpu.models.model_base import Model
    from h2o3_tpu.utils import metrics as mx

    family = {"glm_multinomial": "multinomial",
              "glm_ordinal": "ordinal"}.get(algo, "binomial")
    _, fr = _family_frame(family)
    if algo == "gbm":
        from h2o3_tpu.models.tree.gbm import GBM

        m = GBM(ntrees=3, max_depth=3, seed=1).train(
            y="y", training_frame=fr, x=list("abc"))
    elif algo == "naivebayes":
        from h2o3_tpu.models.naive_bayes import NaiveBayes

        m = NaiveBayes().train(y="y", training_frame=fr, x=list("abc"))
    else:
        m = GLM(family=family).train(y="y", training_frame=fr, x=list("abc"))
    assert type(m)._score_metrics is Model._score_metrics
    cpu = m._score_metrics(fr)
    seen = _as_accelerator(monkeypatch)
    with mx.trace(f"route-{algo}"):
        got = m._score_metrics(fr)
    assert len(seen) == 1
    assert issubclass(seen[0], jax.Array) == (path == "device")
    spans = {e["name"]: e for e in mx.trace_events(f"route-{algo}")}
    assert spans["model.score_metrics"]["labels"] == {
        "algo": m.algo, "path": path}
    assert spans["model.predict_raw"]["parent"] == spans[
        "model.score_metrics"]["id"]
    assert got.nobs == cpu.nobs == fr.nrow
    assert got.logloss == pytest.approx(cpu.logloss, rel=1e-5)


# ---------------------------------------------------------------------------
# the fit's design matrix reaches the training metrics, and no further
# (ISSUE 28)

def _metric_values(mm):
    return {k: v for k, v in mm._v.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


@pytest.mark.parametrize("family,path", [
    ("binomial", "host"), ("gaussian", "host"), ("multinomial", "host"),
    ("poisson", "host"), ("binomial", "device"), ("gaussian", "device"),
    ("multinomial", "device")])
def test_training_metrics_from_the_handed_over_design(monkeypatch, family, path):
    """``train()`` scores the design matrix it fitted on; ``_score_metrics``
    of the training frame transforms it again. The same numbers, with an
    offset column where the family has one, on both metric paths."""
    from h2o3_tpu.utils import flightrec

    _, fr = _family_frame(family)
    if path == "device":
        _as_accelerator(monkeypatch)
    flightrec.reset()
    m = GLM(family=family, lambda_=1e-4,
            offset_column=None if family == "multinomial" else "off").train(
        y="y", training_frame=fr, x=list("abc"))
    designs = [e for e in flightrec.events(kind="dispatch_end")
               if e["site"] == "design"]
    assert len(designs) == 1  # the fit's; the metrics issued none
    again = m._score_metrics(fr)
    got, want = _metric_values(m.training_metrics), _metric_values(again)
    assert got.keys() == want.keys() and got["nobs"] == fr.nrow
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-6, abs=1e-9, nan_ok=True), k


@pytest.mark.parametrize("family", ["binomial", "multinomial"])
def test_no_design_matrix_outlives_train(family):
    """Nothing reachable from a finished model (its ``DataInfo`` is in its
    output, the model in the DKV) holds the (npad, p) design: after
    ``train()`` no live device array has its shape."""
    import gc

    import jax

    _, fr = _family_frame(family, n=1777)
    m = GLM(family=family, lambda_=1e-4).train(
        y="y", training_frame=fr, x=list("abc"))
    P = m.output["datainfo"].ncols_expanded
    gc.collect()
    wide = [a.shape for a in jax.live_arrays()
            if a.ndim == 2 and a.shape[0] == fr.npad and a.shape[1] >= P]
    assert wide == []
    assert m.training_metrics.nobs == fr.nrow
