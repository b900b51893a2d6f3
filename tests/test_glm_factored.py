"""The GLM's factored design: categorical columns as codes
(``DataInfo.transform_factored``), the Gram from codes
(``ops/gram.py::cat_gram_local``, the ``cat_gram_pallas`` kernel in the
Pallas interpreter here) and the linear predictor by gathers.

Against the dense one-hot path (the same program with ``_factored`` off) and
a float64 one-hot reference fit in numpy."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest
from jax.sharding import Mesh

from h2o3_tpu.estimators import H2OGeneralizedLinearEstimator
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import glm
from h2o3_tpu.models.datainfo import DataInfo, FactoredDesign
from h2o3_tpu.ops.gram import cat_gram_local, weighted_gram, weighted_gram_factored
from h2o3_tpu.parallel import mesh as pm
from h2o3_tpu.utils import metrics as mx

SMALL = {"a": 5, "b": 12, "c": 3}
PUBLISHED = {"Month": 12, "DayofMonth": 31, "DayOfWeek": 7, "UniqueCarrier": 29,
             "Origin": 350, "Dest": 350}


@contextlib.contextmanager
def _use_mesh(devs):
    old = pm._mesh
    pm.set_mesh(devs)
    try:
        yield
    finally:
        pm.set_mesh(old)


def _one_d(k):
    return Mesh(np.array(jax.devices("cpu")[:k]), (pm.ROWS_AXIS,))


def _df(n, domains, seed=0, na=0.03):
    """Categoricals with seeded level effects, two numerics, a binary
    label; ``na`` of each categorical missing."""
    rng = np.random.default_rng(seed)
    cols, eta = {}, -1.2 + np.zeros(n)
    for name, k in domains.items():
        codes = rng.integers(0, k, n)
        eta += rng.normal(0, 0.4, k)[codes]
        lv = np.array([f"{name}{i:03d}" for i in range(k)], dtype=object)[codes]
        lv[rng.random(n) < na] = None
        cols[name] = lv
    cols["x1"] = rng.normal(size=n) * 3 + 10
    cols["x2"] = rng.exponential(size=n) * 500
    eta += 0.3 * (cols["x1"] - 10) / 3 - 0.2 * np.log1p(cols["x2"] / 500)
    cols["y"] = np.where(rng.random(n) < 1 / (1 + np.exp(-eta)), "Y", "N")
    return pd.DataFrame(cols)


def _fit(fr, factored=True, monkeypatch=None, **kw):
    if not factored:
        monkeypatch.setattr(glm, "_factored", lambda *a, **k: False)
    est = H2OGeneralizedLinearEstimator(family="binomial", lambda_=1e-3, seed=1, **kw)
    est.train(y="y", training_frame=fr)
    if not factored:
        monkeypatch.undo()
    return est


def _reference(df, lam, alpha=0.5):
    """float64 one-hot fit: H2O's elastic net (intercept unpenalised,
    numerics standardised with the sample sd, first level dropped, NA an
    all-zero indicator row) by IRLS and cyclic coordinate descent to
    1e-13. Coefficients on the original scale, named as the program's."""
    names, blocks = [], []
    for c in df.columns:
        if c == "y":
            continue
        v = df[c]
        if not pd.api.types.is_numeric_dtype(v):
            levels = sorted(x for x in v.unique() if pd.notna(x))
            for lv in levels[1:]:
                names.append(f"{c}.{lv}")
                blocks.append((v == lv).fillna(False).to_numpy(float))
        else:
            x = v.to_numpy(float)
            mean, sd = x.mean(), x.std(ddof=1)
            names.append(c)
            blocks.append((x - mean) / sd)
    X = np.stack(blocks + [np.ones(len(df))], axis=1)
    y = (df["y"] == "Y").to_numpy(float)
    n, p = X.shape
    l1, l2 = lam * alpha * n, lam * (1 - alpha) * n
    beta = np.zeros(p)
    beta[-1] = np.log(y.mean() / (1 - y.mean()))
    for _ in range(50):
        eta = X @ beta
        mu = 1 / (1 + np.exp(-eta))
        W = mu * (1 - mu)
        z = eta + (y - mu) / W
        G, b = (X * W[:, None]).T @ X, (X * W[:, None]).T @ z
        new = beta.copy()
        for _ in range(100000):
            worst = 0.0
            for j in range(p):
                r = b[j] - G[j] @ new + G[j, j] * new[j]
                v = (r / G[j, j] if j == p - 1 else
                     np.sign(r) * max(abs(r) - l1, 0) / (G[j, j] + l2))
                worst, new[j] = max(worst, abs(v - new[j])), v
            if worst < 1e-13:
                break
        step, beta = np.max(np.abs(new - beta)), new
        if step < 1e-11:
            break
    ll = float(np.mean(np.logaddexp(0, X @ beta) - y * (X @ beta)))
    coef = dict(zip(names + ["Intercept"], beta))
    for c in ("x1", "x2"):
        x = df[c].to_numpy(float)
        coef[c] = beta[names.index(c)] / x.std(ddof=1)
        coef["Intercept"] -= coef[c] * x.mean()
    return coef, ll


@pytest.mark.parametrize("domains,n,coef_tol", [
    # small domains: ~20,000 rows a fit; float32 IRLS/ADMM (the ADMM stops
    # at an iterate change of 1e-6) against float64 to 1e-13
    (SMALL, 20000, 2e-4),
    # the published cardinalities on a few thousand rows: 776 columns, a
    # few rows a level, a worse-conditioned Gram: the same f32 solve
    (PUBLISHED, 3000, 2e-3)])
def test_factored_fit_matches_dense_and_float64(domains, n, coef_tol, monkeypatch):
    df = _df(n, domains, seed=1)
    fr = Frame.from_pandas(df)
    calls = mx.counter_value("glm_cat_gram_calls_total")
    fac = _fit(fr)
    assert mx.counter_value("glm_cat_gram_calls_total") > calls
    den = _fit(fr, factored=False, monkeypatch=monkeypatch)
    cf, cd = fac.model.coef, den.model.coef
    assert list(cf) == list(cd)
    # the two designs give the same Gram to float32 rounding; the f32 ADMM
    # amplifies that by the Gram's conditioning
    np.testing.assert_allclose([cf[k] for k in cf], [cd[k] for k in cf],
                               rtol=0, atol=coef_tol / 4)
    assert fac.logloss() == pytest.approx(den.logloss(), rel=1e-6)
    want, ll = _reference(df, 1e-3)
    got = np.array([cf[k] for k in want])
    np.testing.assert_allclose(got, np.array(list(want.values())), rtol=0, atol=coef_tol)
    # the device metrics' float32 logloss against float64's
    assert fac.logloss() == pytest.approx(ll, rel=1e-5)


def _frame_with_nas(n=3000, seed=4):
    df = _df(n, {"a": 6, "b": 40}, seed=seed, na=0.1)
    df.loc[np.random.default_rng(seed).random(n) < 0.05, "x1"] = np.nan
    return Frame.from_pandas(df)


@pytest.mark.parametrize("use_all", [False, True])
def test_factored_gram_equals_the_dense_gram(use_all):
    """G, b and the weight sum from codes equal ``weighted_gram`` on the
    materialised design to float32 rounding: NA codes and (with
    ``use_all_factor_levels`` false) the dropped reference level are
    all-zero indicator rows; the design itself is the same matrix."""
    fr = _frame_with_nas()
    di = DataInfo.fit(fr, ["a", "x1", "b", "x2"], use_all_factor_levels=use_all,
                      add_intercept=True)
    assert di.factorable()
    X, valid = di.transform(fr)
    F, valid2 = di.transform_factored(fr)
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(valid2))
    Xr = np.zeros(X.shape, np.float32)
    for row in np.asarray(F.codes):
        hit = np.flatnonzero(row >= 0)
        Xr[hit, row[hit]] = 1.0
    Xr[:, list(F.dense_pos)] = np.asarray(F.dense).T
    np.testing.assert_array_equal(Xr, np.asarray(X))
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.random(X.shape[0]).astype(np.float32)) * valid
    z = jnp.asarray(rng.normal(size=X.shape[0]).astype(np.float32) * 3)
    G, b, sw = weighted_gram(X, w, z)
    G2, b2, sw2 = weighted_gram_factored(F.codes, F.dense, w, z, p=F.p,
                                         dense_pos=F.dense_pos)
    scale = float(np.abs(np.asarray(G)).max())
    np.testing.assert_allclose(np.asarray(G2), np.asarray(G), rtol=0, atol=2e-6 * scale)
    np.testing.assert_allclose(np.asarray(b2), np.asarray(b), rtol=0,
                               atol=2e-6 * float(np.abs(np.asarray(b)).max()))
    assert float(sw2) == pytest.approx(float(sw), rel=1e-6)
    beta = jnp.asarray(rng.normal(size=F.p).astype(np.float32))
    np.testing.assert_allclose(np.asarray(glm._eta(F, beta)), np.asarray(glm._eta(X, beta)),
                               rtol=1e-5, atol=1e-5)


def test_sharded_gram_matches_one_device():
    """The row-sharded Gram (a local kernel a device, one psum) equals the
    kernel over all the rows on one device, on 8 devices and on a 2x4
    mesh; the fit on the 1-D and the 2-D mesh of the same 8 devices gives
    the same coefficients (every row axis reduced at once)."""
    fits = {}
    for name, mesh in [("one", _one_d(1)), ("1d", _one_d(8)),
                       ("2d", pm.make_mesh_2d(2, 4, jax.devices("cpu")[:8]))]:
        with _use_mesh(mesh):
            fr = _frame_with_nas(n=4096)
            di = DataInfo.fit(fr, ["a", "x1", "b", "x2"], use_all_factor_levels=False,
                              add_intercept=True)
            F, valid = di.transform_factored(fr)
            w = valid * 0.3
            z = jnp.where(valid > 0, 1.5, 0.0)
            got = [np.asarray(a) for a in weighted_gram_factored(
                F.codes, F.dense, w, z, p=F.p, dense_pos=F.dense_pos)]
            whole = [np.asarray(a) for a in cat_gram_local(
                *(jax.device_get(a) for a in (F.codes, F.dense, w, z)),
                p=F.p, dense_pos=F.dense_pos)]
            for a, b in zip(got, whole):
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6 * np.abs(b).max())
            est = H2OGeneralizedLinearEstimator(family="binomial", lambda_=1e-3, seed=1)
            est.train(y="y", training_frame=fr)
            fits[name] = np.asarray(est.model.output["beta_std"])
    np.testing.assert_array_equal(fits["1d"], fits["2d"])
    np.testing.assert_allclose(fits["1d"], fits["one"], rtol=0, atol=1e-4)


def test_predict_on_a_frame_with_other_domains(monkeypatch):
    """Scoring a frame whose categorical domain is ordered differently and
    lacks a training level, and holds one the training never saw (scored
    as NA, an all-zero indicator row), agrees with the dense design, and
    builds no one-hot design."""
    df = _df(4000, {"a": 6, "b": 9}, seed=2)
    est = _fit(Frame.from_pandas(df))
    test = _df(500, {"a": 6, "b": 9}, seed=3)
    test.loc[test["a"] == "a002", "a"] = "a999"  # unseen level, and a002 gone
    fr = Frame.from_pandas(test.iloc[::-1].reset_index(drop=True))
    assert fr.vec("a").domain != est.model.output["datainfo"].columns[0].domain
    before = mx.counter_value("glm_design_bytes_total")
    got = np.asarray(est.model._predict_raw_dev(fr))
    made = mx.counter_value("glm_design_bytes_total") - before
    assert made == fr.npad * 4 * (2 + 3)  # two codes rows, two numerics, intercept
    monkeypatch.setattr(glm, "_factored", lambda *a, **k: False)
    want = np.asarray(est.model._predict_raw_dev(fr))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.array_equal(est.predict(fr).vec("predict").to_numpy(),
                          est.predict(fr).vec("predict").to_numpy())


def test_numeric_only_frame_keeps_the_dense_path(monkeypatch):
    """A frame with no categorical predictor takes exactly the dense path:
    no categorical Gram pass, the dense design's bytes, and bit-identical
    coefficients with the factored design switched off."""
    df = _df(5000, {}, seed=6)
    fr = Frame.from_pandas(df)
    calls = mx.counter_value("glm_cat_gram_calls_total")
    made = mx.counter_value("glm_design_bytes_total")
    a = _fit(fr)
    assert mx.counter_value("glm_cat_gram_calls_total") == calls
    # x1, x2 and the intercept; training metrics reuse the fit's design
    assert mx.counter_value("glm_design_bytes_total") - made == fr.npad * 3 * 4
    b = _fit(fr, factored=False, monkeypatch=monkeypatch)
    np.testing.assert_array_equal(np.asarray(a.model.output["beta_std"]),
                                  np.asarray(b.model.output["beta_std"]))


def test_the_fit_stays_resident_where_the_dense_design_would_stream(monkeypatch):
    """``_plan_streamed`` sizes the ChunkStore by the factored design's
    bytes a row: under a window that holds it (and not the 776-column
    one-hot), the fit goes estimators -> Job -> GLM with no ChunkStore
    planned; the dense design (its one-hot width, ~770 columns) would
    stream."""
    from h2o3_tpu.frame import chunkstore as cs

    df = _df(2000, PUBLISHED, seed=7, na=0.0)
    fr = Frame.from_pandas(df)
    asked = []
    real = cs.ChunkStore.plan

    def plan(npad, bytes_per_row):
        store = real(npad, bytes_per_row)
        asked.append((bytes_per_row, store))
        return store

    monkeypatch.setattr(cs.ChunkStore, "plan", staticmethod(plan))
    monkeypatch.setenv("H2O3_TPU_HBM_WINDOW_BYTES", str(fr.npad * 400))
    streamed = mx.counter_value("glm_fuse_fallbacks_total", reason="streamed")
    est = _fit(fr)
    assert asked[-1] == ((6 + 3 + 3) * 4, None)
    assert mx.counter_value("glm_fuse_fallbacks_total", reason="streamed") == streamed
    assert est.model.output["regularization_path"]
    monkeypatch.setattr(glm, "_factored", lambda *a, **k: False)
    glm.GLM(family="binomial", lambda_=1e-3, max_iterations=1).train(
        y="y", training_frame=fr)
    P = est.model.output["datainfo"].ncols_expanded  # the levels 2,000 rows hold
    assert asked[-1][0] == (P + 3) * 4 and asked[-1][1] is not None


def test_a_streamed_categorical_fit_sizes_its_blocks_by_the_dense_design(
        monkeypatch):
    """Under a window too small even for the factored design the fit
    streams, and the store streams dense design blocks: its blocks are
    sized by the dense bytes a row ((P + 3) * 4), so a block and its
    prefetched neighbours fit the window."""
    from h2o3_tpu.frame import chunkstore as cs

    df = _df(16000, PUBLISHED, seed=8, na=0.0)
    fr = Frame.from_pandas(df)
    made = []
    real = cs.ChunkStore.plan

    def plan(npad, bytes_per_row):
        store = real(npad, bytes_per_row)
        made.append((bytes_per_row, store))
        return store

    monkeypatch.setattr(cs.ChunkStore, "plan", staticmethod(plan))
    window = fr.npad * 40  # under the factored design's 48 B a row
    monkeypatch.setenv("H2O3_TPU_HBM_WINDOW_BYTES", str(window))
    streamed = mx.counter_value("glm_fuse_fallbacks_total", reason="streamed")
    est = glm.GLM(family="binomial", lambda_=1e-3, max_iterations=1)
    model = est.train(y="y", training_frame=fr)
    assert mx.counter_value("glm_fuse_fallbacks_total", reason="streamed") > streamed
    P = model.output["datainfo"].ncols_expanded
    assert [b for b, _ in made] == [(6 + 3 + 3) * 4, (P + 3) * 4]
    store = made[-1][1]
    assert store is not None and store.n_blocks > 1
    assert store.block_rows * (P + 3) * 4 * (1 + store.depth) <= window
