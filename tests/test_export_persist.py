"""MOJO-export parity + binary save/load tests — the MOJO/POJO parity
regression net of upstream (``pyunit_*mojo*``; SURVEY.md §4): train → export
→ score offline with the numpy genmodel → assert row-wise equality with the
in-cluster predictions."""

import numpy as np
import pandas as pd
import pytest

import h2o3_tpu
import h2o3_tpu.models.export  # noqa: F401 — attaches Model.download_mojo
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.genmodel import MojoModel
from h2o3_tpu.models import DRF, GBM, GLM, DeepLearning, KMeans


def _df(n=1500, seed=0, classification=True):
    rng = np.random.default_rng(seed)
    df = pd.DataFrame({
        "num1": rng.normal(size=n),
        "num2": rng.random(n) * 10,
        "cat1": rng.choice(["a", "b", "c"], n),
    })
    df.loc[rng.choice(n, 50, replace=False), "num1"] = np.nan
    eta = df["num1"].fillna(0) + (df["cat1"] == "a") * 2 - 0.3 * df["num2"]
    if classification:
        df["y"] = np.where(eta + rng.normal(size=n) > 0, "pos", "neg")
    else:
        df["y"] = eta + 0.1 * rng.normal(size=n)
    return df


def _parity(model, df, tmp_path, prob_col, tol=1e-5):
    fr = Frame.from_pandas(df)
    path = str(tmp_path / f"{model.algo}.zip")
    model.download_mojo(path)
    mojo = MojoModel.load(path)

    incluster = model.predict(fr)
    offline = mojo.predict(df.drop(columns=["y"]))
    if prob_col is not None:
        a = incluster.vec(prob_col).to_numpy()
        b = offline[prob_col]
    else:
        a = incluster.vec("predict").to_numpy()
        b = offline["predict"]
    np.testing.assert_allclose(
        np.asarray(a, np.float64), np.asarray(b, np.float64), atol=tol, rtol=0
    )
    return mojo


def test_bin_code_equality_device_vs_mojo(tmp_path):
    """Device prebinning and the offline scorer must produce IDENTICAL bin
    codes (atol=0) — the root cause of two rounds of parity failures was an
    f32/f64 searchsorted mismatch between the two paths."""
    df = _df(seed=11, classification=False)
    fr = Frame.from_pandas(df)
    m = GBM(ntrees=2, max_depth=3, seed=3, distribution="gaussian").train(
        y="y", training_frame=fr
    )
    path = str(tmp_path / "bins.zip")
    m.download_mojo(path)
    mojo = MojoModel.load(path)

    from h2o3_tpu.models.tree.binning import bin_frame

    dev = np.asarray(bin_frame(m.output["bin_spec"], fr))[: fr.nrow]
    off = mojo._bin_features(mojo._rows_to_table(df.drop(columns=["y"])))
    np.testing.assert_array_equal(dev.astype(np.int64), off)


def test_gbm_mojo_parity(tmp_path):
    df = _df()
    fr = Frame.from_pandas(df)
    m = GBM(ntrees=10, max_depth=4, seed=3).train(y="y", training_frame=fr)
    _parity(m, df, tmp_path, "pos")


def test_gbm_regression_mojo_parity(tmp_path):
    df = _df(classification=False)
    fr = Frame.from_pandas(df)
    m = GBM(ntrees=10, max_depth=3, seed=3, distribution="gaussian").train(
        y="y", training_frame=fr
    )
    _parity(m, df, tmp_path, None, tol=1e-4)


def test_drf_mojo_parity(tmp_path):
    df = _df(seed=4)
    fr = Frame.from_pandas(df)
    m = DRF(ntrees=10, max_depth=6, seed=3).train(y="y", training_frame=fr)
    _parity(m, df, tmp_path, "pos")


def test_glm_mojo_parity(tmp_path):
    df = _df(seed=5)
    fr = Frame.from_pandas(df)
    m = GLM(family="binomial", lambda_=1e-4).train(y="y", training_frame=fr)
    _parity(m, df, tmp_path, "pos")


def test_glm_hashed_mojo_parity(tmp_path):
    """Export→score round trip for a feature-HASHED GLM: the artifact ships
    hash_buckets (no domain — the point of hashing is that the train domain
    may be Criteo-sized) and the offline scorer re-derives each bucket from
    the raw level string via crc32(col \\0 level) % hash_buckets, including
    the bucket-0 reference-level drop (GLM fits use_all_factor_levels=False).
    Scoring rows include levels NEVER seen in training — hashing must bucket
    them identically on both paths, not NA them."""
    rng = np.random.default_rng(9)
    n, card = 2000, 200
    code = rng.integers(0, card, n)
    df = pd.DataFrame({
        "c": pd.Categorical.from_codes(
            code, categories=[f"v{i}" for i in range(card)]
        ),
        "num1": rng.normal(size=n),
    })
    eta = df["num1"] + np.where(code % 2 == 0, 1.0, -1.0)
    df["y"] = np.where(eta + rng.normal(size=n) > 0, "pos", "neg")
    fr = Frame.from_pandas(df)
    m = GLM(family="binomial", lambda_=1e-4, hash_buckets=16,
            max_iterations=20).train(y="y", training_frame=fr)
    assert m.output["datainfo"].hash_buckets == 16  # hashing actually on
    mojo = _parity(m, df, tmp_path, "pos")
    assert mojo.meta["datainfo"]["hash_buckets"] == 16
    # unseen level: identical buckets (hence probabilities) on both paths
    df2 = df.head(8).copy()
    df2["c"] = [f"unseen{i}" for i in range(8)]
    fr2 = Frame.from_pandas(df2)
    a = np.asarray(m.predict(fr2).vec("pos").to_numpy(), np.float64)
    b = np.asarray(mojo.predict(df2.drop(columns=["y"]))["pos"], np.float64)
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)


def test_deeplearning_mojo_parity(tmp_path):
    df = _df(seed=6)
    fr = Frame.from_pandas(df)
    m = DeepLearning(hidden=[16], epochs=3, seed=3).train(y="y", training_frame=fr)
    _parity(m, df, tmp_path, "pos", tol=1e-3)


def test_kmeans_mojo_clusters(tmp_path):
    df = _df(seed=7).drop(columns=["y"])
    fr = Frame.from_pandas(df)
    m = KMeans(k=3, seed=3).train(training_frame=fr)
    path = str(tmp_path / "kmeans.zip")
    m.download_mojo(path)
    mojo = MojoModel.load(path)
    offline = mojo.predict(df)["cluster"]
    incluster = m.predict(fr).vec(0).to_numpy()
    assert (offline == incluster).mean() > 0.99


def test_single_row_easypredict(tmp_path):
    df = _df(seed=8)
    fr = Frame.from_pandas(df)
    m = GBM(ntrees=5, max_depth=3, seed=3).train(y="y", training_frame=fr)
    path = str(tmp_path / "m.zip")
    m.download_mojo(path)
    mojo = MojoModel.load(path)
    row = {"num1": 0.5, "num2": 3.0, "cat1": "a"}
    out = mojo.predict(row)
    assert out["predict"][0] in ("pos", "neg")
    assert out["pos"][0] + out["neg"][0] == pytest.approx(1.0, abs=1e-6)
    # unseen categorical level routes like NA, not a crash
    out2 = mojo.predict({"num1": 0.5, "num2": 3.0, "cat1": "ZZZ"})
    assert out2["pos"][0] >= 0.0


@pytest.mark.parametrize("builder,kw", [
    (GBM, dict(ntrees=5, max_depth=3, seed=2)),
    (GLM, dict(family="binomial", lambda_=1e-4)),
    (DeepLearning, dict(hidden=[8], epochs=2, seed=2)),
])
def test_binary_save_load_roundtrip(tmp_path, builder, kw):
    df = _df(seed=9)
    fr = Frame.from_pandas(df)
    m = builder(**kw).train(y="y", training_frame=fr)
    before = m.predict(fr).vec("pos").to_numpy()
    p = h2o3_tpu.save_model(m, str(tmp_path) + "/")
    h2o3_tpu.remove(m.key)
    m2 = h2o3_tpu.load_model(p)
    assert m2.key == m.key
    assert h2o3_tpu.get_model(m.key) is m2
    after = m2.predict(fr).vec("pos").to_numpy()
    np.testing.assert_allclose(before, after, atol=1e-6)


def test_generic_model_reimport_scores_live(tmp_path):
    """hex.generic successor: a tmojo zip re-imported as a live model
    predicts identically to the original in-cluster model: probabilities to
    float32 rounding, and every label the same — the artifact scores in
    float64, so the carried max-F1 threshold must not sit on a score."""
    df = _df(seed=14)
    fr = Frame.from_pandas(df)
    m = GBM(ntrees=5, max_depth=3, seed=3).train(y="y", training_frame=fr)
    path = str(tmp_path / "g.zip")
    m.download_mojo(path)

    g = h2o3_tpu.import_mojo(path, model_id="generic_test")
    assert h2o3_tpu.get_model("generic_test") is g
    pa, pb = m.predict(fr), g.predict(fr)
    np.testing.assert_allclose(
        pa.vec("pos").to_numpy(), pb.vec("pos").to_numpy(), atol=1e-5
    )
    la = pa.vec("predict").to_numpy()
    lb = pb.vec("predict").to_numpy()
    np.testing.assert_array_equal(la, lb)
    assert 0 < la.mean() < 1  # both classes are labelled
    assert g.output["source_algo"] == "gbm"


def test_generic_model_carries_the_live_threshold(tmp_path):
    """The threshold a binomial model labels with travels unchanged through
    ``download_mojo`` / ``import_mojo`` and the offline scorer, and it
    separates the scores: the max-F1 threshold of a tree model's metrics
    table is one of its (tied) scores, so the labels' threshold lies strictly
    between that score and the next one below, and a score that comes back
    one float32 ulp off (another precision, another backend) keeps its
    label."""
    from h2o3_tpu.genmodel import MojoModel

    df = _df(seed=14)
    fr = Frame.from_pandas(df)
    m = GBM(ntrees=5, max_depth=3, seed=3).train(y="y", training_frame=fr)
    path = str(tmp_path / "g.zip")
    m.download_mojo(path)
    thr = m.training_metrics.default_threshold
    g = h2o3_tpu.import_mojo(path)
    off = MojoModel.load(path)
    assert g.training_metrics.default_threshold == thr
    assert off.meta["default_threshold"] == thr

    p = m.predict(fr).vec("pos").to_numpy().astype(np.float32)
    scores = np.unique(p)
    f1_score = m.training_metrics._v["max_criteria"]["max_f1"]["threshold"]
    k = int(np.searchsorted(scores, np.float32(f1_score)))
    assert scores[k] == np.float32(f1_score) and k > 0  # it IS a score
    assert scores[k - 1] < thr < scores[k]
    live = m.predict(fr).vec("predict").to_numpy()
    for nudged in (np.nextafter(p, np.float32(0)), np.nextafter(p, np.float32(1))):
        np.testing.assert_array_equal((nudged >= thr).astype(live.dtype), live)
    offline = off.predict(df.drop(columns="y"))
    dom = list(m.output["response_domain"])
    np.testing.assert_array_equal(
        np.asarray([dom.index(v) for v in offline["predict"]]), live)


def test_pojo_standalone_scoring(tmp_path):
    """POJO-successor: a single generated .py scores with numpy only, in a
    bare subprocess with no h2o3_tpu/jax on the path."""
    import os
    import subprocess
    import sys

    from h2o3_tpu.models import GBM
    from h2o3_tpu.models.export import export_pojo

    rng = np.random.default_rng(0)
    n = 2000
    X = rng.normal(size=(n, 5)).astype(np.float32)
    df = pd.DataFrame(X, columns=[f"f{i}" for i in range(5)])
    df["y"] = np.where(X[:, 0] * 2 + X[:, 1] ** 2 > 1, "Y", "N")
    fr = Frame.from_pandas(df)
    m = GBM(ntrees=10, max_depth=4, seed=1).train(y="y", training_frame=fr)
    pojo = os.path.join(str(tmp_path), "model.py")
    export_pojo(m, pojo)
    csv = os.path.join(str(tmp_path), "rows.csv")
    df.drop(columns="y").to_csv(csv, index=False)
    r = subprocess.run(
        [sys.executable, pojo, csv], capture_output=True, text=True,
        env={"PATH": os.environ["PATH"]}, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    import io as _io

    out = pd.read_csv(_io.StringIO(r.stdout))
    ours = m.predict(fr).vec("Y").to_numpy()
    np.testing.assert_allclose(out["Y"].to_numpy(), ours, atol=1e-5)


def test_ordinal_glm_mojo_parity(tmp_path):
    from h2o3_tpu.genmodel import MojoModel
    from h2o3_tpu.models import GLM
    from h2o3_tpu.models.export import export_mojo

    rng = np.random.default_rng(6)
    n = 2000
    x0 = rng.normal(1.0, 2.0, n)
    x1 = rng.normal(size=n)
    yo = np.digitize(0.9 * x0 - x1 + rng.logistic(size=n), [0.0, 2.0])
    df = pd.DataFrame({"x0": x0, "x1": x1, "y": yo.astype(str)})
    fr = Frame.from_pandas(df, column_types={"y": "enum"})
    m = GLM(family="ordinal").train(y="y", training_frame=fr)
    p = str(tmp_path / "ordinal.zip")
    export_mojo(m, p)
    mojo = MojoModel.load(p)
    offline = mojo.score_raw(mojo._rows_to_table(df.drop(columns="y")))
    live = m._predict_raw(fr)
    np.testing.assert_allclose(offline, live, atol=1e-5)
    assert offline.shape == (n, 3)


def test_mojo_leaf_node_assignment_parity(tmp_path):
    """Offline scorer's leaf assignment == in-cluster
    predict_leaf_node_assignment, both types."""
    df = _df(500, seed=6)
    fr = Frame.from_pandas(df)
    m = GBM(ntrees=3, max_depth=3, seed=8).train(y="y", training_frame=fr)
    path = str(tmp_path / "leafmojo.zip")
    m.download_mojo(path)
    mojo = MojoModel.load(path)
    table = {c: df[c].to_numpy() for c in df.columns if c != "y"}

    ids_cluster = m.predict_leaf_node_assignment(fr, type="Node_ID")
    paths_cluster = m.predict_leaf_node_assignment(fr, type="Path")
    ids_mojo = mojo.leaf_node_assignment(table, type="Node_ID")
    paths_mojo = mojo.leaf_node_assignment(table, type="Path")
    for c in ids_cluster.names:
        np.testing.assert_array_equal(
            ids_cluster.vec(c).to_numpy().astype(int), ids_mojo[c])
        pv = paths_cluster.vec(c)
        s = np.asarray(pv.levels())[pv.to_numpy().astype(int)]
        np.testing.assert_array_equal(s.astype(str), paths_mojo[c].astype(str))
