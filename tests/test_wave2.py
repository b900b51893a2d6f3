"""Tree kernel wave 2 (ISSUE 16): GOSS row sampling, exclusive feature
bundling, u8-code-native binned frames, int16 histogram lanes, and
leaf-wise (lossguide) growth. Every lever ships with a forced-off control
that must reproduce today's path bit-for-bit, and every fast path must
stay inside its documented accuracy envelope."""

import contextlib
import os

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.tree import GBM
from h2o3_tpu.models.tree import shared_tree as st
from h2o3_tpu.models.tree.binning import bin_frame, fit_bins, fit_efb
from h2o3_tpu.parallel import mesh as pm
from h2o3_tpu.utils import metrics as mx


@contextlib.contextmanager
def _use_mesh(k: int):
    """Run under a k-device sub-mesh of the 8-device CPU test cloud."""
    devs = jax.devices("cpu")
    assert len(devs) >= k, "8-device conftest pin did not land"
    old = pm._mesh
    pm.set_mesh(Mesh(np.array(devs[:k]), (pm.ROWS_AXIS,)))
    try:
        yield
    finally:
        pm.set_mesh(old)


@contextlib.contextmanager
def _env(**kv):
    old = {k: os.environ.get(k) for k in kv}
    for k, v in kv.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = str(v)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _dense_df(n=3000, seed=0, c=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, c))
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(c)])
    df["y"] = X[:, 0] * 2 - X[:, 1] + 0.3 * rng.normal(size=n)
    return df


def _onehot_df(n=2400, seed=1, levels=8, dense=2):
    """EFB-friendly design: one-hot indicator columns (mutually exclusive
    by construction — zero conflicts) plus a couple of dense columns."""
    rng = np.random.default_rng(seed)
    g = rng.integers(0, levels, n)
    cols = {f"oh{j}": (g == j).astype(np.float32) for j in range(levels)}
    for j in range(dense):
        cols[f"d{j}"] = rng.normal(size=n).astype(np.float32)
    df = pd.DataFrame(cols)
    df["y"] = (
        0.7 * (g % 3) + df["d0"] - 0.5 * df["d1"]
        + 0.2 * rng.normal(size=n)
    )
    return df


def _cls_df(n=4000, seed=2, c=6):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, c))
    eta = X[:, 0] * 1.5 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(c)])
    df["y"] = np.where(rng.random(n) < 1 / (1 + np.exp(-eta)), "a", "b")
    return df, (df["y"] == "a").to_numpy()


def _train(fr, **kw):
    params = dict(ntrees=8, max_depth=4, seed=7, distribution="gaussian")
    params.update(kw)
    return GBM(**params).train(y="y", training_frame=fr)


def _pred(m, fr, col="predict"):
    p = m.predict(fr)
    return p.vec(col if col in p.names else p.names[-1]).to_numpy()


# ---------------------------------------------------------------------------
# GOSS (H2O3_TPU_TREE_GOSS)


def test_goss_factor_amplification_pin():
    """The sampling factor itself: top-a rows by |gradient| keep weight
    1.0 exactly, kept rest rows get exactly (1-a)/b, dropped rows get 0,
    and invalid (sampled-out) rows stay out."""
    rng = np.random.default_rng(0)
    n = 4096
    w = np.ones(n, np.float32)
    w[:100] = 0.0  # already sampled out
    wy = rng.normal(size=n).astype(np.float32) * w
    a, b = 0.2, 0.1
    f = np.asarray(st._goss_factor(
        jnp.asarray(w), jnp.asarray(wy), jax.random.PRNGKey(3), a, b))
    n_valid = int((w > 0).sum())
    k = int(round(a * n_valid))
    amp = (1.0 - a) / b
    assert set(np.unique(f)).issubset({0.0, 1.0, np.float32(amp)})
    assert (f[w == 0] == 0).all()
    # the top-k |gradient| rows are exactly the factor-1.0 rows
    order = np.argsort(-np.abs(wy))
    top = order[:k]
    assert (f[top] == 1.0).all()
    # expected kept-rest count: Binomial(n_valid - k, b/(1-a))
    kept_rest = int((f == np.float32(amp)).sum())
    exp = (n_valid - k) * b / (1 - a)
    assert abs(kept_rest - exp) < 4 * np.sqrt(exp)


def test_goss_ab_parsing_and_validation():
    with _env(H2O3_TPU_TREE_GOSS="0.2,0.1"):
        assert st._goss_ab() == (0.2, 0.1)
    with _env(H2O3_TPU_TREE_GOSS=""):
        assert st._goss_ab() is None
    for bad in ("0.2", "1.1,0.1", "0.5,0.6", "0.2,0", "-0.1,0.5"):
        with _env(H2O3_TPU_TREE_GOSS=bad):
            with pytest.raises(ValueError):
                st._goss_ab()


@pytest.mark.slow
def test_goss_auc_envelope_and_counter():
    """GOSS at (a=0.2, b=0.1) trains on ~30% of rows per tree yet must
    stay inside a tight AUC envelope of the full-data build, and the
    modeled rows-sampled counter must tally exactly (a+b)*npad*ntrees."""
    from sklearn.metrics import roc_auc_score

    df, y = _cls_df()
    fr = Frame.from_pandas(df)
    kw = dict(ntrees=20, max_depth=4, seed=7, distribution="bernoulli")
    base = GBM(**kw).train(y="y", training_frame=fr)
    auc_base = roc_auc_score(y, _pred(base, fr, "a"))
    c0 = mx.counter_value("tree_rows_sampled_total")
    with _env(H2O3_TPU_TREE_GOSS="0.2,0.1"):
        goss = GBM(**kw).train(y="y", training_frame=fr)
    auc_goss = roc_auc_score(y, _pred(goss, fr, "a"))
    assert auc_goss > auc_base - 0.03
    dc = mx.counter_value("tree_rows_sampled_total") - c0
    assert dc == pytest.approx(0.3 * fr.npad * 20, rel=1e-6)


def test_goss_off_bit_identical():
    """The forced-off control: H2O3_TPU_TREE_GOSS='' must reproduce the
    unset-knob build bit-for-bit."""
    fr = Frame.from_pandas(_dense_df(seed=3))
    p0 = _pred(_train(fr), fr)
    with _env(H2O3_TPU_TREE_GOSS=""):
        p1 = _pred(_train(fr), fr)
    np.testing.assert_array_equal(p0, p1)


def test_goss_composes_with_sample_rate():
    """GOSS draws only among rows the per-tree bagging kept (w>0), so the
    two samplers compose rather than clobber each other."""
    fr = Frame.from_pandas(_dense_df(seed=4))
    with _env(H2O3_TPU_TREE_GOSS="0.2,0.1"):
        m = _train(fr, sample_rate=0.7)
    p = _pred(m, fr)
    assert np.isfinite(p).all()
    y = _dense_df(seed=4)["y"].to_numpy()
    assert np.corrcoef(p, y)[0, 1] > 0.8


# ---------------------------------------------------------------------------
# EFB (H2O3_TPU_TREE_EFB)


def test_efb_plan_shrinks_onehot_columns():
    """8 mutually-exclusive one-hot columns + 2 dense must bundle into far
    fewer histogram columns (>= 1.5x shrink, the acceptance floor)."""
    df = _onehot_df()
    fr = Frame.from_pandas(df)
    cols = [c for c in df.columns if c != "y"]
    spec = fit_bins(fr, cols)
    bins = bin_frame(spec, fr)
    plan = fit_efb(spec, bins, nrow=fr.nrow)
    assert plan is not None
    assert plan.n_cols == len(cols)
    assert plan.n_cols / plan.n_cols_b >= 1.5


def _split_structure(m):
    """(col, bin, leaf, na_left) arrays over the REAL node slots of every
    level of every tree — the split-decision fingerprint EFB must not
    perturb."""
    out = []
    for it in m.output["trees"]:
        for t in it:
            h = t.to_host()
            for lv, mask in zip(h.levels, h.real_level_masks()):
                out.append((
                    np.asarray(lv.split_col)[mask],
                    np.asarray(lv.split_bin)[mask],
                    np.asarray(lv.leaf_now)[mask],
                    np.asarray(lv.na_left)[mask],
                ))
    return out


def _integer_onehot_df(n=2400, seed=5, levels=8):
    """Integer-exact EFB parity suite: one-hot features and an integer,
    exactly-zero-mean response. With unit weights the stat lanes stay
    small in-range integers, so f32 sums are exact everywhere and EFB's
    default-cell reconstruction (node_total - sum of non-default) is
    bit-exact — the regime where 'bit-equal splits' is a theorem, not a
    tie-break accident."""
    rng = np.random.default_rng(seed)
    g = rng.integers(0, levels, n // 2)
    y_half = (g % 3 - 1).astype(np.float32)  # in {-1, 0, 1}
    g = np.concatenate([g, g])
    y = np.concatenate([y_half, -y_half])  # integer sum == exactly 0
    cols = {f"oh{j}": (g == j).astype(np.float32) for j in range(levels)}
    cols["flip"] = np.repeat([0.0, 1.0], n // 2).astype(np.float32)
    # one dense column so the BinSpec's code space (max_bins) is wide
    # enough to pack the one-hot columns' ~3-code ranges into one bundle —
    # an all-binary frame caps max_bins at ~5 and no bundle has room.
    # Dense FEATURE values may be float: the stat lanes (unit w, integer y)
    # are what exactness needs
    x = rng.normal(size=n // 2).astype(np.float32)
    cols["dense"] = np.concatenate([x, x])
    df = pd.DataFrame(cols)
    df["y"] = y
    return df


@pytest.mark.parametrize("k", [1, 2, 8])
def test_efb_bit_equal_splits_across_meshes(k):
    """EFB on integer-exact stat lanes must reproduce the unbundled build
    BIT-for-bit — split structure and predictions — on 1-, 2- and 8-device
    meshes, and the bundled-columns counter must tally the C shrink."""
    df = _integer_onehot_df()
    with _use_mesh(k):
        fr = Frame.from_pandas(df)
        kw = dict(ntrees=1, max_depth=4)
        m0 = _train(fr, **kw)
        p0 = _pred(m0, fr)
        c0 = mx.counter_value("tree_cols_bundled_total")
        with _env(H2O3_TPU_TREE_EFB="1"):
            m1 = _train(fr, **kw)
        p1 = _pred(m1, fr)
        for s0, s1 in zip(_split_structure(m0), _split_structure(m1)):
            for a0, a1 in zip(s0, s1):
                np.testing.assert_array_equal(a0, a1)
        np.testing.assert_array_equal(p0, p1)
        assert mx.counter_value("tree_cols_bundled_total") > c0


@pytest.mark.slow
def test_efb_float_gradients_quality_envelope():
    """On float gradient lanes the default-cell reconstruction carries an
    f32-associativity envelope: equal-gain threshold ties may break
    differently, but predictions must stay within a tight envelope of the
    unbundled build."""
    fr = Frame.from_pandas(_onehot_df(seed=5))
    p0 = _pred(_train(fr), fr)
    with _env(H2O3_TPU_TREE_EFB="1"):
        p1 = _pred(_train(fr), fr)
    np.testing.assert_allclose(p0, p1, atol=1e-4)


def test_efb_off_is_default():
    """The knob defaults off: no bundling work, counter quiet."""
    fr = Frame.from_pandas(_onehot_df(seed=6))
    c0 = mx.counter_value("tree_cols_bundled_total")
    _train(fr)
    assert mx.counter_value("tree_cols_bundled_total") == c0


def test_efb_skips_dense_frames():
    """All-dense designs have nothing to bundle: fit_efb declines and the
    build takes the ordinary path (knob on, counter quiet)."""
    fr = Frame.from_pandas(_dense_df(seed=7))
    p0 = _pred(_train(fr), fr)
    c0 = mx.counter_value("tree_cols_bundled_total")
    with _env(H2O3_TPU_TREE_EFB="1"):
        p1 = _pred(_train(fr), fr)
    np.testing.assert_array_equal(p0, p1)
    assert mx.counter_value("tree_cols_bundled_total") == c0


# ---------------------------------------------------------------------------
# int16 histogram lanes (H2O3_TPU_HIST_I16)


def _hist_case(n=3000, c=4, n_nodes=4, n_bins=16, seed=8, integer=True):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, n_bins, size=(n, c)).astype(np.uint8)
    nid = rng.integers(0, n_nodes, size=n).astype(np.int32)
    if integer:
        s = rng.integers(-5, 6, size=(n, 3)).astype(np.float32)
    else:
        s = rng.normal(size=(n, 3)).astype(np.float32)
    # histogram_in_jit takes stats as a sequence of (n,) lanes
    lanes = tuple(jnp.asarray(s[:, i]) for i in range(3))
    return jnp.asarray(bins), jnp.asarray(nid), lanes


def test_i16_exact_on_integer_stats():
    """Small-integer stat lanes (|v| <= 127, integral — the w/count lanes)
    hit the scale-1 EXACT path: the i16 histogram equals the f32 one
    bit-for-bit."""
    from h2o3_tpu.ops.histogram import build_histograms

    bins, nid, lanes = _hist_case()
    h_f32 = np.asarray(build_histograms(bins, nid, lanes, 4, 16))
    with _env(H2O3_TPU_HIST_I16="1"):
        h_i16 = np.asarray(build_histograms(bins, nid, lanes, 4, 16))
    np.testing.assert_array_equal(h_f32, h_i16)


def test_i16_float_stats_envelope():
    """Float lanes quantize at absmax/127 per (node, lane): the histogram
    must match f32 within the 1/254 relative-cell envelope."""
    from h2o3_tpu.ops.histogram import build_histograms

    bins, nid, lanes = _hist_case(seed=9, integer=False)
    h_f32 = np.asarray(build_histograms(bins, nid, lanes, 4, 16))
    with _env(H2O3_TPU_HIST_I16="1"):
        h_i16 = np.asarray(build_histograms(bins, nid, lanes, 4, 16))
    # per-cell error bound: (rows in cell) * scale/2 — bound globally by
    # the max |stat| row count via a loose but safe envelope
    scale = max(float(jnp.abs(s).max()) for s in lanes) / 127.0
    ones = tuple(jnp.ones_like(s) for s in lanes)
    rows_per_cell = np.asarray(build_histograms(bins, nid, ones, 4, 16))
    np.testing.assert_allclose(
        h_i16, h_f32, atol=float(scale) * (rows_per_cell.max() / 2 + 1))


def test_i16_overflow_latch_recomputes_f32():
    """A cell whose quantized sum exceeds +/-32767 trips the latch: the
    counter tallies and the pass recomputes in f32 — output bit-equal to
    the knob-off histogram."""
    from h2o3_tpu.ops.histogram import build_histograms

    # the latch is SHARD-local (the rescale happens before the cross-device
    # reduce), so the per-shard cell must overflow: on the 8-device mesh
    # 4800 rows put 600 q=127 codes in each shard's bin-0 cell (76200 >
    # 32767), tripping every shard's latch
    n = 4800
    bins = np.zeros((n, 2), np.uint8)  # every row in bin 0 of both cols
    nid = np.zeros(n, np.int32)
    lane = jnp.full(n, 127.0, jnp.float32)  # q=127 each
    args = (jnp.asarray(bins), jnp.asarray(nid), (lane, lane, lane))
    h_f32 = np.asarray(build_histograms(*args, 1, 4))
    c0 = mx.counter_value("tree_hist_i16_overflows_total")
    with _env(H2O3_TPU_HIST_I16="1"):
        h_i16 = np.asarray(build_histograms(*args, 1, 4))
    jax.effects_barrier()  # flush the debug.callback carrying the tally
    np.testing.assert_array_equal(h_f32, h_i16)
    assert mx.counter_value("tree_hist_i16_overflows_total") > c0


@pytest.mark.slow
def test_i16_gbm_trains_inside_envelope():
    """End-to-end: quantized histograms perturb near-tie split choices, so
    individual trees diverge across boosting rounds — the MODEL QUALITY
    envelope is the contract: the i16 build's training RMSE must stay
    within 10% of the f32 build's, and the forced-off control must be
    bit-for-bit."""
    df = _dense_df(seed=10)
    y = df["y"].to_numpy()
    fr = Frame.from_pandas(df)
    p0 = _pred(_train(fr), fr)
    with _env(H2O3_TPU_HIST_I16="1"):
        p1 = _pred(_train(fr), fr)
    with _env(H2O3_TPU_HIST_I16="0"):
        p2 = _pred(_train(fr), fr)
    rmse0 = float(np.sqrt(np.mean((p0 - y) ** 2)))
    rmse1 = float(np.sqrt(np.mean((p1 - y) ** 2)))
    assert rmse1 <= rmse0 * 1.10
    np.testing.assert_array_equal(p0, p2)


# ---------------------------------------------------------------------------
# leaf-wise growth (grow_policy=lossguide)


@pytest.mark.slow
def test_lossguide_honors_max_leaves():
    fr = Frame.from_pandas(_dense_df(seed=11))
    m = _train(fr, max_depth=6, grow_policy="lossguide", max_leaves=8)
    for it in m.output["trees"]:
        for t in it:
            assert t.n_leaves <= 8
    # depthwise at the same depth grows far past 8 leaves on this data
    d = _train(fr, max_depth=6)
    assert max(t.n_leaves for it in d.output["trees"] for t in it) > 8


def test_lossguide_huge_budget_matches_depthwise():
    """With max_leaves >= 2^depth the budget never binds: lossguide must
    reproduce the depthwise build bit-for-bit (same splits, same order of
    stat accumulation)."""
    fr = Frame.from_pandas(_dense_df(seed=12))
    p_d = _pred(_train(fr), fr)
    p_l = _pred(
        _train(fr, grow_policy="lossguide", max_leaves=2 ** 4), fr)
    np.testing.assert_array_equal(p_d, p_l)


def test_lossguide_validation():
    fr = Frame.from_pandas(_dense_df(n=500, seed=13))
    with pytest.raises(Exception, match="max_leaves"):
        _train(fr, grow_policy="lossguide")
    with pytest.raises(Exception, match="grow_policy"):
        _train(fr, grow_policy="bogus")


# ---------------------------------------------------------------------------
# u8-code-native frames (H2O3_TPU_TREE_U8CACHE)


def test_u8_cache_returns_same_buffer():
    """Second bin_frame over the same (spec, frame) must be a cache hit:
    the IDENTICAL device buffer, and zero new rebin HBM traffic."""
    df = _dense_df(seed=14)
    fr = Frame.from_pandas(df)
    cols = [c for c in df.columns if c != "y"]
    spec = fit_bins(fr, cols)
    b0 = bin_frame(spec, fr)
    r0 = mx.counter_value("tree_hist_hbm_bytes_total", path="rebin")
    b1 = bin_frame(spec, fr)
    assert b1 is b0
    assert mx.counter_value(
        "tree_hist_hbm_bytes_total", path="rebin") == r0
    with _env(H2O3_TPU_TREE_U8CACHE="0"):
        b2 = bin_frame(spec, fr)
    assert b2 is not b0
    np.testing.assert_array_equal(np.asarray(b2), np.asarray(b0))
    assert mx.counter_value(
        "tree_hist_hbm_bytes_total", path="rebin") > r0


def test_u8_cache_off_bit_identical():
    """The forced-off control: cache disabled must score identically."""
    fr = Frame.from_pandas(_dense_df(seed=15))
    p0 = _pred(_train(fr), fr)
    with _env(H2O3_TPU_TREE_U8CACHE="0"):
        p1 = _pred(_train(fr), fr)
    np.testing.assert_array_equal(p0, p1)


def test_u8_cache_saves_rebin_traffic_across_builds():
    """Two same-spec builds over one frame: the second must add no rebin
    bytes (the wave-2 A/B's >=2x frame-traffic cut comes from here)."""
    fr = Frame.from_pandas(_dense_df(seed=16))
    _train(fr)
    r1 = mx.counter_value("tree_hist_hbm_bytes_total", path="rebin")
    _train(fr)
    assert mx.counter_value(
        "tree_hist_hbm_bytes_total", path="rebin") == r1


# ---------------------------------------------------------------------------
# uplift through the fused whole-tree program (satellite a)


def _uplift_frame(n=4000, seed=17):
    rng = np.random.default_rng(seed)
    x0 = rng.normal(size=n)
    x1 = rng.normal(size=n)
    treat = rng.integers(0, 2, n)
    p = 0.3 + 0.3 * treat * (x0 > 0)
    y = (rng.random(n) < p).astype(int)
    df = pd.DataFrame(
        {"x0": x0, "x1": x1,
         "treatment": np.where(treat, "treatment", "control"),
         "y": y.astype(str)})
    return Frame.from_pandas(
        df, column_types={"y": "enum", "treatment": "enum"})


def test_uplift_runs_the_whole_tree_program():
    """Uplift's 4-lane scan rides a whole-tree program: a default build
    compiles one ``uplift_tree`` program and no per-level step."""
    from h2o3_tpu.models import UpliftDRF, uplift

    fr = _uplift_frame()
    uplift._STEP_CACHE.clear()
    UpliftDRF(ntrees=4, max_depth=3, treatment_column="treatment",
              uplift_metric="KL", seed=11).train(y="y", training_frame=fr)
    assert [k[0] for k in uplift._STEP_CACHE] == ["uplift_tree"]


def test_uplift_fused_matches_legacy_loop():
    """Fused whole-tree uplift must reproduce the per-level legacy loop's
    predictions bit-for-bit (the loop early-breaks, the program pads with
    inert all-leaf levels — same trees either way)."""
    from h2o3_tpu.models import UpliftDRF

    fr = _uplift_frame(seed=18)
    kw = dict(ntrees=4, max_depth=3, treatment_column="treatment",
              uplift_metric="KL", seed=11)
    u_fused = UpliftDRF(**kw).train(y="y", training_frame=fr)._predict_raw(fr)
    with _env(H2O3_TPU_WHOLE_TREE="0"):
        u_legacy = UpliftDRF(**kw).train(
            y="y", training_frame=fr)._predict_raw(fr)
    np.testing.assert_array_equal(np.asarray(u_fused), np.asarray(u_legacy))
