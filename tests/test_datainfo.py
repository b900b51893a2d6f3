"""``DataInfo.transform`` as one traced program (ISSUE 28): the design matrix
against the op-by-op formulas written out in numpy, the program's operands
(a second frame, a re-wrapped one and a refit compile nothing), and GLM's
response lanes made on the device against the host's ``ybuf`` / ``yna``."""

import zlib

import jax
import numpy as np
import pandas as pd
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.datainfo import MEAN_IMPUTATION, SKIP, DataInfo
from h2o3_tpu.parallel.mesh import row_sharding
from h2o3_tpu.utils import flightrec

N = 203  # not a multiple of the mesh's row block: the frame has pad rows


def _df(seed=0, levels=("a", "b", "c", "d"), n=N):
    rng = np.random.default_rng(seed)
    df = pd.DataFrame({
        "x0": rng.normal(2.0, 3.0, n).astype(np.float32),
        "x1": rng.normal(-1.0, 0.5, n).astype(np.float32),
        "c": rng.choice(list(levels), n),
        "d": rng.choice(["u", "v", "w"], n),
    })
    df.loc[rng.choice(n, 17, replace=False), "x0"] = np.nan
    df.loc[rng.choice(n, 11, replace=False), "x1"] = np.nan
    df.loc[rng.choice(n, 13, replace=False), "c"] = None
    return df


def _frame(df):
    return Frame.from_pandas(df.copy())


# ---------------------------------------------------------------------------
# the reference: the op-by-op formulas, in numpy float32, from the host copy

def _codes(frame, name, domain):
    """A column's codes on ``domain`` (-1: NA or a level it does not hold)."""
    v = frame.vec(name)
    lut = {d: i for i, d in enumerate(domain)}
    remap = np.array([lut.get(d, -1) for d in v.domain] + [-1], np.int64)
    raw = v.to_numpy().astype(np.int64)
    return np.where(raw >= 0, remap[np.clip(raw, 0, None)], -1)


def _onehot(codes, width, use_all):
    shifted = codes - (0 if use_all else 1)
    return (shifted[:, None] == np.arange(width)[None, :]).astype(np.float32)


def _reference(di: DataInfo, frame):
    f32 = np.float32
    n = frame.nrow
    ok = np.ones(n, bool)
    cols = []

    def num(name):
        return frame.vec(name).to_numpy().astype(f32)

    for c in di.columns:
        mean, sigma = f32(c.mean), f32(c.sigma)
        if c.pair_domains is not None:
            da, db = c.pair_domains
            ca, cb = _codes(frame, c.pair[0], da), _codes(frame, c.pair[1], db)
            codes = np.where((ca >= 0) & (cb >= 0), ca * len(db) + cb, -1)
            ok &= codes >= 0
            cols.append(_onehot(codes, c.width, di.use_all_factor_levels))
        elif c.pair is not None and c.kind == "num":
            xa, xb = num(c.pair[0]), num(c.pair[1])
            ok &= ~(np.isnan(xa) | np.isnan(xb))
            ma, mb = (f32(m) for m in c.pair_means)
            x = np.where(np.isnan(xa), ma, xa) * np.where(np.isnan(xb), mb, xb)
            if di.standardize:
                x = (x - mean) / sigma
            cols.append(x[:, None])
        elif c.pair is not None:
            codes, x = _codes(frame, c.pair[0], c.domain), num(c.pair[1])
            ok &= (codes >= 0) & ~np.isnan(x)
            x = np.where(np.isnan(x), f32(c.pair_means[1]), x)
            cols.append(
                _onehot(codes, c.width, di.use_all_factor_levels) * x[:, None])
        elif c.kind == "hash":
            v = frame.vec(c.name)
            lut = np.array(
                [zlib.crc32(c.name.encode() + b"\x00" + d.encode())
                 % di.hash_buckets for d in v.domain] + [-1], np.int64)
            raw = v.to_numpy().astype(np.int64)
            codes = np.where(raw >= 0, lut[np.clip(raw, 0, None)], -1)
            ok &= codes >= 0
            cols.append(_onehot(codes, c.width, di.use_all_factor_levels))
        elif c.kind == "cat":
            codes = _codes(frame, c.name, c.domain)
            ok &= codes >= 0
            cols.append(_onehot(codes, c.width, di.use_all_factor_levels))
        else:
            data = num(c.name)
            isna = np.isnan(data)
            ok &= ~isna
            x = np.where(isna, mean, data)
            if di.standardize:
                x = (x - mean) / sigma
            elif di.missing_handling == SKIP:
                x = np.where(isna, f32(0), x)
            cols.append(x[:, None])
    if di.add_intercept:
        cols.append(np.ones((n, 1), f32))
    valid = np.zeros(frame.npad, f32)
    valid[:n] = ok if di.missing_handling == SKIP else 1.0
    X = np.zeros((frame.npad, di.ncols_expanded), f32)
    X[:n] = np.concatenate(cols, axis=1)
    return X * valid[:, None], valid


CASES = {
    "numeric_mean_imputation_standardised": dict(
        x=["x0", "x1"], standardize=True),
    "numeric_mean_imputation_raw": dict(x=["x0", "x1"], standardize=False),
    "numeric_skip_standardised": dict(
        x=["x0", "x1"], standardize=True, missing_handling=SKIP),
    "numeric_skip_raw": dict(
        x=["x0", "x1"], standardize=False, missing_handling=SKIP),
    "categorical_all_levels": dict(x=["c", "x0"]),
    "categorical_reference_level_dropped_skip": dict(
        x=["c", "d"], use_all_factor_levels=False, missing_handling=SKIP),
    "hashed": dict(x=["c", "x1"], hash_buckets=3),
    "hashed_reference_bucket_dropped": dict(
        x=["c"], hash_buckets=3, use_all_factor_levels=False),
    "interaction_numeric_pair": dict(
        x=["x0"], interaction_pairs=[("x0", "x1")]),
    "interaction_categorical_numeric_skip": dict(
        x=["x1"], interaction_pairs=[("c", "x0")], missing_handling=SKIP),
    "interaction_categorical_pair": dict(
        x=["d"], interaction_pairs=[("c", "d")]),
    "intercept": dict(x=["x0", "c"], add_intercept=True),
}


@pytest.mark.parametrize("scoring", ["training_frame", "unseen_levels"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_traced_transform_equals_the_formulas_in_numpy(case, scoring):
    """One dispatch, and the numbers of the op-by-op form: on the frame the
    DataInfo was fitted on (its pad rows included), and on a frame of other
    rows whose categorical column has another domain, one level unseen."""
    train = _frame(_df(0))
    assert train.npad > train.nrow
    di = DataInfo.fit(train, **{"missing_handling": MEAN_IMPUTATION, **CASES[case]})
    frame = train if scoring == "training_frame" else _frame(
        _df(1, levels=("d", "b", "zz", "a")))
    flightrec.reset()
    X, valid = di.transform(frame)
    assert [e["site"] for e in flightrec.events(kind="dispatch_end")] == ["design"]
    want_X, want_valid = _reference(di, frame)
    assert X.shape == (frame.npad, di.ncols_expanded) and X.dtype == np.float32
    np.testing.assert_array_equal(np.asarray(valid), want_valid)
    np.testing.assert_allclose(np.asarray(X), want_X, rtol=1e-6, atol=1e-6)
    assert X.sharding.is_equivalent_to(row_sharding(), X.ndim)
    assert valid.sharding.is_equivalent_to(row_sharding(), valid.ndim)


# ---------------------------------------------------------------------------
# means, sigmas, LUTs and the columns are operands

class _Compiles:
    """Programs compiled (or loaded from the persistent cache) since it was
    made: the listener of ``benchmark/harness/window.py``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1


@pytest.fixture(scope="module")
def compiles():
    return _Compiles()


@pytest.mark.parametrize("second", ["other_rows", "rewrapped", "refitted"])
def test_a_frame_of_the_same_structure_compiles_nothing(compiles, second):
    # a structure of the case's own, so that its first transform compiles
    x = {"other_rows": ["x0", "x1", "c"], "rewrapped": ["x1", "c", "x0"],
         "refitted": ["c", "x0", "x1"]}[second]
    spec = dict(x=x, add_intercept=True,
                interaction_pairs=[("x0", "x1")], hash_buckets=2)
    first = _frame(_df(0))
    di = DataInfo.fit(first, **spec)
    start = compiles.count
    X0, _ = di.transform(first)
    assert compiles.count > start  # the listener hears a compilation
    if second == "other_rows":  # other values, other rows filled: same npad
        frame = _frame(_df(5, n=N - 9))
        assert frame.npad == first.npad and frame.nrow != first.nrow
    else:  # the same device columns under a new frame, as Data.rewrap() does
        frame = Frame([first.vec(n) for n in first.names], list(first.names))
    if second == "refitted":
        di = DataInfo.fit(frame, **{**spec, "standardize": True})
        di.columns[1].mean += 0.25  # other statistics: operands, no constants
    before = compiles.count
    X1, valid = di.transform(frame)
    jax.block_until_ready((X1, valid))
    assert compiles.count == before
    want_X, want_valid = _reference(di, frame)
    np.testing.assert_array_equal(np.asarray(valid), want_valid)
    np.testing.assert_allclose(np.asarray(X1), want_X, rtol=1e-6, atol=1e-6)
    assert X1.shape == X0.shape


# ---------------------------------------------------------------------------
# GLM's response lanes

def _host_lanes(yv, npad, nrow):
    """``ybuf`` / ``yna`` as the streamed path builds them in numpy."""
    y_np = yv.to_numpy()
    if yv.is_categorical():
        y_np = y_np.astype(np.float32)
        y_np[y_np < 0] = np.nan
    ybuf = np.zeros(npad, np.float32)
    ybuf[:nrow] = np.nan_to_num(y_np, nan=0.0)
    yna = np.zeros(npad, np.float32)
    yna[:nrow] = np.isnan(y_np)
    return ybuf, yna


@pytest.mark.parametrize("case", [
    "categorical_label_with_NAs", "numeric_label_with_NaNs",
    "weights_column", "offset_column"])
def test_device_response_lanes_equal_the_host_lanes(case):
    from h2o3_tpu.models.glm import _response_lanes

    df = _df(2)
    rng = np.random.default_rng(9)
    df["y_num"] = rng.normal(size=N).astype(np.float32)
    df.loc[rng.choice(N, 19, replace=False), "y_num"] = np.nan
    df["wt"] = rng.uniform(0.5, 2.0, N).astype(np.float32)
    df.loc[3, "wt"] = np.nan
    df["off"] = rng.normal(size=N).astype(np.float32)
    df.loc[5, "off"] = np.nan
    fr = _frame(df)
    label = "y_num" if case == "numeric_label_with_NaNs" else "c"
    yv = fr.vec(label)
    di = DataInfo.fit(fr, ["x0", "x1"], missing_handling=SKIP)
    _, valid = di.transform(fr)
    weights = fr.vec("wt").data if case == "weights_column" else None
    offset = fr.vec("off").data if case == "offset_column" else None
    y, w, off, nobs = _response_lanes(yv.data, valid, weights, offset)
    ybuf, yna = _host_lanes(yv, fr.npad, fr.nrow)
    assert yna.sum() > 0
    want_w = np.asarray(valid)
    if weights is not None:
        want_w = want_w * np.nan_to_num(np.asarray(weights))
    want_w = want_w * (1.0 - yna)
    want_off = (np.zeros(fr.npad, np.float32) if offset is None
                else np.nan_to_num(np.asarray(offset)))
    np.testing.assert_array_equal(np.asarray(y), ybuf)
    np.testing.assert_array_equal(np.asarray(w), want_w)
    np.testing.assert_array_equal(np.asarray(off), want_off)
    assert y.dtype == w.dtype == off.dtype == np.float32
    assert float(nobs) == pytest.approx(float(want_w.sum()), rel=1e-6)
