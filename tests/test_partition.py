"""The partition update (ISSUE 29): ``shared_tree._partition_update`` routes
rows with no per-row gather, and has to return what the gather form returned,
bit for bit: against a plain numpy statement of the rule, against the old
form (kept here, and only here, as the reference) through whole GBM and DRF
builds, and with its passes counted."""

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.genmodel import goes_left
from h2o3_tpu.models.tree import DRF, GBM
from h2o3_tpu.models.tree import shared_tree as st
from h2o3_tpu.utils import metrics

N_BINS = 256
#: a leaf value that does not survive a single bfloat16 pass
FINE = np.float32(0.1 * (1 + 2.0 ** -20))


def _gather_form(bins_u8, nid, preds, split_col, split_bin, is_cat, cat_mask,
                 na_left, leaf_now, leaf_val, child_base, any_cat=None):
    """The partition as it stood before ISSUE 29: nine per-row gathers."""
    active = nid >= 0
    node = jnp.where(active, nid, 0)
    col = split_col[node]
    b = jnp.take_along_axis(
        bins_u8, col[:, None].astype(jnp.int32), axis=1).squeeze(1).astype(jnp.int32)
    go_left = jnp.where(
        b == 0, na_left[node],
        jnp.where(is_cat[node], cat_mask[node, b], b <= split_bin[node]))
    child = child_base[node] + jnp.where(go_left, 0, 1)
    retired = leaf_now[node]
    new_nid = jnp.where(active, jnp.where(retired, -1, child), -1)
    new_preds = preds + jnp.where(active & retired, leaf_val[node], 0.0)
    return new_nid.astype(jnp.int32), new_preds


def _rule(bins, nid, preds, split_col, split_bin, is_cat, cat_mask, na_left,
          leaf_now, leaf_val, child_base):
    """The rule in plain numpy: ``genmodel.goes_left``, the child ids, the
    retirement and the prediction add."""
    active = nid >= 0
    node = np.where(active, nid, 0)
    b = bins[np.arange(len(nid)), split_col[node]].astype(np.int64)
    left = goes_left(b, na_left[node], cat_mask[node, b], is_cat[node],
                     split_bin[node])
    child = child_base[node] + np.where(left, 0, 1)
    retired = leaf_now[node]
    new_nid = np.where(active & ~retired, child, -1).astype(np.int32)
    add = np.where(active & retired, leaf_val[node], np.float32(0.0))
    return new_nid, (preds + add.astype(np.float32)).astype(np.float32)


def _level(n, C, n_pad, *, cats=False, na_left=None, retired_rows=False,
           all_leaf=False, n_bins=N_BINS, seed=0):
    r = np.random.default_rng(seed)
    bins = r.integers(0, n_bins, (n, C)).astype(np.uint8)
    bins[r.random((n, C)) < 0.15] = 0  # NA codes
    nid = r.integers(-1 if retired_rows else 0, n_pad, n).astype(np.int32)
    if retired_rows:
        nid[: n // 3] = -1
    preds = r.standard_normal(n).astype(np.float32)
    preds[:4] = [-0.0, 0.0, -0.0, 1.0]
    is_cat = (r.random(n_pad) < 0.5) if cats else np.zeros(n_pad, bool)
    cat_mask = ((r.random((n_pad, n_bins)) < 0.5) & is_cat[:, None])
    leaf_val = (r.standard_normal(n_pad).astype(np.float32) * FINE)
    leaf_val[:: 3] = FINE
    if n_pad > 1:
        leaf_val[1] = -0.0
    return (
        bins, nid, preds,
        r.integers(0, C, n_pad).astype(np.int32),
        r.integers(0, n_bins, n_pad).astype(np.int32),
        is_cat, cat_mask,
        (r.random(n_pad) < 0.5) if na_left is None else np.full(n_pad, na_left),
        np.ones(n_pad, bool) if all_leaf else r.random(n_pad) < 0.3,
        leaf_val,
        (2 * r.integers(0, n_pad, n_pad)).astype(np.int32),
    )


def _bits(x):
    return np.asarray(x).view(np.int32)


def _raw(x):
    return np.ascontiguousarray(x).view(np.uint8)


CASES = {
    "numeric": dict(C=28, n_pad=64),
    "categorical": dict(C=28, n_pad=64, cats=True),
    "categorical_21_bins": dict(C=5, n_pad=8, cats=True, n_bins=21),
    "na_left": dict(C=28, n_pad=64, na_left=True),
    "na_right": dict(C=28, n_pad=64, na_left=False),
    "retired_rows": dict(C=28, n_pad=64, retired_rows=True),
    "all_leaf": dict(C=28, n_pad=64, all_leaf=True),
    "n_pad_1": dict(C=28, n_pad=1),
    "n_pad_2": dict(C=28, n_pad=2, cats=True),
    "n_pad_2048": dict(C=28, n_pad=2048, retired_rows=True),
    "n_pad_2048_categorical": dict(C=32, n_pad=2048, cats=True),
    "C_1": dict(C=1, n_pad=64),
    "C_32": dict(C=32, n_pad=64, cats=True, retired_rows=True),
    "C_300": dict(C=300, n_pad=300),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_partition_is_the_rule_bit_for_bit(case):
    level = _level(3000, **CASES[case])
    want_nid, want_preds = _rule(*level)
    old_nid, old_preds = jax.jit(_gather_form)(*level)
    # the gather form is the rule (the reference of the whole-model test)
    np.testing.assert_array_equal(np.asarray(old_nid), want_nid)
    np.testing.assert_array_equal(_bits(old_preds), _bits(want_preds))
    any_cat = bool(CASES[case].get("cats"))
    for args, kw in ((level, {}),  # host tables: is_cat is read
                     (tuple(jnp.asarray(a) for a in level), {}),
                     (tuple(jnp.asarray(a) for a in level), {"any_cat": any_cat})):
        nid, preds = st._partition_update(*args, **kw)
        assert nid.dtype == jnp.int32 and preds.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(nid), want_nid)
        np.testing.assert_array_equal(_bits(preds), _bits(want_preds))


def _records(model):
    return [[{f: np.asarray(getattr(lv, f)) for f in
              ("split_col", "split_bin", "is_cat", "cat_mask", "na_left",
               "leaf_now", "leaf_val", "child_base", "gain", "node_w")}
             for lv in t.levels] for group in model.output["trees"] for t in group]


def _frame(n=3000, seed=11):
    r = np.random.default_rng(seed)
    X = r.normal(size=(n, 6))
    X[r.random((n, 6)) < 0.05] = np.nan
    g = r.choice(list("pqrst"), n)
    eta = X[:, 0] * 2 + np.nan_to_num(X[:, 1]) ** 2 - np.nan_to_num(X[:, 2]) + (g == "q") * 1.5
    y = r.random(n) < 1 / (1 + np.exp(-np.nan_to_num(eta)))
    df = pd.DataFrame(X, columns=list("abcdef"))
    df["g"] = g
    df["y"] = np.where(y, "Y", "N")
    return Frame.from_pandas(df)


@pytest.mark.parametrize("algo", ["gbm_10_trees_depth_6", "drf_depth_12"])
def test_models_are_bit_identical_to_the_gather_forms(monkeypatch, algo):
    """Records and predictions of whole builds, against the same builds with
    the old gather form in the partition's place (numeric columns with NAs
    and one categorical column, so both branches route rows)."""
    fr = _frame()

    def build():
        st._STEP_CACHE.clear()
        if algo.startswith("gbm"):
            b = GBM(ntrees=10, max_depth=6, learn_rate=0.1, min_rows=10.0,
                    score_tree_interval=5, seed=3)
        else:
            b = DRF(ntrees=4, max_depth=12, min_rows=5.0, seed=3,
                    score_tree_interval=100)
        m = b.train(y="y", training_frame=fr)
        return _records(m), m.predict(fr).vec("Y").to_numpy()

    try:
        recs, pred = build()
        monkeypatch.setattr(st, "_partition_update", jax.jit(
            _gather_form, static_argnames=("any_cat",)))
        recs_old, pred_old = build()
    finally:
        st._STEP_CACHE.clear()  # programs traced with the reference in place
    assert len(recs) == len(recs_old)
    for t, t_old in zip(recs, recs_old):
        assert len(t) == len(t_old)
        for lv, lv_old in zip(t, t_old):
            for f in lv:
                assert lv[f].dtype == lv_old[f].dtype, f
                np.testing.assert_array_equal(_raw(lv[f]), _raw(lv_old[f]), err_msg=f)
    np.testing.assert_array_equal(_raw(pred), _raw(pred_old))


@pytest.mark.parametrize("any_cat", [False, True])
def test_row_sharded_partition_adds_no_collective(any_cat):
    """Over a row-sharded mesh the pass is row-local (the tables are
    replicated): the partitioned program has no collective. A replay issues
    one such program a level from the job's thread, and a collective in it
    can deadlock against another program's on the CPU mesh."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("rows",))
    rows, rep = NamedSharding(mesh, P("rows")), NamedSharding(mesh, P())
    n, C, n_pad = 512 * len(jax.devices()), 28, 64

    def S(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    compiled = st._partition_dense.lower(
        S((n, C), jnp.uint8, NamedSharding(mesh, P("rows", None))),
        S((n,), jnp.int32, rows), S((n,), jnp.float32, rows),
        S((n_pad,), jnp.int32), S((n_pad,), jnp.int32), S((n_pad,), jnp.bool_),
        S((n_pad, N_BINS), jnp.bool_), S((n_pad,), jnp.bool_),
        S((n_pad,), jnp.bool_), S((n_pad,), jnp.float32), S((n_pad,), jnp.int32),
        any_cat=any_cat).compile()
    assert not re.findall(
        r"(all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter)",
        compiled.as_text())
    assert all(s.spec == P("rows") for s in compiled.output_shardings)


def _passes() -> float:
    return metrics.counter_value("tree_partition_levels_total{path=dense}")


def test_partition_passes_are_counted():
    """A 2-tree depth-3 build executes 2 x 4 levels; a replay of its trees
    counts its own passes, once a level of every tree."""
    r = np.random.default_rng(2)
    n = 2000
    X = r.normal(size=(n, 4))
    df = pd.DataFrame(X, columns=list("abcd"))
    df["y"] = X[:, 0] * 2 - X[:, 1] + 0.1 * r.normal(size=n)
    fr = Frame.from_pandas(df)
    st._STEP_CACHE.clear()  # the tally is taken while the program is traced
    try:
        before = _passes()
        m = GBM(ntrees=2, max_depth=3, learn_rate=0.5, min_rows=5.0,
                score_tree_interval=100).train(y="y", training_frame=fr)
        built = _passes() - before
    finally:
        st._STEP_CACHE.clear()
    trees = [t for group in m.output["trees"] for t in group]
    assert [len(t.levels) for t in trees] == [4, 4]
    assert built == 8

    bins = jnp.asarray(r.integers(0, 32, (n, 4)).astype(np.uint8))
    before = _passes()
    trees[0].replay(bins, jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.float32))
    assert _passes() - before == 4

    stacked = tuple(
        {f: jnp.stack([jnp.asarray(getattr(t.levels[li], f)) for t in trees])
         for f in ("split_col", "split_bin", "is_cat", "cat_mask", "na_left",
                   "leaf_now", "leaf_val", "child_base")}
        for li in range(4))
    before = _passes()
    st.replay_batch(bins, stacked, jnp.zeros(n, jnp.float32))
    assert _passes() - before == 8
    assert metrics.counter_value("tree_partition_levels_total{path=gather}") == 0
