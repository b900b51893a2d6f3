"""Column-sharded split pipeline (ISSUE 5): the histogram reduce-scatter +
blockwise split scan + per-block winner merge must be INDISTINGUISHABLE from
the replicated path — split decisions, predictions and varimp bit-equal on
1-, 2- and 8-device meshes, including under adversarial exact ties where the
merge's tie-break must reproduce ``jnp.argmax``'s lowest-global-index rule.
"""

import contextlib
import os

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from h2o3_tpu.models.tree import shared_tree as st
from h2o3_tpu.parallel import mesh as pm


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
    os.environ.update({k: str(v) for k, v in kv.items()})
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _bits(a) -> bytes:
    return np.ascontiguousarray(np.asarray(a)).tobytes()


def _tree_fields(tree: st.Tree) -> list[dict]:
    host = tree.to_host()
    return [
        {
            "split_col": lv.split_col, "split_bin": lv.split_bin,
            "is_cat": lv.is_cat, "cat_mask": lv.cat_mask,
            "na_left": lv.na_left, "leaf_now": lv.leaf_now,
            "leaf_val": lv.leaf_val, "child_base": lv.child_base,
            "gain": lv.gain, "node_w": lv.node_w,
        }
        for lv in host.levels
    ]


def _assert_trees_bit_equal(a: st.Tree, b: st.Tree, what: str):
    fa, fb = _tree_fields(a), _tree_fields(b)
    assert len(fa) == len(fb), what
    for li, (la, lb) in enumerate(zip(fa, fb)):
        for k in la:
            assert _bits(la[k]) == _bits(lb[k]), (
                f"{what}: level {li} field {k} diverged between sharded and "
                f"replicated split pipelines"
            )


def _build_one(bins_np, t_np, *, split_shard: int, max_depth=3, n_bins=16,
               node_cap=2048, min_rows=1.0, env=None, is_cat=None, seed=5,
               monotone=None):
    """build_tree under the given H2O3_TPU_SPLIT_SHARD, on the CURRENT mesh.
    ``monotone`` sends the build down the per-level mono loop."""
    n, C = bins_np.shape
    with _env(H2O3_TPU_SPLIT_SHARD=split_shard, **(env or {})):
        bins = pm.shard_rows(jnp.asarray(bins_np))
        w = pm.shard_rows(jnp.ones(n, jnp.float32))
        t = pm.shard_rows(jnp.asarray(t_np, dtype=jnp.float32))
        h = pm.shard_rows(jnp.ones(n, jnp.float32))
        preds = pm.shard_rows(jnp.zeros(n, jnp.float32))
        tree, preds, varimp = st.build_tree(
            bins, w, t, h,
            n_bins=n_bins,
            is_cat_cols=(np.zeros(C, bool) if is_cat is None else is_cat),
            max_depth=max_depth,
            min_rows=min_rows,
            min_split_improvement=0.0,
            learn_rate=0.1,
            preds=preds,
            key=jax.random.PRNGKey(seed),
            varimp=jnp.zeros(C, jnp.float32),
            node_cap=node_cap,
            monotone=monotone,
        )
        return tree, np.asarray(preds), np.asarray(varimp)


def _pad_rows(n_raw: int) -> int:
    return pm.pad_to_shards(n_raw)


def _tie_data(n_pad: int, C: int, n_bins: int, dup_all: bool, seed=0):
    """Adversarial exact-tie data: every weight is 1.0 and every target is
    integer-valued, so histogram sums are exact in f32 and candidate gains
    that tie mathematically tie BIT-exactly. ``dup_all=True`` additionally
    duplicates one column into every column — identical gains in every
    block, so only the lowest-global-index tie-break can pick the winner."""
    rng = np.random.default_rng(seed)
    base = rng.integers(1, n_bins, n_pad).astype(np.uint8)
    if dup_all:
        bins = np.tile(base[:, None], (1, C))
    else:
        bins = rng.integers(1, n_bins, (n_pad, C)).astype(np.uint8)
        bins[:, C // 2:] = bins[:, : C - C // 2]  # mirror block-spanning dups
    t = np.ones(n_pad, np.float32)  # constant target: EVERY candidate gain
    # is exactly 0.0 (wy == w, sums exact) — maximal tie pressure
    return bins, t


@pytest.mark.parametrize("k", [1, 2, 8])
def test_tie_break_constant_target_all_columns_tie(k):
    """Constant target: every (col, bin) candidate's gain is exactly 0.0 in
    every block. jnp.argmax resolves to the lowest bin of the lowest column;
    the sharded merge must land on the identical choice on any mesh."""
    with _use_mesh(k):
        n_pad = _pad_rows(960)
        bins, t = _tie_data(n_pad, C=13, n_bins=16, dup_all=True)
        t1, p1, v1 = _build_one(bins, t, split_shard=1)
        t0, p0, v0 = _build_one(bins, t, split_shard=0)
        _assert_trees_bit_equal(t1, t0, f"ties/{k}dev")
        assert _bits(p1) == _bits(p0)
        assert _bits(v1) == _bits(v0)
        # the replicated argmax picks global column 0 when everything ties;
        # a merge that preferred a later block (or a local index without the
        # block offset) would record a different column
        assert int(np.asarray(t1.levels[0].split_col)[0]) == 0


@pytest.mark.parametrize("k", [2, 8])
def test_tie_break_duplicated_columns_nonzero_gains(k):
    """Duplicated columns with a real signal: identical NON-zero best gains
    appear in several blocks at once; the winner must be the lowest global
    column index (bit-exact vs the replicated scan)."""
    with _use_mesh(k):
        n_pad = _pad_rows(960)
        rng = np.random.default_rng(3)
        bins, _ = _tie_data(n_pad, C=16, n_bins=16, dup_all=True, seed=3)
        t = (rng.integers(0, 2, n_pad) * 2 - 1).astype(np.float32)
        t1, p1, v1 = _build_one(bins, t, split_shard=1, max_depth=4)
        t0, p0, v0 = _build_one(bins, t, split_shard=0, max_depth=4)
        _assert_trees_bit_equal(t1, t0, f"dup-cols/{k}dev")
        assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)
        # every split must sit on column 0 — all 16 columns are copies
        masks = t0.real_level_masks()
        for lv, m in zip(t0.levels, masks):
            split = ~np.asarray(lv.leaf_now) & m
            assert (np.asarray(lv.split_col)[split] == 0).all()


@pytest.mark.parametrize("subtract", ["1", "0"])
def test_parity_both_force_leaf_paths(subtract):
    """Both terminal-level regimes: subtract=1 derives the last level's leaf
    stats from the parents' chosen splits (no histogram at all); subtract=0
    builds a terminal histogram and force-leafs from its totals."""
    n_pad = _pad_rows(700)
    rng = np.random.default_rng(7)
    bins = rng.integers(0, 16, (n_pad, 7)).astype(np.uint8)  # 7 % 8 != 0
    t = rng.normal(size=n_pad).astype(np.float32)
    env = {"H2O3_TPU_HIST_SUBTRACT": subtract}
    t1, p1, v1 = _build_one(bins, t, split_shard=1, env=env)
    t0, p0, v0 = _build_one(bins, t, split_shard=0, env=env)
    _assert_trees_bit_equal(t1, t0, f"force-leaf/subtract={subtract}")
    assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)


@pytest.mark.parametrize("subtract", ["1", "0"])
def test_parity_saturated_levels(subtract):
    """Deep tree with a small node_cap: the sharded scan must stay bit-equal
    through the saturated while_loop region — with its sibling-subtraction
    carry, and in the direct scheme, whose loop threads a dummy parent carry
    (the only sharded pins on that loop)."""
    n_pad = _pad_rows(600)
    rng = np.random.default_rng(11)
    bins = rng.integers(0, 255, (n_pad, 6)).astype(np.uint8)
    t = rng.normal(size=n_pad).astype(np.float32)
    env = {"H2O3_TPU_SHAPE_BUCKETS": "0", "H2O3_TPU_HIST_SUBTRACT": subtract}
    kw = dict(max_depth=8, n_bins=255, node_cap=8)
    t1, p1, v1 = _build_one(bins, t, split_shard=1, env=env, **kw)
    t0, p0, v0 = _build_one(bins, t, split_shard=0, env=env, **kw)
    # the saturated region must actually exist for this shape, or the test
    # is not exercising the while_loop at all
    assert st._sat_region(8, 8) == (3, 5)
    _assert_trees_bit_equal(t1, t0, f"saturated/subtract={subtract}")
    assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)


# ---------------------------------------------------------------------------
# the per-level monotone loop (every monotone build's path) and categorical
# columns on a sharded mesh: the sharded scan against the replicated one on
# the adversarial data that, until ISSUE 30, only a comparison with the
# interpret-only fused split kernel covered


def _replay_column_sweep(tree: st.Tree, C: int, col: int, n_bins: int = 16):
    """The tree's prediction as a function of ``col``'s bin (1..n_bins-1;
    bin 0 is the NA slot and direction-free), every other column held at
    bin 8."""
    probe = np.full((n_bins, C), 8, np.uint8)
    probe[:, col] = np.arange(n_bins)
    _, pp = tree.replay(jnp.asarray(probe), jnp.zeros(n_bins, jnp.int32),
                        jnp.zeros(n_bins, jnp.float32))
    return np.asarray(pp)[1:]


@pytest.mark.parametrize("k", [1, 2, 8])
def test_mono_tie_break(k):
    """Constant target, every column a duplicate, five columns constrained:
    every candidate is feasible (all child values are equal) and every gain
    is exactly 0.0, so only lowest-global-index tie-breaking picks the
    winner — in the mono scan's masked argmax and in the sharded merge."""
    with _use_mesh(k):
        n_pad = _pad_rows(960)
        bins, t = _tie_data(n_pad, C=13, n_bins=16, dup_all=True)
        mono = np.zeros(13, np.int32)
        mono[[0, 4, 9]] = 1
        mono[[2, 7]] = -1
        t1, p1, v1 = _build_one(bins, t, split_shard=1, monotone=mono)
        t0, p0, v0 = _build_one(bins, t, split_shard=0, monotone=mono)
        _assert_trees_bit_equal(t1, t0, f"mono-ties/{k}dev")
        assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)
        assert int(np.asarray(t1.levels[0].split_col)[0]) == 0


@pytest.mark.parametrize("k", [1, 2, 8])
def test_mono_constrained_signal(k):
    """A real signal that VIOLATES the constraint: the target falls with
    column 0, which is constrained +1. The sharded mono scan must match the
    replicated one bit for bit (integer-exact sums) and the fitted function
    must be non-decreasing along column 0, while an unconstrained build of
    the same data is not (so the constraint did the work)."""
    with _use_mesh(k):
        n_pad = _pad_rows(960)
        rng = np.random.default_rng(31)
        bins = rng.integers(1, 16, (n_pad, 6)).astype(np.uint8)
        t = (16.0 - bins[:, 0].astype(np.float32)
             + rng.integers(-2, 3, n_pad).astype(np.float32))
        mono = np.zeros(6, np.int32)
        mono[0] = 1
        t1, p1, v1 = _build_one(bins, t, split_shard=1, monotone=mono,
                                max_depth=4)
        t0, p0, v0 = _build_one(bins, t, split_shard=0, monotone=mono,
                                max_depth=4)
        _assert_trees_bit_equal(t1, t0, f"mono-signal/{k}dev")
        assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)
        sweep = _replay_column_sweep(t1, 6, 0)
        assert (np.diff(sweep) >= -1e-6).all(), sweep
        free, _, _ = _build_one(bins, t, split_shard=1, max_depth=4)
        assert (np.diff(_replay_column_sweep(free, 6, 0)) < -1e-3).any()


@pytest.mark.parametrize("k", [2, 8])
def test_mono_with_categoricals_sharded(k):
    """A monotone build on a frame that also has categorical columns, on a
    sharded mesh: every block runs the mean-sort branch on its local
    columns and the feasibility mask on its numeric ones; categorical
    winners carry ``mono_col`` 0, so their children inherit the parent's
    bounds. Bit parity with the replicated scan, a categorical split used
    somewhere, and the constrained column still monotone."""
    with _use_mesh(k):
        n_pad = _pad_rows(960)
        rng = np.random.default_rng(43)
        bins = rng.integers(1, 16, (n_pad, 7)).astype(np.uint8)
        bins[:, 2] = rng.integers(0, 7, n_pad)   # cat col, 6 levels
        bins[:, 5] = rng.integers(0, 5, n_pad)   # cat col, 4 levels
        is_cat = np.zeros(7, bool)
        is_cat[[2, 5]] = True
        t = (3.0 * (bins[:, 2] % 3).astype(np.float32)
             - bins[:, 0].astype(np.float32)
             + rng.integers(-2, 3, n_pad).astype(np.float32))
        mono = np.zeros(7, np.int32)
        mono[0] = 1
        kw = dict(is_cat=is_cat, monotone=mono, max_depth=4)
        t1, p1, v1 = _build_one(bins, t, split_shard=1, **kw)
        t0, p0, v0 = _build_one(bins, t, split_shard=0, **kw)
        _assert_trees_bit_equal(t1, t0, f"mono-cat/{k}dev")
        assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)
        assert any(
            np.asarray(lv.is_cat)[~np.asarray(lv.leaf_now) & m].any()
            for lv, m in zip(t1.to_host().levels, t1.real_level_masks())
        )
        sweep = _replay_column_sweep(t1, 7, 0)
        assert (np.diff(sweep) >= -1e-6).all(), sweep


@pytest.mark.parametrize("k", [2, 8])
def test_cat_sharded_tie_break(k):
    """Duplicated categorical columns spanning column blocks plus duplicated
    numeric columns: the winner merge must still be lowest-global-index,
    bit-equal to the replicated scan, with the (N, B) membership mask riding
    the winner gather."""
    with _use_mesh(k):
        n_pad = _pad_rows(960)
        rng = np.random.default_rng(37)
        base_cat = rng.integers(0, 7, n_pad).astype(np.uint8)
        base_num = rng.integers(1, 16, n_pad).astype(np.uint8)
        # 10 columns: cat duplicates at 1,4,8 / numeric duplicates elsewhere
        bins = np.tile(base_num[:, None], (1, 10))
        is_cat = np.zeros(10, bool)
        for c in (1, 4, 8):
            bins[:, c] = base_cat
            is_cat[c] = True
        t = rng.integers(-3, 4, n_pad).astype(np.float32)
        t1, p1, v1 = _build_one(bins, t, split_shard=1, is_cat=is_cat,
                                max_depth=4)
        t0, p0, v0 = _build_one(bins, t, split_shard=0, is_cat=is_cat,
                                max_depth=4)
        _assert_trees_bit_equal(t1, t0, f"cat-sharded-ties/{k}dev")
        assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)
        # a categorical split must actually win somewhere, and among the
        # duplicated columns of a kind only the LOWEST index may appear
        used = {True: set(), False: set()}
        for lv, m in zip(t1.to_host().levels, t1.real_level_masks()):
            sel = ~np.asarray(lv.leaf_now) & m
            for cat in (True, False):
                pick = sel & (np.asarray(lv.is_cat) == cat)
                used[cat] |= set(np.asarray(lv.split_col)[pick].tolist())
        assert used[True] == {1} and used[False] <= {0}, used


def test_parity_categorical_and_model_level():
    """End-to-end GBM with categorical columns: predictions, varimp and the
    canonical records are bit-equal between the pipelines."""
    rng = np.random.default_rng(0)
    n = 2000
    X = rng.normal(size=(n, 5))
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(5)])
    df["c0"] = pd.Categorical(rng.choice(list("abcdefg"), n))
    df["c1"] = pd.Categorical(rng.choice(list("uvwxyz"), n))
    df["y"] = (
        X[:, 0] * 2 - X[:, 1]
        + (df["c0"].cat.codes.to_numpy() % 3)
        + 0.3 * rng.normal(size=n)
    )

    def run(shard):
        with _env(H2O3_TPU_SPLIT_SHARD=shard):
            from h2o3_tpu.frame.frame import Frame
            from h2o3_tpu.models.tree import GBM

            fr = Frame.from_pandas(df)
            m = GBM(
                ntrees=4, max_depth=4, seed=7, distribution="gaussian",
                col_sample_rate=0.7, sample_rate=0.8,
            ).train(y="y", training_frame=fr)
            p = np.asarray(m.predict(fr).vec("predict").to_numpy())
            vi = [
                (r["variable"], float(r["relative_importance"]))
                for r in m.varimp()
            ]
            return p, vi

    p1, v1 = run(1)
    p0, v0 = run(0)
    assert _bits(p1.astype(np.float64)) == _bits(p0.astype(np.float64))
    assert v1 == v0


def test_collective_byte_counters_measure_the_claim():
    """tree_collective_bytes_total{phase}: the sharded pipeline's
    hist-reduce volume must undercut the replicated one >= 2x (it is 1/P
    by construction), and the winner gather must be accounted (nonzero)
    yet small next to the histogram traffic it replaces."""
    from h2o3_tpu.utils import metrics as mx

    n_pad = _pad_rows(700)
    rng = np.random.default_rng(19)
    bins = rng.integers(0, 32, (n_pad, 28)).astype(np.uint8)  # bench C=28
    t = rng.normal(size=n_pad).astype(np.float32)

    def bytes_for(shard):
        before_h = mx.counter_value(
            "tree_collective_bytes_total", phase="hist_reduce")
        before_w = mx.counter_value(
            "tree_collective_bytes_total", phase="winner_gather")
        _build_one(bins, t, split_shard=shard, n_bins=32, seed=23)
        return (
            mx.counter_value(
                "tree_collective_bytes_total", phase="hist_reduce") - before_h,
            mx.counter_value(
                "tree_collective_bytes_total", phase="winner_gather") - before_w,
        )

    h1, w1 = bytes_for(1)
    h0, w0 = bytes_for(0)
    assert h0 > 0 and h1 > 0
    assert w0 == 0  # replicated path has no winner gather
    assert w1 > 0
    assert h0 >= 2 * (h1 + w1), (h0, h1, w1)


def test_hist_override_scatter_reaches_scatter_impl():
    from h2o3_tpu.ops import histogram as hg

    with _env(H2O3_TPU_HIST="scatter"):
        assert hg._select_local() is hg._hist_scatter_local
    with _env(H2O3_TPU_HIST="matmul"):
        assert hg._select_local() is hg._hist_matmul_local


def test_sharded_histogram_bit_equal_and_padded():
    """histogram_in_jit(col_sharded=True): each column block is bit-equal to
    the replicated psum's slice; divisibility padding columns are all-zero
    (C=7 on an 8-device mesh exercises C < P block padding)."""
    from h2o3_tpu.ops.histogram import histogram_in_jit

    rng = np.random.default_rng(2)
    n, C, N, B = _pad_rows(2000), 7, 8, 16
    bins = pm.shard_rows(jnp.asarray(rng.integers(0, B, (n, C)), jnp.uint8))
    nid = pm.shard_rows(jnp.asarray(rng.integers(-1, N, n), jnp.int32))
    w = pm.shard_rows(jnp.asarray(rng.random(n), jnp.float32))
    wy = pm.shard_rows(jnp.asarray(rng.normal(size=n), jnp.float32))
    rep = jax.jit(
        lambda b, i, *s: histogram_in_jit(b, i, s, N, B)
    )(bins, nid, w, wy, w)
    shd = jax.jit(
        lambda b, i, *s: histogram_in_jit(b, i, s, N, B, col_sharded=True)
    )(bins, nid, w, wy, w)
    rep, shd = np.asarray(rep), np.asarray(shd)
    Cp = pm.pad_cols_to_shards(C)
    assert shd.shape[1] == Cp
    assert _bits(rep) == _bits(shd[:, :C])
    assert not shd[:, C:].any()
