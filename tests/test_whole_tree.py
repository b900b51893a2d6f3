"""Device-resident whole-tree build contracts (ISSUE 1): O(1) host
dispatches per tree, shape-bucketed padding that is provably inert, and
compile amortization across same-shape builds."""

import numpy as np
import pandas as pd
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.tree import GBM
from h2o3_tpu.models.tree import shared_tree as st


def _df(n=2000, seed=0, c=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, c))
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(c)])
    df["y"] = X[:, 0] * 2 - X[:, 1] + 0.3 * rng.normal(size=n)
    return df


def _train(fr, **kw):
    params = dict(ntrees=10, max_depth=4, seed=7, distribution="gaussian",
                  score_tree_interval=5)
    params.update(kw)
    return GBM(**params).train(y="y", training_frame=fr)


def test_whole_tree_dispatches_o1_per_tree():
    """The whole-tree contract: host dispatches per tree are O(1), not
    O(depth). With the scanned chunk builder they are FRACTIONAL (one
    dispatch covers a whole scoring interval); the per-level escape hatch
    (H2O3_TPU_WHOLE_TREE=0) pays >= depth dispatches per tree — the counter
    must see both regimes or it is not counting."""
    fr = Frame.from_pandas(_df())
    st.reset_build_stats()
    _train(fr)
    fused = st.reset_build_stats()
    assert fused["trees_built"] == 10
    # ntrees=10, interval=5 -> 2 chunk dispatches, NOT 10 * (depth + 1)
    assert fused["dispatches"] <= 2
    assert fused["dispatches"] / fused["trees_built"] < 1  # O(1), amortized


def test_per_level_escape_hatch_dispatches_o_depth(monkeypatch):
    monkeypatch.setenv("H2O3_TPU_WHOLE_TREE", "0")
    fr = Frame.from_pandas(_df())
    st.reset_build_stats()
    _train(fr)
    legacy = st.reset_build_stats()
    assert legacy["trees_built"] == 10
    # per-level loop: every tree pays at least one dispatch per grown level
    assert legacy["dispatches"] >= legacy["trees_built"] * 2
    assert legacy["dispatches"] > 10 * 2  # strictly worse than whole-tree


def test_bucketed_padding_scores_identical(monkeypatch):
    """Shape-bucketed padding (H2O3_TPU_SHAPE_BUCKETS) must be inert: a
    bucketed build (cols padded to 8, bins to a power of two) scores
    IDENTICALLY to the exact-shape build — padded bins are empty, padded
    columns are disabled, and the column-sampling RNG draws at the real
    column count. Uses col_sample_rate < 1 so the RNG-width guarantee is
    actually load-bearing."""
    df = _df(c=5)  # 5 cols -> pads to 8 when bucketing
    kw = dict(col_sample_rate=0.7, sample_rate=0.8)

    monkeypatch.setenv("H2O3_TPU_SHAPE_BUCKETS", "1")
    fr = Frame.from_pandas(df)
    p_bucketed = _train(fr, **kw).predict(fr).vec("predict").to_numpy()
    vi_bucketed = _train(fr, **kw).varimp()

    monkeypatch.setenv("H2O3_TPU_SHAPE_BUCKETS", "0")
    fr = Frame.from_pandas(df)
    p_exact = _train(fr, **kw).predict(fr).vec("predict").to_numpy()
    vi_exact = _train(fr, **kw).varimp()

    np.testing.assert_array_equal(np.asarray(p_bucketed), np.asarray(p_exact))
    assert len(vi_bucketed) == len(vi_exact)  # no phantom padded columns
    for ra, rb in zip(vi_bucketed, vi_exact):
        assert ra["variable"] == rb["variable"]
        assert float(ra["relative_importance"]) == pytest.approx(
            float(rb["relative_importance"])
        )


def test_same_shape_twice_compiles_once():
    """Two GBMs of the same shape in one process: the second build's tree
    programs must ALL come from the in-process cache (zero compiles) —
    the compile-amortization half of the whole-tree design."""
    fr = Frame.from_pandas(_df(seed=1))
    _train(fr)  # whatever this compiles...
    st.reset_build_stats()
    _train(fr, seed=99)  # ...a same-shape rebuild reuses, seed is not shape
    again = st.reset_build_stats()
    assert again["tree_programs_compiled"] == 0
    assert again["tree_program_cache_hits"] >= 1


def test_nbins_bucket_collapses_nearby_shapes(monkeypatch):
    """The bin-axis ladder: nbins 100 and 120 both round to 128, so the
    second model's tree program is a cache HIT — the AutoML/grid sweep
    amortization the ladder exists for. (Bin EDGES still differ — only the
    compiled program is shared, not the splits.)"""
    monkeypatch.setenv("H2O3_TPU_SHAPE_BUCKETS", "1")
    # many distinct values so fit_bins actually uses ~nbins quantile bins
    fr = Frame.from_pandas(_df(n=4000, seed=2))
    _train(fr, nbins=100)
    st.reset_build_stats()
    _train(fr, nbins=120)
    stats = st.reset_build_stats()
    assert stats["tree_programs_compiled"] == 0
    assert stats["tree_program_cache_hits"] >= 1


# ---------------------------------------------------------------------------
# The grouped histogram inside the whole-tree program (ISSUE 33): from the
# first level wider than one node tile the histogram reads its rows in node
# order. The kernel runs in the interpreter with a node tile of 8, so that a
# tree of a few thousand rows has levels of 2 to 16 tiles.

_TILES = (128, 8, 8)  # H2O3_TPU_PALLAS_TILES: row, column, node
_GROUPED_COUNTERS = (
    "tree_hist_grouped_levels_total", "tree_hist_chunk_visits_total",
    "tree_node_tiles_total", "tree_sat_levels_total")


def _one_tree(monkeypatch, depth, node_cap, *, forest, order=True, devices=1,
              n=4096, c=5):
    """One whole-tree program over ``n`` rows through ``_run_counted`` with
    the Pallas kernel interpreted: a forest's tree (0/1 response as the
    gradient, a 0.632 bag, ``min_rows`` 1, 3 of 5 columns a node) or a GBM's
    (real-valued gradients, ``min_rows`` 10). ``order=False`` withholds the
    row order by its predicate — there is no knob. Returns (nid, preds,
    varimp, records, counts) as numpy and the counters' movement."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from h2o3_tpu.ops import histogram as hg
    from h2o3_tpu.parallel import mesh as pm
    from h2o3_tpu.utils import metrics

    monkeypatch.setenv("H2O3_TPU_HIST", "pallas")
    monkeypatch.setenv("H2O3_TPU_PALLAS_TILES", ",".join(map(str, _TILES)))
    if not order:
        monkeypatch.setattr(hg, "_wants_row_order", lambda *a: False)
    rng = np.random.default_rng(5)
    bins = rng.integers(1, 32, (n, c)).astype(np.uint8)
    if forest:
        w = (rng.random(n) < 0.632).astype(np.float32)
        t = (rng.random(n) < 0.5).astype(np.float32)
        h = w
    else:
        w = np.ones(n, np.float32)
        t = rng.normal(size=n).astype(np.float32)
        h = rng.random(n).astype(np.float32)
    old = pm._mesh
    pm.set_mesh(Mesh(np.array(jax.devices("cpu")[:devices]), (pm.ROWS_AXIS,)))
    st._STEP_CACHE.clear()
    st._PROG_COLL.clear()
    try:
        prog = st._tree_program(depth, 32, node_cap, ())
        before = {k: metrics.counter_value(k) for k in _GROUPED_COUNTERS}
        out = st._run_counted(prog, (
            pm.shard_rows(jnp.asarray(bins)),
            pm.shard_rows(jnp.zeros(n, jnp.float32)), jnp.zeros(c, jnp.float32),
            pm.shard_rows(jnp.asarray(w)), pm.shard_rows(jnp.asarray(w * t)),
            pm.shard_rows(jnp.asarray(h)), jax.random.PRNGKey(3),
            jnp.ones(c, jnp.float32), jnp.zeros(c, bool),
            jnp.float32(1.0 if forest else 10.0), jnp.float32(1e-5),
            jnp.float32(1.0 if forest else 0.1), jnp.float32(np.inf),
            jnp.float32(0.6 if forest else 1.0), None,
        ), counts_from=lambda o: o[4])
        out = jax.tree_util.tree_map(np.asarray, out)
        moved = {k: metrics.counter_value(k) - v for k, v in before.items()}
    finally:
        pm.set_mesh(old)
        st._STEP_CACHE.clear()
        st._PROG_COLL.clear()
        monkeypatch.undo()
    return out, moved


def _grouped_levels(depth, node_cap, sat_levels, n, devices=1):
    """From the level structure alone: how many of a tree's histogram levels
    are wider than one node tile, and the most grid steps a level may take
    whose rows are in node-tile order / at all before it goes dense."""
    sat_start, _ = st._sat_region(depth, node_cap)
    levels = list(range(depth if sat_start is None else sat_start))
    levels += [sat_start] * sat_levels
    n_r = -(-(n // devices) // _TILES[0])
    tiles = [-(-max(min(1 << d, node_cap) // 2, 1) // _TILES[2]) for d in levels]
    wide = [t for t in tiles if t > 1]
    return (len(wide), devices * sum(n_r + t - 1 for t in wide),
            devices * sum(2 * n_r + t - 1 for t in wide))


def _same_records(a, b):
    """Node ids, predictions, varimp and every record array, bit for bit."""
    import jax

    la, lb = (jax.tree_util.tree_leaves(x[:4]) for x in (a, b))
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


def test_grouped_histogram_builds_the_same_forest_tree(monkeypatch):
    """A depth-10 forest tree (``node_cap`` 256: levels 8 and 9 are the
    saturated ``while_loop``) with its histograms grouped from level 5 on,
    against the same build with the order withheld: every record array, the
    node ids and the predictions are bit-identical (0/1 sums are exact in
    any order). The counters say what ran: one grouped level for every level
    wider than a node tile, the loop's by the levels it executed; far fewer
    grid steps than the dense pass and never more than the visit list."""
    depth, cap, n = 10, 256, 4096
    on, moved = _one_tree(monkeypatch, depth, cap, forest=True)
    off, moved_off = _one_tree(monkeypatch, depth, cap, forest=True, order=False)
    _same_records(on, off)
    sat = int(on[4][0])
    assert sat >= 1 and moved["tree_sat_levels_total"] == sat
    levels, _, most = _grouped_levels(depth, cap, sat, n)
    assert levels == 3 + sat
    assert moved["tree_hist_grouped_levels_total"] == levels
    assert moved["tree_hist_chunk_visits_total"] == int(on[4][1]) <= most
    n_r = n // _TILES[0]
    dense = n_r * sum(t for t in (2, 4, 8) + (16,) * sat)
    assert moved["tree_hist_chunk_visits_total"] < dense / 4
    assert moved_off["tree_hist_grouped_levels_total"] == 0
    assert moved_off["tree_hist_chunk_visits_total"] == 0
    assert moved_off["tree_node_tiles_total"] == moved["tree_node_tiles_total"]


def test_sorted_level_and_the_next_visit_a_chunk_once(monkeypatch):
    """The invariant the order rests on, where it holds exactly: children are
    numbered in their parents' order, so at the level of the sort and at the
    next one the rows lie in node-tile order and a level takes at most
    ``n_r + n_nt − 1`` chunk visits. (Two levels on, the grandchildren of one
    sorted node interleave inside its segment and may straddle a tile: the
    visit list has room for that, the first test pins its bound.) Depth 7:
    levels 5 and 6 build 16 and 32 nodes, two and four tiles of 8."""
    out, moved = _one_tree(monkeypatch, 7, 256, forest=True)
    levels, in_order, _ = _grouped_levels(7, 256, 0, 4096)
    assert levels == moved["tree_hist_grouped_levels_total"] == 2
    assert 0 < moved["tree_hist_chunk_visits_total"] <= in_order


def test_grouped_histogram_is_shard_local(monkeypatch):
    """The same forest tree on the 8-device mesh: every shard sorts its own
    rows (no row crosses a device, the reduce is untouched) and the tree is
    the one-device tree, bit for bit."""
    depth, cap, n = 10, 256, 4096
    one, _ = _one_tree(monkeypatch, depth, cap, forest=True)
    eight, moved = _one_tree(monkeypatch, depth, cap, forest=True, devices=8)
    _same_records(one, eight)
    levels, _, most = _grouped_levels(depth, cap, int(eight[4][0]), n, devices=8)
    assert moved["tree_hist_grouped_levels_total"] == levels
    assert 0 < moved["tree_hist_chunk_visits_total"] <= most


def test_grouped_histogram_holds_a_deep_gbm_tree(monkeypatch):
    """Real-valued statistics: a depth-9 GBM tree with the order on sums a
    cell's float32 terms in another order than with the order withheld. The
    tree is held to the parity tolerances of sibling subtraction
    (``test_hist_subtraction_matches_direct``): the same splits, the
    predictions within 1e-5."""
    on, moved = _one_tree(monkeypatch, 9, 2048, forest=False)
    off, _ = _one_tree(monkeypatch, 9, 2048, forest=False, order=False)
    assert moved["tree_hist_grouped_levels_total"] == 4  # levels 5..8
    for rec_on, rec_off in zip(on[3], off[3]):
        np.testing.assert_array_equal(rec_on["leaf_now"], rec_off["leaf_now"])
        split = ~rec_on["leaf_now"]
        np.testing.assert_array_equal(
            rec_on["split_col"][split], rec_off["split_col"][split])
        np.testing.assert_array_equal(
            rec_on["split_bin"][split], rec_off["split_bin"][split])
    np.testing.assert_allclose(on[1], off[1], rtol=0, atol=1e-5)
