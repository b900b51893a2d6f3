"""The default forest against its plain reference (ISSUE 32), on the CPU at
20,000 x 28: ``H2ORandomForestEstimator`` at ``drf_higgs``'s settings
(``max_depth`` 20, ``mtries`` -1, 0.632 bootstrap, ``min_rows`` 1) through
the normal path, followed by ``benchmark/configs/drf_higgs_ref.py``; the
planted faults; the exact column draw; the bootstrap function's two callers;
the node-tile counter. The readings at the cell's own size are in PERF.md.
"""

import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.configs import drf_higgs as cfgmod  # noqa: E402
from benchmark.tests.faults_drf import PLANTED  # noqa: E402
from h2o3_tpu.models.tree import drf as drfmod  # noqa: E402
from h2o3_tpu.models.tree import shared_tree as st  # noqa: E402
from h2o3_tpu.utils import metrics as mx  # noqa: E402

ROWS, SEEDS = 20_000, (77, 78, 2**31 + 5)
COUNTERS = ("tree_node_tiles_total", "tree_sat_levels_total", "tree_trees_built_total")


def _cfg(**estimator) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", "drf_higgs.json")) as f:
        cfg = json.load(f)
    cfg["rows"] = ROWS
    cfg["estimator"].update(estimator)
    return cfg


@functools.lru_cache(maxsize=None)
def _forest(seed: int, ntrees: int = 1):
    """(cfg, X, y, outputs, estimator, counter deltas) of one fitted forest."""
    from benchmark.configs.higgs_data import make_frame

    cfg = _cfg(ntrees=ntrees)
    data = make_frame(ROWS, cfg["cols"], seed)
    before = {c: mx.counter_value(c) for c in COUNTERS}
    est = cfgmod.build_estimator(cfg)
    cfgmod.train(est, data)
    delta = {c: mx.counter_value(c) - before[c] for c in COUNTERS}
    assert cfgmod.passes(cfg, est) == ntrees
    X, y = data.host()
    return cfg, X, y, cfgmod.outputs(est), est, delta


def _over(cfg, checks: dict) -> dict:
    return {k: v for k, v in checks.items() if not v <= cfg["limits"][k]}


@pytest.mark.parametrize("seed", SEEDS)
def test_forest_follows_its_reference(seed):
    """Every gap under its limit, and the reference saw a whole tree: all
    its leaves, the shallow levels' nodes and the sampled deep ones."""
    cfg, X, y, model, est, _ = _forest(seed)
    got = cfgmod.compare(cfg, X, y, model, control=True)
    assert set(got["program"]) == set(cfg["limits"])
    assert not _over(cfg, got["program"]), got
    assert got["program"]["offer_gap"] == 0.0
    tree = est.model.output["trees"][0][0]
    assert got["reference"]["leaves"] == tree.n_leaves > 500
    assert got["diagnostic"]["gain_nodes"] > 63 + 100  # levels 0-5 and deep ones
    # the control (one precision down) is not correct
    assert _over(cfg, got["control"]), got["control"]


@pytest.mark.parametrize("fault,check", [
    ("bernoulli_offer", "offer_gap"), ("bag_ignored", "leaf_gap"), ("altered", "leaf_gap")])
def test_planted_fault_trips_a_limit(fault, check):
    cfg, X, y, model, _, _ = _forest(SEEDS[0])
    got = cfgmod.compare(cfg, X, y, PLANTED["drf_higgs"][fault](model, X, y, cfg))
    assert check in _over(cfg, got["program"]), got["program"]


def test_the_parents_bernoulli_draw_in_the_program_trips_offer_gap(monkeypatch):
    """The fault planted under the timed path itself: the column draw this
    PR replaced (each column with probability mtries / C, all of them where
    a node drew none) in ``_offered_columns``' place."""
    def bernoulli(key, cols_enabled, col_sample_rate, n_pad, n_cols_real=None):
        C = cols_enabled.shape[0]
        Cr = n_cols_real or C
        keep = jax.random.uniform(key, (n_pad, Cr)) < col_sample_rate
        keep = jnp.where(keep.any(axis=1, keepdims=True), keep, True)
        return cols_enabled[None, :] * jnp.pad(keep, ((0, 0), (0, C - Cr)))

    monkeypatch.setattr(st, "_offered_columns", bernoulli)
    monkeypatch.setattr(st, "_STEP_CACHE", {})  # a program traced with the fault
    monkeypatch.setattr(st, "_PROG_COLL", {})
    try:
        cfg, X, y, model, _, _ = _forest.__wrapped__(SEEDS[0])
    finally:
        jax.clear_caches()  # no later test may meet the faulty executable
    got = cfgmod.compare(cfg, X, y, model)["program"]
    assert got["offer_gap"] > 0.5 and got["leaf_gap"] <= cfg["limits"]["leaf_gap"], got


def test_every_node_is_offered_exactly_mtries_columns():
    """In the growth levels and in the saturated loop (levels 11-19 at 2048
    node slots), padding slots included; each column about equally often;
    the terminal level scans nothing and offers nothing."""
    _, _, _, model, est, _ = _forest(SEEDS[0])
    levels = model["trees"][0]
    assert len(levels) == 21 and levels[11]["col_offer"].shape == (2048, 28)
    for lv in levels[:-1]:
        assert (lv["col_offer"].sum(axis=1) == 5).all()
        split = ~lv["leaf_now"]
        assert lv["col_offer"][split, lv["split_col"][split]].all()
    assert not levels[-1]["col_offer"].any()
    share = np.concatenate([lv["col_offer"] for lv in levels[11:20]]).mean(axis=0)
    assert np.abs(share - 5 / 28).max() < 0.015  # 18,432 draws: sd 0.0028
    assert est.model.offered_columns(0)[3].shape == (8, 28)


@pytest.mark.parametrize("rate,enabled,pad,want", [
    (1.0, 28, 0, 28), (5 / 28, 28, 4, 5), (0.1, 5, 0, 3), (5 / 28, 3, 0, 3), (0.01, 28, 0, 1)])
def test_offered_columns_draw(rate, enabled, pad, want):
    """k = max(1, round(rate x C_real)) distinct columns a node, from the
    enabled ones alone (all of them where fewer are enabled), never a pad
    column, and the same draw whatever the column padding."""
    C = 28
    on = jnp.asarray((np.arange(C) < enabled).astype(np.float32))
    key = jax.random.PRNGKey(9)
    m = np.asarray(st._offered_columns(key, jnp.pad(on, (0, pad)), jnp.float32(rate), 512, C))
    assert m.shape == (512, C + pad) and set(np.unique(m)) <= {0.0, 1.0}
    assert (m.sum(axis=1) == want).all()
    assert not m[:, enabled:].any()
    assert (m[:, :C] == np.asarray(st._offered_columns(key, on, jnp.float32(rate), 512, C))).all()
    # nodes differ: every subset turns up where there are few, else many
    assert len({r.tobytes() for r in m}) >= min(math.comb(enabled, want), 400)


def test_rate_one_offers_every_column():
    """GBM's default: nothing is sampled, every scanned level offers all."""
    from benchmark.configs.higgs_data import make_frame
    from h2o3_tpu.estimators import H2OGradientBoostingEstimator

    est = H2OGradientBoostingEstimator(ntrees=2, max_depth=3, seed=1)
    est.train(y="label", training_frame=make_frame(2000, 6, 3).frame)
    offered = est.model.offered_columns(1)
    assert [o.shape for o in offered] == [(1, 6), (2, 6), (4, 6), (8, 6)]
    assert all(o.all() for o in offered[:-1]) and not offered[-1].any()


def test_builder_and_model_draw_the_same_bag():
    """One function, two callers: the root's cover in the record (what the
    chunk program summed under ITS mask) is the number of rows the model
    re-derives; two trees' bags are independent draws; no pad row is in."""
    assert drfmod.bootstrap_mask is st.bootstrap_mask
    _, _, _, model, est, _ = _forest(SEEDS[1], 2)
    bags = model["inbag"]
    for t, bag in enumerate(bags):
        root = est.model.output["trees"][t][0].levels[0]
        assert bag.shape == (est.model.output["bootstrap"]["npad"],)
        assert float(np.asarray(root.node_w)[0]) == bag.sum()
        assert abs(bag[:ROWS].mean() - 0.632) < 5 * np.sqrt(0.632 * 0.368 / ROWS)
        assert not bag[ROWS:].any()
    both = (bags[0] & bags[1])[:ROWS].mean()
    assert abs(both - 0.632**2) < 0.02 and not (bags[0] == bags[1]).all()
    with pytest.raises(ValueError, match="tree 2"):
        est.model.inbag_rows(2)


@pytest.mark.parametrize("depth", [6, 20])
def test_node_tiles_counter_equals_the_count_by_hand(depth):
    """With sibling subtraction a level builds half its node slots: a
    depth-6 tree asks for 1, 1, 2, 4, 8, 16 nodes, one tile of 64 each; a
    depth-20 tree for 1 ... 512 nodes (8 + 2 + 4 + 8 = 22 tiles), then 1,024
    nodes = 16 tiles for every saturated level that ran; the terminal level
    builds nothing."""
    if depth == 20:
        *_, delta = _forest(SEEDS[1], 2)
        trees, sat = 2, delta["tree_sat_levels_total"]
        assert sat == 18  # nine levels a tree: the frontier never emptied
        want = trees * 22 + 16 * sat
    else:
        from benchmark.configs.higgs_data import make_frame
        from h2o3_tpu.estimators import H2OGradientBoostingEstimator

        data = make_frame(4000, 28, 5)
        before = {c: mx.counter_value(c) for c in COUNTERS}
        H2OGradientBoostingEstimator(ntrees=3, max_depth=6, seed=1).train(
            y="label", training_frame=data.frame)
        delta = {c: mx.counter_value(c) - before[c] for c in COUNTERS}
        want = 3 * 6
    assert delta["tree_node_tiles_total"] == want, delta
