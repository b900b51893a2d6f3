"""Fused Pallas histogram→split pipeline (ISSUE 6, ``H2O3_TPU_SPLIT_FUSE``):
the blocked-layout histogram kernel + VMEM-tile split kernel + winner
assembly must be INDISTINGUISHABLE from the unfused pipeline — split
decisions, predictions and varimp bit-equal on the PR-5 adversarial tie
suites across 1/2/8-device meshes (interpret mode on the CPU CI cloud),
mixed categorical/numeric frames must route cat columns to the fallback
scan, and the kernel result must track an f64 reference within the bf16
2-term split's accuracy envelope (carried over from test_hist_pallas.py).
"""

import contextlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from h2o3_tpu.models.tree import shared_tree as st
from h2o3_tpu.parallel import mesh as pm


@contextlib.contextmanager
def _use_mesh(k: int):
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


_FIELDS = (
    "split_col", "split_bin", "is_cat", "cat_mask", "na_left", "leaf_now",
    "leaf_val", "child_base", "gain", "node_w",
)


def _assert_trees_bit_equal(a: st.Tree, b: st.Tree, what: str):
    ha, hb = a.to_host(), b.to_host()
    assert len(ha.levels) == len(hb.levels), what
    for li, (la, lb) in enumerate(zip(ha.levels, hb.levels)):
        for k in _FIELDS:
            assert _bits(getattr(la, k)) == _bits(getattr(lb, k)), (
                f"{what}: level {li} field {k} diverged between fused and "
                f"unfused split pipelines"
            )


def _build_one(bins_np, t_np, *, split_fuse, hist="pallas", max_depth=3,
               n_bins=16, node_cap=2048, min_rows=1.0, env=None,
               is_cat=None, seed=5, monotone=None):
    """build_tree under the given H2O3_TPU_SPLIT_FUSE on the CURRENT mesh.
    ``hist='pallas'`` pins BOTH pipelines to the Pallas histogram kernel
    (interpreter on CPU) so the comparison isolates the split pipeline."""
    n, C = bins_np.shape
    with _env(H2O3_TPU_SPLIT_FUSE=split_fuse, H2O3_TPU_HIST=hist,
              **(env or {})):
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


def _tie_data(n_pad: int, C: int, n_bins: int, seed=0):
    """PR-5 adversarial exact-tie data: unit weights, constant target —
    every candidate gain is exactly 0.0 and every column is a duplicate,
    so only lowest-global-index tie-breaking picks the winner."""
    rng = np.random.default_rng(seed)
    base = rng.integers(1, n_bins, n_pad).astype(np.uint8)
    return np.tile(base[:, None], (1, C)), np.ones(n_pad, np.float32)


@pytest.mark.parametrize("k", [1, 2, 8])
def test_fused_tie_break_constant_target(k):
    """Constant target: every (col, bin) candidate gain is exactly 0.0;
    the fused kernel's per-column argmax + the assembly's column argmax
    must land on jnp.argmax's lowest-global-index choice on any mesh."""
    with _use_mesh(k):
        n_pad = pm.pad_to_shards(960)
        bins, t = _tie_data(n_pad, C=13, n_bins=16)
        t1, p1, v1 = _build_one(bins, t, split_fuse="1")
        t0, p0, v0 = _build_one(bins, t, split_fuse="0")
        _assert_trees_bit_equal(t1, t0, f"fused-ties/{k}dev")
        assert _bits(p1) == _bits(p0)
        assert _bits(v1) == _bits(v0)
        assert int(np.asarray(t1.levels[0].split_col)[0]) == 0


@pytest.mark.parametrize("k", [2, 8])
def test_fused_tie_break_duplicated_columns_nonzero_gains(k):
    """Duplicated columns spanning blocks with a real ±1 signal (exact in
    f32): identical non-zero best gains in several column tiles at once —
    the sharded fused merge must pick the lowest global column."""
    with _use_mesh(k):
        n_pad = pm.pad_to_shards(960)
        rng = np.random.default_rng(3)
        bins, _ = _tie_data(n_pad, C=16, n_bins=16, seed=3)
        t = (rng.integers(0, 2, n_pad) * 2 - 1).astype(np.float32)
        t1, p1, v1 = _build_one(bins, t, split_fuse="1", max_depth=4)
        t0, p0, v0 = _build_one(bins, t, split_fuse="0", max_depth=4)
        _assert_trees_bit_equal(t1, t0, f"fused-dup-cols/{k}dev")
        assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)
        masks = t0.real_level_masks()
        for lv, m in zip(t0.to_host().levels, masks):
            split = ~np.asarray(lv.leaf_now) & m
            assert (np.asarray(lv.split_col)[split] == 0).all()


@pytest.mark.parametrize("subtract", ["1", "0"])
def test_fused_parity_both_force_leaf_paths(subtract):
    """Both terminal regimes under fuse: subtract=1 derives leaf stats from
    the parents' splits (no histogram), subtract=0 force-leafs from the
    blocked histogram's column-0 totals. Integer targets keep every sum
    exact, so parity is bitwise."""
    n_pad = pm.pad_to_shards(700)
    rng = np.random.default_rng(7)
    bins = rng.integers(0, 16, (n_pad, 7)).astype(np.uint8)  # 7 % 8 != 0
    t = rng.integers(-3, 4, n_pad).astype(np.float32)
    env = {"H2O3_TPU_HIST_SUBTRACT": subtract}
    t1, p1, v1 = _build_one(bins, t, split_fuse="1", env=env)
    t0, p0, v0 = _build_one(bins, t, split_fuse="0", env=env)
    _assert_trees_bit_equal(t1, t0, f"fused-force-leaf/subtract={subtract}")
    assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)


def test_fused_parity_coarsened_saturated_levels():
    """Deep tree, small node_cap, bin adaptivity on: the saturated
    while_loop runs at coarsened bins — blocked_coarsen + the blocked
    sibling-subtraction carry must stay bit-equal to the dense pipeline."""
    n_pad = pm.pad_to_shards(600)
    rng = np.random.default_rng(11)
    bins = rng.integers(0, 255, (n_pad, 6)).astype(np.uint8)
    t = rng.integers(-3, 4, n_pad).astype(np.float32)
    env = {"H2O3_TPU_BIN_ADAPT": "1", "H2O3_TPU_SHAPE_BUCKETS": "0"}
    kw = dict(max_depth=8, n_bins=255, node_cap=8)
    t1, p1, v1 = _build_one(bins, t, split_fuse="1", env=env, **kw)
    t0, p0, v0 = _build_one(bins, t, split_fuse="0", env=env, **kw)
    shifts = st._bin_shifts(8, 255, ())
    assert st._sat_region(8, 8, shifts)[1] >= 2
    _assert_trees_bit_equal(t1, t0, "fused-coarsened-sat")
    assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)


@pytest.mark.parametrize("k", [1, 8])
def test_fused_mixed_categorical_routes_to_fallback(k):
    """Mixed categorical/numeric frame: on 1 device the fused pipeline
    routes cat columns to the mean-sort fallback branch (numeric stays on
    the kernel); on an 8-device mesh every block runs the mean-sort branch
    on its BLOCK-LOCAL dense gather inside the fused sharded scan (the
    ISSUE-15 closure — the build no longer drops to the dense scan).
    Either way: bit parity."""
    with _use_mesh(k):
        n_pad = pm.pad_to_shards(700)
        rng = np.random.default_rng(13)
        bins = rng.integers(0, 16, (n_pad, 7)).astype(np.uint8)
        bins[:, 2] = rng.integers(0, 7, n_pad)   # cat col, 6 levels
        bins[:, 5] = rng.integers(0, 5, n_pad)   # cat col, 4 levels
        is_cat = np.zeros(7, bool)
        is_cat[[2, 5]] = True
        t = rng.integers(-3, 4, n_pad).astype(np.float32)
        t1, p1, v1 = _build_one(bins, t, split_fuse="1", is_cat=is_cat)
        t0, p0, v0 = _build_one(bins, t, split_fuse="0", is_cat=is_cat)
        _assert_trees_bit_equal(t1, t0, f"fused-cat/{k}dev")
        assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)
        # the trees must actually use a categorical split somewhere, or the
        # routing was never exercised
        assert any(
            np.asarray(lv.is_cat)[~np.asarray(lv.leaf_now) & m].any()
            for lv, m in zip(t0.to_host().levels, t0.real_level_masks())
        )
        assert _split_fuse_expected(k, is_cat.any())


def _split_fuse_expected(k: int, any_cat: bool) -> bool:
    """Document the POST-CLOSURE fallback matrix in executable form: with
    the gate on, categorical + sharded builds fuse too (only uplift falls
    back structurally — and tallies tree_fused_fallbacks_total)."""
    with _env(H2O3_TPU_SPLIT_FUSE="1"):
        active = st._split_fuse_active(
            (2, 5) if any_cat else (), st._split_shard_on()
        )
    return active


def _free_compile_state():
    """Drop in-memory compiled executables after a compile-heavy test.

    These ISSUE-15 suites add ~50 whole-tree-sized programs (mono/cat
    sweeps across three sub-meshes, the autotuner's candidate grid) to a
    tier-1 process that already holds several hundred; past that point
    this jaxlib's CPU backend can segfault inside XLA codegen on the NEXT
    large compile (reproduced at test_fused_via_dense/f64_accuracy —
    fresh-process compiles of the identical HLO are fine). Freeing the
    one-shot executables keeps the long-lived process at its pre-ISSUE-15
    footprint; later tests re-read the persistent compile cache instead
    of recompiling, so the wall cost is small."""
    jax.clear_caches()


@pytest.mark.parametrize("k", [1, 2, 8])
def test_fused_mono_tie_break(k):
    """ISSUE-15 closure (a): monotone builds run the fused Pallas lane —
    the constraint mask lives in the kernel grid step and the bound state
    rides the fused level carry. Adversarial exact-tie data (constant
    target, duplicated columns): decisions must be bit-equal to the
    SPLIT_FUSE=0 path (the legacy per-level mono loop) on every mesh."""
    with _use_mesh(k):
        n_pad = pm.pad_to_shards(960)
        bins, t = _tie_data(n_pad, C=13, n_bins=16)
        mono = np.zeros(13, np.int32)
        mono[[0, 4, 9]] = 1
        mono[[2, 7]] = -1
        t1, p1, v1 = _build_one(bins, t, split_fuse="1", monotone=mono)
        t0, p0, v0 = _build_one(bins, t, split_fuse="0", monotone=mono)
        _assert_trees_bit_equal(t1, t0, f"fused-mono-ties/{k}dev")
        assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)
    _free_compile_state()


@pytest.mark.parametrize("k", [1, 2, 8])
def test_fused_mono_constrained_signal(k):
    """Monotone fused lane on a frame with a REAL signal that violates the
    constraint on some columns: the fused build must both match the
    unfused mono path bit-for-bit (integer-exact sums) and actually
    enforce the constraint (leaf means along a +1 column never decrease
    with the bin, checked through predictions on a 1-column sweep)."""
    with _use_mesh(k):
        n_pad = pm.pad_to_shards(960)
        rng = np.random.default_rng(31)
        bins = rng.integers(1, 16, (n_pad, 6)).astype(np.uint8)
        # target ANTI-monotone in column 0 — the +1 constraint must refuse
        # those splits (or clamp their children)
        t = (16.0 - bins[:, 0].astype(np.float32)
             + rng.integers(-2, 3, n_pad).astype(np.float32))
        mono = np.zeros(6, np.int32)
        mono[0] = 1
        t1, p1, v1 = _build_one(bins, t, split_fuse="1", monotone=mono,
                                max_depth=4)
        t0, p0, v0 = _build_one(bins, t, split_fuse="0", monotone=mono,
                                max_depth=4)
        _assert_trees_bit_equal(t1, t0, f"fused-mono-signal/{k}dev")
        assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)
        # enforcement probe: per-row prediction as a function of col-0's
        # bin must be non-decreasing when every other column is constant
        probe = np.zeros((16, 6), np.uint8)
        probe[:, :] = 8
        probe[:, 0] = np.arange(16)
        tr = t1
        nid = jnp.zeros(16, jnp.int32)
        pp = jnp.zeros(16, jnp.float32)
        _, pp = tr.replay(jnp.asarray(probe), nid, pp)
        pp = np.asarray(pp)[1:]  # bin 0 is the NA slot — direction-free
        assert (np.diff(pp) >= -1e-6).all(), pp
    _free_compile_state()


@pytest.mark.parametrize("k", [2, 8])
def test_fused_cat_sharded_tie_break(k):
    """ISSUE-15 closure (a): categorical frames on SHARDED meshes run the
    fused lane (block-local mean-sort gather). Adversarial ties: duplicated
    categorical columns spanning column blocks plus duplicated numeric
    columns — winner merge must still be lowest-global-index, bit-equal to
    the unfused dense sharded scan."""
    with _use_mesh(k):
        n_pad = pm.pad_to_shards(960)
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
        t1, p1, v1 = _build_one(bins, t, split_fuse="1", is_cat=is_cat,
                                max_depth=4)
        t0, p0, v0 = _build_one(bins, t, split_fuse="0", is_cat=is_cat,
                                max_depth=4)
        _assert_trees_bit_equal(t1, t0, f"fused-cat-sharded-ties/{k}dev")
        assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)
        # a categorical split must actually win somewhere, and among the
        # duplicated cat columns only the LOWEST index may appear
        host = t0.to_host()
        used_cat_cols = set()
        for lv, m in zip(host.levels, t0.real_level_masks()):
            sel = ~np.asarray(lv.leaf_now) & m & np.asarray(lv.is_cat)
            used_cat_cols |= set(np.asarray(lv.split_col)[sel].tolist())
        assert used_cat_cols and used_cat_cols <= {1}, used_cat_cols
    _free_compile_state()


def test_streamed_mono_matches_resident():
    """Satellite: the streamed-GBM gate accepts monotone builds — the
    bound state is per-node, so it rides the host level loop across row
    blocks. Split decisions must equal the resident mono build's
    level-for-level (same integer-tie regime as the oocore pins), preds
    within the block-summation envelope."""
    import pandas as pd

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.tree import GBM

    rng = np.random.default_rng(41)
    n = 4096
    df = pd.DataFrame({
        "a": rng.integers(0, 50, n).astype(np.float64),
        "b": rng.normal(size=n),
        "c": rng.normal(size=n),
    })
    df["y"] = (df["a"] * 0.1 + 0.5 * df["b"]
               + 0.1 * rng.normal(size=n)).astype(np.float64)
    kw = dict(ntrees=4, max_depth=3, seed=7,
              monotone_constraints={"a": 1})

    def run(window):
        env = {"H2O3_TPU_HBM_WINDOW_BYTES": window} if window else {}
        with _env(**env):
            fr = Frame.from_pandas(df)
            m = GBM(**kw).train(y="y", training_frame=fr)
            pr = m.predict(fr)
            return m, pr.vec(pr.names[-1]).to_numpy()

    m_res, p_res = run(None)
    # ~8 blocks through a 1/8th window
    bytes_per_row = 3 + 28
    m_str, p_str = run(str(n * bytes_per_row // 8))
    np.testing.assert_allclose(p_str, p_res, rtol=1e-5, atol=1e-5)
    for g_res, g_str in zip(m_res.output["trees"], m_str.output["trees"]):
        for lv_r, lv_s in zip(g_res[0].to_host().levels,
                              g_str[0].to_host().levels):
            np.testing.assert_array_equal(lv_r.split_col, lv_s.split_col)
            np.testing.assert_array_equal(lv_r.split_bin, lv_s.split_bin)
    _free_compile_state()


def test_tile_autotuner_sweeps_once_per_bucket(tmp_path, monkeypatch):
    """H2O3_TPU_PALLAS_TILES=auto (ISSUE 15 / ROADMAP 4b): the first
    resolve of a shape bucket runs ONE micro-sweep, a same-bucket resolve
    adds zero (counter-pinned), the winner persists to the compile-cache
    dir (a fresh in-process cache reads it back sweep-free), and explicit
    'ROW,COL,NODE' values bypass the tuner unchanged. The grid shrinks to
    two candidates here — the test pins the CACHING contract, not sweep
    quality, and the full grid's 12 interpret-mode compiles would bloat
    the tier-1 process (see _free_compile_state)."""
    from h2o3_tpu.ops import hist_pallas as hp
    from h2o3_tpu.utils import metrics as mx

    monkeypatch.setattr(
        hp, "_sweep_grid", lambda c, n: [(256, 4, 32), (512, 8, 64)])
    with _env(H2O3_TPU_PALLAS_TILES="auto",
              JAX_COMPILATION_CACHE_DIR=str(tmp_path)):
        s0 = mx.counter_value("pallas_tile_sweeps_total")
        tiles = hp.tiles_for(12, 64, 32, 3)
        assert mx.counter_value("pallas_tile_sweeps_total") == s0 + 1
        assert len(tiles) == 3 and all(v > 0 for v in tiles)
        # same bucket (cols round to 16, nodes/bins to pow2): zero sweeps
        assert hp.tiles_for(10, 50, 30, 3) == tiles
        assert mx.counter_value("pallas_tile_sweeps_total") == s0 + 1
        # cold in-process cache, warm persistent store: still zero sweeps
        hp._TUNED_TILES.clear()
        assert hp.tiles_for(12, 64, 32, 3) == tiles
        assert mx.counter_value("pallas_tile_sweeps_total") == s0 + 1
    with _env(H2O3_TPU_PALLAS_TILES="256,4,32"):
        assert hp.tiles_for(12, 64, 32, 3) == (256, 4, 32)
        assert mx.counter_value("pallas_tile_sweeps_total") == s0 + 1
    _free_compile_state()


def test_fused_fallback_counter_uplift():
    """tree_fused_fallbacks_total{reason=uplift}: the one structural hole
    left in the tree matrix tallies when the fuse gate is on; the closed
    mono/cat_sharded cases must NOT tally."""
    from h2o3_tpu.utils import metrics as mx

    with _env(H2O3_TPU_SPLIT_FUSE="1"):
        u0 = mx.counter_value("tree_fused_fallbacks_total", reason="uplift")
        m0 = mx.counter_value("tree_fused_fallbacks_total", reason="mono")
        c0 = mx.counter_value("tree_fused_fallbacks_total",
                              reason="cat_sharded")
        assert st._split_fuse_active((), st._split_shard_on(), uplift=True) \
            is False
        assert mx.counter_value(
            "tree_fused_fallbacks_total", reason="uplift") == u0 + 1
        # the closed cases fuse — and tally nothing
        assert st._split_fuse_active((2, 5), True) is True
        assert mx.counter_value(
            "tree_fused_fallbacks_total", reason="mono") == m0
        assert mx.counter_value(
            "tree_fused_fallbacks_total", reason="cat_sharded") == c0


def test_fused_via_dense_impls_parity():
    """H2O3_TPU_HIST=scatter + FUSE=1: the blocked layout is produced by
    re-blocking the scatter histogram (the CPU correctness lane) — the
    split kernel must still match the dense scan bit-for-bit."""
    n_pad = pm.pad_to_shards(700)
    rng = np.random.default_rng(17)
    bins = rng.integers(0, 16, (n_pad, 9)).astype(np.uint8)
    t = rng.integers(-2, 3, n_pad).astype(np.float32)
    t1, p1, v1 = _build_one(bins, t, split_fuse="1", hist="scatter")
    t0, p0, v0 = _build_one(bins, t, split_fuse="0", hist="scatter")
    _assert_trees_bit_equal(t1, t0, "fused-via-scatter")
    assert _bits(p1) == _bits(p0) and _bits(v1) == _bits(v0)


def test_fused_f64_accuracy_bound():
    """Carried over from test_hist_pallas: the fused pipeline built on the
    Pallas histogram kernel must track a float64 scatter+scan reference —
    the winner's child stats within the kernel's 5e-5 relative envelope,
    and the winning gain within 5e-4 of the f64 gain evaluated at the SAME
    candidate (gains subtract nearly-equal numbers, so their envelope is
    looser than the stats')."""
    from h2o3_tpu.ops.hist_pallas import hist_pallas_local, plan_layout
    from h2o3_tpu.ops.split_pallas import fused_split_scan

    rng = np.random.default_rng(9)
    n, c, N, B = 4096, 6, 16, 64
    bins = rng.integers(1, B, size=(n, c)).astype(np.uint8)
    bins[rng.random((n, c)) < 0.1] = 0  # NA bin occupied
    nid = rng.integers(0, N, size=n).astype(np.int32)
    w = rng.random(n).astype(np.float32)
    t = rng.normal(size=n).astype(np.float32)
    stats = np.stack([w, w * t, w], axis=1).astype(np.float32)

    lay = plan_layout(c, N, B, 3)
    blk = hist_pallas_local(
        jnp.asarray(bins), jnp.asarray(nid), jnp.asarray(stats), N, B,
        interpret=True, blocked=True,
    )
    sp = fused_split_scan(
        blk, lay, jnp.zeros(c, bool), jnp.ones((N, c), jnp.float32),
        10.0, 0.0, (), interpret=True,
    )

    # f64 reference: exact scatter histogram + exact prefix scan
    ref = np.zeros((N, c, B, 3), np.float64)
    st64 = stats.astype(np.float64)
    for col in range(c):
        np.add.at(ref[:, col], (nid, bins[:, col]), st64)
    na = ref[:, :, 0, :]
    data = ref[:, :, 1:, :]
    cum = np.cumsum(data, axis=2)
    left = cum[:, :, :-1, :]
    right = cum[:, :, -1:, :] - left
    tot = ref.sum(axis=2)[:, 0, :]

    def fit(s):
        w_ = s[..., 0]
        return -np.where(w_ > 0, s[..., 1] ** 2 / np.maximum(w_, 1e-300), 0.0)

    col_i = np.asarray(sp["col"])
    t_i = np.asarray(sp["split_bin"]) - 1
    nal = np.asarray(sp["na_left"])
    nodes = np.arange(N)
    L64 = left[nodes, col_i, t_i] + np.where(
        nal[:, None], na[nodes, col_i], 0.0
    )
    R64 = right[nodes, col_i, t_i] + np.where(
        ~nal[:, None], na[nodes, col_i], 0.0
    )
    for got, want in ((np.asarray(sp["Lst"]), L64), (np.asarray(sp["Rst"]), R64)):
        err = np.abs(got - want) / np.maximum(np.abs(want), 1.0)
        assert err.max() < 5e-5, f"child stats rel err {err.max():.2e}"
    g64 = (
        fit(tot)[nodes]
        - fit(left[nodes, col_i, t_i] + np.where(nal[:, None], na[nodes, col_i], 0))
        - fit(right[nodes, col_i, t_i] + np.where(~nal[:, None], na[nodes, col_i], 0))
    )
    gerr = np.abs(np.asarray(sp["gain"]) - g64) / np.maximum(np.abs(g64), 1.0)
    assert gerr.max() < 5e-4, f"gain rel err vs f64 {gerr.max():.2e}"


def test_hist_hbm_counter_measures_the_claim():
    """tree_hist_hbm_bytes_total{path}: the fused pipeline's modeled
    hist+split HBM traffic must undercut the unfused Pallas pipeline's
    ≥2× at the same shape (it drops both unscramble passes), and each mode
    must tally under its own path label."""
    from h2o3_tpu.utils import metrics as mx

    with _use_mesh(8):
        n_pad = pm.pad_to_shards(700)
        rng = np.random.default_rng(19)
        bins = rng.integers(0, 32, (n_pad, 28)).astype(np.uint8)
        t = rng.integers(-3, 4, n_pad).astype(np.float32)

        def bytes_for(fuse, path):
            before = mx.counter_value("tree_hist_hbm_bytes_total", path=path)
            _build_one(bins, t, split_fuse=fuse, n_bins=32, seed=23)
            return mx.counter_value(
                "tree_hist_hbm_bytes_total", path=path) - before

        fused_b = bytes_for("1", "fused")
        unfused_b = bytes_for("0", "pallas_unfused")
        assert fused_b > 0 and unfused_b > 0
        assert unfused_b >= 2 * fused_b, (unfused_b, fused_b)


def test_fused_hist_reduce_bytes_shrink_with_sharding():
    """Under fuse the hist_reduce collective still reduce-scatters: the
    8-device sharded tally must undercut the fused replicated one ≥2×."""
    from h2o3_tpu.utils import metrics as mx

    with _use_mesh(8):
        n_pad = pm.pad_to_shards(700)
        rng = np.random.default_rng(29)
        bins = rng.integers(0, 32, (n_pad, 28)).astype(np.uint8)
        t = rng.integers(-3, 4, n_pad).astype(np.float32)

        def bytes_for(shard):
            before = mx.counter_value(
                "tree_collective_bytes_total", phase="hist_reduce")
            _build_one(bins, t, split_fuse="1", n_bins=32, seed=31,
                       env={"H2O3_TPU_SPLIT_SHARD": shard})
            return mx.counter_value(
                "tree_collective_bytes_total", phase="hist_reduce") - before

        sharded = bytes_for("1")
        replicated = bytes_for("0")
        assert sharded > 0 and replicated >= 2 * sharded, (replicated, sharded)


def test_pallas_tiles_knob():
    """H2O3_TPU_PALLAS_TILES reshapes the kernel grid (the sweep hook) and
    the result still matches the default-tile kernel within the bf16
    envelope; a malformed spec fails loudly."""
    from h2o3_tpu.ops import hist_pallas as hp

    rng = np.random.default_rng(21)
    n, c, N, B = 1000, 11, 8, 17
    bins = jnp.asarray(rng.integers(0, B, (n, c)).astype(np.uint8))
    nid = jnp.asarray(rng.integers(0, N, n).astype(np.int32))
    stats = jnp.asarray(
        np.stack([np.ones(n), rng.normal(size=n), np.ones(n)], 1)
        .astype(np.float32))

    base = hp.hist_pallas_local(
        bins, nid, stats, N, B, interpret=True, tiles=hp._tiles())
    with _env(H2O3_TPU_PALLAS_TILES="256,4,32"):
        tiles = hp._tiles()
        assert tiles == (256, 4, 32)
        lay = hp.plan_layout(c, N, B, 3, tiles=tiles)
        assert lay.ct == 4 and lay.nt == 8  # nt clamps to n_nodes
        swept = hp.hist_pallas_local(
            bins, nid, stats, N, B, interpret=True, tiles=tiles)
    np.testing.assert_allclose(
        np.asarray(swept), np.asarray(base), rtol=1e-4, atol=1e-3)
    with _env(H2O3_TPU_PALLAS_TILES="16,0"):
        with pytest.raises(ValueError):
            hp._tiles()


def test_fused_scanned_chunk_close():
    """build_trees_scanned (the bench/GBM hot path) under fuse: multi-tree
    residuals are no longer integer-exact, so the pin is a tight allclose
    on predictions plus identical level-0 split decisions."""
    with _use_mesh(8):
        n = pm.pad_to_shards(2000)
        rng = np.random.default_rng(23)
        bins = pm.shard_rows(jnp.asarray(
            rng.integers(0, 32, (n, 12)).astype(np.uint8)))
        y = pm.shard_rows(jnp.asarray(rng.normal(size=n).astype(np.float32)))
        w = pm.shard_rows(jnp.ones(n, jnp.float32))

        def grad_fn(F, y_, w_):
            return y_ - F, jnp.ones_like(F)

        def run(fuse):
            with _env(H2O3_TPU_SPLIT_FUSE=fuse, H2O3_TPU_HIST="pallas"):
                preds = pm.shard_rows(jnp.zeros(n, jnp.float32))
                F, vi, stacked = st.build_trees_scanned(
                    bins, w, y, preds, jnp.zeros(12, jnp.float32),
                    jax.random.PRNGKey(7), 3, grad_fn=grad_fn,
                    grad_key=("fuse-ab", fuse), sample_rate=1.0, n_bins=32,
                    is_cat_cols=np.zeros(12, bool), max_depth=4,
                    min_rows=10.0, min_split_improvement=1e-5,
                    learn_rates=np.full(3, 0.3, np.float32),
                    max_abs_leaf=float("inf"), col_sample_rate=1.0,
                    col_sample_rate_per_tree=1.0,
                )
                trees = st.trees_from_stacked(stacked, 3)
                return np.asarray(F), trees

        f1, trees1 = run("1")
        f0, trees0 = run("0")
        np.testing.assert_allclose(f1, f0, rtol=1e-5, atol=1e-6)
        for a, b in zip(trees1, trees0):
            np.testing.assert_array_equal(
                a.levels[0].split_col, b.levels[0].split_col)
            np.testing.assert_array_equal(
                a.levels[0].split_bin, b.levels[0].split_bin)
