"""Regression net for the Pallas TPU histogram kernel (ops/hist_pallas.py) —
the gpu_hist-successor the project is named for. Runs the kernel in the
Pallas interpreter (CPU CI) against the exact scatter reference over an
adversarial shape grid: tile boundaries, NA bin occupancy, categorical
codes, ragged row counts, retired rows, and the 2-term bf16 split's
accuracy bound."""

import contextlib
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.ops import hist_pallas as hp
from h2o3_tpu.ops.hist_pallas import NODE_TILE, ROW_TILE, hist_pallas_local
from h2o3_tpu.ops.histogram import _hist_scatter_local


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


def _free_compile_state():
    """Drop in-memory compiled executables after a compile-heavy test: past
    several hundred whole-tree-sized programs in one tier-1 process this
    jaxlib's CPU backend can segfault inside XLA codegen on the NEXT large
    compile (fresh-process compiles of the identical HLO are fine)."""
    jax.clear_caches()


def _make_case(n, c, n_nodes, n_bins, seed, na_frac=0.1, retired_frac=0.1,
               zero_w_frac=0.1):
    rng = np.random.default_rng(seed)
    bins = rng.integers(1, n_bins, size=(n, c)).astype(np.uint8)
    bins[rng.random((n, c)) < na_frac] = 0  # NA bin 0 occupied
    nid = rng.integers(0, n_nodes, size=n).astype(np.int32)
    nid[rng.random(n) < retired_frac] = -1  # retired rows
    w = rng.random(n).astype(np.float32)
    w[rng.random(n) < zero_w_frac] = 0.0  # sampled-out rows
    t = rng.normal(size=n).astype(np.float32)
    wy = w * t
    wh = w * rng.random(n).astype(np.float32)
    # production GBM shape: 3 stat lanes (w, wy, wh); the kernel is
    # S-generic and the uplift case below covers S=4
    stats = np.stack([w, wy, wh], axis=1)
    # retired rows must arrive pre-masked (histogram_in_jit's contract)
    stats[nid < 0] = 0.0
    return (jnp.asarray(bins), jnp.asarray(nid), jnp.asarray(stats))


CASES = [
    # (n_rows, n_cols, n_nodes, n_bins) — each probes a distinct boundary
    pytest.param(1000, 4, 8, 256, id="rows-not-row-tile-multiple"),
    pytest.param(ROW_TILE, 3, 1, 256, id="single-node-exact-tile"),
    pytest.param(700, 5, NODE_TILE + 16, 256, id="nodes-over-node-tile"),
    pytest.param(1300, 11, 8, 64, id="cols-over-col-tile-small-bins"),
    pytest.param(257, 2, 4, 17, id="odd-bins-lane-padding"),
]


@pytest.mark.parametrize("n,c,n_nodes,n_bins", CASES)
def test_pallas_matches_scatter(n, c, n_nodes, n_bins):
    args = _make_case(n, c, n_nodes, n_bins, seed=n + c)
    got = hist_pallas_local(*args, n_nodes, n_bins, interpret=True)
    ref = jax.jit(
        _hist_scatter_local, static_argnums=(3, 4)
    )(*args, n_nodes, n_bins)
    assert got.shape == (c, n_nodes * n_bins, 3)
    # bf16 2-term split: ~16 mantissa bits on the stats operand; the
    # contraction then accumulates in f32. Bound the relative error by the
    # per-(node,col) mass actually present (measured ~1.5e-5; single-pass
    # bf16 — the regression this guards — is ~2e-3).
    scale = np.maximum(np.abs(np.asarray(ref)), 1.0)
    err = np.abs(np.asarray(got) - np.asarray(ref)) / scale
    assert err.max() < 5e-5, f"max rel err {err.max():.2e}"


def _reference_row_tiled(bins, nid, stats, n_nodes, n_bins):
    """What the kernel computes, in plain ``jax.numpy``: for every ROW_TILE
    rows the stat-scaled node one-hot, split into two bf16 terms, two
    float32 dots against the bin one-hot of every column, the two results
    added, and the row tiles added in order."""
    n, c = bins.shape
    ns = stats.shape[1]
    hist = jnp.zeros((n_nodes * ns, c * n_bins), jnp.float32)
    for lo in range(0, n, ROW_TILE):
        b, i, s = (x[lo:lo + ROW_TILE] for x in (bins, nid, stats))
        node_1h = (i[:, None] == jnp.arange(n_nodes)[None, :]).astype(jnp.float32)
        a = (node_1h[:, :, None] * s[:, None, :]).reshape(len(i), n_nodes * ns)
        a_hi = a.astype(jnp.bfloat16).astype(jnp.float32)
        e = (b.astype(jnp.int32)[:, :, None] == jnp.arange(n_bins)[None, None, :])
        e = e.astype(jnp.float32).reshape(len(i), c * n_bins)
        a_lo = (a - a_hi).astype(jnp.bfloat16).astype(jnp.float32)
        hist = hist + (jnp.dot(a_hi.T, e, precision="highest")
                       + jnp.dot(a_lo.T, e, precision="highest"))
    h = hist.reshape(n_nodes, ns, c, n_bins)
    return jnp.transpose(h, (2, 0, 3, 1)).reshape(c, n_nodes * n_bins, ns)


def _pr30_kernel(bins_ref, nid_ref, stats_ref, out_ref, *, nt, ct, bpad, ns):
    """The kernel's grid step as it was until ISSUE 31, kept here as the
    bit-for-bit reference: both operands lane-tiled with ``jnp.tile``, rows
    on the sublanes, the indicator contracted twice."""
    from jax.experimental import pallas as pl

    i_nt, i_r = pl.program_id(0), pl.program_id(2)
    r = bins_ref.shape[1]
    node_j = i_nt * nt + jax.lax.broadcasted_iota(jnp.int32, (r, nt * ns), 1) // ns
    a = (nid_ref[:] == node_j).astype(jnp.float32) * jnp.tile(stats_ref[:], (1, nt))
    colrep = jnp.tile(bins_ref[0].astype(jnp.int32), (1, bpad))
    bin_j = jax.lax.broadcasted_iota(jnp.int32, (r, ct * bpad), 1) // ct
    e = (colrep == bin_j).astype(jnp.bfloat16)
    a_hi = a.astype(jnp.bfloat16)
    a_lo = (a - a_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    dims = (((0,), (0,)), ((), ()))
    contrib = jax.lax.dot_general(
        a_hi, e, dims, preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(a_lo, e, dims, preferred_element_type=jnp.float32)

    @pl.when(i_r == 0)
    def _():
        out_ref[...] = contrib

    @pl.when(i_r > 0)
    def _():
        out_ref[...] = out_ref[...] + contrib


def _pr30_hist(bins_u8, nid, stats, n_nodes, n_bins):
    """``hist_pallas_local`` around :func:`_pr30_kernel` (interpreter): its
    (npad, 1) / (npad, S) / (n_ct, npad, CT) operands and its unscramble."""
    import functools

    from jax.experimental import pallas as pl

    from h2o3_tpu.ops.hist_pallas import plan_layout

    n, c = bins_u8.shape
    ns = stats.shape[1]
    lay = plan_layout(c, n_nodes, n_bins, ns)
    nt, ct, bpad, n_nt, n_ct = lay.nt, lay.ct, lay.bpad, lay.n_nt, lay.n_ct
    n_r = -(-n // ROW_TILE)
    npad, cpad = n_r * ROW_TILE, n_ct * ct
    bins_u8 = jnp.pad(bins_u8, ((0, npad - n), (0, cpad - c)))
    nid = jnp.pad(nid, (0, npad - n), constant_values=-1)
    stats = jnp.pad(stats, ((0, npad - n), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_pr30_kernel, nt=nt, ct=ct, bpad=bpad, ns=ns),
        grid=(n_nt, n_ct, n_r),
        in_specs=[pl.BlockSpec((1, ROW_TILE, ct), lambda a, b, r: (b, r, 0)),
                  pl.BlockSpec((ROW_TILE, 1), lambda a, b, r: (r, 0)),
                  pl.BlockSpec((ROW_TILE, ns), lambda a, b, r: (r, 0))],
        out_specs=pl.BlockSpec((nt * ns, ct * bpad), lambda a, b, r: (a, b)),
        out_shape=jax.ShapeDtypeStruct((n_nt * nt * ns, cpad * bpad), jnp.float32),
        interpret=True,
    )(jnp.transpose(bins_u8.reshape(npad, n_ct, ct), (1, 0, 2)),
      nid.reshape(npad, 1), stats)
    h = jnp.transpose(out.reshape(n_nt * nt, ns, n_ct, bpad, ct), (2, 4, 0, 3, 1))
    h = h.reshape(cpad, n_nt * nt, bpad, ns)[:c, :n_nodes, :n_bins, :]
    return h.reshape(c, n_nodes * n_bins, ns)


@pytest.mark.parametrize("ns", [3, 4])
@pytest.mark.parametrize("c,n_bins", [(28, 256), (5, 20)])
@pytest.mark.parametrize("n_nodes", [1, 16, 64, 130])
def test_pallas_moved_lanes_and_nothing_else(n_nodes, c, n_bins, ns):
    """ISSUE 31 rebuilt the grid step (rows on the lanes, one trip of the
    indicator through the MXU): over the frontier widths of the cells (1,
    16), a full node tile (64) and more than two (130), S = 3 and 4, the
    cells' 28 x 256 and a shape the lane rule pads (5 x 20), with a row
    count that is no multiple of the row tile and retired rows, every cell
    of the histogram is the old step's float32 — a cell's sum is over the
    same rows whatever lane it lives in. Bit for bit in all but a handful
    of cells: the step now contracts ``(2·S·NT, R) x (Bpad, R)`` where it
    contracted ``(R, S·NT) x (R, CT·Bpad)`` twice, and the CPU's own dot (as
    the chip's compiler, PERF.md §6) orders the partial sums of a 512-row
    contraction by the operands' shapes, so a cell in ten thousand rounds
    the other way. The plain ``jax.numpy`` reference says what the sum is."""
    n = 2 * ROW_TILE + 77
    rng = np.random.default_rng(n_nodes + c + ns)
    bins = rng.integers(0, n_bins, (n, c)).astype(np.uint8)
    nid = rng.integers(0, n_nodes, n).astype(np.int32)
    nid[rng.random(n) < 0.1] = -1
    stats = rng.normal(size=(n, ns)).astype(np.float32)
    stats[nid < 0] = 0.0
    args = (jnp.asarray(bins), jnp.asarray(nid), jnp.asarray(stats))
    got = np.asarray(hist_pallas_local(*args, n_nodes, n_bins, interpret=True))
    assert got.shape == (c, n_nodes * n_bins, ns)
    old = np.asarray(_pr30_hist(*args, n_nodes, n_bins))
    np.testing.assert_allclose(got, old, rtol=1e-6, atol=1e-6)
    assert (got.view(np.int32) != old.view(np.int32)).mean() < 1e-3
    want = np.asarray(_reference_row_tiled(*args, n_nodes, n_bins))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)
    _free_compile_state()


def test_pallas_f64_accuracy_bound():
    """The kernel's result tracks a float64 scatter reference to ≤5e-5 rel
    (measured ~1.5e-5) — the accuracy envelope of the 2-term bf16 MXU
    split."""
    args = _make_case(4096, 6, 32, 256, seed=9)
    got = np.asarray(hist_pallas_local(*args, 32, 256, interpret=True))
    bins, nid, stats = (np.asarray(a) for a in args)
    ref = np.zeros((6, 32 * 256, 3), np.float64)
    stats = stats.astype(np.float64)
    active = nid >= 0
    for col in range(6):
        idx = nid[active] * 256 + bins[active, col]
        np.add.at(ref[col], idx, stats[active])
    scale = np.maximum(np.abs(ref), 1.0)
    err = np.abs(got - ref) / scale
    assert err.max() < 5e-5, f"max rel err vs f64 {err.max():.2e}"


def test_pallas_retired_rows_contribute_nothing():
    args = list(_make_case(800, 3, 4, 64, seed=3, retired_frac=0.0))
    # retire every row -> histogram must be exactly zero
    args[1] = jnp.full(800, -1, jnp.int32)
    got = hist_pallas_local(*args, 4, 64, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), 0.0)


def test_pallas_zero_stat_rows_contribute_nothing():
    """Sampled-out rows keep a valid nid but carry all-zero stats (the
    builder zeroes w/wy/wy²/wh); their cells must match a reference built
    with those rows removed entirely."""
    args = list(
        _make_case(800, 3, 4, 64, seed=3, retired_frac=0.0, zero_w_frac=0.0)
    )
    mask = np.zeros(800, bool)
    mask[::5] = True
    stats = np.asarray(args[2]).copy()
    stats[mask] = 0.0
    args[2] = jnp.asarray(stats)
    got = hist_pallas_local(*args, 4, 64, interpret=True)
    kept = [jnp.asarray(np.asarray(a)[~mask]) for a in args]
    ref = jax.jit(_hist_scatter_local, static_argnums=(3, 4))(*kept, 4, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-3)


def test_pallas_categorical_codes_roundtrip():
    """Categorical bins are plain codes 1..K; every (node, code) cell mass
    must land exactly where the scatter reference puts it."""
    rng = np.random.default_rng(4)
    n, k = 1536, 7  # 7 levels -> bins 1..7
    bins = rng.integers(1, k + 1, size=(n, 1)).astype(np.uint8)
    nid = rng.integers(0, 3, size=n).astype(np.int32)
    w = np.ones(n, np.float32)
    z = np.zeros(n, np.float32)
    # S=4 here on purpose: the kernel is stat-lane-generic (uplift runs 4)
    args = (jnp.asarray(bins), jnp.asarray(nid),
            jnp.asarray(np.stack([w, w, z, w], axis=1)))
    got = hist_pallas_local(*args, 3, k + 1, interpret=True)
    ref = jax.jit(_hist_scatter_local, static_argnums=(3, 4))(*args, 3, k + 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-3)
    # every row accounted for: total w mass equals n
    assert abs(float(np.asarray(got)[0, :, 0].sum()) - n) < 1e-3


def test_scatter_chunked_matches_unchunked(monkeypatch):
    """The lax.scan row-chunked scatter (memory bound for big shards) must
    agree with the single-chunk path it replaces. Chunk forced tiny so the
    test exercises padding + multi-chunk accumulation."""
    from h2o3_tpu.ops import histogram as H

    rng = np.random.default_rng(3)
    n, c, n_nodes, n_bins = 1000, 5, 8, 16
    bins = jnp.asarray(rng.integers(0, n_bins, (n, c)).astype(np.uint8))
    nid = jnp.asarray(rng.integers(-1, n_nodes, n).astype(np.int32))
    w = np.asarray(rng.random(n).astype(np.float32))
    wy = np.asarray(rng.normal(size=n).astype(np.float32))
    stats = np.stack([w, wy, w], axis=1)
    stats[np.asarray(nid) < 0] = 0.0  # pre-masked, per the local-impl contract
    stats = jnp.asarray(stats)
    ref = H._hist_scatter_local(bins, nid, stats, n_nodes, n_bins)
    monkeypatch.setattr(H, "_SCATTER_ROW_CHUNK", 96)  # 1000 -> 11 chunks + pad
    out = H._hist_scatter_local(bins, nid, stats, n_nodes, n_bins)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)


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


def test_pallas_tiles_knob():
    """H2O3_TPU_PALLAS_TILES reshapes the kernel grid (the sweep hook) and
    the result still matches the default-tile kernel within the bf16
    envelope; a malformed spec fails loudly. The triple's column tile is the
    one a step takes where all the columns' output block would not fit."""
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
        assert lay.ct == c and lay.nt == 8  # nt clamps to n_nodes; 70 KB: wide
        big = hp.plan_layout(64, 2048, 256, 3, tiles=tiles)
        assert (big.ct, big.nt, big.n_ct, big.n_nt) == (4, 32, 16, 64)
        swept = hp.hist_pallas_local(
            bins, nid, stats, N, B, interpret=True, tiles=tiles)
    np.testing.assert_allclose(
        np.asarray(swept), np.asarray(base), rtol=1e-4, atol=1e-3)
    with _env(H2O3_TPU_PALLAS_TILES="16,0"):
        with pytest.raises(ValueError):
            hp._tiles()


@pytest.mark.parametrize("c,n_nodes,n_bins,ns,wide", [
    (28, 1, 255, 3, True), (28, 32, 256, 3, True), (28, 64, 256, 3, False),
    (32, 64, 256, 3, False), (32, 2048, 256, 3, False), (7, 8, 64, 3, True),
    (5, 80, 17, 4, True), (300, 4, 256, 3, False)])
def test_plan_layout_is_the_kernels_geometry(c, n_nodes, n_bins, ns, wide):
    """``plan_layout`` is what ``hist_pallas_local`` tiles by and what the
    modelled-bytes tally (``path=pallas_unfused``) sizes the kernel's padded
    output from: whole tiles cover the problem, the lane dimension is a
    multiple of 128, and ``nbytes`` is the float32 size of that output. A
    step takes all the columns (no column padded: 28 stay 28) while its
    output block is under ``WIDE_BLOCK_BYTES`` — the cells' frontiers up to
    32 nodes — and ``COL_TILE`` of them from a full node tile on; 7 columns
    of 64 bins are a shape the lane rule pads (7·64 is no multiple of 128:
    128 bins a column)."""
    from h2o3_tpu.ops import hist_pallas as hp

    lay = hp.plan_layout(c, n_nodes, n_bins, ns)
    assert lay.nt == min(hp.NODE_TILE, n_nodes)
    assert lay.ct == (c if wide else min(hp.COL_TILE, c))
    if not wide:  # all the columns' block at their own bin padding: too big
        assert 4 * lay.nt * ns * c * (-(-n_bins // 16) * 16) > hp.WIDE_BLOCK_BYTES
    else:
        assert lay.nbytes // lay.n_nt <= hp.WIDE_BLOCK_BYTES and lay.n_ct == 1
    assert (lay.ct * lay.bpad) % 128 == 0 and n_bins <= lay.bpad < n_bins + 128
    assert (lay.n_nt - 1) * lay.nt < n_nodes <= lay.n_nt * lay.nt
    assert (lay.n_ct - 1) * lay.ct < c <= lay.n_ct * lay.ct
    rows, lanes = lay.n_nt * lay.nt * ns, lay.n_ct * lay.ct * lay.bpad
    assert lay.nbytes == 4 * rows * lanes
    out = jax.eval_shape(
        lambda b, n, s: hp.hist_pallas_local(b, n, s, n_nodes, n_bins,
                                             interpret=True),
        jax.ShapeDtypeStruct((64, c), jnp.uint8),
        jax.ShapeDtypeStruct((64,), jnp.int32),
        jax.ShapeDtypeStruct((64, ns), jnp.float32))
    assert out.shape == (c, n_nodes * n_bins, ns)  # what the unscramble leaves


def _hbm_paths() -> dict:
    """Every ``tree_hist_hbm_bytes_total`` sample, by its ``path`` label."""
    from h2o3_tpu.utils import metrics as mx

    fam = mx.REGISTRY._families["tree_hist_hbm_bytes_total"]
    return {lab["path"]: float(v) for lab, v in fam.samples() if "path" in lab}


@pytest.mark.parametrize("hist,path", [("", "dense"), ("pallas", "pallas_unfused")])
def test_hist_hbm_counter_paths_of_a_default_build(hist, path):
    """The modelled-bytes tally of a default GBM build writes one histogram
    path — ``dense`` (the CPU's scatter) or ``pallas_unfused`` (the chip's
    kernel, here in the interpreter) — and ``rebin`` for the binning pass;
    a second build on the same frame re-reads the cached codes and adds no
    ``rebin`` bytes. ``tree_rebin_bytes_per_call`` reads ``path=rebin``."""
    import pandas as pd

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.tree import GBM

    rng = np.random.default_rng(12)
    n = 1024
    df = pd.DataFrame(rng.normal(size=(n, 4)), columns=list("abcd"))
    df["y"] = df["a"] - df["b"] + 0.1 * rng.normal(size=n)
    fr = Frame.from_pandas(df)

    def build():
        before = _hbm_paths()
        GBM(ntrees=2, max_depth=3, seed=3).train(y="y", training_frame=fr)
        return {k: v - before.get(k, 0.0) for k, v in _hbm_paths().items()
                if v != before.get(k, 0.0)}

    with _env(H2O3_TPU_HIST=hist):
        first, second = build(), build()
    assert set(first) == {path, "rebin"}, first
    assert first["rebin"] == 5.0 * fr.npad * 4  # one f32 read + one u8 write
    assert set(second) == {path} and second[path] == first[path], second
    _free_compile_state()


def test_kernel_key_follows_tiles_and_hist_override():
    """``_kernel_key()`` is what keeps a cached tree program from serving
    another kernel configuration: the tile triple, the raw tile spec
    ('auto' resolves to the built-in triple, so only the raw spec tells it
    from ''), ``H2O3_TPU_HIST`` and ``HIST_I16`` — and nothing else."""
    from h2o3_tpu.models.tree import shared_tree as st
    from h2o3_tpu.ops import hist_pallas as hp

    with _env(H2O3_TPU_PALLAS_TILES="", H2O3_TPU_HIST="",
              H2O3_TPU_HIST_I16="0"):
        base = st._kernel_key()
        assert base == ((hp.ROW_TILE, hp.COL_TILE, hp.NODE_TILE), "", "", False)
        keys = {base}
        for knob, value in (("H2O3_TPU_PALLAS_TILES", "256,4,32"),
                            ("H2O3_TPU_PALLAS_TILES", "auto"),
                            ("H2O3_TPU_HIST", "matmul"),
                            ("H2O3_TPU_HIST", "pallas"),
                            ("H2O3_TPU_HIST_I16", "1")):
            with _env(**{knob: value}):
                keys.add(st._kernel_key())
        assert len(keys) == 6, keys
        with _env(H2O3_TPU_PALLAS_TILES="auto"):
            assert st._kernel_key()[0] == base[0]  # same triple, other key
        # a level program is cached under it: a flip compiles a new one
        st._level_step(1, 2, 16, False)
        n0 = len(st._STEP_CACHE)
        st._level_step(1, 2, 16, False)
        assert len(st._STEP_CACHE) == n0
        with _env(H2O3_TPU_HIST="matmul"):
            st._level_step(1, 2, 16, False)
        assert len(st._STEP_CACHE) == n0 + 1


# ---------------------------------------------------------------------------
# The grouped pass (ISSUE 33): rows read in node order, a row chunk contracted
# only against the node tiles whose rows it holds — against the dense call.

SMALL = (128, 8, 8)  # row, column, node tile: many tiles at test sizes
REAL = (hp.ROW_TILE, hp.COL_TILE, hp.NODE_TILE)
GROUPED_SHAPES = [
    # (n_rows, n_cols, n_nodes, n_bins, stat lanes, tiles)
    pytest.param(1000, 28, 16, 256, 3, SMALL, id="2-tiles-28-cols"),
    pytest.param(1000, 13, 40, 64, 4, SMALL, id="5-tiles-13-cols-4-lanes"),
    pytest.param(2000, 28, 128, 32, 3, SMALL, id="16-tiles"),
    pytest.param(1300, 28, 128, 256, 3, REAL, id="2-tiles-of-64-4-col-tiles"),
    pytest.param(1300, 11, 130, 256, 4, REAL, id="ragged-3rd-tile-2-col-tiles"),
]


def _grouped_case(n, c, n_nodes, n_bins, ns, seed, integer):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, n_bins, size=(n, c)).astype(np.uint8)
    nid = rng.integers(0, n_nodes, size=n).astype(np.int32)
    nid[rng.random(n) < 0.2] = -1  # retired, or the sibling that is not built
    stats = (rng.integers(0, 3, (n, ns)) if integer
             else rng.normal(size=(n, ns))).astype(np.float32)
    return jnp.asarray(bins), jnp.asarray(nid), jnp.asarray(stats)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "tiles"))
def _grouped(bins, sort_key, nid, stats, n_nodes, n_bins, tiles):
    """The histogram of ``nid`` over rows sorted by ``sort_key``."""
    order, (nid_s,) = hp.sort_rows(
        bins, sort_key, stats, n_nodes, n_bins, tiles=tiles, carry=(nid,))
    return hp.hist_pallas_grouped(
        order, nid_s, n_nodes, n_bins, bins.shape[1], interpret=True,
        tiles=tiles)


def _grid(n, c, n_nodes, n_bins, ns, tiles):
    lay = hp.plan_layout(c, n_nodes, n_bins, ns, tiles=tiles)
    return lay, -(-n // tiles[0])


@pytest.mark.parametrize("integer", [True, False], ids=["0-1-2-sums", "real"])
@pytest.mark.parametrize("rows", ["node-order", "random-order"])
@pytest.mark.parametrize("n,c,n_nodes,n_bins,ns,tiles", GROUPED_SHAPES)
def test_grouped_matches_dense(n, c, n_nodes, n_bins, ns, tiles, rows, integer):
    """Right whatever the order: with the rows sorted by node, and with the
    rows sorted by a key that has nothing to do with the nodes, the grouped
    pass returns the dense call's cells — bit for bit where the statistics
    are small integers (a forest's 0/1 sums), to 1e-6 of the largest cell
    where they are real (float32 sums in another order)."""
    bins, nid, stats = _grouped_case(n, c, n_nodes, n_bins, ns, n + c, integer)
    key = nid if rows == "node-order" else jnp.asarray(
        np.random.default_rng(1).integers(0, 1 << 20, n).astype(np.int32))
    got, steps = _grouped(bins, key, nid, stats, n_nodes, n_bins, tiles)
    want = hist_pallas_local(
        bins, nid, stats, n_nodes, n_bins, interpret=True, tiles=tiles)
    assert got.shape == want.shape == (c, n_nodes * n_bins, ns)
    if integer:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=0, atol=1e-6 * scale)
    lay, n_r = _grid(n, c, n_nodes, n_bins, ns, tiles)
    if rows == "node-order":
        assert int(steps) <= (n_r + lay.n_nt - 1) * lay.n_ct
    else:
        assert int(steps) <= lay.n_nt * n_r * lay.n_ct


def test_grouped_tile_without_rows_is_zero():
    """A node tile that owns no row still gets one visit, which zeroes its
    block: it comes back all zero, not as whatever VMEM held — a middle tile,
    the last tile, and every tile at once."""
    n, c, n_nodes, n_bins = 1500, 5, 40, 16
    bins, nid, stats = _grouped_case(n, c, n_nodes, n_bins, 3, 9, True)
    for empty in ([2], [4], [0, 1, 2, 3, 4]):
        nid_e = jnp.where(jnp.isin(nid // SMALL[2], jnp.asarray(empty)), -1, nid)
        got, _ = _grouped(bins, nid_e, nid_e, stats, n_nodes, n_bins, SMALL)
        want = hist_pallas_local(
            bins, nid_e, stats, n_nodes, n_bins, interpret=True, tiles=SMALL)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        cells = np.asarray(got).reshape(c, n_nodes, n_bins, 3)
        for t in empty:
            assert not cells[:, t * SMALL[2]:(t + 1) * SMALL[2]].any()


@pytest.mark.parametrize("dead", ["retired", "zero-statistics"])
@pytest.mark.parametrize("n", [1999, 2048])
def test_grouped_skips_chunks_of_rows_that_add_nothing(n, dead):
    """Rows no cell can see sort last — retired at the sort, or with every
    statistic zero (out of a forest's bag) — and the chunks that hold nothing
    else are never visited (``n`` that is no multiple of the row tile pads
    with such rows); the cells are the dense call's."""
    c, n_nodes, n_bins = 7, 32, 32
    bins, nid, stats = _grouped_case(n, c, n_nodes, n_bins, 3, n, True)
    keep = jnp.arange(n) % 4 == 0  # three in four add nothing
    if dead == "retired":
        nid = jnp.where(keep, nid, -1)
        stats = stats + 1.0  # no row without statistics
    else:
        stats = jnp.where(keep[:, None], stats + 1.0, 0.0)
    got, steps = _grouped(bins, nid, nid, stats, n_nodes, n_bins, SMALL)
    want = hist_pallas_local(
        bins, nid, stats, n_nodes, n_bins, interpret=True, tiles=SMALL)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    lay, n_r = _grid(n, c, n_nodes, n_bins, 3, SMALL)
    live_chunks = -(-int((keep & (nid >= 0)).sum()) // SMALL[0])
    assert live_chunks < n_r // 2
    assert int(steps) <= (live_chunks + lay.n_nt - 1) * lay.n_ct


def test_grouped_takes_the_dense_kernel_past_its_visit_list():
    """Rows in no order at all at 16 node tiles: every tile's run of chunks is
    the whole frame, 16 x n_r visits against a list of 2 n_r + 15 — the level
    takes the dense kernel over the same operands and says so in its steps."""
    n, c, n_nodes, n_bins = 2048, 5, 128, 16
    bins, nid, stats = _grouped_case(n, c, n_nodes, n_bins, 3, 3, True)
    key = jnp.asarray(np.random.default_rng(2).integers(0, 1 << 20, n), jnp.int32)
    got, steps = _grouped(bins, key, nid, stats, n_nodes, n_bins, SMALL)
    lay, n_r = _grid(n, c, n_nodes, n_bins, 3, SMALL)
    assert lay.n_nt * n_r > hp._visit_list_len(n_r, lay.n_nt)
    assert int(steps) == lay.n_nt * n_r * lay.n_ct
    want = hist_pallas_local(
        bins, nid, stats, n_nodes, n_bins, interpret=True, tiles=SMALL)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_grouped_fits_reads_the_visit_list_off_the_shard():
    """Whether a level can run grouped is a matter of shapes: the visit list
    (one int32 a visit, in SMEM) holds ``drf_higgs``'s 6,029,312 rows and
    the published 11M with room, and refuses a shard past 50M rows."""
    lay = hp.plan_layout(28, 1024, 256, 3, tiles=REAL)
    assert lay.n_nt == 16
    assert hp.grouped_fits(6_029_312, lay, hp.ROW_TILE)
    assert hp.grouped_fits(11_010_048, lay, hp.ROW_TILE)
    assert not hp.grouped_fits(60_000_000, lay, hp.ROW_TILE)


@pytest.mark.parametrize("n,c", [(1000, 28), (1024, 5)])
def test_rows_go_into_the_order_and_come_back(n, c):
    """What the tree program permutes once a tree, by sorting: the order is
    the stable sort by node with the rows that add nothing last; a float
    lane, an int lane and uint8 codes carried into it are the gathered rows
    (zeros in the places that pad a shard to whole row tiles), the order's
    own codes read back from the kernels' layout are the same, and lanes
    restored are the lanes they were."""
    bins, nid, stats = _grouped_case(n, c, 16, 256, 3, n, True)
    lane = jnp.arange(n, dtype=jnp.float32) * 0.5 - 3.0
    order, (lane_s, nid_s, bins_s) = hp.sort_rows(
        bins, nid, stats, 16, 256, tiles=SMALL, carry=(lane, nid, bins))
    perm = np.asarray(order.perm)
    npad = perm.shape[0]
    assert npad % SMALL[0] == 0 and sorted(perm) == list(range(npad))
    live = (np.asarray(nid) >= 0) & (np.asarray(stats) != 0).any(axis=1)
    assert int(order.n_live[0]) == live.sum()
    key = np.where(live, np.asarray(nid), 1 << 30)
    np.testing.assert_array_equal(perm[:n], np.argsort(key, kind="stable"))

    def padded(x):
        return np.concatenate(
            [np.asarray(x), np.zeros((npad - n,) + x.shape[1:], x.dtype)])

    for got, want in ((lane_s, lane), (nid_s, nid), (bins_s, bins),
                      (hp.order_codes(order, c), bins)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), padded(want)[perm])
    np.testing.assert_array_equal(
        np.asarray(order.stats_t), padded(stats)[perm].T)
    back = hp.restore_rows(order, (lane_s, nid_s), n)
    np.testing.assert_array_equal(np.asarray(back[0]), np.asarray(lane))
    np.testing.assert_array_equal(np.asarray(back[1]), np.asarray(nid))
