"""Regression net for the Pallas TPU histogram kernel (ops/hist_pallas.py) —
the gpu_hist-successor the project is named for. Runs the kernel in the
Pallas interpreter (CPU CI) against the exact scatter reference over an
adversarial shape grid: tile boundaries, NA bin occupancy, categorical
codes, ragged row counts, retired rows, and the 2-term bf16 split's
accuracy bound."""

import contextlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

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


@pytest.mark.parametrize("c,n_nodes,n_bins,ns", [
    (28, 1, 255, 3), (32, 64, 256, 3), (32, 2048, 256, 3), (5, 80, 17, 4)])
def test_plan_layout_is_the_kernels_geometry(c, n_nodes, n_bins, ns):
    """``plan_layout`` is what ``hist_pallas_local`` tiles by and what the
    modelled-bytes tally (``path=pallas_unfused``) sizes the kernel's padded
    output from: whole tiles cover the problem, the lane dimension is a
    multiple of 128, and ``nbytes`` is the float32 size of that output."""
    from h2o3_tpu.ops import hist_pallas as hp

    lay = hp.plan_layout(c, n_nodes, n_bins, ns)
    assert lay.nt == min(hp.NODE_TILE, n_nodes) and lay.ct == min(hp.COL_TILE, c)
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
