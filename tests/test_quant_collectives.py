"""Quantized collective lane + hierarchical reduction placement (ISSUE 9,
``ops/collectives.py``): the block-quantized reduces must (a) be bit-for-bit
inert when off, (b) keep the PR-5 adversarial tie suites bit-exact when on
(power-of-two scales make integer payloads lossless), (c) keep model quality
inside the pinned envelopes (GBM AUC, GLM coefficients), and (d) report the
wire-compression claim through the new ``{lane}`` counter dimension. Also
pins the satellite fix: saturated-region byte tallies now scale by the
EXECUTED while_loop iterations, not the trace-time n_sat upper bound.
"""

import os

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from h2o3_tpu.ops import collectives as cl
from h2o3_tpu.parallel import mesh as pm
from tests.test_split_shard import (
    _assert_trees_bit_equal,
    _bits,
    _build_one,
    _env,
    _pad_rows,
    _tie_data,
    _use_mesh,
)

QUANT1 = {"H2O3_TPU_COLLECTIVE_QUANT": "1"}
QUANT0 = {"H2O3_TPU_COLLECTIVE_QUANT": "0"}


def _sharded(fn, out_spec):
    mesh = pm.get_mesh()
    return jax.jit(pm.shard_map(
        fn, mesh=mesh, in_specs=(P(),), out_specs=out_spec, check_vma=False))


def _rs_exact(v):
    return jax.lax.psum_scatter(
        v, pm.ROWS_AXIS, scatter_dimension=0, tiled=True)


# ---------------------------------------------------------------------------
# quantizer + wrapper semantics


def test_block_quantizer_lossless_for_small_integers():
    """Power-of-two scales: any block of integer values with |x| <= 127
    round-trips bit-exactly — the adversarial tie suites' regime."""
    rng = np.random.default_rng(0)
    x = rng.integers(-127, 128, (4, 2, 64)).astype(np.float32)
    q, s = cl._encode8(jnp.asarray(x))
    back = np.asarray(cl._decode8(q, s))
    assert _bits(back) == _bits(x)
    # scales are exact powers of two (or the all-zero-block placeholder 1)
    sv = np.asarray(s).ravel()
    assert np.all(np.logical_or(sv == 1.0, np.log2(sv) == np.round(np.log2(sv))))
    # and a lossy block still lands within half a scale step
    big = rng.normal(size=(1, 2, 64)).astype(np.float32) * 1000
    q, s = cl._encode8(jnp.asarray(big))
    err = np.abs(np.asarray(cl._decode8(q, s)) - big)
    assert err.max() <= np.asarray(s).max() / 2 + 1e-3


def test_quant_reduce_scatter_bit_exact_on_integer_payloads():
    """The wrapped reduce-scatter under QUANT=1 equals the stock
    psum_scatter bit-for-bit when local contributions are small integers."""
    with _use_mesh(8), _env(**QUANT1):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.integers(-120, 121, (16, 33)).astype(np.float32))
        got = _sharded(
            lambda v: cl.psum_scatter(v, n_dev=8), P(pm.ROWS_AXIS))(x)
        want = _sharded(_rs_exact, P(pm.ROWS_AXIS))(x)
        assert _bits(got) == _bits(want)


def test_quant_float_error_bounded_and_residual_pass_tightens():
    """General float payloads: single-pass int8 error stays under the
    scale-step bound; the residual-correction pass (passes=2, the
    Gram/gradient lane) cuts it by ~two orders of magnitude."""
    with _use_mesh(8), _env(**QUANT1):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(16, 257)).astype(np.float32))
        want = np.asarray(_sharded(_rs_exact, P(pm.ROWS_AXIS))(x))
        got1 = np.asarray(_sharded(
            lambda v: cl.psum_scatter(v, n_dev=8), P(pm.ROWS_AXIS))(x))
        got2 = np.asarray(_sharded(
            lambda v: cl.psum_scatter(v, n_dev=8, passes=2),
            P(pm.ROWS_AXIS))(x))
        amax = float(np.abs(np.asarray(x)).max())
        err1 = np.abs(got1 - want).max()
        err2 = np.abs(got2 - want).max()
        # 8 senders x half a scale step each, scales <= 2*amax/127
        assert err1 <= 8 * amax / 127 + 1e-5
        assert err2 < err1 / 20


def test_quant_psum_chunks_match_scatter_blocks():
    """The consistency invariant behind the tie-suite parity: a wrapped
    replicated psum is the wrapped reduce-scatter + exact gather, so chunk
    d of the replicated result is BIT-identical to sharded device d's
    block — for arbitrary float data."""
    with _use_mesh(8), _env(**QUANT1):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(16, 19)).astype(np.float32))
        full = _sharded(lambda v: cl.psum(v, n_dev=8), P())(x)
        blocks = _sharded(
            lambda v: cl.psum_scatter(v, n_dev=8), P(pm.ROWS_AXIS))(x)
        assert _bits(full) == _bits(blocks)


def test_hierarchical_two_stage_bit_exact_on_integers():
    """H2O3_TPU_COLLECTIVE_HIER=2 on the 8-device proxy (4 fake-ICI pairs):
    stage-1 exact inner reduce + stage-2 quantized cross exchange must
    still deal device d global chunk d, bit-exactly for integer data."""
    with _use_mesh(8), _env(H2O3_TPU_COLLECTIVE_HIER="2", **QUANT1):
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.integers(-100, 101, (16, 21)).astype(np.float32))
        got = _sharded(
            lambda v: cl.psum_scatter(v, n_dev=8), P(pm.ROWS_AXIS))(x)
        gotf = _sharded(lambda v: cl.psum(v, n_dev=8), P())(x)
    with _use_mesh(8), _env(**QUANT0):
        want = _sharded(_rs_exact, P(pm.ROWS_AXIS))(x)
        wantf = _sharded(lambda v: jax.lax.psum(v, pm.ROWS_AXIS), P())(x)
    assert _bits(got) == _bits(want)
    assert _bits(gotf) == _bits(wantf)


# ---------------------------------------------------------------------------
# end-to-end: trees


def test_quant_off_is_bit_identical_to_unset():
    """H2O3_TPU_COLLECTIVE_QUANT=0 must be byte-for-byte today's path."""
    with _use_mesh(8):
        n_pad = _pad_rows(700)
        rng = np.random.default_rng(7)
        bins = rng.integers(0, 16, (n_pad, 7)).astype(np.uint8)
        t = rng.normal(size=n_pad).astype(np.float32)
        t0, p0, v0 = _build_one(bins, t, split_shard=1)
        tq, pq, vq = _build_one(bins, t, split_shard=1, env=QUANT0)
        _assert_trees_bit_equal(tq, t0, "QUANT=0 vs unset")
        assert _bits(pq) == _bits(p0) and _bits(vq) == _bits(v0)


@pytest.mark.parametrize("k", [2, 8])
def test_adversarial_tie_suites_bit_exact_under_quant(k):
    """The PR-5 adversarial tie suites under QUANT=1: unit weights +
    integer targets make every local payload an exact int8 block, so split
    decisions stay bit-identical to the exact lane — and the sharded and
    replicated pipelines stay bit-identical to each other."""
    with _use_mesh(k):
        n_pad = _pad_rows(960)
        bins, t = _tie_data(n_pad, C=13, n_bins=16, dup_all=True)
        tq1, pq1, vq1 = _build_one(bins, t, split_shard=1, env=QUANT1)
        tq0, pq0, vq0 = _build_one(bins, t, split_shard=0, env=QUANT1)
        te, pe, ve = _build_one(bins, t, split_shard=1, env=QUANT0)
        _assert_trees_bit_equal(tq1, tq0, f"quant ties shard-vs-repl/{k}dev")
        _assert_trees_bit_equal(tq1, te, f"quant-vs-exact ties/{k}dev")
        assert _bits(pq1) == _bits(pe) and _bits(vq1) == _bits(ve)
        # dup columns with real signal: identical best gains in every
        # block — the lowest-global-index tie-break must survive the lane
        rng = np.random.default_rng(3)
        bins2, _ = _tie_data(n_pad, C=16, n_bins=16, dup_all=True, seed=3)
        t2 = (rng.integers(0, 2, n_pad) * 2 - 1).astype(np.float32)
        tq, _, _ = _build_one(bins2, t2, split_shard=1, max_depth=4, env=QUANT1)
        te2, _, _ = _build_one(bins2, t2, split_shard=1, max_depth=4, env=QUANT0)
        _assert_trees_bit_equal(tq, te2, f"dup-cols quant-vs-exact/{k}dev")


def test_quant_counters_report_lane_and_2x_fewer_bytes():
    """The {lane} dimension on tree_collective_bytes_total: a QUANT=1 build
    tallies its hist_reduce volume on the quant lane at >=2x (3.94x
    modeled: int8 + one f32 scale per 256 block vs f32) fewer bytes than
    the exact control at the same shape."""
    from h2o3_tpu.utils import metrics as mx

    def deltas(env):
        keys = [dict(phase="hist_reduce"),
                dict(phase="hist_reduce", lane="quant"),
                dict(phase="hist_reduce", lane="exact")]
        before = [mx.counter_value("tree_collective_bytes_total", **k)
                  for k in keys]
        _build_one(bins, t, split_shard=1, n_bins=32, seed=23, env=env)
        return [mx.counter_value("tree_collective_bytes_total", **k) - b
                for k, b in zip(keys, before)]

    with _use_mesh(8):
        n_pad = _pad_rows(700)
        rng = np.random.default_rng(19)
        bins = rng.integers(0, 32, (n_pad, 28)).astype(np.uint8)
        t = rng.normal(size=n_pad).astype(np.float32)
        tot_q, lane_q, lane_e = deltas(QUANT1)
        tot_x, lane_qx, lane_ex = deltas(QUANT0)
    assert tot_q > 0 and lane_q == tot_q and lane_e == 0
    assert tot_x > 0 and lane_qx == 0 and lane_ex == tot_x
    assert tot_x >= 2 * tot_q, (tot_x, tot_q)


def test_hierarchical_lane_splits_counter_by_stage():
    """Under HIER the stage-1 (intra-group, exact) and stage-2 (cross-group,
    quantized) volumes land on their own lanes."""
    from h2o3_tpu.utils import metrics as mx

    with _use_mesh(8), _env(H2O3_TPU_COLLECTIVE_HIER="2"):
        n_pad = _pad_rows(700)
        rng = np.random.default_rng(5)
        bins = rng.integers(0, 16, (n_pad, 8)).astype(np.uint8)
        t = rng.normal(size=n_pad).astype(np.float32)
        q0 = mx.counter_value(
            "tree_collective_bytes_total", phase="hist_reduce", lane="quant")
        e0 = mx.counter_value(
            "tree_collective_bytes_total", phase="hist_reduce", lane="exact")
        _build_one(bins, t, split_shard=1, env=QUANT1)
        dq = mx.counter_value(
            "tree_collective_bytes_total", phase="hist_reduce",
            lane="quant") - q0
        de = mx.counter_value(
            "tree_collective_bytes_total", phase="hist_reduce",
            lane="exact") - e0
    assert dq > 0 and de > 0  # both stages accounted, on their own lanes
    assert de > dq  # stage-1 moves the full f32 volume, stage-2 the 1/P int8


# ---------------------------------------------------------------------------
# end-to-end: model quality envelopes


def _class_frame(n, c, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, c)).astype(np.float32)
    eta = X[:, 0] - 0.5 * X[:, 1] + 0.25 * X[:, 2] * X[:, 3]
    y = rng.random(n) < 1.0 / (1.0 + np.exp(-eta))
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(c)])
    df["label"] = np.where(y, "s", "b")
    return df


@pytest.mark.slow
def test_gbm_auc_delta_within_pin_under_quant():
    """8-device mesh, the A/B shape (16k rows): training-AUC delta between
    the quantized and exact lanes stays inside the acceptance pin 1e-3."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.tree import GBM

    df = _class_frame(16000, 12)

    def auc(env):
        with _env(**env):
            m = GBM(ntrees=10, max_depth=5, seed=7).train(
                y="label", training_frame=Frame.from_pandas(df))
            return float(m.training_metrics.auc)

    with _use_mesh(8):
        delta = abs(auc(QUANT1) - auc(QUANT0))
    assert delta <= 1e-3, delta


@pytest.mark.parametrize("k", [1, 2, 8])
def test_glm_coefficients_within_envelope_under_quant(k):
    """The Gram reduce rides the quant lane with the residual-correction
    pass: IRLS coefficients stay within the pinned parity envelope on
    1/2/8-device meshes (on 1 device the lane is inert — delta exactly 0)."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.glm import GLM

    df = _class_frame(2000, 8, seed=1)

    def coefs(env):
        with _env(**env):
            m = GLM(family="binomial", lambda_=1e-4, max_iterations=20,
                    seed=1).train(y="label", training_frame=Frame.from_pandas(df))
            return m.coef

    with _use_mesh(k):
        c1 = coefs(QUANT1)
        c0 = coefs(QUANT0)
    dmax = max(abs(c1[key] - c0[key]) for key in c0)
    if k == 1:
        assert dmax == 0.0
    else:
        assert dmax <= 2e-3, dmax


@pytest.mark.slow
def test_dl_sharded_grad_quant_parity():
    """DL's flat-gradient scatter under QUANT=1 (residual pass): final
    predictions stay close to the exact lane's — the per-step ~1e-5
    relative gradient error must not compound into divergence."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.deeplearning import DeepLearning

    df = _class_frame(4096, 8, seed=2)

    def preds(env):
        with _env(**env):
            fr = Frame.from_pandas(df)
            m = DeepLearning(hidden=[16, 16], epochs=3, mini_batch_size=256,
                             seed=3).train(y="label", training_frame=fr)
            return np.asarray(
                m.predict(fr).vec("s").to_numpy(), np.float64)

    with _use_mesh(8):
        p1 = preds(QUANT1)
        p0 = preds(QUANT0)
    assert np.max(np.abs(p1 - p0)) <= 0.05


# ---------------------------------------------------------------------------
# satellite: saturated-region tallies scale by EXECUTED iterations


def test_sat_region_tally_counts_executed_not_nsat():
    """Two same-shape deep builds (max_depth=8, node_cap=8 — a 5-level
    saturated while_loop region): one on data that stops splitting after
    depth 1 (2 distinct bin values), one on rich data that splits to the
    bottom. The old tally scaled both by n_sat; the fixed one reads the
    executed iteration count from the build stats, so the early-exit build
    must tally strictly less and the sat counter must match reality."""
    from h2o3_tpu.models.tree import shared_tree as st
    from h2o3_tpu.utils import metrics as mx

    def build(bins, t):
        h0 = mx.counter_value(
            "tree_collective_bytes_total", phase="hist_reduce")
        s0 = st.BUILD_STATS["sat_levels_executed"]
        _build_one(bins, t, split_shard=1, max_depth=8, node_cap=8,
                   env={"H2O3_TPU_SHAPE_BUCKETS": "0"})
        return (
            mx.counter_value(
                "tree_collective_bytes_total", phase="hist_reduce") - h0,
            st.BUILD_STATS["sat_levels_executed"] - s0,
        )

    with _use_mesh(8):
        n_pad = _pad_rows(600)
        rng = np.random.default_rng(11)
        assert st._sat_region(8, 8)[1] >= 2  # region must exist
        # early-exit data: one informative column with two values — after
        # the depth-0 split both children are single-bin pure nodes
        bins_small = rng.integers(1, 3, (n_pad, 3)).astype(np.uint8)
        bins_small[:, 1:] = bins_small[:, :1]  # duplicates, same 2 bins
        t_small = (bins_small[:, 0] == 1).astype(np.float32)
        bytes_small, sat_small = build(bins_small, t_small)
        # rich data: splits keep landing until depth exhausts
        bins_rich = rng.integers(0, 16, (n_pad, 3)).astype(np.uint8)
        t_rich = rng.normal(size=n_pad).astype(np.float32)
        bytes_rich, sat_rich = build(bins_rich, t_rich)
    assert sat_small < sat_rich, (sat_small, sat_rich)
    # identical shapes → identical per-level tally; only the executed sat
    # count differs, so the early-exit build must tally strictly less (the
    # old n_sat scaling made these equal)
    assert bytes_small < bytes_rich, (bytes_small, bytes_rich)
