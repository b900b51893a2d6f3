"""Out-of-core data plane (ISSUE 11, frame/chunkstore.py): compressed
device frames + streaming block epochs for datasets past the HBM window.

The acceptance pins:
- a frame that FITS the window takes the resident path unchanged
  (``ChunkStore.plan`` returns None → bit-parity by construction, asserted
  byte-equal), and ``H2O3_TPU_FRAME_COMPRESS=0`` restores the resident
  behavior bit-for-bit even with a window configured;
- a frame FORCED through a multi-block window trains GBM with the SAME
  split decisions as the resident build (gains differ only by f32
  block-summation order) and 1e-6-level predictions, GLM to matching
  coefficients, DL to a working model — across 1/2/8-device meshes;
- an oversized frame (tiny forced window) trains correctly through >= 4
  eviction cycles with the peak device residency bounded by the window;
- kill-and-resume (PR-10 / PR-2 recovery) survives mid-stream at 1e-6.
"""

import contextlib
import os

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from h2o3_tpu.frame import chunkstore as cs
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.parallel import mesh as pm
from h2o3_tpu.utils import metrics as mx


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


def _frame(n=4000, c=8, seed=0, regression=False):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, c)).astype(np.float32)
    eta = X[:, 0] - 0.5 * X[:, 1] + 0.25 * X[:, 2]
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(c)])
    if regression:
        df["label"] = (eta + 0.3 * rng.normal(size=n)).astype(np.float32)
    else:
        y = rng.random(n) < 1.0 / (1.0 + np.exp(-eta))
        df["label"] = np.where(y, "s", "b")
    return Frame.from_pandas(df)


def _p1(model, fr):
    pf = model.predict(fr)
    return pf.vec(pf.names[-1]).to_numpy()


def _tree_decisions(model):
    out = []
    for group in model.output["trees"]:
        for t in group:
            h = t.to_host()
            out.append([(np.asarray(lv.split_col), np.asarray(lv.split_bin),
                         np.asarray(lv.leaf_now)) for lv in h.levels])
    return out


# ---------------------------------------------------------------------------
# ChunkStore unit behavior


def test_plan_gates():
    # no window -> resident
    with _env(H2O3_TPU_HBM_WINDOW_BYTES="0"):
        assert cs.ChunkStore.plan(10_000, 32) is None
    # fits the window -> resident
    with _env(H2O3_TPU_HBM_WINDOW_BYTES=str(10_000 * 32 + 1)):
        assert cs.ChunkStore.plan(10_000, 32) is None
    # compress off -> resident even with a window
    with _env(H2O3_TPU_HBM_WINDOW_BYTES="4096", H2O3_TPU_FRAME_COMPRESS="0"):
        assert cs.ChunkStore.plan(10_000, 32) is None
    # past the window -> streams with >1 block
    with _env(H2O3_TPU_HBM_WINDOW_BYTES="65536"):
        st = cs.ChunkStore.plan(100_000, 32)
        assert st is not None and st.n_blocks > 1
        q = pm.block_quantum()
        assert st.block_rows % q == 0


def test_plan_overwindow_single_block_still_streams():
    # Boundary pin (ISSUE 19): block geometry is quantized to the shard
    # multiple, so a one-quantum frame can never split into two blocks — a
    # window smaller than its footprint used to silently fall back to the
    # unbounded resident path. It must stream through the store's
    # accounted window instead, as a single quantum-floor block.
    q = pm.block_quantum()
    bpr = 32
    need = q * bpr
    with _env(H2O3_TPU_HBM_WINDOW_BYTES=str(need // 4)):
        st = cs.ChunkStore.plan(q, bpr)
        assert st is not None
        assert st.n_blocks == 1 and st.block_rows == q
        assert st.window == need // 4  # the accounted LRU budget, not need
    # the same geometry WITH room for the whole frame stays resident
    with _env(H2O3_TPU_HBM_WINDOW_BYTES=str(need * 4)):
        assert cs.ChunkStore.plan(q, bpr) is None


def test_store_lru_eviction_updates_and_gauges():
    h0 = mx.counter_value("frame_bytes_resident", tier="host")
    d0 = mx.counter_value("frame_bytes_resident", tier="hbm")
    e0 = mx.counter_value("frame_chunk_evictions_total")
    st = cs.ChunkStore(1024, 16, window=4096, prefetch=1)
    st.add_empty("x", (1024, 4), np.float32)
    st.lane("x")[:] = np.arange(1024 * 4, dtype=np.float32).reshape(1024, 4)
    assert mx.counter_value("frame_bytes_resident", tier="host") - h0 == \
        st.lane("x").nbytes
    for bi, blk in st.stream(("x",)):
        lo, hi = st.span(bi)
        assert np.array_equal(np.asarray(blk["x"]), st.lane("x")[lo:hi])
    assert st.evictions > 0
    assert mx.counter_value("frame_chunk_evictions_total") > e0
    # peak bounded by the window (pre-upload eviction)
    assert st.peak_hbm <= st.window
    # update writes through to the host tier and the window copy
    st.update(0, x=jnp.zeros((st.rows(0), 4), jnp.float32))
    assert (st.lane("x")[: st.rows(0)] == 0).all()
    got = st.fetch(0, ("x",))["x"]
    assert (np.asarray(got) == 0).all()
    st.close()
    assert mx.counter_value("frame_bytes_resident", tier="host") == \
        pytest.approx(h0)
    assert mx.counter_value("frame_bytes_resident", tier="hbm") == \
        pytest.approx(d0)
    assert cs.LAST_STORE_STATS["peak_hbm"] <= cs.LAST_STORE_STATS["window"]


def test_vec_release_rebuild_bit_equal():
    fr = _frame(500, 4, seed=3)
    v = fr.vec("x1")
    before = np.asarray(v.data)
    hbm0 = mx.counter_value("frame_bytes_resident", tier="hbm")
    freed = v.release_device()
    assert freed > 0
    assert mx.counter_value("frame_bytes_resident", tier="hbm") == \
        pytest.approx(hbm0 - freed)
    assert v._data is None and v.npad == len(before)
    after = np.asarray(v.data)  # lazy rebuild
    assert before.tobytes() == after.tobytes()
    # frame-level spill is a no-op under COMPRESS=0
    with _env(H2O3_TPU_FRAME_COMPRESS="0"):
        assert fr.spill_to_host() == 0


# ---------------------------------------------------------------------------
# GBM streaming parity


@pytest.mark.parametrize("n_dev", [1, 2, 8])
def test_gbm_streaming_matches_resident(n_dev):
    with _use_mesh(n_dev):
        fr = _frame(3000, 6, seed=7)
        kw = dict(ntrees=4, max_depth=4, seed=11, score_tree_interval=2)
        from h2o3_tpu.models.tree import GBM

        m_res = GBM(**kw).train(y="label", training_frame=fr)
        with _env(H2O3_TPU_HBM_WINDOW_BYTES=str(48 * 1024)):
            fr2 = _frame(3000, 6, seed=7)
            m_str = GBM(**kw).train(y="label", training_frame=fr2)
        assert cs.LAST_STORE_STATS["n_blocks"] > 1  # really streamed
        dres, dstr = _tree_decisions(m_res), _tree_decisions(m_str)
        assert len(dres) == len(dstr)
        for tr, ts in zip(dres, dstr):
            assert len(tr) == len(ts)
            for (c1, b1, l1), (c2, b2, l2) in zip(tr, ts):
                # identical split decisions: the streamed histogram differs
                # from the resident one only by f32 block-summation order
                assert np.array_equal(l1, l2)
                live = ~l1
                assert np.array_equal(c1[live], c2[live])
                assert np.array_equal(b1[live], b2[live])
        np.testing.assert_allclose(_p1(m_res, fr), _p1(m_str, fr), atol=1e-6)
        np.testing.assert_allclose(
            m_res.training_metrics.logloss, m_str.training_metrics.logloss,
            atol=1e-6)


def test_gbm_small_frame_fits_window_stays_resident_byte_equal():
    fr = _frame(2000, 6, seed=5)
    from h2o3_tpu.models.tree import GBM

    kw = dict(ntrees=3, max_depth=3, seed=2)
    m0 = GBM(**kw).train(y="label", training_frame=fr)
    # a window the frame fits: plan() declines, the resident programs run
    with _env(H2O3_TPU_HBM_WINDOW_BYTES=str(1 << 30)):
        from h2o3_tpu.frame import chunkstore as _cs

        assert _cs.ChunkStore.plan(fr.npad, 6 + 28) is None
        m1 = GBM(**kw).train(y="label", training_frame=fr)
    assert _p1(m0, fr).tobytes() == _p1(m1, fr).tobytes()


def test_compress_off_restores_resident_bit_for_bit():
    fr = _frame(2500, 6, seed=9)
    from h2o3_tpu.models.tree import GBM

    kw = dict(ntrees=3, max_depth=3, seed=4)
    m0 = GBM(**kw).train(y="label", training_frame=fr)
    e0 = mx.counter_value("frame_chunk_evictions_total")
    with _env(H2O3_TPU_HBM_WINDOW_BYTES="32768", H2O3_TPU_FRAME_COMPRESS="0"):
        m1 = GBM(**kw).train(y="label", training_frame=fr)
    assert mx.counter_value("frame_chunk_evictions_total") == e0
    assert _p1(m0, fr).tobytes() == _p1(m1, fr).tobytes()


# ---------------------------------------------------------------------------
# GLM / DL streaming parity


@pytest.mark.parametrize("n_dev", [1, 8])
def test_glm_streaming_coef_parity(n_dev):
    with _use_mesh(n_dev):
        fr = _frame(4000, 8, seed=13)
        from h2o3_tpu.models.glm import GLM

        kw = dict(family="binomial", lambda_=1e-4, max_iterations=15, seed=1)
        m_res = GLM(**kw).train(y="label", training_frame=fr)
        with _env(H2O3_TPU_HBM_WINDOW_BYTES=str(96 * 1024)):
            fr2 = _frame(4000, 8, seed=13)
            m_str = GLM(**kw).train(y="label", training_frame=fr2)
        assert cs.LAST_STORE_STATS["n_blocks"] > 1
        delta = max(abs(m_res.coef[k] - m_str.coef[k]) for k in m_res.coef)
        assert delta < 2e-5
        np.testing.assert_allclose(
            m_res.training_metrics.logloss, m_str.training_metrics.logloss,
            atol=1e-6)


def test_glm_streaming_gaussian_and_elastic_net():
    fr = _frame(4000, 8, seed=17, regression=True)
    from h2o3_tpu.models.glm import GLM

    kw = dict(family="gaussian", alpha=0.5, lambda_=1e-3, max_iterations=12,
              seed=1)
    m_res = GLM(**kw).train(y="label", training_frame=fr)
    with _env(H2O3_TPU_HBM_WINDOW_BYTES=str(96 * 1024)):
        fr2 = _frame(4000, 8, seed=17, regression=True)
        m_str = GLM(**kw).train(y="label", training_frame=fr2)
    delta = max(abs(m_res.coef[k] - m_str.coef[k]) for k in m_res.coef)
    assert delta < 2e-5


def test_dl_streaming_trains():
    from h2o3_tpu.models.deeplearning import DeepLearning

    with _env(H2O3_TPU_HBM_WINDOW_BYTES=str(96 * 1024)):
        fr = _frame(4000, 8, seed=21)
        m = DeepLearning(hidden=[16, 16], epochs=2, mini_batch_size=64,
                         seed=3).train(y="label", training_frame=fr)
    assert cs.LAST_STORE_STATS["n_blocks"] > 1
    assert m.output["epochs_trained"] == 2
    assert all(np.isfinite(e["loss"]) for e in m.scoring_history)
    assert float(m.training_metrics.auc) > 0.6


# ---------------------------------------------------------------------------
# oversized-frame smoke + chaos


def test_oversized_frame_trains_through_eviction_cycles():
    """Tiny forced window: rows x lanes >> window, >= 4 eviction cycles,
    peak device residency bounded by the window, model still correct."""
    from h2o3_tpu.models.tree import GBM

    e0 = mx.counter_value("frame_chunk_evictions_total")
    with _env(H2O3_TPU_HBM_WINDOW_BYTES=str(24 * 1024)):
        fr = _frame(6000, 6, seed=23)
        # frame lanes ~ 6000 * 34 B ~ 200 KiB >> 24 KiB window
        m = GBM(ntrees=4, max_depth=4, seed=5).train(
            y="label", training_frame=fr)
    stats = cs.LAST_STORE_STATS
    assert stats["n_blocks"] >= 4
    assert stats["evictions"] >= 4
    assert mx.counter_value("frame_chunk_evictions_total") - e0 >= 4
    assert stats["peak_hbm"] <= stats["window"]
    assert float(m.training_metrics.auc) > 0.7
    assert mx.counter_value("frame_prefetch_overlap_seconds") > 0


def test_gbm_streaming_kill_and_resume_matches_uninterrupted(tmp_path):
    """PR-10/PR-2 recovery mid-stream: abort at an interval boundary,
    resume from the interval snapshot, land within 1e-6 of the
    uninterrupted streamed run."""
    from h2o3_tpu.models.tree import GBM
    from h2o3_tpu.utils import faults

    ckdir = str(tmp_path)
    kw = dict(max_depth=3, seed=6, score_tree_interval=2)
    with _env(H2O3_TPU_HBM_WINDOW_BYTES=str(48 * 1024)):
        fr = _frame(3000, 6, seed=29)
        full = GBM(ntrees=6, **kw).train(y="label", training_frame=fr)
        assert cs.LAST_STORE_STATS["n_blocks"] > 1
        with faults.inject(abort={"gbm": 4}):
            with pytest.raises(faults.TrainAbort):
                GBM(ntrees=6, export_checkpoints_dir=ckdir, **kw).train(
                    y="label", training_frame=fr)
        snaps = [f for f in os.listdir(ckdir) if f.startswith("gbm_ckpt")]
        assert snaps, "no interval snapshot was exported mid-stream"
        from h2o3_tpu import persist

        prior = persist.load_model(os.path.join(ckdir, snaps[0]))
        assert prior.output["ntrees_actual"] == 4
        resumed = GBM(ntrees=6, checkpoint=prior.key, **kw).train(
            y="label", training_frame=fr)
    assert resumed.output["ntrees_actual"] == 6
    np.testing.assert_allclose(
        resumed.training_metrics.logloss, full.training_metrics.logloss,
        atol=1e-6)
    np.testing.assert_allclose(_p1(resumed, fr), _p1(full, fr), atol=1e-6)


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
    # drop the one-shot executables (see test_hist_pallas._free_compile_state)
    jax.clear_caches()
