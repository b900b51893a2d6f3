"""Whole-program GLM IRLS + DeepLearning epoch fusion (ISSUE 8): the fused
lanes (H2O3_TPU_GLM_FUSE, H2O3_TPU_DL_EPOCH_CHUNK, H2O3_TPU_DL_GRAD_SHARD)
must be coefficient-equivalent to the per-iteration/per-epoch paths —
bit-exact where the math is unchanged (DL epoch chunking, the sharded Gram
blocks vs the replicated einsum, shape-bucket padding), f32-envelope where
the solve moved on-device — while dropping host dispatches from
O(iterations|epochs) to O(.../K), reporting into the PR-5 collective
counters, and keeping PR-2 checkpoint kill-and-resume pinned.
"""

import contextlib
import os

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.deeplearning import DeepLearning
from h2o3_tpu.models.glm import GLM
from h2o3_tpu.parallel import mesh as pm
from h2o3_tpu.utils import faults
from h2o3_tpu.utils import metrics as mx


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
    for k, v in kv.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = str(v)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _df(n=1200, c=6, seed=0, classify=True):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, c)).astype(np.float32)
    eta = X[:, 0] - 0.5 * X[:, 1] + 0.25 * X[:, 2]
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(c)])
    if classify:
        y = rng.random(n) < 1.0 / (1.0 + np.exp(-eta))
        df["y"] = np.where(y, "a", "b")
    else:
        df["y"] = (eta + 0.3 * rng.normal(size=n)).astype(np.float32)
    return df


def _coefs(m):
    return np.array([m.coef[k] for k in sorted(m.coef)])


# ---------------------------------------------------------------------------
# sharded Gram blocks vs the replicated einsum (mesh sweep)


@pytest.mark.parametrize("k", [1, 2, 8])
def test_sharded_gram_matches_replicated_einsum(k):
    """psum_scatter'd contiguous G row blocks + one all_gather must equal
    the replicated-einsum Gram bit-for-bit on the same mesh (XLA:CPU sums
    per-device partials in the same order either way)."""
    from h2o3_tpu.ops.gram import weighted_gram, weighted_gram_sharded

    with _use_mesh(k):
        n = pm.pad_to_shards(2000)
        p = pm.pad_cols_to_shards(8)
        rng = np.random.default_rng(1)
        X = pm.shard_rows(jnp.asarray(rng.normal(size=(n, p)).astype(np.float32)))
        w = pm.shard_rows(jnp.asarray(
            np.abs(rng.normal(size=n)).astype(np.float32)))
        z = pm.shard_rows(jnp.asarray(rng.normal(size=n).astype(np.float32)))
        Gr, br, swr = jax.jit(weighted_gram)(X, w, z)
        Gs, bs, sws = jax.jit(
            lambda X, w, z: weighted_gram_sharded(X, w, z))(X, w, z)
        np.testing.assert_array_equal(np.asarray(Gr), np.asarray(Gs))
        np.testing.assert_array_equal(np.asarray(br), np.asarray(bs))
        np.testing.assert_allclose(
            float(swr), float(sws), rtol=1e-6)


def test_device_solvers_match_host():
    """The on-device jitter-ladder Cholesky and ADMM reproduce the host
    float64 solutions within the f32 envelope, including the unit pad
    diagonal keeping padded columns at exactly zero."""
    from h2o3_tpu.ops.gram import (
        admm_elastic_net, admm_elastic_net_device, cho_solve_jitter_device,
        solve_cholesky)

    rng = np.random.default_rng(2)
    p, pad = 10, 2
    A = rng.normal(size=(40, p))
    G = A.T @ A + 0.1 * np.eye(p)
    b = rng.normal(size=p)
    Gp = np.zeros((p + pad, p + pad))
    Gp[:p, :p] = G
    bp = np.concatenate([b, np.zeros(pad)])
    pad_diag = (np.arange(p + pad) >= p).astype(np.float32)

    xh = solve_cholesky(G, b)
    xd, ok = jax.jit(cho_solve_jitter_device)(
        jnp.asarray(Gp, jnp.float32), jnp.asarray(bp, jnp.float32),
        jnp.asarray(pad_diag))
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(xd)[p:], 0.0)
    np.testing.assert_allclose(np.asarray(xd)[:p], xh, rtol=2e-4, atol=2e-4)

    zh = admm_elastic_net(G, b, l1=0.8, l2=0.4, intercept_idx=p - 1)
    zd, ok = admm_elastic_net_device(
        jnp.asarray(Gp, jnp.float32), jnp.asarray(bp, jnp.float32),
        jnp.float32(0.8), jnp.float32(0.4), jnp.int32(p - 1),
        jnp.asarray(pad_diag), jnp.float32(p))
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(zd)[p:], 0.0)
    np.testing.assert_allclose(np.asarray(zd)[:p], zh, atol=5e-4)


# ---------------------------------------------------------------------------
# GLM fused lane


def test_glm_fused_matches_unfused_elastic_net():
    """Fused (on-device ADMM) vs unfused (host f64 ADMM) coefficient parity
    on the elastic-net lane, plus the dispatch contract: O(iters/K) fused
    vs O(iters) unfused."""
    fr = Frame.from_pandas(_df(seed=3))
    kw = dict(family="binomial", lambda_=1e-4, max_iterations=20, seed=1)
    d0 = mx.counter_value("glm_dispatches_total")
    i0 = mx.counter_value("glm_irls_iterations_total")
    m_f = GLM(**kw).train(y="y", training_frame=fr)
    d1 = mx.counter_value("glm_dispatches_total")
    i1 = mx.counter_value("glm_irls_iterations_total")
    with _env(H2O3_TPU_GLM_FUSE="0"):
        m_u = GLM(**kw).train(y="y", training_frame=fr)
    d2 = mx.counter_value("glm_dispatches_total")
    i2 = mx.counter_value("glm_irls_iterations_total")

    np.testing.assert_allclose(_coefs(m_f), _coefs(m_u), atol=1e-4)
    fused_disp, fused_iters = d1 - d0, i1 - i0
    unfused_disp, unfused_iters = d2 - d1, i2 - i1
    assert unfused_disp == unfused_iters  # one host dispatch per iteration
    assert fused_disp <= -(-fused_iters // 8) + 1  # chunks of K=8
    pf = m_f.predict(fr)
    pu = m_u.predict(fr)
    np.testing.assert_allclose(
        pf.vec(pf.names[-1]).to_numpy(), pu.vec(pu.names[-1]).to_numpy(),
        atol=1e-4)


def test_glm_fused_matches_unfused_cholesky_lane():
    """lambda=0 routes the solve through the device Cholesky jitter ladder
    (no ADMM); gaussian + binomial both stay in the f32 envelope."""
    for fam, classify in (("gaussian", False), ("binomial", True)):
        fr = Frame.from_pandas(_df(seed=4, classify=classify))
        kw = dict(family=fam, lambda_=0.0, alpha=0.0, max_iterations=15,
                  seed=1)
        m_f = GLM(**kw).train(y="y", training_frame=fr)
        with _env(H2O3_TPU_GLM_FUSE="0"):
            m_u = GLM(**kw).train(y="y", training_frame=fr)
        np.testing.assert_allclose(_coefs(m_f), _coefs(m_u), atol=2e-4)


@pytest.mark.parametrize("k", [2, 8])
def test_glm_fused_mesh_sweep_and_gram_counters(k):
    """The fused lane on 2- and 8-device sub-meshes: coefficients match the
    1-device fused run, and the gram_reduce/gram_gather collective phases
    tally (replication-volume model; a 1-device mesh moves nothing)."""
    df = _df(seed=5)
    kw = dict(family="binomial", lambda_=1e-4, max_iterations=10, seed=1)
    with _use_mesh(1):
        m1 = GLM(**kw).train(y="y", training_frame=Frame.from_pandas(df))
    with _use_mesh(k):
        g0 = mx.counter_value("tree_collective_bytes_total",
                              phase="gram_reduce")
        a0 = mx.counter_value("tree_collective_bytes_total",
                              phase="gram_gather")
        mk = GLM(**kw).train(y="y", training_frame=Frame.from_pandas(df))
        assert mx.counter_value(
            "tree_collective_bytes_total", phase="gram_reduce") > g0
        assert mx.counter_value(
            "tree_collective_bytes_total", phase="gram_gather") > a0
    np.testing.assert_allclose(_coefs(m1), _coefs(mk), atol=2e-4)


def test_glm_bucketed_padding_is_inert():
    """Shape-bucketed design columns (zero columns + unit solve diagonal)
    must not move the coefficients beyond XLA reduction-order rounding: the
    padded Gram's real block contracts the same products, but XLA may tile
    the einsum differently at the padded shape, so the pin is the f32
    reduction envelope, not bit-equality (the padded COEFFICIENTS
    themselves are exactly zero — asserted via the solver unit test)."""
    df = _df(seed=6)  # 6 features + intercept = 7 -> pads to 8
    kw = dict(family="binomial", lambda_=1e-4, max_iterations=10, seed=1)
    with _use_mesh(1):
        m_b = GLM(**kw).train(y="y", training_frame=Frame.from_pandas(df))
        with _env(H2O3_TPU_SHAPE_BUCKETS="0"):
            m_e = GLM(**kw).train(y="y", training_frame=Frame.from_pandas(df))
    np.testing.assert_allclose(_coefs(m_b), _coefs(m_e), atol=1e-5)


def test_glm_same_bucket_rebuild_zero_new_compiles():
    """The PR-1 ladder applied to GLM program keys: a rebuild on a frame
    whose design width lands in the SAME 4-column bucket (and same row
    bucket) must compile ZERO new fused chunk programs."""
    df_a = _df(seed=7, c=6)   # 6 + intercept = 7 -> bucket 8
    df_b = _df(seed=8, c=7)   # 7 + intercept = 8 -> bucket 8
    kw = dict(family="binomial", lambda_=1e-4, max_iterations=6, seed=1)
    GLM(**kw).train(y="y", training_frame=Frame.from_pandas(df_a))
    c0 = mx.counter_value("glm_programs_compiled_total")
    h0 = mx.counter_value("glm_program_cache_hits_total")
    GLM(**kw).train(y="y", training_frame=Frame.from_pandas(df_b))
    assert mx.counter_value("glm_programs_compiled_total") == c0
    assert mx.counter_value("glm_program_cache_hits_total") > h0


def test_glm_fused_checkpoint_kill_and_resume_bit_exact(tmp_path):
    """PR-2's exact-trajectory contract under the fused lane: with
    export_checkpoints_dir set the chunk clamps to K=1 (irls_state
    snapshots land at every iteration boundary), and a killed run resumed
    from the snapshot reproduces the uninterrupted FUSED trajectory
    bit-for-bit."""
    from h2o3_tpu.persist import load_model

    fr = Frame.from_pandas(_df(seed=9))
    kw = dict(family="binomial", max_iterations=25, seed=1)
    with _env(H2O3_TPU_GLM_FUSE="8"):
        full = GLM(**kw).train(y="y", training_frame=fr)
        ckdir = str(tmp_path / "glm_ck")
        with faults.inject(abort={"glm": 3}):
            with pytest.raises(faults.TrainAbort):
                GLM(export_checkpoints_dir=ckdir, **kw).train(
                    y="y", training_frame=fr)
        snaps = [f for f in os.listdir(ckdir) if "glm_ckpt" in f]
        assert snaps
        prior = load_model(os.path.join(ckdir, snaps[0]))
        # checkpoints-on clamps the chunk: the snapshot position is an
        # exact iteration boundary
        assert prior.output["irls_state"]["it"] <= 3
        resumed = GLM(checkpoint=prior.key, **kw).train(
            y="y", training_frame=fr)
    np.testing.assert_array_equal(
        np.asarray(resumed.output["beta_std"]),
        np.asarray(full.output["beta_std"]))


def _free_compile_state():
    """Drop in-memory compiled executables after a compile-heavy test —
    the ISSUE-15 suites add dozens of programs (fused multinomial on three
    sub-meshes, dropout lanes) to a long-lived tier-1 process that this
    jaxlib's CPU backend can otherwise crash compiling into (see the
    test_hist_pallas twin of this helper); later tests re-read the
    persistent compile cache, so the wall cost is small."""
    jax.clear_caches()


def _df_multinomial(n=1200, c=5, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, c)).astype(np.float32)
    eta = np.stack([X[:, 0], -X[:, 1], 0.5 * X[:, 2]], 1)
    pm_ = np.exp(eta)
    pm_ /= pm_.sum(1, keepdims=True)
    yk = np.array([rng.choice(3, p=pr) for pr in pm_])
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(c)])
    df["y"] = np.array(["a", "b", "c"])[yk]
    return df


def test_glm_fused_multinomial_parity_and_dispatches():
    """ISSUE-15 closure (b): the K-class cycling IRLS runs as ONE fused
    program (lax.scan over classes inside one while_loop). Coefficient
    parity <= 2e-3 vs the host f64 cycling loop at equal iteration count,
    and dispatches/model drop >= 3x (counter-pinned: the host loop pays
    one dispatch per (iteration, class))."""
    fr = Frame.from_pandas(_df_multinomial(seed=21))
    # objective_epsilon=0 pins both lanes to the FULL iteration budget so
    # the dispatch ratio compares equal work
    kw = dict(family="multinomial", max_iterations=8, seed=1,
              objective_epsilon=0.0)
    d0 = mx.counter_value("glm_dispatches_total")
    m_f = GLM(**kw).train(y="y", training_frame=fr)
    d1 = mx.counter_value("glm_dispatches_total")
    with _env(H2O3_TPU_GLM_FUSE="0"):
        m_u = GLM(**kw).train(y="y", training_frame=fr)
    d2 = mx.counter_value("glm_dispatches_total")
    fused_disp, unfused_disp = d1 - d0, d2 - d1
    assert unfused_disp == 8 * 3  # one per (iteration, class)
    assert unfused_disp >= 3 * fused_disp, (unfused_disp, fused_disp)
    Bf = np.asarray(m_f.output["beta_multinomial_std"])
    Bu = np.asarray(m_u.output["beta_multinomial_std"])
    np.testing.assert_allclose(Bf, Bu, atol=2e-3)
    pf = m_f.predict(fr)
    pu = m_u.predict(fr)
    np.testing.assert_allclose(
        pf.vec(pf.names[-1]).to_numpy(), pu.vec(pu.names[-1]).to_numpy(),
        atol=1e-4)
    _free_compile_state()


@pytest.mark.parametrize("k", [2, 8])
def test_glm_fused_multinomial_mesh_sweep(k):
    """The fused multinomial's sharded Gram (per-class psum_scatter +
    gather) on 2/8-device sub-meshes matches the 1-device fused run."""
    df = _df_multinomial(seed=22)
    kw = dict(family="multinomial", max_iterations=6, seed=1)
    with _use_mesh(1):
        m1 = GLM(**kw).train(y="y", training_frame=Frame.from_pandas(df))
    with _use_mesh(k):
        mk = GLM(**kw).train(y="y", training_frame=Frame.from_pandas(df))
    np.testing.assert_allclose(
        np.asarray(m1.output["beta_multinomial_std"]),
        np.asarray(mk.output["beta_multinomial_std"]), atol=2e-3)
    _free_compile_state()


def test_glm_fused_multinomial_kill_and_resume_bit_exact(tmp_path):
    """Multinomial irls_state (NEW in ISSUE 15): with
    export_checkpoints_dir the fused chunk clamps to one outer iteration,
    snapshots carry (it, ll_prev, Beta), and a killed run resumed from the
    snapshot reproduces the uninterrupted FUSED trajectory bit-for-bit."""
    from h2o3_tpu.persist import load_model

    fr = Frame.from_pandas(_df_multinomial(seed=23))
    kw = dict(family="multinomial", max_iterations=8, seed=1,
              objective_epsilon=0.0)
    full = GLM(**kw).train(y="y", training_frame=fr)
    ckdir = str(tmp_path / "glm_mn_ck")
    with faults.inject(abort={"glm": 3}):
        with pytest.raises(faults.TrainAbort):
            GLM(export_checkpoints_dir=ckdir, **kw).train(
                y="y", training_frame=fr)
    snaps = [f for f in os.listdir(ckdir) if "glm_ckpt" in f]
    assert snaps
    prior = load_model(os.path.join(ckdir, snaps[0]))
    st_ = prior.output["irls_state"]
    assert st_["multinomial"] and st_["it"] <= 3
    resumed = GLM(checkpoint=prior.key, **kw).train(y="y", training_frame=fr)
    np.testing.assert_array_equal(
        np.asarray(resumed.output["beta_multinomial_std"]),
        np.asarray(full.output["beta_multinomial_std"]))
    _free_compile_state()


def test_glm_fused_ordinal_matches_host_driver():
    """The fused on-device BFGS ordinal fit converges to the host
    L-BFGS-B optimum (the NLL is convex in this parameterization);
    predictions within the f32 optimization envelope."""
    rng = np.random.default_rng(24)
    n, c = 1000, 4
    X = rng.normal(size=(n, c)).astype(np.float32)
    lat = X[:, 0] - 0.7 * X[:, 1] + 0.5 * rng.normal(size=n)
    yk = np.digitize(lat, [-0.7, 0.7])
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(c)])
    df["y"] = np.array(["lo", "mid", "hi"])[yk]
    fr = Frame.from_pandas(df)
    m_f = GLM(family="ordinal", seed=1).train(y="y", training_frame=fr)
    with _env(H2O3_TPU_GLM_FUSE="0"):
        m_h = GLM(family="ordinal", seed=1).train(y="y", training_frame=fr)
    np.testing.assert_allclose(
        np.asarray(m_f.output["beta_std"]),
        np.asarray(m_h.output["beta_std"]), atol=2e-3)
    np.testing.assert_allclose(
        np.asarray(m_f.output["theta"]),
        np.asarray(m_h.output["theta"]), atol=2e-3)
    pf = m_f.predict(fr)
    ph = m_h.predict(fr)
    np.testing.assert_allclose(
        pf.vec(pf.names[-1]).to_numpy(), ph.vec(ph.names[-1]).to_numpy(),
        atol=2e-3)


def test_glm_fallback_counter_p_values_quiet():
    """compute_p_values rides the fused IRLS lane (ISSUE 16): the
    glm_fuse_fallbacks_total{reason=p_values} counter stays quiet and the
    fused chunk program compiles/hits like any other fit."""
    fr = Frame.from_pandas(_df(seed=25))
    f0 = mx.counter_value("glm_fuse_fallbacks_total", reason="p_values")
    c0 = mx.counter_value("glm_programs_compiled_total")
    h0 = mx.counter_value("glm_program_cache_hits_total")
    m = GLM(family="binomial", lambda_=0.0, alpha=0.0, compute_p_values=True,
            max_iterations=5, seed=1).train(y="y", training_frame=fr)
    assert "p_values" in m.output
    assert mx.counter_value(
        "glm_fuse_fallbacks_total", reason="p_values") == f0
    assert (mx.counter_value("glm_programs_compiled_total") > c0
            or mx.counter_value("glm_program_cache_hits_total") > h0)


def test_glm_p_values_fused_parity():
    """Fused-lane p-values (covariance from the final device Gram at the
    converged beta) must match the unfused per-iteration path within the
    f32 trajectory envelope."""
    fr = Frame.from_pandas(_df(seed=10))
    m_f = GLM(family="binomial", lambda_=0.0, alpha=0.0,
              compute_p_values=True, max_iterations=10, seed=1).train(
        y="y", training_frame=fr)
    with _env(H2O3_TPU_GLM_FUSE="0"):
        m_u = GLM(family="binomial", lambda_=0.0, alpha=0.0,
                  compute_p_values=True, max_iterations=10, seed=1).train(
            y="y", training_frame=fr)
    np.testing.assert_allclose(
        np.asarray(m_f.output["p_values"], dtype=np.float64),
        np.asarray(m_u.output["p_values"], dtype=np.float64), atol=1e-6)


# ---------------------------------------------------------------------------
# DL fused lanes


def test_dl_epoch_chunk_bit_identical_and_dispatches():
    """Folding K epochs into one program (donated carry, host-side
    permutation RNG, threaded dropout key) must reproduce the per-epoch
    trajectory BIT-identically, with O(epochs/K) dispatches."""
    fr = Frame.from_pandas(_df(seed=11))
    kw = dict(hidden=[16], epochs=4, mini_batch_size=64, seed=7)
    with _env(H2O3_TPU_DL_GRAD_SHARD="0"):
        d0 = mx.counter_value("dl_dispatches_total")
        m_c = DeepLearning(**kw).train(y="y", training_frame=fr)
        d1 = mx.counter_value("dl_dispatches_total")
        with _env(H2O3_TPU_DL_EPOCH_CHUNK="1"):
            m_1 = DeepLearning(**kw).train(y="y", training_frame=fr)
        d2 = mx.counter_value("dl_dispatches_total")
    assert d1 - d0 == 1     # 4 epochs, one chunk
    assert d2 - d1 == 4     # per-epoch control
    pc = m_c.predict(fr)
    p1 = m_1.predict(fr)
    np.testing.assert_array_equal(
        pc.vec(pc.names[-1]).to_numpy(), p1.vec(p1.names[-1]).to_numpy())
    # per-epoch history is preserved under chunking
    assert [h["epoch"] for h in m_c.scoring_history] == [1, 2, 3, 4]
    np.testing.assert_allclose(
        [h["loss"] for h in m_c.scoring_history],
        [h["loss"] for h in m_1.scoring_history], rtol=1e-5)


def test_dl_grad_shard_parity_and_counters():
    """The sharded gradient reduction (flat psum_scatter + per-shard
    optimizer + params all_gather) stays within the reduction-order
    envelope of the replicated lane and tallies dl_grad_reduce /
    dl_param_gather."""
    fr = Frame.from_pandas(_df(seed=12))
    kw = dict(hidden=[16], epochs=4, mini_batch_size=64, seed=7)
    g0 = mx.counter_value("tree_collective_bytes_total",
                          phase="dl_grad_reduce")
    a0 = mx.counter_value("tree_collective_bytes_total",
                          phase="dl_param_gather")
    m_s = DeepLearning(**kw).train(y="y", training_frame=fr)
    assert mx.counter_value(
        "tree_collective_bytes_total", phase="dl_grad_reduce") > g0
    assert mx.counter_value(
        "tree_collective_bytes_total", phase="dl_param_gather") > a0
    with _env(H2O3_TPU_DL_GRAD_SHARD="0"):
        m_r = DeepLearning(**kw).train(y="y", training_frame=fr)
    ps = m_s.predict(fr)
    pr = m_r.predict(fr)
    np.testing.assert_allclose(
        ps.vec(ps.names[-1]).to_numpy(), pr.vec(pr.names[-1]).to_numpy(),
        atol=1e-4)


@pytest.mark.parametrize("k", [2, 8])
def test_dl_mesh_sweep_chunk_invariance(k):
    """Chunked-vs-per-epoch bit-identity holds on every sub-mesh size
    (the sharded grad lane is active on >1-device meshes)."""
    df = _df(seed=13)
    kw = dict(hidden=[8], epochs=3, mini_batch_size=64, seed=4)
    with _use_mesh(k):
        fr = Frame.from_pandas(df)
        m_c = DeepLearning(**kw).train(y="y", training_frame=fr)
        with _env(H2O3_TPU_DL_EPOCH_CHUNK="1"):
            m_1 = DeepLearning(**kw).train(y="y", training_frame=fr)
        pc = m_c.predict(fr)
        p1 = m_1.predict(fr)
        np.testing.assert_array_equal(
            pc.vec(pc.names[-1]).to_numpy(), p1.vec(p1.names[-1]).to_numpy())


def test_dl_bucketed_input_bit_identical():
    """Input-width bucketing (zero-padded first kernel rows) must be
    bit-inert: the padded rows start at zero, receive zero gradients, and
    the real-weight trajectory is unchanged."""
    df = _df(seed=14, c=6)  # D=6 -> pads to 8
    kw = dict(hidden=[8], epochs=3, mini_batch_size=64, seed=4)
    fr = Frame.from_pandas(df)
    m_b = DeepLearning(**kw).train(y="y", training_frame=fr)
    assert int(m_b.output["input_pad"]) == 2
    k0 = np.asarray(m_b.output["params"]["params"]["Dense_0"]["kernel"])
    np.testing.assert_array_equal(k0[6:], 0.0)  # pad rows stayed zero
    with _env(H2O3_TPU_SHAPE_BUCKETS="0"):
        m_e = DeepLearning(**kw).train(y="y", training_frame=fr)
    assert int(m_e.output["input_pad"]) == 0
    pb = m_b.predict(fr)
    pe = m_e.predict(fr)
    # padded rows contribute exact zeros to every dot product; the only
    # permissible deviation is XLA re-tiling the wider matmul
    np.testing.assert_allclose(
        pb.vec(pb.names[-1]).to_numpy(), pe.vec(pe.names[-1]).to_numpy(),
        atol=1e-6)


def test_dl_same_bucket_rebuild_zero_new_compiles():
    """A rebuild on a frame in the same input-width bucket (and row
    bucket) must compile ZERO new epoch-chunk programs."""
    kw = dict(hidden=[8], epochs=2, mini_batch_size=64, seed=4)
    DeepLearning(**kw).train(
        y="y", training_frame=Frame.from_pandas(_df(seed=15, c=6)))
    c0 = mx.counter_value("dl_programs_compiled_total")
    h0 = mx.counter_value("dl_program_cache_hits_total")
    # 7 features -> same 8-wide bucket as 6; rows unchanged -> same npad;
    # the minibatch trip count is a DYNAMIC argument, so a different row
    # count inside the bucket would not recompile either
    DeepLearning(**kw).train(
        y="y", training_frame=Frame.from_pandas(_df(seed=16, c=7)))
    assert mx.counter_value("dl_programs_compiled_total") == c0
    assert mx.counter_value("dl_program_cache_hits_total") > h0


def test_dl_dropout_trains_on_sharded_lane_with_ctl_parity():
    """ISSUE-15 closure (c): dropout no longer gates the sharded-gradient
    lane — each device folds its shard index into the minibatch dropout
    key. The H2O3_TPU_DL_GRAD_SHARD=ctl lane is the replicated control
    drawing the SAME masks (per-chunk folds): trajectory parity pinned at
    1e-4 preds. The old replicated lane (full-batch masks) must genuinely
    DIFFER — proving the dropout actually fires — and GRAD_SHARD=0 still
    restores it."""
    fr = Frame.from_pandas(_df(seed=26))
    kw = dict(hidden=[16], epochs=4, mini_batch_size=64, seed=7,
              activation="RectifierWithDropout",
              hidden_dropout_ratios=[0.3], input_dropout_ratio=0.1)
    g0 = mx.counter_value("tree_collective_bytes_total",
                          phase="dl_grad_reduce")
    m_s = DeepLearning(**kw).train(y="y", training_frame=fr)
    assert mx.counter_value(
        "tree_collective_bytes_total", phase="dl_grad_reduce") > g0, \
        "dropout training no longer engaged the sharded lane"
    with _env(H2O3_TPU_DL_GRAD_SHARD="ctl"):
        m_c = DeepLearning(**kw).train(y="y", training_frame=fr)
    with _env(H2O3_TPU_DL_GRAD_SHARD="0"):
        m_r = DeepLearning(**kw).train(y="y", training_frame=fr)
    ps = m_s.predict(fr)
    pc = m_c.predict(fr)
    pr = m_r.predict(fr)
    a = ps.vec(ps.names[-1]).to_numpy()
    b = pc.vec(pc.names[-1]).to_numpy()
    c = pr.vec(pr.names[-1]).to_numpy()
    np.testing.assert_allclose(a, b, atol=1e-4)  # the trajectory-parity pin
    # full-batch masks are a DIFFERENT dropout stream: if these matched,
    # the parity above would be vacuous (dropout never fired)
    assert np.max(np.abs(a - c)) > 1e-3
    _free_compile_state()


def test_dl_shard_fallback_counter_reasons():
    """dl_shard_fallbacks_total{reason}: batch indivisibility and
    non-elementwise optimizer state still fall back — and tally."""
    fr = Frame.from_pandas(_df(seed=27))
    b0 = mx.counter_value("dl_shard_fallbacks_total",
                          reason="batch_indivisible")
    # 63 % 8 != 0 on the 8-device mesh -> replicated + counter
    DeepLearning(hidden=[8], epochs=2, mini_batch_size=63, seed=4).train(
        y="y", training_frame=fr)
    assert mx.counter_value(
        "dl_shard_fallbacks_total", reason="batch_indivisible") > b0
    o0 = mx.counter_value("dl_shard_fallbacks_total", reason="opt_state")
    # momentum SGD carries a schedule step counter -> non-elementwise
    DeepLearning(hidden=[8], epochs=2, mini_batch_size=64, seed=4,
                 adaptive_rate=False, rate=0.01, rate_decay=0.9,
                 momentum_start=0.5).train(y="y", training_frame=fr)
    assert mx.counter_value(
        "dl_shard_fallbacks_total", reason="opt_state") > o0


def test_dl_chunked_checkpoint_resume_matches_full():
    """Key-based continuation into the chunked driver: the RNG fast-forward
    keeps the resumed trajectory identical to an uninterrupted chunked
    run."""
    fr = Frame.from_pandas(_df(seed=17))
    kw = dict(hidden=[8], seed=4, mini_batch_size=64)
    full = DeepLearning(epochs=5, **kw).train(y="y", training_frame=fr)
    part = DeepLearning(epochs=2, **kw).train(y="y", training_frame=fr)
    resumed = DeepLearning(epochs=5, checkpoint=part.key, **kw).train(
        y="y", training_frame=fr)
    assert resumed.output["epochs_trained"] == 5
    assert len(resumed.scoring_history) == 3  # only the 3 new epochs ran
    pf = full.predict(fr)
    pr = resumed.predict(fr)
    np.testing.assert_array_equal(
        pf.vec(pf.names[-1]).to_numpy(), pr.vec(pr.names[-1]).to_numpy())
