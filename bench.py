"""North-star benchmark: GBM trees/sec on a Higgs-like binary task (BASELINE
config #2, scaled to single-chip memory).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
Extra diagnostic fields: ``breakdown`` — per-phase device seconds per tree
(hist / split / partition / host+other), ``mfu`` — issued-FLOP utilization
estimate for the histogram phase.

Each entry runs in its OWN subprocess (``python bench.py --phase NAME``):
a fresh backend per phase means one phase OOMing or crashing the TPU
runtime cannot starve the entries after it (the 20260731T0101Z artifact
lost 10M/join/GLM/breakdown to exactly that cascade — a RESOURCE_EXHAUSTED
in the 10M build poisoned every later allocation in the shared process).
One process for each chip: the parent never touches jax, so the device is
free for each child, and the children run one at a time.

A phase that fails makes the run exit non-zero; the other phases' results
are still printed. A run that finds a device whose peak is not in
``_PEAK_FLOPS`` fails: there is no nominal peak for the CPU, and a number
from a CPU run is not a device metric.

Baseline: **measured** (round 5) — sklearn 1.9.0 HistGradientBoosting on the
EXACT headline workload (same generator/rows/depth/bins/min-rows/lr, leaf cap
off, AUC-matched at 0.8452 vs 0.8454) builds 3.52 trees/sec on one pinned
Xeon 2.10 GHz thread on this box (median of 4 OMP_NUM_THREADS=1 fits — the
protocol is IN the script; rep spread 5.54-5.84 s). BASELINE.md records the
box specs and the 16-node-cluster equivalence arithmetic.
vs_baseline = measured / 3.52 (i.e. TPU chip vs one CPU core).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time
import traceback

import numpy as np
import pandas as pd

# row-count scale factor (plumbing tests / constrained windows):
# H2O3_TPU_BENCH_SCALE=0.01 runs every entry at 1% size. Default full size.
_SCALE = float(os.environ.get("H2O3_TPU_BENCH_SCALE", "1"))
N_ROWS = max(int(1_000_000 * _SCALE), 10_000)
N_COLS = 28  # Higgs feature count
N_TREES = 20
DEPTH = 6
BASELINE_TREES_PER_SEC = 3.52  # measured: tools/bench_cpu_baseline.py (BASELINE.md)

# Peak dense matmul throughput used for the MFU estimate, by device kind.
# f32 dots run as multi-pass bf16 on the MXU; we report against the bf16 peak
# (the honest ceiling for this formulation). Source: Google Cloud TPU
# documentation ("TPU v5e": 197 TFLOP/s bf16; "TPU v4": 275 TFLOP/s bf16).
_PEAK_FLOPS = {
    "v5 lite": 197e12,  # TPU v5e bf16
    "v5e": 197e12,
    "v4": 275e12,
}


def _peak_flops(device_kind: str) -> float:
    """The peak for a device kind; a device not in the table is an error,
    not a default."""
    kind = device_kind.lower()
    for k, v in _PEAK_FLOPS.items():
        if k in kind:
            return v
    raise RuntimeError(
        f"bench.py knows no peak FLOP/s for device kind {device_kind!r} "
        f"(table: {sorted(_PEAK_FLOPS)}); it measures the chip and does not "
        "run on anything else")


def make_data(n=N_ROWS, c=N_COLS, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, c)).astype(np.float32)
    eta = (
        1.5 * X[:, 0]
        - X[:, 1]
        + 0.8 * X[:, 2] * X[:, 3]
        + np.sin(2 * X[:, 4])
        + 0.5 * X[:, 5] ** 2
        - 1.0
    )
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-eta))).astype(np.int32)
    df = pd.DataFrame(X, columns=[f"f{i}" for i in range(c)])
    df["label"] = np.where(y == 1, "s", "b")
    return df


def _emit(payload: dict) -> None:
    print(json.dumps(payload))


def _phase_breakdown(
    fr, n_trees: int, total_s: float, nbins: int = 255
) -> tuple[dict, float, float]:
    """Time the histogram / split / partition phases standalone on the bench
    data shapes and estimate histogram-phase MFU.

    Returns ({phase: sec_per_tree}, hist_flops_per_tree,
    hist_flops_traced_per_tree). Phases are timed as the same jitted programs
    the level loop runs, summed over the per-level node counts
    1,2,4,...,2^(DEPTH-1); "host_other" is the remainder of the measured
    wall time. ``hist_flops`` prices the standalone direct-scheme programs
    timed here (every node's histogram built — the denominator for "mfu");
    ``hist_flops_traced`` prices the program that actually RAN: with
    H2O3_TPU_HIST_SUBTRACT=1 each level past the root builds only ONE
    sibling per pair (half the frontier) and derives the other by
    subtraction, so crediting the traced ph_hist time with every node's
    FLOPs would overstate mfu_traced ~2x.
    """
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.models.tree.binning import BinSpec, fit_bins, bin_frame
    from h2o3_tpu.ops.histogram import build_histograms
    from h2o3_tpu.parallel.mesh import row_sharding

    cols = [c for c in fr.names if c != "label"]
    spec = fit_bins(fr, cols, nbins=nbins)  # same bins the headline ran at
    bins_u8 = bin_frame(spec, fr)
    n_pad = bins_u8.shape[0]
    n_bins = spec.max_bins

    rng = np.random.default_rng(0)
    w = jax.device_put(jnp.ones(n_pad, jnp.float32), row_sharding())
    wy = jax.device_put(
        jnp.asarray(rng.normal(size=n_pad).astype(np.float32)), row_sharding()
    )

    def timed(f, *args, reps=3):
        out = f(*args)  # warmup/compile
        jax.tree.map(lambda x: x.block_until_ready(), out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = f(*args)
        jax.tree.map(lambda x: x.block_until_ready(), out)
        return (time.perf_counter() - t0) / reps

    from h2o3_tpu.models.tree.shared_tree import _subtract_enabled

    subtract = _subtract_enabled()
    hist_s = 0.0
    hist_flops = 0.0
    hist_flops_traced = 0.0
    for level in range(DEPTH):
        n_nodes = 2**level
        # nodes whose histogram the fused program actually BUILDS at this
        # level: all of them in the direct scheme; one sibling per pair
        # (half) under subtraction, except the root which has no sibling
        n_built = n_nodes if (level == 0 or not subtract) else n_nodes // 2
        nid = jax.device_put(
            jnp.asarray(rng.integers(0, n_nodes, n_pad).astype(np.int32)),
            row_sharding(),
        )
        hist_s += timed(
            lambda b, n, ww, wwy: build_histograms(
                b, n, (ww, wwy, ww), n_nodes, n_bins),
            bins_u8,
            nid,
            w,
            wy,
        )
        # matmul-path issued FLOPs: 3 stats x 2*n*N*(C*B) per level (the
        # wy2 lane was dropped — its gain contribution cancels exactly)
        hist_flops += 3 * 2.0 * n_pad * n_nodes * len(cols) * n_bins
        hist_flops_traced += 3 * 2.0 * n_pad * n_built * len(cols) * n_bins

    # split scan at the deepest level's node count (the most expensive one)
    from h2o3_tpu.models.tree.shared_tree import _split_scan

    n_nodes = 2 ** (DEPTH - 1)
    hist = jnp.zeros((n_nodes, len(cols), n_bins, 3), jnp.float32).at[:, :, :, 0].set(1.0)
    split_fn = jax.jit(
        lambda h: _split_scan(
            h,
            jnp.zeros(len(cols), bool),
            jnp.ones((n_nodes, len(cols)), jnp.float32),
            jnp.float32(10.0),
            jnp.float32(1e-5),
        )
    )
    split_s = timed(split_fn, hist) * DEPTH  # ~same cost each level

    # partition update: recompute nid children assignment over all rows
    @jax.jit
    def partition(b, n):
        col = jnp.zeros(n_pad, jnp.int32)
        thr = jnp.full(n_pad, 128, jnp.int32)
        bv = jnp.take_along_axis(b.astype(jnp.int32), col[:, None], axis=1)[:, 0]
        return jnp.where(bv <= thr, n * 2, n * 2 + 1)

    nid = jax.device_put(jnp.zeros(n_pad, jnp.int32), row_sharding())
    part_s = timed(partition, bins_u8, nid) * DEPTH

    per_tree = {
        "hist_s": round(hist_s, 4),
        "split_s": round(split_s, 4),
        "partition_s": round(part_s, 4),
    }
    # The training loop runs these phases FUSED in one scanned dispatch per
    # scoring interval; the per-phase numbers above are standalone-dispatch
    # diagnostics (each pays its own dispatch). fused_tree_s is the actual
    # per-tree device cost.
    try:
        from h2o3_tpu.models.tree.distributions import grad_hess
        from h2o3_tpu.models.tree.shared_tree import build_trees_scanned

        spec2 = fit_bins(fr, cols)
        t0 = time.perf_counter()
        out = build_trees_scanned(
            bins_u8, w, wy, jnp.zeros(n_pad, jnp.float32),
            jnp.zeros(len(cols), jnp.float32), jax.random.PRNGKey(0), 4,
            grad_fn=lambda F_, y_, w_: grad_hess("bernoulli", F_, y_, w_, 0.0),
            grad_key=("bench", "bernoulli"),
            sample_rate=1.0, n_bins=n_bins, is_cat_cols=spec2.is_cat,
            max_depth=DEPTH, min_rows=10.0, min_split_improvement=1e-5,
            learn_rates=np.full(4, 0.1, np.float32), max_abs_leaf=float("inf"),
            col_sample_rate=1.0, col_sample_rate_per_tree=1.0,
        )
        jax.tree.map(lambda x: x.block_until_ready(), out[0])
        per_tree["fused_compile_s"] = round(time.perf_counter() - t0, 4)
        t0 = time.perf_counter()
        out = build_trees_scanned(
            bins_u8, w, wy, jnp.zeros(n_pad, jnp.float32),
            jnp.zeros(len(cols), jnp.float32), jax.random.PRNGKey(0), 4,
            grad_fn=lambda F_, y_, w_: grad_hess("bernoulli", F_, y_, w_, 0.0),
            grad_key=("bench", "bernoulli"),
            sample_rate=1.0, n_bins=n_bins, is_cat_cols=spec2.is_cat,
            max_depth=DEPTH, min_rows=10.0, min_split_improvement=1e-5,
            learn_rates=np.full(4, 0.1, np.float32), max_abs_leaf=float("inf"),
            col_sample_rate=1.0, col_sample_rate_per_tree=1.0,
        )
        jax.tree.map(lambda x: x.block_until_ready(), out[0])
        per_tree["fused_tree_s"] = round((time.perf_counter() - t0) / 4, 4)
    except Exception as e:
        per_tree["fused_tree_error"] = repr(e)
    device_s = per_tree.get("fused_tree_s", hist_s + split_s + part_s)
    per_tree["host_other_s"] = round(max(total_s / n_trees - device_s, 0.0), 4)
    return per_tree, hist_flops, hist_flops_traced


def _drop_models(*models) -> None:
    """Unregister bench models: a registered model pins its training frame
    through ``params.training_frame``, so DKV.remove(frame) alone does not
    free HBM for the later entries."""
    from h2o3_tpu.cluster.registry import DKV

    for m in models:
        if m is not None:
            DKV.remove(m.key)


def _make_data_device(n: int, c: int = N_COLS, seed: int = 0, labeler=None,
                      col_prefix: str = "f"):
    """Bench frame synthesized ON DEVICE: a 10M-row frame is ~1.2 GB of
    host→device upload, and the metrics here are trees/rows per second,
    not ingest.

    ``labeler(key, X) -> (int8 codes, domain)`` defaults to the same
    Bernoulli generative model as :func:`make_data`."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.frame.frame import CAT, NUM, Frame, Vec
    from h2o3_tpu.parallel.mesh import pad_to_shards, row_sharding

    npad = pad_to_shards(n)

    def _bernoulli(ku, X):
        eta = (1.5 * X[:, 0] - X[:, 1] + 0.8 * X[:, 2] * X[:, 3]
               + jnp.sin(2 * X[:, 4]) + 0.5 * X[:, 5] ** 2 - 1.0)
        u = jax.random.uniform(ku, (X.shape[0],))
        return (u < jax.nn.sigmoid(eta)).astype(jnp.int8), ("b", "s")

    label_fn = labeler or _bernoulli
    domain_box = []

    @functools.partial(jax.jit, out_shardings=row_sharding())
    def gen(key):
        kx, ku = jax.random.split(key)
        X = jax.random.normal(kx, (npad, c), jnp.float32)
        y, domain = label_fn(ku, X)
        domain_box.append(domain)  # trace-time constant
        pad = jnp.arange(npad) >= n
        X = jnp.where(pad[:, None], jnp.nan, X)
        y = jnp.where(pad, -1, y).astype(jnp.int8)
        return X, y

    X, y = gen(jax.random.PRNGKey(seed))
    vecs = [Vec(X[:, i], NUM, name=f"{col_prefix}{i}", nrow=n) for i in range(c)]
    vecs.append(Vec(y, CAT, name="label", nrow=n, domain=domain_box[0]))
    return Frame(vecs, register=True)


def _bench_10m() -> dict:
    """GBM at 10M rows single chip (binned uint8 ≈ 280 MB on device)."""
    from h2o3_tpu.cluster.registry import DKV
    from h2o3_tpu.models.tree import GBM

    n10 = int(10_000_000 * _SCALE)
    fr = _make_data_device(n10)
    m0 = m = None
    try:
        kw = dict(max_depth=DEPTH, learn_rate=0.1, min_rows=10.0,
                  score_tree_interval=1000, seed=42)
        m0 = GBM(ntrees=5, **kw).train(y="label", training_frame=fr)  # compile
        t0 = time.time()
        m = GBM(ntrees=5, **kw).train(y="label", training_frame=fr)
        dt = time.time() - t0
        return {
            "rows": n10,
            "trees_per_sec": round(5 / dt, 3),
            "auc": round(float(m.training_metrics.auc), 4),
        }
    finally:
        # failure path too: a leaked 10M frame starves every later entry
        _drop_models(m0, m)
        DKV.remove(fr.key)
        del fr


def _bench_join_10m() -> dict:
    """Device sort-merge join (frame/ops.py merge) at 10M x 1M rows."""
    import h2o3_tpu
    from h2o3_tpu.frame import ops

    import jax
    import jax.numpy as jnp

    from h2o3_tpu.cluster.registry import DKV
    from h2o3_tpu.frame.frame import NUM, Frame, Vec
    from h2o3_tpu.parallel.mesh import pad_to_shards, row_sharding

    def _dev_frame(n, key, kmax, with_x):
        npad = pad_to_shards(n)

        @functools.partial(jax.jit, out_shardings=row_sharding())
        def gen(k):
            kk, kx = jax.random.split(k)
            ks = (jax.random.randint(kk, (npad,), 0, kmax) if with_x
                  else jnp.arange(npad)).astype(jnp.float32)
            xs = jax.random.normal(kx, (npad,), jnp.float32)
            pad = jnp.arange(npad) >= n
            return (jnp.where(pad, jnp.nan, ks), jnp.where(pad, jnp.nan, xs))

        ks, xs = gen(key)
        return Frame([Vec(ks, NUM, name="k", nrow=n),
                      Vec(xs, NUM, name="x" if with_x else "y", nrow=n)],
                     register=True)

    left = right = out = None
    try:
        nl, nr = int(10_000_000 * _SCALE), int(1_000_000 * _SCALE)
        left = _dev_frame(nl, jax.random.PRNGKey(1), nr, True)
        right = _dev_frame(nr, jax.random.PRNGKey(2), nr, False)
        out = ops.merge(left, right, by=["k"])  # warm compile
        t0 = time.time()
        out = ops.merge(left, right, by=["k"])
        dt = time.time() - t0
        return {"left_rows": nl, "right_rows": nr,
                "out_rows": out.nrow, "seconds": round(dt, 3),
                "rows_per_sec": round(out.nrow / dt, 0)}
    finally:
        for fr in (left, right):  # free HBM before the phase breakdown runs
            if fr is not None:
                DKV.remove(fr.key)
        del left, right, out


def _bench_cat_1m() -> dict:
    """GBM on a categorical-heavy frame (BASELINE config #3 workload shape:
    Criteo-style high-cardinality enums + numerics). Exercises the
    mean-sorted categorical split path and enum code storage at scale."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.cluster.registry import DKV
    from h2o3_tpu.models.tree import GBM

    n = max(int(1_000_000 * _SCALE), 10_000)
    n_num, n_cat, card = 20, 8, 200

    def labeler(ku, X):
        eta = 1.2 * X[:, 0] - X[:, 1] + 0.5 * X[:, 2] * X[:, 3] - 0.5
        u = jax.random.uniform(ku, (X.shape[0],))
        return (u < jax.nn.sigmoid(eta)).astype(jnp.int8), ("b", "s")

    fr = _make_data_device(n, c=n_num, labeler=labeler)
    fr2 = m0 = m = None
    try:
        # append device-generated enum columns (codes depend on numerics so
        # the categorical splits carry signal)
        from h2o3_tpu.frame.frame import CAT, Frame, Vec

        key = jax.random.PRNGKey(9)
        vecs = [fr.vec(nm) for nm in fr.names]
        for j in range(n_cat):
            kj = jax.random.fold_in(key, j)
            base = fr.vec(f"f{j % n_num}").data
            noise = jax.random.randint(kj, base.shape, 0, card // 4)
            codes = (
                (jnp.abs(jnp.nan_to_num(base)) * 37 + noise) % card
            ).astype(jnp.int16)
            vecs.insert(-1, Vec(codes, CAT, name=f"cat{j}", nrow=n,
                                domain=tuple(f"l{i}" for i in range(card))))
        fr2 = Frame(vecs, register=True)

        kw = dict(max_depth=DEPTH, learn_rate=0.1, min_rows=10.0,
                  score_tree_interval=1000, seed=42)
        m0 = GBM(ntrees=5, **kw).train(y="label", training_frame=fr2)
        t0 = time.time()
        m = GBM(ntrees=5, **kw).train(y="label", training_frame=fr2)
        dt = time.time() - t0
        # compiled group-by (frame/munge.py, ISSUE 20): all value columns'
        # segment stats in ONE mesh-sharded dispatch over the 200-level enum
        from h2o3_tpu.frame import ops

        gb_spec = {"f0": ["sum", "mean"], "f1": ["min", "max"],
                   "f2": ["count", "sd"]}
        ops.group_by(fr2, "cat0").agg(gb_spec)  # warm compile
        t0 = time.time()
        ops.group_by(fr2, "cat0").agg(gb_spec)
        gb_dt = time.time() - t0
        return {
            "rows": n, "num_cols": n_num, "cat_cols": n_cat,
            "cardinality": card, "trees_per_sec": round(5 / dt, 3),
            "auc": round(float(m.training_metrics.auc), 4),
            "groupby_s": round(gb_dt, 3),
            "groupby_rows_per_sec": round(n / max(gb_dt, 1e-9), 0),
        }
    finally:
        _drop_models(m0, m)
        DKV.remove(fr.key)
        if fr2 is not None:
            DKV.remove(fr2.key)


def _bench_dl(n: int = max(int(100_000 * _SCALE), 5_000), d: int = 784, k: int = 10) -> dict:
    """Sync-SGD MLP rows/sec (BASELINE config #4: Hogwild→sync-SGD MLP).
    MNIST-shaped synthetic: 100k x 784 → 10 classes, 2x128 hidden."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.cluster.registry import DKV
    from h2o3_tpu.models.deeplearning import DeepLearning

    def labeler(kw, X):
        W = jax.random.normal(kw, (d, k), jnp.float32)
        return (jnp.argmax(X @ W, axis=1).astype(jnp.int8),
                tuple(str(i) for i in range(k)))

    fr = _make_data_device(n, c=d, seed=5, labeler=labeler, col_prefix="p")
    m0 = m = None
    try:
        from h2o3_tpu.utils import metrics as _mx

        kw = dict(hidden=(128, 128), epochs=1.0, mini_batch_size=256, seed=3)
        m0 = DeepLearning(**kw).train(y="label", training_frame=fr)  # compile
        d0 = _mx.counter_value("dl_dispatches_total")
        e0 = _mx.counter_value("dl_epochs_total")
        t0 = time.time()
        m = DeepLearning(**kw).train(y="label", training_frame=fr)
        dt = time.time() - t0
        epochs = int(_mx.counter_value("dl_epochs_total") - e0) or 1
        return {"rows": n, "cols": d, "epochs": 1,
                "rows_per_sec": round(n / dt, 0), "seconds": round(dt, 3),
                # per-round tracked summary (ISSUE 8): wall seconds per
                # epoch plus the chunked-driver dispatch count
                "dl_epoch_s": round(dt / epochs, 3),
                "dispatches_per_model": int(
                    _mx.counter_value("dl_dispatches_total") - d0)}
    finally:
        _drop_models(m0, m)
        DKV.remove(fr.key)
        del fr


def _bench_automl(fr_small) -> dict:
    """AutoML wall-clock (BASELINE secondary metric): max_models budget on a
    50k-row slice of the bench frame.

    Runs the SAME AutoML twice in this fresh process: the first pass pays
    every jit compile its shapes need (``cold_s`` — in-memory caches empty;
    the persistent XLA cache may soften it, so its pre-run entry count is
    recorded), the second hits the warm caches (``warm_s``). cold/warm is
    the VERDICT r4 missing-#5 question: does compile amortize across an
    AutoML run, or dominate it?"""
    import math

    from h2o3_tpu.automl import AutoML

    from h2o3_tpu.models.tree.shared_tree import reset_build_stats

    def run(seed):
        reset_build_stats()
        t0 = time.time()
        aml = AutoML(max_models=3, nfolds=0, seed=seed,
                     max_runtime_secs=900.0, include_algos=["GBM", "GLM"])
        aml.train(y="label", training_frame=fr_small)
        dt = time.time() - t0
        # reset_build_stats snapshots the registry counters (BUILD_STATS is
        # a registry view) — the same values /3/Metrics would serve
        return dt, aml.leaderboard, reset_build_stats()

    cache_entries = _compile_cache_entries()
    cold_s, lb, cold_stats = run(11)
    _drop_models(*(lb.models if lb else ()))
    warm_s, lb, warm_stats = run(11)

    out = {"max_models": 3,
           "cold_s": round(cold_s, 3),
           "warm_s": round(warm_s, 3),
           # per-round tracked summary (ISSUE 8): total AutoML wall time
           # across the cold+warm passes — the end-to-end number the fused
           # GLM/DL lanes must not regress
           "automl_total_s": round(cold_s + warm_s, 3),
           "compile_share_est": round(max(cold_s - warm_s, 0.0) / cold_s, 3)
           if cold_s > 0 else None,
           "persistent_cache_entries_before": cache_entries,
           # shape-bucketed whole-tree amortization (ISSUE 1): the warm pass
           # repeats the cold pass's shapes, so compiled should drop to 0
           # and every tree program come from the in-process cache
           "tree_programs_compiled": [
               cold_stats["tree_programs_compiled"],
               warm_stats["tree_programs_compiled"],
           ],
           "tree_program_cache_hits": [
               cold_stats["tree_program_cache_hits"],
               warm_stats["tree_program_cache_hits"],
           ],
           "dispatches_per_tree": [
               round(s["dispatches"] / max(s["trees_built"], 1), 4)
               for s in (cold_stats, warm_stats)
           ],
           "models_built": len(lb.models) if lb else 0}
    if lb and lb.models:
        auc = float(lb.as_table()[0].get("auc", float("nan")))
        if math.isfinite(auc):  # bare NaN would break the one-line JSON
            out["leader_auc"] = round(auc, 4)
    _drop_models(*(lb.models if lb else ()))
    return out


def _compile_cache_entries() -> int | None:
    """Entry count of the persistent XLA compile cache (None if the dir does
    not exist): distinguishes a truly cold run from one the cache pre-warmed."""
    from h2o3_tpu import config

    d = config.compile_cache_dir()
    return len(os.listdir(d)) if os.path.isdir(d) else None


def _bench_glm_1m(fr) -> dict:
    """GLM binomial IRLS on the bench frame (BASELINE config #1 analog):
    Gram + solve per iteration, the hex.glm hot loop. Reports the fused-
    lane contract numbers (ISSUE 8): measured iterations/sec and host
    dispatches per model from the registry counters — O(iterations/K)
    fused vs O(iterations) unfused."""
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.utils import metrics as _mx

    kw = dict(family="binomial", lambda_=1e-4, max_iterations=20, seed=1)
    GLM(**kw).train(y="label", training_frame=fr)  # compile
    i0 = _mx.counter_value("glm_irls_iterations_total")
    d0 = _mx.counter_value("glm_dispatches_total")
    g0 = sum(_mx.counter_value("tree_collective_bytes_total", phase=ph)
             for ph in ("gram_reduce", "gram_gather"))
    t0 = time.time()
    m = GLM(**kw).train(y="label", training_frame=fr)
    dt = time.time() - t0
    iters = int(_mx.counter_value("glm_irls_iterations_total") - i0) or kw[
        "max_iterations"]
    return {
        "rows": N_ROWS,
        "seconds": round(dt, 3),
        "auc": round(float(m.training_metrics.auc), 4),
        "iterations": iters,
        "glm_iters_per_s": round(iters / max(dt, 1e-9), 3),
        "dispatches_per_model": int(
            _mx.counter_value("glm_dispatches_total") - d0),
        "gram_collective_bytes": round(sum(
            _mx.counter_value("tree_collective_bytes_total", phase=ph)
            for ph in ("gram_reduce", "gram_gather")) - g0, 1),
    }


def _collective_microbench(n_nodes=64, n_bins=128, iters=10) -> dict | None:
    """MEASURED seconds for every hot collective phase at bench shapes —
    the histogram all-reduce vs reduce-scatter + winner gather (trees), the
    Gram reduce-scatter + solve gather (fused GLM), the flat-gradient
    scatter + param gather (sharded DL) — timed as standalone dispatches on
    the real mesh (collectives inside the fused programs cannot be
    host-timed individually; this calibration fills
    ``tree_collective_seconds_total{phase}``). The reduces run through the
    ops/collectives lane, so whatever lane is ACTIVE (quantized,
    hierarchical, exact) is what gets measured — the --quant-ab seconds are
    measured, not modeled. Returns None on a 1-device mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.models.glm import _glm_pad_cols
    from h2o3_tpu.models.tree.shared_tree import _COLL_SECONDS, _split_shard_on
    from h2o3_tpu.ops import collectives
    from h2o3_tpu.parallel.mesh import (
        col_axis_name, get_mesh, n_col_shards, pad_cols_to_shards,
        pad_flat_to_shards, shard_map)

    mesh = get_mesh()
    n_dev = int(mesh.devices.size)
    if n_dev <= 1:
        return None
    # scattered results shard over the COLUMN-BLOCK axis (the whole 1-D
    # mesh, or the cols axis of a 2-D pod mesh — the wrappers run their
    # exact rows-axis stage internally either way)
    cax = col_axis_name(mesh)
    n_blk = n_col_shards(mesh)
    Cp = pad_cols_to_shards(N_COLS, mesh)
    hist = jnp.ones((Cp, n_nodes * n_bins, 3), jnp.float32)  # one local hist
    win = jnp.ones((n_nodes, 14), jnp.float32)  # ~the winner tuple payload
    p_pad = _glm_pad_cols(N_COLS + 1)  # bench GLM design width (+intercept)
    gram = jnp.ones((p_pad, p_pad), jnp.float32)
    # bench DL network (hidden 64x64 on the bench frame) flat param vector
    n_param = (N_COLS * 64 + 64) + (64 * 64 + 64) + (64 + 1)
    grad = jnp.ones((pad_flat_to_shards(n_param, mesh),), jnp.float32)

    def timed(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    sm = lambda f, outs: jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(),), out_specs=outs, check_vma=False))
    ar_s = timed(sm(
        lambda v: collectives.psum(v, n_dev=n_dev, lane_axis=-1), P()), hist)
    rs_s = timed(sm(
        lambda v: collectives.psum_scatter(v, n_dev=n_dev, lane_axis=-1),
        P(cax)), hist)
    wg_s = timed(sm(lambda v: jax.lax.all_gather(v, cax), P()), win)
    gr_s = timed(sm(
        lambda v: collectives.psum_scatter(v, n_dev=n_dev, passes=2),
        P(cax)), gram)
    gg_s = timed(sm(
        lambda v: jax.lax.all_gather(
            v, cax, axis=0, tiled=True), P()),
        gram.reshape(n_blk, -1)[0])
    dg_s = timed(sm(
        lambda v: collectives.psum_scatter(v, n_dev=n_dev, passes=2),
        P(cax)), grad)
    pg_s = timed(sm(
        lambda v: jax.lax.all_gather(v, cax, axis=0, tiled=True),
        P()), grad.reshape(n_blk, -1)[0])
    sharded = _split_shard_on()
    _COLL_SECONDS.inc(rs_s if sharded else ar_s, phase="hist_reduce")
    if sharded:
        _COLL_SECONDS.inc(wg_s, phase="winner_gather")
    _COLL_SECONDS.inc(gr_s, phase="gram_reduce")
    _COLL_SECONDS.inc(gg_s, phase="gram_gather")
    _COLL_SECONDS.inc(dg_s, phase="dl_grad_reduce")
    _COLL_SECONDS.inc(pg_s, phase="dl_param_gather")
    return {
        "allreduce_s": round(ar_s, 6),
        "reduce_scatter_s": round(rs_s, 6),
        "winner_gather_s": round(wg_s, 6),
        "gram_reduce_s": round(gr_s, 6),
        "gram_gather_s": round(gg_s, 6),
        "dl_grad_reduce_s": round(dg_s, 6),
        "dl_param_gather_s": round(pg_s, 6),
        "mode": "sharded" if sharded else "replicated",
        "lane": "quant" if collectives.quant_enabled() else "exact",
    }


def _phase_headline() -> dict:
    """1M-row GBM trees/sec — the driver's headline metric — plus the
    per-phase breakdown and MFU estimate (same process: they share the
    uploaded frame and the warm compile)."""
    import jax

    import h2o3_tpu
    from h2o3_tpu.models.tree import GBM

    peak = _peak_flops(jax.devices()[0].device_kind)  # unknown device: fail now
    df = make_data()
    fr = h2o3_tpu.upload_file(df)

    kw = dict(
        max_depth=DEPTH,
        learn_rate=0.1,
        min_rows=10.0,
        score_tree_interval=1000,
        seed=42,
    )
    # bin-count A/B knob for TPU windows: the histogram kernel's indicator
    # build is ∝ bins, and 127 quantile bins still exceed upstream's
    # default split resolution (nbins=20)
    from h2o3_tpu.models.tree.binning import MAX_BINS

    nbins_env = os.environ.get("H2O3_TPU_BENCH_NBINS")
    if nbins_env:
        # fit_bins clamps silently — clamp HERE too so the recorded metric
        # label always matches what actually ran
        kw["nbins"] = max(min(int(nbins_env), MAX_BINS), 2)
    # warmup: compile the full configuration (the chunk-scanned builder
    # specializes on chunk length, so warmup must use the same ntrees)
    GBM(ntrees=N_TREES, **kw).train(y="label", training_frame=fr)

    # counters come from the cluster metrics registry — the same numbers
    # GET /3/Metrics serves — so bench artifacts and the live endpoint can
    # never disagree (BUILD_STATS is a view over the same registry)
    from h2o3_tpu.models.tree.shared_tree import reset_build_stats
    from h2o3_tpu.utils import metrics as _mx

    reset_build_stats()
    _coll_phases = ("hist_reduce", "winner_gather")
    _hbm_paths = ("pallas_unfused", "dense")
    coll_before = {
        ph: _mx.counter_value("tree_collective_bytes_total", phase=ph)
        for ph in _coll_phases
    }
    hbm_before = {
        p: _mx.counter_value("tree_hist_hbm_bytes_total", path=p)
        for p in _hbm_paths
    }
    t0 = time.time()
    m = GBM(ntrees=N_TREES, **kw).train(y="label", training_frame=fr)
    dt = time.time() - t0
    tps = N_TREES / dt
    coll_bytes = {
        ph: _mx.counter_value("tree_collective_bytes_total", phase=ph)
        - coll_before[ph]
        for ph in _coll_phases
    }
    hbm_bytes = {
        p: _mx.counter_value("tree_hist_hbm_bytes_total", path=p)
        - hbm_before[p]
        for p in _hbm_paths
    }
    try:  # measured collective seconds (fills tree_collective_seconds_total)
        coll_s = _collective_microbench()
    except Exception as e:  # noqa: BLE001 — diagnostics never sink the headline
        coll_s = {"error": repr(e)[:120]}
    registry_block = _mx.REGISTRY.compact_snapshot()
    stats = {
        "dispatches": int(_mx.counter_value("tree_dispatches_total")),
        "trees_built": int(_mx.counter_value("tree_trees_built_total")),
        "tree_programs_compiled": int(_mx.counter_value(
            "tree_programs_compiled_total")),
        "tree_program_cache_hits": int(_mx.counter_value(
            "tree_program_cache_hits_total")),
    }
    reset_build_stats()

    payload = {
        # the registry-snapshot block (tools/latest_bench_ok.py requires it)
        "metrics_registry": registry_block,
        "metric": f"GBM trees/sec ({N_ROWS // 1_000_000}M rows x {N_COLS} cols, depth {DEPTH}"
                  + (f", nbins={kw['nbins']}" if "nbins" in kw else "")
                  + f", AUC={m.training_metrics.auc:.4f})",
        "value": round(tps, 3),
        "unit": "trees/sec/chip",
        "vs_baseline": round(tps / BASELINE_TREES_PER_SEC, 3),
        # whole-tree contract (ISSUE 1): O(1) host dispatches per tree —
        # per-level dispatch would read DEPTH+1 here
        "dispatches_per_tree": round(
            stats["dispatches"] / max(stats["trees_built"], 1), 4
        ),
        "tree_programs_compiled": stats["tree_programs_compiled"],
        "tree_program_cache_hits": stats["tree_program_cache_hits"],
        # split-phase collective traffic, from the traced-program byte tally
        # (replication-volume model, ops/histogram.py): the sharded split
        # pipeline's acceptance metric — a sharded run must undercut the
        # replicated control >= 2x at the same shape
        "psum_bytes_per_tree": round(
            sum(coll_bytes.values()) / max(stats["trees_built"], 1), 1
        ),
        "psum_bytes_by_phase": {
            ph: round(v, 1) for ph, v in coll_bytes.items()
        },
        # modeled hist+split HBM traffic (traced-structure tally,
        # tree_hist_hbm_bytes_total)
        "hist_hbm_bytes_per_tree": round(
            sum(hbm_bytes.values()) / max(stats["trees_built"], 1), 1
        ),
        "hist_hbm_bytes_by_path": {
            p: round(v, 1) for p, v in hbm_bytes.items() if v
        },
    }
    if coll_s is not None:
        payload["collective_s"] = coll_s
    hist_flops = None
    hist_flops_traced = None
    try:
        breakdown, hist_flops, hist_flops_traced = _phase_breakdown(
            fr, N_TREES, dt, nbins=kw.get("nbins", MAX_BINS))
        payload["breakdown"] = breakdown
        if breakdown["hist_s"] > 0:
            payload["mfu"] = round(hist_flops / breakdown["hist_s"] / peak, 4)
        payload["device_kind"] = jax.devices()[0].device_kind
    except Exception as e:  # diagnostics must never sink the headline number
        payload["breakdown_error"] = repr(e)
    # trace-based breakdown of the program that actually RAN (VERDICT r4
    # weak #2): phase shares from a jax profiler trace of one more train,
    # attributed via the ph_* named scopes. Requires the HLO dump that
    # _child_main arranged before backend init.
    try:
        import profile_fused  # path added by _child_main

        dump_dir = os.environ.get(profile_fused._DUMP_ENV)
        if dump_dir:
            prof = profile_fused.trace_phases(
                lambda: GBM(ntrees=N_TREES, **kw).train(
                    y="label", training_frame=fr
                ),
                dump_dir,
            )
            payload["fused_profile"] = prof
            if (
                hist_flops_traced is not None
                and prof.get("phases_s", {}).get("ph_hist", 0) > 0
            ):
                # phases_s is a PER-DEVICE mean and hist_flops_traced is the
                # whole mesh's work AS THE TRACED PROGRAM ISSUES IT (under
                # H2O3_TPU_HIST_SUBTRACT=1 only the actually-built sibling
                # histograms count): each of n_devices chips does ~1/n
                per_dev_flops = (
                    hist_flops_traced * N_TREES
                    / max(prof.get("n_devices", 1), 1)
                )
                payload["mfu_traced"] = round(
                    per_dev_flops / prof["phases_s"]["ph_hist"] / peak, 4
                )
            profile_fused.cleanup_dump_dir()
    except Exception as e:
        payload["fused_profile_error"] = repr(e)
    return payload


def _bench_hash_1m() -> dict:
    """GLM over feature-hashed 10^6-cardinality enums (BASELINE config #3's
    Criteo shape): proves the hashed path trains with BOUNDED design-matrix
    HBM at any cardinality (VERDICT r4 missing #4). Levels follow a hot-set
    + uniform-tail mixture (Criteo-like skew) with label signal on the hot
    levels, so the AUC shows the hashed representation actually learns."""
    import jax
    import jax.numpy as jnp

    import h2o3_tpu
    from h2o3_tpu.frame.frame import CAT, NUM, Frame, Vec
    from h2o3_tpu.models.glm import GLM

    from h2o3_tpu.parallel.mesh import pad_to_shards, row_sharding

    n = max(int(1_000_000 * _SCALE), 10_000)
    card, n_hot, buckets = 1_000_000, 1_000, 256
    npad = pad_to_shards(n)

    @functools.partial(jax.jit, out_shardings=row_sharding())
    def gen(key):
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        x0 = jax.random.normal(k1, (npad,), jnp.float32)
        # 90% of rows draw from n_hot hot levels, 10% from the 10^6 tail
        hot = jax.random.randint(k2, (npad,), 0, n_hot)
        tail = jax.random.randint(k3, (npad,), n_hot, card)
        is_hot = jax.random.uniform(k4, (npad,)) < 0.9
        codes = jnp.where(is_hot, hot, tail).astype(jnp.int32)
        eta = 1.2 * x0 + jnp.where(is_hot & (hot % 2 == 0), 1.0, -0.3)
        y = (jax.random.uniform(k5, (npad,)) < jax.nn.sigmoid(eta))
        pad = jnp.arange(npad) >= n
        return (
            jnp.where(pad, jnp.nan, x0),
            jnp.where(pad, -1, codes),
            jnp.where(pad, -1, y.astype(jnp.int8)),
        )

    x0, codes, y = gen(jax.random.PRNGKey(17))
    domain = tuple(f"v{i}" for i in range(card))
    vecs = [
        Vec(x0, NUM, name="x0", nrow=n),
        Vec(codes, CAT, name="c0", nrow=n, domain=domain),
        Vec(y, CAT, name="label", nrow=n, domain=("b", "s")),
    ]
    fr = Frame(vecs, register=True)

    kw = dict(family="binomial", lambda_=1e-4, max_iterations=8,
              hash_buckets=buckets)
    GLM(**kw).train(y="label", training_frame=fr)  # warm/compile
    t0 = time.time()
    m = GLM(**kw).train(y="label", training_frame=fr)
    dt = time.time() - t0
    out = {
        "rows": n,
        "cardinality": card,
        "hash_buckets": buckets,
        # GLM fits with use_all_factor_levels=False: bucket 0 is the
        # reference level, + x0 + intercept
        "ncols_expanded": (buckets - 1) + 2,
        "seconds": round(dt, 3),
        "auc": round(float(m.training_metrics.auc), 4),
    }
    # trees on the SAME 10^6-level enum: the binned path tail-clamps past
    # the bin budget (MIGRATION.md scale-limits #2) — prove it trains with
    # bounded HBM too, and record what clamping costs in AUC. The GLM
    # result must survive ANY tree failure mode, including the parent
    # killing this child at the phase budget: emit the GLM-only payload NOW
    # (the parent keeps the LAST parseable stdout line, and its timeout
    # path reads the killed child's captured stdout).
    _emit(out)
    try:
        from h2o3_tpu.models.tree import GBM

        gkw = dict(ntrees=5, max_depth=DEPTH, learn_rate=0.1, min_rows=10.0,
                   score_tree_interval=1000, seed=42)
        GBM(**gkw).train(y="label", training_frame=fr)  # warm
        t0 = time.time()
        gm = GBM(**gkw).train(y="label", training_frame=fr)
        out["gbm_trees_per_sec"] = round(gkw["ntrees"] / (time.time() - t0), 3)
        out["gbm_auc"] = round(float(gm.training_metrics.auc), 4)
    except Exception as e:  # noqa: BLE001 — diagnostics only
        out["gbm_error"] = repr(e)
    _emit(out)  # GLM+GBM survive a DL failure/kill the same way
    # DL over the hashed block — BASELINE config #4's Criteo-CTR shape
    # (sparse categorical CTR via sync-SGD MLP); hash_buckets bounds the
    # input layer exactly as it bounds the GLM design matrix
    try:
        from h2o3_tpu.models.deeplearning import DeepLearning

        dkw = dict(hidden=[64, 32], epochs=1, mini_batch_size=1024,
                   hash_buckets=buckets, seed=7)
        DeepLearning(**dkw).train(y="label", training_frame=fr)  # warm/compile
        t0 = time.time()
        dm = DeepLearning(**dkw).train(y="label", training_frame=fr)
        ddt = time.time() - t0
        out["dl_seconds"] = round(ddt, 3)
        out["dl_rows_per_sec"] = round(n / max(ddt, 1e-9), 1)
        out["dl_auc"] = round(float(dm.training_metrics.auc), 4)
    except Exception as e:  # noqa: BLE001 — diagnostics only
        out["dl_error"] = repr(e)
    return out


def _phase_glm_1m() -> dict:
    """GLM IRLS at 1M rows (BASELINE config #1: Airlines-1M analog)."""
    import h2o3_tpu

    fr = h2o3_tpu.upload_file(make_data())
    return _bench_glm_1m(fr)


def _phase_automl_50k() -> dict:
    import h2o3_tpu

    small = h2o3_tpu.upload_file(make_data().iloc[: max(int(50_000 * _SCALE), 5_000)])
    return _bench_automl(small)


# name -> (runner, parent-side wall budget seconds). Budgets are generous —
# each child pays its own backend init + compile.
_PHASES: dict = {
    "headline": (_phase_headline, 1500),
    "scale_10m": (_bench_10m, 900),       # VERDICT r4: evidence beyond 1M
    "cat_1m": (_bench_cat_1m, 900),       # BASELINE config #3 workload shape
    "join_10m": (_bench_join_10m, 600),   # ASTMerge successor at scale
    "glm_1m": (_phase_glm_1m, 600),
    "hash_1m": (_bench_hash_1m, 900),     # Criteo-cardinality hashed enums (+GBM)
    "dl_100k": (_bench_dl, 600),          # sync-SGD MLP (BASELINE config #4)
    "automl_50k": (_phase_automl_50k, 1800),  # cold + warm passes
}
# stop launching new phases past this parent deadline so the driver's own
# timeout never truncates the output mid-line
DEADLINE_S = float(os.environ.get("H2O3_TPU_BENCH_DEADLINE_S", 3000))


def _devmem_block() -> dict:
    """Per-phase HBM attribution snapshot (utils/devmem.py): live + peak
    bytes per owning residency plane, and the device in_use/unattributed
    split when the backend reports memory_stats. Every phase subprocess
    embeds one, so the artifact shows peak-per-owner-PER-PHASE — the
    number the TPU-window A/Bs compare against the static capacity model
    (tools/tpu_mem_analysis.py --live is the interactive twin)."""
    from h2o3_tpu.utils import devmem

    devmem.poll(force=True)
    s = devmem.status()
    out = {
        "owned_bytes": s["owned_bytes"],
        "peak_owned_bytes": s["peak_owned_bytes"],
    }
    for k in ("in_use_bytes", "limit_bytes", "unattributed_bytes"):
        if s.get(k) is not None:
            out[k] = s[k]
    return out


def _ledger_block() -> dict:
    """Per-job resource ledgers accumulated in this phase subprocess
    (utils/jobacct.py): device-seconds + dispatch counts by site,
    collective bytes by lane, frame-window bytes, queue waits — keyed by
    job id. The artifact twin of the ``/3/Jobs`` ledger embed; every
    phase's training runs as a Job, so this shows which job spent the
    phase's device time. latest_bench_ok pins the totals as finite and
    bounded by the phase wall."""
    from h2o3_tpu.utils import jobacct

    return jobacct.all_jobs()


def _child_main(phase: str) -> int:
    """Run one phase in this (fresh) process; print its JSON dict. A phase
    that raises prints ``{"error": ...}`` and the process exits 1."""
    try:
        if phase == "headline":
            # arrange the XLA HLO dump BEFORE jax loads, so the fused-profile
            # trace (tools/profile_fused.py) can attribute ops to phases
            sys.path.insert(
                0,
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools"),
            )
            import profile_fused

            profile_fused.prepare_dump_dir()
        import h2o3_tpu

        h2o3_tpu.init(log_level="WARN")
        out = _PHASES[phase][0]()
        if isinstance(out, dict):
            out["devmem"] = _devmem_block()
            led = _ledger_block()
            if led:
                out["jobs"] = led
    except Exception as e:  # noqa: BLE001 — the phase boundary: report, fail
        _emit({"error": repr(e), "traceback": traceback.format_exc(limit=20)})
        return 1
    _emit(out)
    return 0


def _run_phase_subprocess(phase: str, timeout_s: float) -> dict:
    # One process for each chip: this parent has not imported jax, so the
    # child gets the device, and subprocess.run returns only when the child
    # has exited — phases never overlap.
    import subprocess

    try:
        proc = subprocess.run(
            [sys.executable, __file__, "--phase", phase],
            capture_output=True,
            text=True,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired as e:
        # a killed child may still have emitted partial results (hash_1m
        # emits its GLM payload before attempting GBM) — keep them
        for line in reversed((e.stdout or "").strip().splitlines()):
            try:
                d = json.loads(line)
                if isinstance(d, dict):
                    # a killed phase is a failed phase; what it had already
                    # printed is kept beside the error
                    d.setdefault(
                        "error", f"partial: phase killed at {timeout_s:.0f}s"
                    )
                    return d
            except json.JSONDecodeError:
                continue
        return {"error": f"phase timed out after {timeout_s:.0f}s (parent kill)"}
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            d = json.loads(line)
            if isinstance(d, dict):
                if proc.returncode != 0:
                    d.setdefault("error", f"phase exited rc={proc.returncode}")
                return d
        except json.JSONDecodeError:
            continue
    return {
        "error": f"no JSON from phase (rc={proc.returncode})",
        "stderr_tail": proc.stderr[-800:],
    }


def main() -> int:
    if "--phase" in sys.argv:
        return _child_main(sys.argv[sys.argv.index("--phase") + 1])

    t_start = time.time()
    payload: dict = {}
    failed: list[str] = []
    for phase, (_, budget) in _PHASES.items():
        if phase != "headline" and time.time() - t_start > DEADLINE_S:
            payload[f"{phase}_error"] = "skipped: parent deadline reached"
            failed.append(phase)
            continue
        out = _run_phase_subprocess(phase, budget)
        # progress breadcrumb on stderr: if the wrapper kills this parent
        # before the final stdout line, the per-phase results still exist
        # in the captured log
        print(f"[bench] {phase}: {json.dumps(out)}",
              file=sys.stderr, flush=True)
        err = out.pop("error", None)
        if err is not None:
            failed.append(phase)
        if phase == "headline":
            if err is not None:
                # headline child failed: keep the driver contract
                # (metric/value/unit always present and parseable)
                payload.update(
                    {
                        "metric": f"GBM trees/sec ({N_ROWS // 1_000_000}M rows x {N_COLS} cols, depth {DEPTH})",
                        "value": 0.0,
                        "unit": "trees/sec/chip",
                        "vs_baseline": 0.0,
                        "error": err,
                        "traceback": out.get("traceback", ""),
                    }
                )
            else:
                payload.update(out)
        else:
            out.pop("traceback", None)
            if err is not None:
                payload[f"{phase}_error"] = err
            if out:
                payload[phase] = out
    # tracked per-round summary (ISSUE 8 / ROADMAP item 5): lift the
    # GLM/DL/AutoML phase numbers to headline keys so the round-over-round
    # artifact diff shows the whole-program gains at a glance
    # (tools/latest_bench_ok.py sanity-checks them when present)
    for phase, k in (("glm_1m", "glm_iters_per_s"),
                     ("dl_100k", "dl_epoch_s"),
                     ("automl_50k", "automl_total_s")):
        ph = payload.get(phase)
        if isinstance(ph, dict) and ph.get(k) is not None:
            payload[k] = ph[k]
    if failed:
        payload["failed_phases"] = failed
    _emit(payload)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
