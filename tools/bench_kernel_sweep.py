#!/usr/bin/env python
"""Pallas histogram kernel tile sweep on the REAL TPU: measures hist time per (ROW_TILE, COL_TILE, n_bins, n_nodes) so the
next kernel iteration picks tiles from data, not guesses.

A grid step is bound by its instruction schedule (PERF.md §3: how to read it
from the compiler with no chip): below 16 nodes by pushing the 0/1 bin
indicator (∝ ROWS·CT·Bpad) into the MXU as weights, one push a cycle, and
its two compares a push on the VPU; from a full node tile on by the MXU's
result pops (M = 2·S·nt rows: the 2-term split is stacked on M). The lane
shuffles that bound it until ISSUE 31 are gone — bin count and tile sizes
are the levers.

    python tools/bench_kernel_sweep.py        # prints one JSON line per cfg
    python tools/bench_kernel_sweep.py --split-ab [--rows N]
        # sharded-vs-replicated split pipeline A/B (H2O3_TPU_SPLIT_SHARD):
        # one JSON line per mode with fused_tree_s + psum_bytes_per_tree,
        # then a {"split_ab": ...} summary line. Runs on any backend (the
        # 8-device CPU mesh is the CI proxy; queue on TPU for real numbers).
    python tools/bench_kernel_sweep.py --fallback-ab [--rows N]
        # fallback-matrix closure A/B (ISSUE 15): multinomial GLM and
        # dropout DL each run the NOW-fused lane vs the forced
        # fallback it replaces (kill-switch knobs), with parity pins and
        # dispatch/wall ratios in a {"fallback_ab": ...} summary line.

    python tools/bench_kernel_sweep.py --wave2-ab [--rows N]
        # tree-kernel wave-2 A/B (ISSUE 16): GOSS row sampling, EFB column
        # bundling, the u8-code cache, int16 hist lanes and lossguide
        # growth each run knob-on vs knob-off with parity/quality pins
        # (bit-identical controls, AUC/RMSE envelopes, shrink ratios),
        # then a {"wave2_ab": ...} summary line.

    python tools/bench_kernel_sweep.py --munge-ab [--rows N]
        # compiled-munging-plane A/B (H2O3_TPU_MUNGE_FUSE, ISSUE 20):
        # group-by / join / sort each run the fused mesh-sharded lane vs
        # the eager seed path on the SAME data, plus the 10-op expression
        # chain's dispatch-count pin, then a {"munge_ab": ...} summary
        # with the acceptance pins (fused wall <= 0.5x eager for group-by
        # and join, sort no worse, chain dispatches cut >= 5x, joins /
        # sort / chain bit-equal, group-by counts exact + sums allclose).

    python tools/bench_kernel_sweep.py --oocore-ab [--rows N]
        # streamed-vs-resident out-of-core A/B (ISSUE 11): forces an HBM
        # window of 1/10th the frame's training lanes, measures wall time,
        # AUC and the peak frame device bytes per mode (+ a COMPRESS=0
        # control), then an {"oocore_ab": ...} summary with the acceptance
        # pins (peak bounded by the window, rows >= 10x window).

The tile sweep varies ROW/COL/NODE tiles through the H2O3_TPU_PALLAS_TILES
knob (a static compile key — every setting gets its own executable), so no
module monkeypatching and no jit-cache clearing is needed.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def split_ab(rows: int = 10_000, cols: int = 28, depth: int = 6,
             trees: int = 4) -> None:
    """A/B the column-sharded split pipeline against the replicated path on
    the SAME mesh and data: per-tree fused seconds (median of 3 timed chunk
    dispatches after a compile warmup) and the per-tree collective byte
    tally, per mode. The env toggle works in-process because the tree
    program caches key on the mode."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.models.tree import shared_tree as st
    from h2o3_tpu.parallel.mesh import get_mesh, pad_to_shards, shard_rows
    from h2o3_tpu.utils import metrics as mx

    n = pad_to_shards(rows)
    rng = np.random.default_rng(0)
    bins = shard_rows(jnp.asarray(
        rng.integers(0, 128, (n, cols)).astype(np.uint8)))
    y = shard_rows(jnp.asarray(rng.normal(size=n).astype(np.float32)))
    w = shard_rows(jnp.ones(n, jnp.float32))

    def grad_fn(F, y_, w_):  # gaussian residuals, unit hessian
        return y_ - F, jnp.ones_like(F)

    results = {}
    for mode in ("1", "0"):
        os.environ["H2O3_TPU_SPLIT_SHARD"] = mode
        times = []
        h0 = mx.counter_value(
            "tree_collective_bytes_total", phase="hist_reduce")
        w0 = mx.counter_value(
            "tree_collective_bytes_total", phase="winner_gather")
        for rep in range(4):  # rep 0 = compile warmup
            preds = shard_rows(jnp.zeros(n, jnp.float32))
            varimp = jnp.zeros(cols, jnp.float32)
            t0 = time.perf_counter()
            out = st.build_trees_scanned(
                bins, w, y, preds, varimp, jax.random.PRNGKey(7), trees,
                grad_fn=grad_fn, grad_key="gaussian-ab", sample_rate=1.0,
                n_bins=128, is_cat_cols=np.zeros(cols, bool),
                max_depth=depth, min_rows=10.0, min_split_improvement=1e-5,
                learn_rates=np.full(trees, 0.1, np.float32),
                max_abs_leaf=float("inf"), col_sample_rate=1.0,
                col_sample_rate_per_tree=1.0,
            )
            jax.block_until_ready(out[0])
            if rep:
                times.append(time.perf_counter() - t0)
        built = 4 * trees
        rec = {
            "phase": "split_ab",
            "mode": "sharded" if mode == "1" else "replicated",
            "n_devices": get_mesh().devices.size,
            "rows": n, "cols": cols, "depth": depth, "trees": trees,
            "fused_tree_s": round(sorted(times)[len(times) // 2] / trees, 4),
            "psum_bytes_per_tree": round((
                mx.counter_value(
                    "tree_collective_bytes_total", phase="hist_reduce")
                + mx.counter_value(
                    "tree_collective_bytes_total", phase="winner_gather")
                - h0 - w0) / built, 1),
        }
        print(json.dumps(rec), flush=True)
        results[rec["mode"]] = rec
    os.environ.pop("H2O3_TPU_SPLIT_SHARD", None)
    if len(results) == 2 and results["sharded"]["psum_bytes_per_tree"] > 0:
        print(json.dumps({"split_ab": {
            "bytes_ratio_replicated_over_sharded": round(
                results["replicated"]["psum_bytes_per_tree"]
                / results["sharded"]["psum_bytes_per_tree"], 2),
            "time_ratio_replicated_over_sharded": round(
                results["replicated"]["fused_tree_s"]
                / max(results["sharded"]["fused_tree_s"], 1e-9), 3),
        }}), flush=True)


def _ab_frame(rows: int, cols: int, seed: int = 0, classify: bool = True):
    """Synthetic numeric frame + binary/real response for the GLM/DL A/Bs."""
    import pandas as pd

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    eta = X[:, 0] - 0.5 * X[:, 1] + 0.25 * X[:, 2]
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(cols)])
    if classify:
        y = rng.random(rows) < 1.0 / (1.0 + np.exp(-eta))
        df["label"] = np.where(y, "s", "b")
    else:
        df["label"] = (eta + 0.3 * rng.normal(size=rows)).astype(np.float32)
    from h2o3_tpu.frame.frame import Frame

    return Frame.from_pandas(df)


def _hist_sum_count(name: str):
    """(sum, count) of an unlabeled registry histogram."""
    from h2o3_tpu.utils import metrics as mx

    for labels, _cum, s, n in mx.REGISTRY.histogram(name).samples():
        if not labels:
            return float(s), int(n)
    return 0.0, 0


def glm_ab(rows: int = 8_000, cols: int = 12) -> None:
    """Fused-vs-unfused whole-program GLM IRLS A/B (H2O3_TPU_GLM_FUSE,
    ISSUE 8) on the SAME mesh and frame: hot-loop iterations/sec from the
    glm_irls_iteration_seconds histogram (whole-train wall time is
    dominated by transform/scoring overhead both lanes share), host
    dispatches per model (O(iters/K) fused vs O(iters) unfused) and the
    Gram collective byte tally, per mode, then a {"glm_ab": ...} summary.
    The env toggle works in-process because the fused chunk programs key
    on the knob-derived lanes and the unfused path never touches them."""
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.parallel.mesh import get_mesh
    from h2o3_tpu.utils import metrics as mx

    fr = _ab_frame(rows, cols)
    # epsilons pinned to zero-ish so BOTH lanes run the full iteration
    # budget: the A/B measures steady-state iterations/sec of the hot
    # loop, not time-to-convergence on an easy synthetic problem
    kw = dict(family="binomial", lambda_=1e-4, max_iterations=20, seed=1,
              beta_epsilon=0.0, objective_epsilon=0.0)
    results = {}
    for mode in ("fused", "unfused"):
        if mode == "unfused":
            os.environ["H2O3_TPU_GLM_FUSE"] = "0"
        else:
            os.environ.pop("H2O3_TPU_GLM_FUSE", None)
        GLM(**kw).train(y="label", training_frame=fr)  # compile warmup
        g0 = sum(mx.counter_value("tree_collective_bytes_total", phase=ph)
                 for ph in ("gram_reduce", "gram_gather"))
        d0 = mx.counter_value("glm_dispatches_total")
        s0, c0 = _hist_sum_count("glm_irls_iteration_seconds")
        n_rep = 3
        times = []
        for _ in range(n_rep):
            t0 = time.perf_counter()
            m = GLM(**kw).train(y="label", training_frame=fr)
            times.append(time.perf_counter() - t0)
        s1, c1 = _hist_sum_count("glm_irls_iteration_seconds")
        iters = c1 - c0
        disp = int(mx.counter_value("glm_dispatches_total") - d0)
        gbytes = sum(
            mx.counter_value("tree_collective_bytes_total", phase=ph)
            for ph in ("gram_reduce", "gram_gather")) - g0
        med = sorted(times)[len(times) // 2]
        rec = {
            "phase": "glm_ab", "mode": mode,
            "n_devices": get_mesh().devices.size,
            "rows": rows, "cols": cols,
            "train_s": round(med, 4),
            "iters_per_s": round(iters / max(s1 - s0, 1e-9), 3),
            "iteration_ms": round((s1 - s0) / max(iters, 1) * 1000, 3),
            "dispatches_per_model": round(disp / n_rep, 2),
            "gram_bytes_per_model": round(gbytes / n_rep, 1),
            "auc": round(float(m.training_metrics.auc), 4),
        }
        print(json.dumps(rec), flush=True)
        results[mode] = rec
    os.environ.pop("H2O3_TPU_GLM_FUSE", None)
    if len(results) == 2 and results["unfused"]["iters_per_s"] > 0:
        print(json.dumps({"glm_ab": {
            "iters_per_s_ratio_fused_over_unfused": round(
                results["fused"]["iters_per_s"]
                / results["unfused"]["iters_per_s"], 3),
            "dispatch_ratio_unfused_over_fused": round(
                results["unfused"]["dispatches_per_model"]
                / max(results["fused"]["dispatches_per_model"], 1e-9), 2),
            "auc_delta": round(
                abs(results["fused"]["auc"] - results["unfused"]["auc"]), 5),
        }}), flush=True)


def dl_ab(rows: int = 20_000, cols: int = 16) -> None:
    """Chunked-vs-per-epoch DeepLearning A/B (H2O3_TPU_DL_EPOCH_CHUNK +
    H2O3_TPU_DL_GRAD_SHARD, ISSUE 8) on the SAME mesh and frame: measured
    epochs/sec, host dispatches per model and the gradient collective byte
    tally, per mode, then a {"dl_ab": ...} summary. The control pins
    chunk=1 + shard=0 (the pre-fusion lane)."""
    from h2o3_tpu.models.deeplearning import DeepLearning
    from h2o3_tpu.parallel.mesh import get_mesh
    from h2o3_tpu.utils import metrics as mx

    fr = _ab_frame(rows, cols)
    kw = dict(hidden=[64, 64], epochs=4, mini_batch_size=256, seed=3)
    results = {}
    for mode in ("chunked", "per_epoch"):
        if mode == "per_epoch":
            os.environ["H2O3_TPU_DL_EPOCH_CHUNK"] = "1"
            os.environ["H2O3_TPU_DL_GRAD_SHARD"] = "0"
        else:
            os.environ.pop("H2O3_TPU_DL_EPOCH_CHUNK", None)
            os.environ.pop("H2O3_TPU_DL_GRAD_SHARD", None)
        DeepLearning(**kw).train(y="label", training_frame=fr)  # warmup
        d0 = mx.counter_value("dl_dispatches_total")
        g0 = sum(mx.counter_value("tree_collective_bytes_total", phase=ph)
                 for ph in ("dl_grad_reduce", "dl_param_gather"))
        s0, c0 = _hist_sum_count("dl_epoch_seconds")
        n_rep = 3
        times = []
        for _ in range(n_rep):
            t0 = time.perf_counter()
            m = DeepLearning(**kw).train(y="label", training_frame=fr)
            times.append(time.perf_counter() - t0)
        s1, c1 = _hist_sum_count("dl_epoch_seconds")
        epochs = c1 - c0
        disp = int(mx.counter_value("dl_dispatches_total") - d0)
        gbytes = sum(
            mx.counter_value("tree_collective_bytes_total", phase=ph)
            for ph in ("dl_grad_reduce", "dl_param_gather")) - g0
        med = sorted(times)[len(times) // 2]
        rec = {
            "phase": "dl_ab", "mode": mode,
            "n_devices": get_mesh().devices.size,
            "rows": rows, "cols": cols,
            "train_s": round(med, 4),
            "epochs_per_s": round(epochs / max(s1 - s0, 1e-9), 3),
            "epoch_s": round((s1 - s0) / max(epochs, 1), 4),
            "dispatches_per_model": round(disp / n_rep, 2),
            "grad_bytes_per_model": round(gbytes / n_rep, 1),
            "auc": round(float(m.training_metrics.auc), 4),
        }
        print(json.dumps(rec), flush=True)
        results[mode] = rec
    for k in ("H2O3_TPU_DL_EPOCH_CHUNK", "H2O3_TPU_DL_GRAD_SHARD"):
        os.environ.pop(k, None)
    if len(results) == 2 and results["per_epoch"]["epochs_per_s"] > 0:
        print(json.dumps({"dl_ab": {
            "epochs_per_s_ratio_chunked_over_per_epoch": round(
                results["chunked"]["epochs_per_s"]
                / results["per_epoch"]["epochs_per_s"], 3),
            "dispatch_ratio_per_epoch_over_chunked": round(
                results["per_epoch"]["dispatches_per_model"]
                / max(results["chunked"]["dispatches_per_model"], 1e-9), 2),
            "auc_delta": round(
                abs(results["chunked"]["auc"] - results["per_epoch"]["auc"]),
                5),
        }}), flush=True)


def quant_ab(rows: int = 16_000, cols: int = 12) -> None:
    """Quantized-collective-lane A/B (H2O3_TPU_COLLECTIVE_QUANT, ISSUE 9)
    on the SAME mesh and frames: per mode (quant / exact), a GBM train
    (modeled per-phase collective bytes WITH the {lane} split, train wall
    seconds, AUC) plus a GLM train (Gram bytes, coefficient vector) plus
    MEASURED reduce seconds at the bench histogram/Gram shapes through the
    active lane — then a {"quant_ab": ...} summary with the byte ratios and
    the accuracy deltas the acceptance pins (hist_reduce >= 2x fewer
    modeled bytes, GBM AUC delta <= 1e-3, GLM coefficient parity). The env
    toggle works in-process because every program cache keys on the lane
    through mesh_key(). On the CPU proxy the quantized lane's measured
    seconds are usually SLOWER (the int8 encode + all_to_all emulation of a
    fused quantized collective is extra host-side work); the wire-byte
    model is the claim, and the real-TPU/DCN window decides the wall-clock
    question — which is why the measured seconds ride along."""
    import time as _time

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as Spec

    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.models.tree import GBM
    from h2o3_tpu.ops import collectives
    from h2o3_tpu.parallel.mesh import (
        col_axis_name, get_mesh, pad_cols_to_shards, shard_map)
    from h2o3_tpu.utils import metrics as mx

    mesh = get_mesh()
    n_dev = mesh.devices.size
    fr = _ab_frame(rows, cols)
    phases = ("hist_reduce", "winner_gather", "gram_reduce", "gram_gather")

    def measured_reduce_s(iters=10):
        hist = jnp.ones((pad_cols_to_shards(28), 64 * 128, 3), jnp.float32)
        fn = jax.jit(shard_map(
            lambda v: collectives.psum_scatter(
                v, n_dev=n_dev, lane_axis=-1),
            mesh=mesh, in_specs=(Spec(),),
            out_specs=Spec(col_axis_name(mesh)),
            check_vma=False))
        out = fn(hist)
        jax.block_until_ready(out)
        t0 = _time.perf_counter()
        for _ in range(iters):
            out = fn(hist)
        jax.block_until_ready(out)
        return (_time.perf_counter() - t0) / iters

    results = {}
    for mode in ("quant", "exact"):
        os.environ["H2O3_TPU_COLLECTIVE_QUANT"] = (
            "1" if mode == "quant" else "0")
        b0 = {(ph, ln): mx.counter_value(
            "tree_collective_bytes_total", phase=ph, **(
                {"lane": ln} if ln else {}))
            for ph in phases for ln in ("", "quant", "exact")}

        GBM(ntrees=10, max_depth=5, seed=7).train(
            y="label", training_frame=fr)  # compile warmup
        t0 = _time.perf_counter()
        m = GBM(ntrees=10, max_depth=5, seed=7).train(
            y="label", training_frame=fr)
        gbm_s = _time.perf_counter() - t0
        glm = GLM(family="binomial", lambda_=1e-4, max_iterations=20,
                  seed=1).train(y="label", training_frame=fr)

        db = {}
        for ph in phases:
            for ln in ("", "quant", "exact"):
                v = mx.counter_value(
                    "tree_collective_bytes_total", phase=ph, **(
                        {"lane": ln} if ln else {})) - b0[(ph, ln)]
                if v:
                    db[ph if not ln else f"{ph}{{lane={ln}}}"] = round(v, 1)
        rec = {
            "phase": "quant_ab", "mode": mode, "n_devices": n_dev,
            "rows": rows, "cols": cols,
            "quant_block": collectives.quant_block(),
            "gbm_train_s": round(gbm_s, 4),
            "gbm_auc": round(float(m.training_metrics.auc), 5),
            "glm_coef": {k: round(v, 8) for k, v in glm.coef.items()},
            "glm_auc": round(float(glm.training_metrics.auc), 5),
            "collective_bytes": db,
            "measured_hist_reduce_s": round(measured_reduce_s(), 6),
        }
        print(json.dumps(rec), flush=True)
        results[mode] = rec
    os.environ.pop("H2O3_TPU_COLLECTIVE_QUANT", None)
    if len(results) == 2:
        q, e = results["quant"], results["exact"]
        hq = q["collective_bytes"].get("hist_reduce", 0)
        he = e["collective_bytes"].get("hist_reduce", 0)
        coef_delta = max(
            abs(q["glm_coef"][k] - e["glm_coef"][k]) for k in e["glm_coef"])
        print(json.dumps({"quant_ab": {
            "hist_bytes_ratio_exact_over_quant": round(he / max(hq, 1), 2),
            "gram_bytes_ratio_exact_over_quant": round(
                e["collective_bytes"].get("gram_reduce", 0)
                / max(q["collective_bytes"].get("gram_reduce", 0), 1), 2),
            "gbm_auc_delta": round(abs(q["gbm_auc"] - e["gbm_auc"]), 5),
            "glm_coef_max_delta": round(coef_delta, 8),
            "time_ratio_exact_over_quant": round(
                e["gbm_train_s"] / max(q["gbm_train_s"], 1e-9), 3),
            "measured_hist_reduce_s": {
                "quant": q["measured_hist_reduce_s"],
                "exact": e["measured_hist_reduce_s"],
            },
        }}), flush=True)


def oocore_ab(rows: int = 120_000, cols: int = 12) -> None:
    """Streamed-vs-resident out-of-core A/B (H2O3_TPU_HBM_WINDOW_BYTES /
    H2O3_TPU_FRAME_COMPRESS, ISSUE 11) on the SAME mesh and data: the
    streamed mode forces an HBM window of 1/10th of the frame's training
    lanes (rows >= 10x window — the acceptance geometry), the resident
    mode runs today's whole-frame path, and a COMPRESS=0 control proves
    the kill switch routes back to resident. Per mode: GBM train wall
    seconds, AUC, and the peak frame device bytes (streamed = the
    ChunkStore's measured peak, resident = the frame lanes' modeled
    residency), then an {"oocore_ab": ...} summary carrying the acceptance
    pins (peak bounded by the window, rows_over_window >= 10, AUC delta)."""
    import time as _time

    from h2o3_tpu.frame import chunkstore as cs
    from h2o3_tpu.models.tree import GBM
    from h2o3_tpu.parallel.mesh import get_mesh, pad_to_shards
    from h2o3_tpu.utils import metrics as mx

    bytes_per_row = cols + 28  # bins u8 + six f32 lanes + nid i32
    npad = pad_to_shards(rows)
    window = int(npad * bytes_per_row // 10)
    kw = dict(ntrees=10, max_depth=5, seed=7, score_tree_interval=5)
    results = {}
    for mode in ("resident", "streamed", "compress0"):
        os.environ.pop("H2O3_TPU_HBM_WINDOW_BYTES", None)
        os.environ.pop("H2O3_TPU_FRAME_COMPRESS", None)
        if mode == "streamed":
            os.environ["H2O3_TPU_HBM_WINDOW_BYTES"] = str(window)
        elif mode == "compress0":
            os.environ["H2O3_TPU_HBM_WINDOW_BYTES"] = str(window)
            os.environ["H2O3_TPU_FRAME_COMPRESS"] = "0"
        cs.LAST_STORE_STATS.clear()
        e0 = mx.counter_value("frame_chunk_evictions_total")
        fr = _ab_frame(rows, cols)
        GBM(**kw).train(y="label", training_frame=fr)  # compile warmup
        t0 = _time.perf_counter()
        m = GBM(**kw).train(y="label", training_frame=fr)
        dt = _time.perf_counter() - t0
        # the window stats now come from the REGISTRY (ChunkStore.close
        # publishes frame_window_peak_bytes there — same numbers
        # /3/Metrics serves); the dict stays as the geometry alias
        stats = dict(cs.LAST_STORE_STATS)
        streamed = bool(stats.get("n_blocks", 0) > 1)
        peak = (mx.counter_value("frame_window_peak_bytes")
                if streamed else npad * bytes_per_row)
        rec = {
            "phase": "oocore_ab", "mode": mode,
            "n_devices": get_mesh().devices.size,
            "rows": rows, "cols": cols,
            "window_bytes": window if mode != "resident" else 0,
            "streamed": streamed,
            "train_s": round(dt, 4),
            "auc": round(float(m.training_metrics.auc), 5),
            "peak_frame_device_bytes": int(peak),
            "n_blocks": stats.get("n_blocks", 1),
            "block_rows": stats.get("block_rows", npad),
            "evictions": int(
                mx.counter_value("frame_chunk_evictions_total") - e0),
            "prefetch_overlap_s": round(mx.counter_value(
                "frame_prefetch_overlap_seconds"), 4),
        }
        print(json.dumps(rec), flush=True)
        results[mode] = rec
    for k in ("H2O3_TPU_HBM_WINDOW_BYTES", "H2O3_TPU_FRAME_COMPRESS"):
        os.environ.pop(k, None)
    if len(results) == 3:
        r, s, c0 = (results[m] for m in ("resident", "streamed", "compress0"))
        print(json.dumps({"oocore_ab": {
            "rows_over_window": round(
                npad * bytes_per_row / max(window, 1), 2),
            "streamed_engaged": s["streamed"],
            "compress0_stayed_resident": not c0["streamed"],
            "peak_within_window": s["peak_frame_device_bytes"] <= window,
            "peak_bytes_ratio_resident_over_streamed": round(
                r["peak_frame_device_bytes"]
                / max(s["peak_frame_device_bytes"], 1), 2),
            "time_ratio_streamed_over_resident": round(
                s["train_s"] / max(r["train_s"], 1e-9), 3),
            "auc_delta": round(abs(s["auc"] - r["auc"]), 5),
            "compress0_auc_delta": round(abs(c0["auc"] - r["auc"]), 5),
        }}), flush=True)


def fallback_ab(rows: int = 8_000, cols: int = 12) -> None:
    """Fallback-matrix closure A/B (ISSUE 15): for each production shape
    that used to hit a slow lane — multinomial GLM, dropout DL — run the
    NOW-fused lane against the forced fallback it replaces (the respective
    kill-switch knob), on the SAME mesh and data. Per mode: wall seconds +
    host dispatches; then a {"fallback_ab": ...} summary with the parity
    pins (GLM coef delta <= 2e-3, DL preds <= 1e-4 vs the =ctl same-masks
    control) and the dispatch/wall ratios. (Monotone GBM has one lane, the
    per-level loop: nothing to compare.)"""
    import jax

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.deeplearning import DeepLearning
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.parallel.mesh import get_mesh
    from h2o3_tpu.utils import metrics as mx

    n_dev = int(get_mesh().devices.size)
    summary = {}

    def timed(fn, counter):
        fn()  # compile warmup
        d0 = mx.counter_value(counter)
        t0 = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
        return out, dt, int(mx.counter_value(counter) - d0)

    rng = np.random.default_rng(0)
    import pandas as pd

    # ---- (a) multinomial GLM: fused class-scan chunk vs the host f64
    # cycling loop (H2O3_TPU_GLM_FUSE=0) ----
    K = 3
    X = rng.normal(size=(rows, 5)).astype(np.float32)
    eta = np.stack([X[:, 0], -X[:, 1], 0.5 * X[:, 2]], 1)
    pmat = np.exp(eta)
    pmat /= pmat.sum(1, keepdims=True)
    yk = np.array([rng.choice(K, p=pr_) for pr_ in pmat])
    dfg = pd.DataFrame(X, columns=[f"g{i}" for i in range(5)])
    dfg["label"] = np.array(["a", "b", "c"])[yk]
    fr_g = Frame.from_pandas(dfg)
    kw_g = dict(family="multinomial", max_iterations=10, seed=1,
                objective_epsilon=0.0)
    betas = {}
    for mode, fuse in (("fused", ""), ("fallback", "0")):
        if fuse:
            os.environ["H2O3_TPU_GLM_FUSE"] = fuse
        else:
            os.environ.pop("H2O3_TPU_GLM_FUSE", None)

        def run_g():
            m = GLM(**kw_g).train(y="label", training_frame=fr_g)
            return np.asarray(m.output["beta_multinomial_std"])

        B, dt, disp = timed(run_g, "glm_dispatches_total")
        betas[mode] = B
        rec = {"phase": "fallback_ab", "case": "multinomial_glm",
               "mode": mode, "n_devices": n_dev, "rows": rows,
               "classes": K, "train_s": round(dt, 4), "dispatches": disp}
        print(json.dumps(rec), flush=True)
        summary[f"glm_{mode}"] = rec
    os.environ.pop("H2O3_TPU_GLM_FUSE", None)
    glm_delta = float(np.max(np.abs(betas["fused"] - betas["fallback"])))

    # ---- (b) dropout DL: sharded-grad lane vs the =ctl same-masks
    # replicated control (the parity pin) AND the =0 replicated lane (the
    # wall-clock fallback it replaces) ----
    fr_d = _ab_frame(rows, cols)
    kw_d = dict(hidden=[64], epochs=4, mini_batch_size=256, seed=3,
                activation="RectifierWithDropout",
                hidden_dropout_ratios=[0.3], input_dropout_ratio=0.1)
    dpreds = {}
    for mode, knob in (("fused", None), ("ctl", "ctl"), ("fallback", "0")):
        if knob is None:
            os.environ.pop("H2O3_TPU_DL_GRAD_SHARD", None)
        else:
            os.environ["H2O3_TPU_DL_GRAD_SHARD"] = knob

        def run_d():
            m = DeepLearning(**kw_d).train(y="label", training_frame=fr_d)
            pr = m.predict(fr_d)
            return pr.vec(pr.names[-1]).to_numpy()

        p, dt, disp = timed(run_d, "dl_dispatches_total")
        dpreds[mode] = p
        rec = {"phase": "fallback_ab", "case": "dropout_dl", "mode": mode,
               "n_devices": n_dev, "rows": rows,
               "train_s": round(dt, 4), "dispatches": disp}
        print(json.dumps(rec), flush=True)
        summary[f"dl_{mode}"] = rec
    os.environ.pop("H2O3_TPU_DL_GRAD_SHARD", None)
    dl_ctl_delta = float(np.max(np.abs(dpreds["fused"] - dpreds["ctl"])))

    print(json.dumps({"fallback_ab": {
        # parity pins
        "glm_coef_max_delta": round(glm_delta, 7),
        "dl_ctl_pred_max_delta": round(dl_ctl_delta, 7),
        # dispatch contracts (the raw-speed coverage claim)
        "glm_dispatch_ratio_fallback_over_fused": round(
            summary["glm_fallback"]["dispatches"]
            / max(summary["glm_fused"]["dispatches"], 1), 2),
        # wall ratios (fused must be no worse than the lane it replaces)
        "glm_time_ratio_fused_over_fallback": round(
            summary["glm_fused"]["train_s"]
            / max(summary["glm_fallback"]["train_s"], 1e-9), 3),
        "dl_time_ratio_fused_over_fallback": round(
            summary["dl_fused"]["train_s"]
            / max(summary["dl_fallback"]["train_s"], 1e-9), 3),
    }}), flush=True)


def mesh2d_ab(rows: int = 10_000, cols: int = 28, depth: int = 6,
              trees: int = 4) -> None:
    """1-D vs 2-D mesh A/B (H2O3_TPU_MESH_ROWS, ISSUE 14) on the SAME
    device set and data: the legacy 1-D rows mesh against the 2x4 (and
    4x2) rows×cols pod meshes — per mode, fused tree seconds plus the
    collective bytes BY PHASE (hist_reduce including the 2-D stage-1 exact
    rows psum, winner_gather shrinking to the cols width), then a
    {"mesh2d_ab": ...} summary with the acceptance pins (per-phase bytes
    recorded on every shape; 2-D fused_tree_s no worse than ~1-D on the
    proxy). On the CPU proxy all 8 'devices' are one host's threads — the
    placement claim (exact stage intra-host, quantized stage cross) is the
    queued v5e-16 pod bracket's number; the proxy pins correctness and the
    no-regression bound."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.models.tree import shared_tree as st
    from h2o3_tpu.parallel import mesh as pm
    from h2o3_tpu.utils import metrics as mx

    def grad_fn(F, y_, w_):  # gaussian residuals, unit hessian
        return y_ - F, jnp.ones_like(F)

    phases = ("hist_reduce", "winner_gather")
    results = {}
    for mode, shape in (("1d", None), ("2x4", (2, 4)), ("4x2", (4, 2))):
        pm.set_mesh(None if shape is None else pm.make_mesh_2d(*shape))
        n = pm.pad_to_shards(rows)
        rng = np.random.default_rng(0)
        bins = pm.shard_rows(jnp.asarray(
            rng.integers(0, 128, (n, cols)).astype(np.uint8)))
        y = pm.shard_rows(jnp.asarray(rng.normal(size=n).astype(np.float32)))
        w = pm.shard_rows(jnp.ones(n, jnp.float32))
        times = []
        b0 = {ph: mx.counter_value("tree_collective_bytes_total", phase=ph)
              for ph in phases}
        for rep in range(4):  # rep 0 = compile warmup
            preds = pm.shard_rows(jnp.zeros(n, jnp.float32))
            varimp = jnp.zeros(cols, jnp.float32)
            t0 = time.perf_counter()
            out = st.build_trees_scanned(
                bins, w, y, preds, varimp, jax.random.PRNGKey(7), trees,
                grad_fn=grad_fn, grad_key="gaussian-m2d", sample_rate=1.0,
                n_bins=128, is_cat_cols=np.zeros(cols, bool),
                max_depth=depth, min_rows=10.0, min_split_improvement=1e-5,
                learn_rates=np.full(trees, 0.1, np.float32),
                max_abs_leaf=float("inf"), col_sample_rate=1.0,
                col_sample_rate_per_tree=1.0,
            )
            jax.block_until_ready(out[0])
            if rep:
                times.append(time.perf_counter() - t0)
        built = 4 * trees
        by_phase = {
            ph: round((mx.counter_value(
                "tree_collective_bytes_total", phase=ph) - b0[ph]) / built, 1)
            for ph in phases
        }
        rec = {
            "phase": "mesh2d_ab", "mode": mode,
            "mesh": dict(pm.get_mesh().shape),
            "n_devices": int(pm.get_mesh().devices.size),
            "rows": n, "cols": cols, "depth": depth, "trees": trees,
            "fused_tree_s": round(sorted(times)[len(times) // 2] / trees, 4),
            "psum_bytes_by_phase": by_phase,
            "psum_bytes_per_tree": round(sum(by_phase.values()), 1),
        }
        print(json.dumps(rec), flush=True)
        results[mode] = rec
    pm.set_mesh(None)
    if len(results) == 3:
        r1, r2 = results["1d"], results["2x4"]
        print(json.dumps({"mesh2d_ab": {
            "time_ratio_2x4_over_1d": round(
                r2["fused_tree_s"] / max(r1["fused_tree_s"], 1e-9), 3),
            "time_ratio_4x2_over_1d": round(
                results["4x2"]["fused_tree_s"]
                / max(r1["fused_tree_s"], 1e-9), 3),
            "winner_gather_ratio_1d_over_2x4": round(
                r1["psum_bytes_by_phase"]["winner_gather"]
                / max(r2["psum_bytes_by_phase"]["winner_gather"], 1), 2),
            "phases_recorded_all_modes": all(
                all(v > 0 for v in r["psum_bytes_by_phase"].values())
                for r in results.values()),
        }}), flush=True)


def wave2_ab(rows: int = 8_000) -> None:
    """Tree kernel wave-2 A/B (ISSUE 16): GOSS, EFB, u8-code-native frames,
    int16 hist lanes and lossguide growth, each against the baseline path
    on the SAME data, with the forced-off knob controls pinned bit-for-bit.
    One JSON line per case, then a {"wave2_ab": ...} summary carrying the
    acceptance pins: GOSS row-stats ratio >= 2x at AUC delta <= 1e-3, EFB
    C shrink >= 1.5x with bit-equal splits, u8-native rebin traffic cut
    >= 2x across repeated builds, every knob=0 control bit-for-bit."""
    import pandas as pd

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.tree import GBM
    from h2o3_tpu.utils import metrics as mx

    rng = np.random.default_rng(0)
    summary = {}

    def envs(**kv):
        for k, v in kv.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = str(v)

    def pred(m, fr, col):
        pr = m.predict(fr)
        return pr.vec(col if col in pr.names else pr.names[-1]).to_numpy()

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0

    # ---- (a) GOSS: (a=0.2, b=0.1) vs full rows, binomial AUC pin ----
    from sklearn.metrics import roc_auc_score

    # 4x the base rows, a strong signal and modest capacity: the AUC-delta
    # pin wants the CONVERGED regime (both models capture the same signal),
    # not the overfit regime where the sampled fit drifts by more than the
    # pin just from which rows each tree saw
    rows_g = rows * 4
    X = rng.normal(size=(rows_g, 8)).astype(np.float32)
    eta = 3.0 * (1.5 * X[:, 0] - X[:, 1] + 0.5 * X[:, 2] * X[:, 3])
    yb = rng.random(rows_g) < 1 / (1 + np.exp(-eta))
    dfc = pd.DataFrame(X, columns=[f"x{i}" for i in range(8)])
    dfc["label"] = np.where(yb, "a", "b")
    fr_c = Frame.from_pandas(dfc)
    trees = 60
    kw_c = dict(ntrees=trees, max_depth=3, seed=7, distribution="bernoulli")
    aucs, gpreds = {}, {}
    for mode, knob in (("baseline", None), ("goss", "0.2,0.1"),
                       ("goss_off", "")):
        envs(H2O3_TPU_TREE_GOSS=knob)
        r0 = mx.counter_value("tree_rows_sampled_total")
        m, dt = timed(lambda: GBM(**kw_c).train(
            y="label", training_frame=fr_c))
        p = pred(m, fr_c, "a")
        gpreds[mode] = p
        aucs[mode] = roc_auc_score(yb, p)
        rec = {"phase": "wave2_ab", "case": "goss", "mode": mode,
               "rows": rows_g, "trees": trees, "train_s": round(dt, 4),
               "auc": round(aucs[mode], 6),
               "rows_sampled": mx.counter_value(
                   "tree_rows_sampled_total") - r0}
        print(json.dumps(rec), flush=True)
        summary[f"goss_{mode}"] = rec
    envs(H2O3_TPU_TREE_GOSS=None)
    # modeled per-level row-stat work: kept rows vs all rows
    kept_frac = summary["goss_goss"]["rows_sampled"] / (fr_c.npad * trees)
    summary["goss_row_stats_ratio"] = round(1.0 / max(kept_frac, 1e-9), 2)
    summary["goss_auc_delta"] = round(
        abs(aucs["baseline"] - aucs["goss"]), 6)
    summary["goss_off_bit_identical"] = bool(
        np.array_equal(gpreds["baseline"], gpreds["goss_off"]))

    # ---- (b) EFB: one-hot design, C shrink + bit-equal splits. The
    # parity frame uses an INTEGER exactly-zero-mean response so the stat
    # lanes stay in-range integers and the default-cell reconstruction is
    # bit-exact (the theorem regime; float lanes carry an f32-associativity
    # envelope and may break equal-gain threshold ties differently) ----
    levels, dense = 12, 3
    g = rng.integers(0, levels, rows // 2)
    yh = (g % 3 - 1).astype(np.float32)
    g = np.concatenate([g, g])
    dfe = pd.DataFrame(
        {f"oh{j}": (g == j).astype(np.float32) for j in range(levels)})
    for j in range(dense):
        dfe[f"d{j}"] = rng.normal(size=rows).astype(np.float32)
    dfe["label"] = (0.7 * (g % 3) + dfe["d0"] - 0.5 * dfe["d1"]
                    + 0.2 * rng.normal(size=rows))
    fr_e = Frame.from_pandas(dfe)
    kw_e = dict(ntrees=8, max_depth=5, seed=7, distribution="gaussian")
    dfp = dfe.drop(columns=["label"]).copy()
    dfp["label"] = np.concatenate([yh, -yh])  # integer sum == exactly 0
    fr_p = Frame.from_pandas(dfp)
    kw_p = dict(ntrees=1, max_depth=5, seed=7, distribution="gaussian")

    def split_structure(m):
        out = []
        for it in m.output["trees"]:
            for t in it:
                h = t.to_host()
                for lv, mk in zip(h.levels, h.real_level_masks()):
                    out.append((np.asarray(lv.split_col)[mk],
                                np.asarray(lv.split_bin)[mk],
                                np.asarray(lv.leaf_now)[mk]))
        return out

    emodels = {}
    for mode, knob in (("baseline", None), ("efb", "1")):
        envs(H2O3_TPU_TREE_EFB=knob)
        c0 = mx.counter_value("tree_cols_bundled_total")
        m, dt = timed(lambda: GBM(**kw_e).train(
            y="label", training_frame=fr_e))
        emodels[mode] = GBM(**kw_p).train(y="label", training_frame=fr_p)
        rec = {"phase": "wave2_ab", "case": "efb", "mode": mode,
               "rows": rows, "cols": levels + dense,
               "train_s": round(dt, 4),
               "cols_bundled": mx.counter_value(
                   "tree_cols_bundled_total") - c0}
        print(json.dumps(rec), flush=True)
        summary[f"efb_{mode}"] = rec
    envs(H2O3_TPU_TREE_EFB=None)
    # C shrink straight from the plan (counter tallies per build/chunk)
    from h2o3_tpu.models.tree.binning import bin_frame, fit_bins, fit_efb

    cols_e = [c for c in dfe.columns if c != "label"]
    spec_e = fit_bins(fr_e, cols_e)
    plan_e = fit_efb(spec_e, bin_frame(spec_e, fr_e), nrow=fr_e.nrow)
    summary["efb_c_shrink"] = round(
        plan_e.n_cols / plan_e.n_cols_b, 2) if plan_e else 1.0
    summary["efb_splits_bit_equal"] = bool(all(
        all(np.array_equal(a, b) for a, b in zip(s0, s1))
        for s0, s1 in zip(split_structure(emodels["baseline"]),
                          split_structure(emodels["efb"]))))

    # ---- (c) u8-code-native frames: rebin HBM traffic across 3 repeated
    # builds over one frame, cache on vs off ----
    rebin = {}
    upreds = {}
    for mode, knob in (("u8cache", None), ("u8cache_off", "0")):
        envs(H2O3_TPU_TREE_U8CACHE=knob)
        fr_u = Frame.from_pandas(dfe)  # fresh frame: empty bin cache
        r0 = mx.counter_value("tree_hist_hbm_bytes_total", path="rebin")
        for rep in range(3):
            m = GBM(**kw_e).train(y="label", training_frame=fr_u)
        upreds[mode] = pred(m, fr_u, "predict")
        rebin[mode] = mx.counter_value(
            "tree_hist_hbm_bytes_total", path="rebin") - r0
        rec = {"phase": "wave2_ab", "case": "u8_native", "mode": mode,
               "rows": rows, "builds": 3, "rebin_bytes": rebin[mode]}
        print(json.dumps(rec), flush=True)
    envs(H2O3_TPU_TREE_U8CACHE=None)
    summary["u8_rebin_bytes_ratio"] = round(
        rebin["u8cache_off"] / max(rebin["u8cache"], 1.0), 2)
    summary["u8_off_bit_identical"] = bool(
        np.array_equal(upreds["u8cache"], upreds["u8cache_off"]))

    # ---- (d) int16 hist lanes: envelope + forced-off control ----
    ipreds = {}
    for mode, knob in (("f32", None), ("i16", "1"), ("i16_off", "0")):
        envs(H2O3_TPU_HIST_I16=knob)
        o0 = mx.counter_value("tree_hist_i16_overflows_total")
        m, dt = timed(lambda: GBM(**kw_e).train(
            y="label", training_frame=fr_e))
        ipreds[mode] = pred(m, fr_e, "predict")
        rec = {"phase": "wave2_ab", "case": "i16", "mode": mode,
               "rows": rows, "train_s": round(dt, 4),
               "overflows": mx.counter_value(
                   "tree_hist_i16_overflows_total") - o0}
        print(json.dumps(rec), flush=True)
    envs(H2O3_TPU_HIST_I16=None)
    yl = dfe["label"].to_numpy()
    rmse = {m: float(np.sqrt(np.mean((p - yl) ** 2)))
            for m, p in ipreds.items()}
    # quantized near-tie splits diverge tree-by-tree; model QUALITY is the
    # envelope that holds (same contract as the parity tests)
    summary["i16_rmse_ratio"] = round(rmse["i16"] / max(rmse["f32"], 1e-9), 4)
    summary["i16_off_bit_identical"] = bool(
        np.array_equal(ipreds["f32"], ipreds["i16_off"]))

    # ---- (e) lossguide: bounded-leaves headline + unbound control ----
    for mode, kw_l in (
            ("depthwise", {}),
            ("lossguide", dict(grow_policy="lossguide", max_leaves=16)),
            ("lossguide_unbound",
             dict(grow_policy="lossguide", max_leaves=2 ** 5))):
        m, dt = timed(lambda: GBM(**kw_e, **kw_l).train(
            y="label", training_frame=fr_e))
        rec = {"phase": "wave2_ab", "case": "lossguide", "mode": mode,
               "rows": rows, "train_s": round(dt, 4),
               "max_n_leaves": max(t.n_leaves
                                   for it in m.output["trees"] for t in it)}
        print(json.dumps(rec), flush=True)
        summary[f"lossguide_{mode}"] = rec
        ipreds[mode] = pred(m, fr_e, "predict")
    summary["lossguide_leaves_bounded"] = bool(
        summary["lossguide_lossguide"]["max_n_leaves"] <= 16)
    summary["lossguide_unbound_bit_identical"] = bool(np.array_equal(
        ipreds["depthwise"], ipreds["lossguide_unbound"]))

    print(json.dumps({"wave2_ab": {
        k: summary[k] for k in (
            "goss_row_stats_ratio", "goss_auc_delta",
            "goss_off_bit_identical", "efb_c_shrink",
            "efb_splits_bit_equal", "u8_rebin_bytes_ratio",
            "u8_off_bit_identical", "i16_rmse_ratio",
            "i16_off_bit_identical", "lossguide_leaves_bounded",
            "lossguide_unbound_bit_identical")
    }}), flush=True)


def munge_ab(rows: int = 200_000) -> None:
    """Compiled munging plane A/B (H2O3_TPU_MUNGE_FUSE, ISSUE 20) on the
    SAME host data per mode: group-by (all value columns' segment stats in
    one mesh-sharded dispatch vs one eager segment-reduce per column),
    join (radix all_to_all gid exchange + device expansion vs global
    lexsort + host np.repeat), sort (one cached key-prep+lexsort program
    vs staged eager), and the 10-op rapids-style expression chain (ONE
    fused program vs 10 eager kernels, counter-proven). One JSON line per
    (case, mode), then a {"munge_ab": ...} summary carrying the acceptance
    pins: fused wall <= 0.5x eager for group-by and join, sort no worse,
    chain dispatches cut >= 5x, joins/sort/chain bit-equal, group-by
    counts/extrema exact with float sums allclose (per-shard accumulation
    + psum reorder f32 addition — bit-parity there is not the contract)."""
    from h2o3_tpu.frame import ops as fops
    from h2o3_tpu.frame.frame import CAT, NUM, Frame, Vec
    from h2o3_tpu.parallel.mesh import get_mesh
    from h2o3_tpu.utils import metrics as mx

    n = rows
    n_dev = int(get_mesh().devices.size)
    rng = np.random.default_rng(0)

    # one host copy of every input: both modes build their frames from the
    # SAME bytes, so parity failures can only come from the compute lanes
    gcard = max(64, n // 2000)
    a = rng.normal(size=n)
    a[::97] = np.nan
    b = rng.normal(size=n)
    c = rng.normal(size=n)
    g = rng.integers(0, gcard, size=n).astype(np.int64)
    # join geometry mirrors bench.py join_10m: right side unique keys
    # (dimension-table shape), left random over them -> out rows == n
    nr = max(n // 10, 8)
    kl = rng.integers(0, nr, size=n).astype(np.float64)
    kr = rng.permutation(nr).astype(np.float64)
    yr = rng.normal(size=nr)

    def gb_frame():
        return Frame(
            [Vec.from_numpy(a, NUM, name="a"),
             Vec.from_numpy(b, NUM, name="b"),
             Vec.from_numpy(c, NUM, name="c"),
             Vec.from_numpy(g, CAT, name="g",
                            domain=[str(i) for i in range(gcard)])],
            ["a", "b", "c", "g"])

    def join_frames():
        L = Frame([Vec.from_numpy(kl, NUM, name="k"),
                   Vec.from_numpy(a, NUM, name="x")], ["k", "x"])
        R = Frame([Vec.from_numpy(kr, NUM, name="k"),
                   Vec.from_numpy(yr, NUM, name="y")], ["k", "y"])
        return L, R

    GB_SPEC = {"a": ["sum", "mean", "min", "max", "count"],
               "b": ["sum", "sd"], "c": ["max", "count"]}

    def timed(fn):
        fn()  # compile warmup
        t0 = time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0

    results, outs = {}, {}
    for mode in ("fused", "eager"):
        os.environ["H2O3_TPU_MUNGE_FUSE"] = "1" if mode == "fused" else "0"
        fr = gb_frame()
        gb, gb_s = timed(
            lambda: fops.group_by(fr, "g").agg(GB_SPEC).to_pandas())
        L, R = join_frames()
        jn, join_s = timed(
            lambda: fops.merge(L, R, by=["k"]).to_pandas())
        so, sort_s = timed(
            lambda: fops.sort(fr, ["g", "a"],
                              ascending=[True, False]).to_pandas())

        def chain():
            va, vb = fr.vec("a"), fr.vec("b")
            cx = (va * 2.0 + vb) / 3.0          # 3 ops
            d = (cx > 0) & (vb < 1.0)           # 3 ops
            e = fops.ifelse(d, cx, va - vb)     # 2 ops
            return (e * e + 1.0).to_numpy()     # 2 ops
        chain()  # compile warmup (outside the dispatch-count window)
        d0 = {op: mx.counter_value("munge_dispatches_total", op=op)
              for op in ("elementwise", "expr_fuse")}
        t0 = time.perf_counter()
        ch = chain()
        chain_s = time.perf_counter() - t0
        disp = sum(mx.counter_value("munge_dispatches_total", op=op) - d0[op]
                   for op in ("elementwise", "expr_fuse"))

        outs[mode] = {"gb": gb, "jn": jn, "so": so, "ch": ch}
        rec = {"phase": "munge_ab", "mode": mode, "rows": n,
               "n_devices": n_dev, "groupby_groups": gcard,
               "join_out_rows": int(len(jn)),
               "groupby_s": round(gb_s, 4), "join_s": round(join_s, 4),
               "sort_s": round(sort_s, 4), "chain_s": round(chain_s, 4),
               "chain_dispatches": int(disp)}
        print(json.dumps(rec), flush=True)
        results[mode] = rec
    os.environ.pop("H2O3_TPU_MUNGE_FUSE", None)

    def frames_equal(fa, fb, close=()):
        if list(fa.columns) != list(fb.columns) or fa.shape != fb.shape:
            return False
        for col in fa.columns:
            xa, xb = fa[col].to_numpy(), fb[col].to_numpy()
            if xa.dtype == object:
                ok = list(xa) == list(xb)
            elif col in close:
                ok = np.allclose(xa, xb, rtol=1e-5, atol=1e-4,
                                 equal_nan=True)
            else:
                ok = np.array_equal(xa, xb, equal_nan=True)
            if not ok:
                return False
        return True

    f, e = results["fused"], results["eager"]
    gb_close = ("sum_a", "mean_a", "sum_b", "sd_b")
    parity = {
        "groupby_parity_ok": frames_equal(
            outs["fused"]["gb"], outs["eager"]["gb"], close=gb_close),
        "join_bit_equal": frames_equal(outs["fused"]["jn"],
                                       outs["eager"]["jn"]),
        "sort_bit_equal": frames_equal(outs["fused"]["so"],
                                       outs["eager"]["so"]),
        "chain_bit_equal": bool(np.array_equal(
            outs["fused"]["ch"], outs["eager"]["ch"], equal_nan=True)),
    }
    print(json.dumps({"munge_ab": {
        "rows": n, "n_devices": n_dev,
        "groupby_wall_ratio_fused_over_eager": round(
            f["groupby_s"] / max(e["groupby_s"], 1e-9), 3),
        "join_wall_ratio_fused_over_eager": round(
            f["join_s"] / max(e["join_s"], 1e-9), 3),
        "sort_wall_ratio_fused_over_eager": round(
            f["sort_s"] / max(e["sort_s"], 1e-9), 3),
        "chain_wall_ratio_fused_over_eager": round(
            f["chain_s"] / max(e["chain_s"], 1e-9), 3),
        "chain_dispatch_ratio": round(
            e["chain_dispatches"] / max(f["chain_dispatches"], 1), 2),
        **parity,
        "parity_ok": all(parity.values()),
    }}), flush=True)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.ops import hist_pallas

    n, c = 1_000_000, 28
    rng = np.random.default_rng(0)
    base_bins = rng.integers(0, 255, (n, c)).astype(np.uint8)
    w = jnp.ones(n, jnp.float32)
    wy = jnp.asarray(rng.normal(size=n).astype(np.float32))

    results = []
    for row_tile in (256, 512, 1024, 2048):
        for col_tile in (4, 8, 14, 28):
            for n_bins in (255, 127, 63):
                for n_nodes in (16, 64):
                    # tiles flow through the knob (static compile key: each
                    # setting compiles its own executable — no stale-cache
                    # clearing, and the exact production read path is what
                    # gets swept)
                    os.environ["H2O3_TPU_PALLAS_TILES"] = (
                        f"{row_tile},{col_tile},{hist_pallas.NODE_TILE}"
                    )
                    bins = jnp.asarray(
                        (base_bins % n_bins).astype(np.uint8)
                    )
                    nid = jnp.asarray(
                        rng.integers(0, n_nodes, n).astype(np.int32)
                    )
                    try:
                        stats = jnp.stack([w, wy, w], 1)  # 3-lane GBM shape
                        fn = lambda: hist_pallas.hist_pallas_local(
                            bins, nid, stats, n_nodes, n_bins,
                            tiles=hist_pallas._tiles(),
                        )
                        out = fn()
                        jax.block_until_ready(out)
                        t0 = time.perf_counter()
                        for _ in range(3):
                            out = fn()
                        jax.block_until_ready(out)
                        dt = (time.perf_counter() - t0) / 3
                        rec = {"row_tile": row_tile, "col_tile": col_tile,
                               "n_bins": n_bins, "n_nodes": n_nodes,
                               "hist_s": round(dt, 4)}
                    except Exception as e:  # noqa: BLE001 — sweep must finish
                        rec = {"row_tile": row_tile, "col_tile": col_tile,
                               "n_bins": n_bins, "n_nodes": n_nodes,
                               "error": repr(e)[:200]}
                    print(json.dumps(rec), flush=True)
                    results.append(rec)
    os.environ.pop("H2O3_TPU_PALLAS_TILES", None)

    ok = [r for r in results if "hist_s" in r]
    if ok:
        best = min(ok, key=lambda r: r["hist_s"])
        print(json.dumps({"best": best}))


if __name__ == "__main__":
    kw = {}
    if "--rows" in sys.argv:
        kw["rows"] = int(sys.argv[sys.argv.index("--rows") + 1])
    if "--split-ab" in sys.argv:
        split_ab(**kw)
    elif "--glm-ab" in sys.argv:
        glm_ab(**kw)
    elif "--dl-ab" in sys.argv:
        dl_ab(**kw)
    elif "--quant-ab" in sys.argv:
        quant_ab(**kw)
    elif "--oocore-ab" in sys.argv:
        oocore_ab(**kw)
    elif "--fallback-ab" in sys.argv:
        fallback_ab(**kw)
    elif "--mesh2d-ab" in sys.argv:
        mesh2d_ab(**kw)
    elif "--wave2-ab" in sys.argv:
        wave2_ab(**kw)
    elif "--munge-ab" in sys.argv:
        munge_ab(**kw)
    else:
        main()
