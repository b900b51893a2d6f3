"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the normal path once, through the entry points a user
calls, at the full width of the one shape that has ever run on a chip
(``bench.make_data()``: 1M rows x 28 columns, 255 bins, depth 6, 20 trees):

    h2o3_tpu.init() -> upload_file -> H2OGradientBoostingEstimator.train
    -> auc -> predict -> start_server(port=0) -> POST /3/Predictions/rows
    -> GET /3/Cloud

then three short legs at ``bench.py``'s own shapes so every plane the
benchmark needs is known alive: GLM binomial on the same frame,
DeepLearning 128-128 on 100k x 784, and one group_by().agg + merge through
the munge plane. Every model leg's answers on a small input are compared
with the host MOJO scorer (the repo's own reference), every leg runs twice
so the first call (compile) is reported apart from the second, and the run
fails — exit code != 0, no result line — when

- jax finds no TPU (the sandbox, or a machine whose chip is held);
- any leg raises (no leg is wrapped in a ``try`` that lets the run go on);
- the GBM AUC leaves 0.845 +- 0.005;
- a fallback, OOM-degrade or hang counter moved;
- /3/Cloud is degraded or reports another platform;
- a frame column lives on fewer devices than the mesh has;
- on >1 device, a reduce the sharded lanes must issue moved no bytes.

The last line of stdout is ``{"ok": true, "device": {...}}``. The chip
belongs to one process: run nothing else that touches jax beside this.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import urllib.request

N_ROWS = 1_000_000
N_COLS = 28
DL_ROWS, DL_COLS, DL_CLASSES = 100_000, 784, 10
JOIN_RIGHT_ROWS = 100_000
AUC_BAND = (0.840, 0.850)
PLATFORM = "tpu"
REF_ROWS = 256  # rows of the small input every model is checked on

# counters that must not move: each is a lane silently giving way
GUARDS = (
    "glm_fuse_fallbacks_total", "dl_shard_fallbacks_total",
    "munge_fuse_fallbacks_total",
    "oom_degrades_total", "dispatch_hangs_total",
)
# reduces the sharded default lanes issue on a >1-device mesh
SHARDED_PHASES = ("hist_reduce", "winner_gather", "gram_reduce",
                  "dl_grad_reduce")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(tag: str, **kv) -> None:
    print(f"[smoke] {tag} " + json.dumps(kv, default=str), flush=True)


def twice(fn):
    """(first-call seconds, second-call seconds, second result): the first
    call pays tracing and compilation, the second is the warm path."""
    t0 = time.perf_counter()
    fn()
    t1 = time.perf_counter()
    out = fn()
    return round(t1 - t0, 3), round(time.perf_counter() - t1, 3), out


def counters(prefix: str = "") -> dict:
    from h2o3_tpu.utils import metrics

    return {k: v for k, v in metrics.REGISTRY.compact_snapshot().items()
            if k.startswith(prefix) and not isinstance(v, dict)}


def moved(before: dict, prefix: str) -> dict:
    return {k: v - before.get(k, 0) for k, v in counters(prefix).items()
            if v != before.get(k, 0)}


def on_all_devices(frame, n_dev: int, what: str) -> None:
    for name in frame.names:
        got = len(frame.vec(name).data.sharding.device_set)
        check(got == n_dev,
              f"{what}: column {name!r} on {got} of {n_dev} devices")


def mojo_reference(model, df, tmp: str, atol: float) -> float:
    """Max |chip - host| over the class probabilities of ``df``'s rows: the
    repo's own parity idiom (tests/test_export_persist._parity) — the
    offline MOJO scorer is plain numpy on the host."""
    import numpy as np

    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.genmodel import MojoModel

    path = os.path.join(tmp, f"{model.model_id}.zip")
    model.download_mojo(path)
    offline = MojoModel.load(path).predict(df)
    live = model.predict(Frame.from_pandas(df))
    worst = 0.0
    for d in model.output["response_domain"]:
        a = np.asarray(live.vec(str(d)).to_numpy(), np.float64)
        b = np.asarray(offline[str(d)], np.float64)
        check(a.shape == (len(df),) and bool(np.isfinite(a).all()),
              f"{model.model_id}: predictions for class {d!r} not finite "
              f"of shape ({len(df)},)")
        worst = max(worst, float(np.abs(a - b).max()))
    check(worst <= atol, f"{model.model_id}: chip vs host MOJO scorer differ "
                         f"by {worst:.3g} > {atol:g}")
    return worst


SHED_WAIT_S = 300  # how long a cold scorer may keep shedding 504
SHEDS: list = []  # model ids of the 504s post_rows retried


def post_rows(url: str, model, rows: list) -> dict:
    """POST /3/Predictions/rows as a client does: 504 + Retry-After is the
    tier's documented answer while a cold model's scorer program compiles
    past H2O3_TPU_SCORE_DEADLINE_MS (the dispatcher keeps compiling), so it
    is retried — counted, and bounded; any other status raises."""
    import urllib.error

    req = urllib.request.Request(
        url + "/3/Predictions/rows",
        data=json.dumps({"model": model.model_id, "rows": rows}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    t_end = time.monotonic() + SHED_WAIT_S
    while True:
        try:
            with urllib.request.urlopen(req, timeout=300) as r:
                return json.loads(r.read())["predictions"]
        except urllib.error.HTTPError as e:
            if e.code != 504 or time.monotonic() > t_end:
                raise
            SHEDS.append(model.model_id)
            time.sleep(float(e.headers.get("Retry-After") or 1))


def rows_equal_predict(url: str, model, rows: list, cols: list) -> None:
    """The REST scoring tier's answers == Model.predict on the same rows
    (the byte-equality contract of tests/test_serving.py)."""
    import numpy as np
    import pandas as pd

    from h2o3_tpu.frame.frame import Frame

    got = post_rows(url, model, rows)
    pf = model.predict(Frame.from_pandas(
        pd.DataFrame({c: [r.get(c) for r in rows] for c in cols})))
    dom = model.output["response_domain"]
    for d in dom:
        want = [float(v) for v in pf.vec(str(d)).to_numpy()]
        check(got[str(d)] == want,
              f"{model.model_id}: /3/Predictions/rows {got[str(d)]} != "
              f"Model.predict {want}")
    labels = list(np.asarray(dom, dtype=object)[pf.vec("predict").to_numpy()])
    check(got["predict"] == labels, f"{model.model_id}: labels differ")


def leg_gbm(df, fr, tmp: str, n_dev: int):
    import jax

    from h2o3_tpu.estimators import H2OGradientBoostingEstimator
    from h2o3_tpu.frame import chunkstore

    # the admission plane meets a real bytes_limit here for the first time:
    # the builder's own policy gate must keep this 1M x 28 build resident
    check(chunkstore.ChunkStore.plan(fr.npad, N_COLS + 28) is None,
          "the 1M x 28 GBM build was routed to the streamed lane")
    lanes0 = counters("tree_hist_hbm_bytes_total")

    def train():
        return H2OGradientBoostingEstimator(
            ntrees=20, max_depth=6, learn_rate=0.1, min_rows=10, seed=42,
        ).train(y="label", training_frame=fr)

    first_s, second_s, gbm = twice(train)
    auc = float(gbm.auc())
    check(AUC_BAND[0] <= auc <= AUC_BAND[1],
          f"GBM AUC {auc:.4f} outside {AUC_BAND}")
    lanes = moved(lanes0, "tree_hist_hbm_bytes_total")
    ran = {k.split("path=")[1].rstrip("}") for k in lanes}
    if PLATFORM == "tpu":
        check("pallas_unfused" in ran and "dense" not in ran,
              f"no Pallas histogram lane ran on the chip: {lanes}")

    def predict():
        pf = gbm.predict(fr)
        jax.block_until_ready([pf.vec(n).data for n in pf.names])
        return pf

    p_first_s, p_second_s, pf = twice(predict)
    check(pf.nrow == fr.nrow, f"predict returned {pf.nrow} rows")
    on_all_devices(pf, n_dev, "GBM predictions")
    diff = mojo_reference(gbm, df.iloc[:REF_ROWS].drop(columns=["label"]),
                          tmp, atol=1e-5)
    say("gbm", auc=round(auc, 4), train_first_s=first_s,
        train_second_s=second_s, predict_first_s=p_first_s,
        predict_second_s=p_second_s, hist_lane_bytes=lanes,
        mojo_max_abs_diff=diff)
    return gbm


def leg_serve(df, gbm, tmp: str) -> None:
    """In-process REST server: scoring answers equal Model.predict — one row
    with a null on the big model, one with an unseen enum level on a small
    model trained on a CSV this script writes and import_file parses."""
    import jax
    import numpy as np
    import pandas as pd

    import h2o3_tpu
    from h2o3_tpu import serving
    from h2o3_tpu.estimators import H2OGradientBoostingEstimator
    from h2o3_tpu.frame import parse
    from h2o3_tpu.genmodel import _native_mod
    from h2o3_tpu.serving import residency

    server = h2o3_tpu.start_server(port=0)
    try:
        with urllib.request.urlopen(server.url + "/3/Cloud", timeout=60) as r:
            cloud = json.loads(r.read())
        check(cloud.get("platform") == PLATFORM,
              f"/3/Cloud platform {cloud.get('platform')!r} != {PLATFORM!r}")
        check(cloud["cloud_healthy"] and "degraded" not in cloud,
              f"/3/Cloud not healthy: {cloud}")

        feats = [c for c in df.columns if c != "label"]
        rows = [{c: float(df[c].iloc[i]) for c in feats} for i in range(4)]
        rows[1]["f3"] = None
        t0 = time.perf_counter()
        rows_equal_predict(server.url, gbm, rows, feats)
        first_s = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        post_rows(server.url, gbm, rows)
        second_s = round(time.perf_counter() - t0, 3)

        rng = np.random.default_rng(7)
        n = 2000
        small = pd.DataFrame({
            "a": rng.normal(size=n), "b": rng.normal(size=n),
            "c": rng.choice(["x", "y", "z"], n)})
        small["y"] = np.where(
            small["a"] + (small["c"] == "x") + rng.normal(size=n) > 0,
            "dog", "cat")
        small.loc[::13, "a"] = np.nan
        path = os.path.join(tmp, "smoke_enum.csv")
        small.to_csv(path, index=False)
        csv_lane = ("native" if parse._try_native_csv(path, ",") is not None
                    else "pandas")
        efr = h2o3_tpu.import_file(path)
        check(efr.nrow == n and efr.types["c"] == "enum",
              f"import_file: {efr.nrow} rows, types {efr.types}")
        egbm = H2OGradientBoostingEstimator(
            ntrees=8, max_depth=3, seed=1).train(y="y", training_frame=efr)
        rows_equal_predict(server.url, egbm, [
            {"a": 0.37, "b": -1.25, "c": "x"},
            {"a": None, "b": 0.0, "c": "NEVER_SEEN"},
            {"a": 2.25, "b": 0.5, "c": None},
        ], ["a", "b", "c"])
        mojo_reference(egbm, small.iloc[:REF_ROWS].drop(columns=["y"]), tmp,
                       atol=1e-5)
        # B8 decides the scoring lane's placement; this only says where the
        # scorer's device arguments live today (serving/residency.py)
        with residency.MANAGER.hold(serving.scorer_for(gbm.model)) as dev:
            placed = sorted({d.id for leaf in jax.tree_util.tree_leaves(dev)
                             for d in leaf.devices()})
        say("serve", url=server.url, cloud_platform=cloud["platform"],
            rows_first_s=first_s, rows_second_s=second_s, csv_lane=csv_lane,
            mojo_lane="native" if _native_mod() is not None else "numpy",
            cold_scorer_504s=len(SHEDS),
            scorer_args_on_devices=placed)
    finally:
        server.stop()


def leg_glm(df, fr, tmp: str) -> None:
    from h2o3_tpu.estimators import H2OGeneralizedLinearEstimator

    def train():  # bench._bench_glm_1m's arguments
        return H2OGeneralizedLinearEstimator(
            family="binomial", lambda_=1e-4, max_iterations=20, seed=1,
        ).train(y="label", training_frame=fr)

    first_s, second_s, glm = twice(train)
    auc = float(glm.auc())
    check(0.75 <= auc <= AUC_BAND[1],
          f"GLM AUC {auc:.4f}: a linear fit of this generator sits below "
          f"the GBM and well above 0.75")
    # eta is a 29-term f32 dot product: 1e-4 covers a bf16-pass matmul
    diff = mojo_reference(glm, df.iloc[:REF_ROWS].drop(columns=["label"]),
                          tmp, atol=1e-4)
    say("glm", auc=round(auc, 4), train_first_s=first_s,
        train_second_s=second_s, mojo_max_abs_diff=diff)


def leg_dl(tmp: str) -> None:
    """bench._bench_dl's shape: 100k x 784 -> 10 classes, hidden 128-128."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from h2o3_tpu.estimators import H2ODeepLearningEstimator

    def labeler(kw, X):
        W = jax.random.normal(kw, (DL_COLS, DL_CLASSES), jnp.float32)
        return (jnp.argmax(X @ W, axis=1).astype(jnp.int8),
                tuple(str(i) for i in range(DL_CLASSES)))

    fr = bench._make_data_device(DL_ROWS, c=DL_COLS, seed=5, labeler=labeler,
                                 col_prefix="p")

    def train():
        return H2ODeepLearningEstimator(
            hidden=(128, 128), epochs=1.0, mini_batch_size=256, seed=3,
        ).train(y="label", training_frame=fr)

    first_s, second_s, dl = twice(train)
    logloss = float(dl.logloss())
    check(np.isfinite(logloss) and logloss < np.log(DL_CLASSES),
          f"DL logloss {logloss:.4f} is no better than a uniform guess")
    small = fr.subset_rows(np.arange(REF_ROWS)).to_pandas()
    # three matmul layers at the TPU's default precision (bf16 passes, eps
    # 2^-8 = 4e-3) against the f64 host scorer: logits of magnitude ~5 carry
    # ~2e-2, and a class probability moves by at most that
    diff = mojo_reference(dl, small.drop(columns=["label"]), tmp, atol=3e-2)
    say("dl", logloss=round(logloss, 4), train_first_s=first_s,
        train_second_s=second_s, mojo_max_abs_diff=diff)


def leg_munge(n_dev: int) -> None:
    """group_by().agg (bench._bench_cat_1m's spec over a 200-level enum)
    and merge (bench._bench_join_10m's key shape at a tenth of its rows),
    each against pandas on the host."""
    import numpy as np
    import pandas as pd

    import h2o3_tpu
    from h2o3_tpu.frame import ops

    rng = np.random.default_rng(3)
    df = pd.DataFrame({
        "g": np.char.add("l", rng.integers(0, 200, N_ROWS).astype(str)),
        "k": rng.integers(0, JOIN_RIGHT_ROWS, N_ROWS).astype(np.float32),
        "f0": rng.normal(size=N_ROWS).astype(np.float32),
        "f1": rng.normal(size=N_ROWS).astype(np.float32),
        "f2": rng.normal(size=N_ROWS).astype(np.float32)})
    right = pd.DataFrame({
        "k": np.arange(JOIN_RIGHT_ROWS, dtype=np.float32),
        "y": rng.normal(size=JOIN_RIGHT_ROWS).astype(np.float32)})
    fr = h2o3_tpu.upload_file(df)
    rfr = h2o3_tpu.upload_file(right)
    on_all_devices(fr, n_dev, "munge frame")

    spec = {"f0": ["sum", "mean"], "f1": ["min", "max"], "f2": ["count", "sd"]}
    g_first_s, g_second_s, out = twice(
        lambda: ops.group_by(fr, "g").agg(spec).to_pandas())
    want = df.groupby("g").agg(
        sum_f0=("f0", "sum"), mean_f0=("f0", "mean"), min_f1=("f1", "min"),
        max_f1=("f1", "max"), count_f2=("f2", "size"), sd_f2=("f2", "std"),
    ).reset_index()
    check(list(out["g"]) == list(want["g"]), "group_by: group keys differ")
    for c in want.columns[1:]:
        # f32 segment sums over ~5k rows a group reorder across shards
        check(np.allclose(out[c], want[c], rtol=1e-3, atol=1e-3),
              f"group_by: {c} differs from pandas")

    def join():
        j = ops.merge(fr, rfr, by=["k"])
        return j.nrow, float(np.sum(j.vec("y").to_numpy(), dtype=np.float64))

    m_first_s, m_second_s, (nrow, ysum) = twice(join)
    ref = right["y"].to_numpy(np.float64)[df["k"].to_numpy(np.int64)].sum()
    check(nrow == N_ROWS, f"merge: {nrow} rows, expected {N_ROWS}")
    check(abs(ysum - ref) <= 1e-6 * N_ROWS, f"merge: sum(y) {ysum} != {ref}")
    say("munge", groupby_first_s=g_first_s, groupby_second_s=g_second_s,
        merge_first_s=m_first_s, merge_second_s=m_second_s, merge_rows=nrow)


def run() -> None:
    """Every leg, in order, in this one process (after h2o3_tpu.init()).
    Raises on the first failure; returns only when all of it held."""
    import jax

    import bench
    import h2o3_tpu
    from h2o3_tpu import config
    from h2o3_tpu.utils import flightrec, metrics, telemetry

    n_dev = len(jax.devices())
    guards0 = {g: counters(g) for g in GUARDS}
    coll0 = counters("tree_collective_bytes_total")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        t0 = time.perf_counter()
        df = bench.make_data(n=N_ROWS, c=N_COLS)
        fr = h2o3_tpu.upload_file(df)
        check(fr.nrow == N_ROWS and fr.ncol == N_COLS + 1,
              f"upload_file: {fr.nrow} x {fr.ncol}")
        on_all_devices(fr, n_dev, "training frame")
        say("upload", rows=fr.nrow, cols=fr.ncol,
            seconds=round(time.perf_counter() - t0, 3))

        gbm = leg_gbm(df, fr, tmp, n_dev)
        leg_serve(df, gbm, tmp)
        leg_glm(df, fr, tmp)
        leg_dl(tmp)
        leg_munge(n_dev)

    coll = {k: v for k, v in moved(coll0, "tree_collective_bytes_total").items()
            if "lane=" not in k}  # the per-phase totals, not the lane split
    if n_dev > 1:
        for ph in SHARDED_PHASES:
            check(any(f"phase={ph}" in k and v > 0 for k, v in coll.items()),
                  f"{n_dev} devices but no {ph} bytes moved: {coll}")
    for g in GUARDS:
        delta = moved(guards0[g], g)
        check(not delta, f"{g} moved: {delta}")
    cloud = h2o3_tpu.cluster_info()  # the degraded latch is one-way
    check(cloud["cloud_healthy"], f"cloud degraded: {cloud}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    # jax's compile event covers "compile OR load from the persistent
    # cache", so a warm run counts as many programs, each far quicker
    programs = sorted(
        (e["msg"] for e in telemetry.events(4096) if e["kind"] == "compile"),
        key=lambda m: -float(m.split(" in ")[-1].split()[0]))
    # the hang watchdog's margin: the longest completed dispatch span per
    # site against H2O3_TPU_HANG_MIN_SECS (a cold compile sits inside one)
    spans: dict = {}
    for e in flightrec.events():
        if e["kind"] == "dispatch_end":
            site = e.get("site", "?")
            spans[site] = max(spans.get(site, 0.0),
                              round(float(e.get("dur_ms") or 0) / 1e3, 2))
    say("end", collective_bytes=coll, gbm_auc=round(float(gbm.auc()), 4),
        peak_bytes_in_use=peaks, programs_compiled_or_loaded=len(programs),
        slowest_compile_or_load=programs[:6], longest_dispatch_s=spans,
        hang_floor_s=config.get_float("H2O3_TPU_HANG_MIN_SECS"),
        compile_cache_entries=bench._compile_cache_entries(),
        compile_cache_hits=metrics.counter_value("compile_cache_hits_total"))


def main() -> int:
    t0 = time.perf_counter()
    import importlib.metadata as md

    import jax

    import bench
    import h2o3_tpu
    from h2o3_tpu import config

    entries0 = bench._compile_cache_entries()
    info = h2o3_tpu.init()
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if device["platform"] != PLATFORM:
        print(f"chip_smoke: no TPU — jax reports platform "
              f"{device['platform']!r} ({device['kind']}, {device['count']} "
              f"device(s)); this script proves the program on the chip and "
              f"does not fall back", file=sys.stderr)
        return 1
    say("device", **device, mesh=info["mesh"],
        versions={p: md.version(p) for p in ("jax", "jaxlib", "libtpu")},
        compile_cache_dir=config.compile_cache_dir(),
        compile_cache_placed_by=("JAX_COMPILATION_CACHE_DIR"
                                 if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                                 else "default <checkout>/.jax_cache"),
        compile_cache_entries_at_start=entries0)
    run()
    say("done", wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
