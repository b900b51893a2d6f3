"""Scoring-tier tests (ISSUE 7): the coalescing batch scorer, the
``/3/Predictions/rows`` route, bounded prediction-frame retention, and the
persistent-compile-cache cross-process proof.

The parity suite is the load-bearing part: the compiled batch scorer must be
BYTE-equal to ``Model.predict`` through the frame path (same replay ops in
the same order, no cross-row reductions — the same inertness argument as the
PR-1 shape buckets) and must agree with the offline MOJO scorer, including
NA and unseen-categorical rows.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pandas as pd
import pytest

from h2o3_tpu.cluster.registry import DKV
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import GBM
from h2o3_tpu.utils import metrics as _mx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# rows exercising the adaptation corners: NA numeric, missing column,
# unseen categorical level, numeric-typed payload for everything else
SCORE_ROWS = [
    {"a": 0.37, "b": -1.25, "c": "x"},
    {"a": None, "b": 0.0, "c": "NEVER_SEEN"},
    {"a": 2.25, "b": float("nan"), "c": "z"},
    {"b": 0.5, "c": "y"},  # a absent entirely
    {"a": -0.75, "b": 1.5, "c": None},
]


def _rows_df(rows=SCORE_ROWS):
    return pd.DataFrame({
        "a": [r.get("a") for r in rows],
        "b": [r.get("b") for r in rows],
        "c": [r.get("c") for r in rows],
    })


@pytest.fixture(scope="module")
def binom_model():
    rng = np.random.default_rng(7)
    n = 900
    df = pd.DataFrame({
        "a": rng.normal(size=n),
        "b": rng.normal(size=n),
        "c": rng.choice(["x", "y", "z"], n),
        "y": np.where(rng.random(n) < 0.5, "dog", "cat"),
    })
    df.loc[::13, "a"] = np.nan
    fr = Frame.from_pandas(df, destination_frame="serve_train")
    return GBM(ntrees=8, max_depth=3, seed=1).train(y="y", training_frame=fr)


def _frame_path_probs(model, rows=SCORE_ROWS):
    pf = model.predict(Frame.from_pandas(_rows_df(rows)))
    dom = model.output["response_domain"]
    probs = np.stack([pf.vec(str(d)).to_numpy() for d in dom], axis=1)
    codes = pf.vec("predict").to_numpy()
    labels = np.asarray(dom, dtype=object)[codes]
    return probs, labels


def test_rows_scorer_byte_equal_frame_path(binom_model):
    from h2o3_tpu import serving

    out = serving.score_rows(binom_model, SCORE_ROWS)
    dom = binom_model.output["response_domain"]
    got = np.stack([np.asarray(out[str(d)], np.float32) for d in dom], axis=1)
    want, labels = _frame_path_probs(binom_model)
    assert got.tobytes() == want.tobytes()  # BYTE-equal, not allclose
    assert list(out["predict"]) == list(labels)


def test_rows_scorer_column_table_payload(binom_model):
    """The column-table payload shape scores identically to row dicts."""
    from h2o3_tpu import serving

    table = {
        "a": [r.get("a") for r in SCORE_ROWS],
        "b": [r.get("b") for r in SCORE_ROWS],
        "c": [r.get("c") for r in SCORE_ROWS],
    }
    a = serving.score_rows(binom_model, SCORE_ROWS)
    b = serving.score_rows(binom_model, table)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_rows_scorer_matches_mojo(binom_model, tmp_path):
    from h2o3_tpu import serving
    from h2o3_tpu.genmodel import MojoModel
    from h2o3_tpu.models.export import export_mojo

    path = str(tmp_path / "serve.zip")
    export_mojo(binom_model, path)
    mojo = MojoModel.load(path)
    live = serving.score_rows(binom_model, SCORE_ROWS)
    # the MOJO scores the SAME rows (dict rows include the NA/unseen cases)
    off = mojo.predict(_rows_df(SCORE_ROWS))
    dom = binom_model.output["response_domain"]
    for d in dom:
        np.testing.assert_allclose(
            np.asarray(live[str(d)], np.float64),
            np.asarray(off[str(d)], np.float64), atol=1e-5)
    assert [str(v) for v in live["predict"]] == [str(v) for v in off["predict"]]


def test_regression_and_multinomial_byte_equal(rng):
    from h2o3_tpu import serving

    n = 500
    rows = [{"a": 0.5, "b": 1.0}, {"a": None, "b": -2.0}]
    df2 = pd.DataFrame({"a": [0.5, None], "b": [1.0, -2.0]})
    # regression
    df = pd.DataFrame({"a": rng.normal(size=n), "b": rng.normal(size=n),
                       "y": rng.normal(size=n)})
    m = GBM(ntrees=5, max_depth=3, seed=1).train(
        y="y", training_frame=Frame.from_pandas(df, destination_frame="sv_reg"))
    out = serving.score_rows(m, rows)
    pf = m.predict(Frame.from_pandas(df2))
    assert (pf.vec("predict").to_numpy().tobytes()
            == np.asarray(out["predict"], np.float32).tobytes())
    # multinomial
    df = pd.DataFrame({"a": rng.normal(size=n), "b": rng.normal(size=n),
                       "y": rng.choice(["r", "g", "bl"], n)})
    m3 = GBM(ntrees=4, max_depth=3, seed=1).train(
        y="y", training_frame=Frame.from_pandas(df, destination_frame="sv_mul"))
    out = serving.score_rows(m3, rows)
    pf = m3.predict(Frame.from_pandas(df2))
    for c in ("r", "g", "bl"):
        assert (pf.vec(c).to_numpy().tobytes()
                == np.asarray(out[c], np.float32).tobytes())


def test_batch_bucket_reuses_program(binom_model):
    """Batch sizes within one rows-bucket (and a second scoring pass of the
    same model) compile ZERO new scorer programs — the serving half of the
    PR-1 shape-bucket contract."""
    from h2o3_tpu import serving

    serving.score_rows(binom_model, SCORE_ROWS)  # warm the bucket
    compiled = _mx.counter_value("serving_scorer_programs_total",
                                 event="compile")
    hits0 = _mx.counter_value("serving_scorer_programs_total", event="hit")
    serving.score_rows(binom_model, SCORE_ROWS[:2])
    serving.score_rows(binom_model, SCORE_ROWS * 4)  # 20 rows, same bucket
    assert _mx.counter_value(
        "serving_scorer_programs_total", event="compile") == compiled
    assert _mx.counter_value(
        "serving_scorer_programs_total", event="hit") >= hits0 + 2


def test_coalescing_batches_concurrent_requests(binom_model, monkeypatch):
    """Concurrent submits coalesce into fewer dispatches (occupancy > 1)
    and every request still gets ITS rows' predictions."""
    from h2o3_tpu import serving
    from h2o3_tpu.serving import BATCH_OCCUPANCY

    monkeypatch.setenv("H2O3_TPU_SCORE_BATCH_WINDOW_MS", "60")
    occ0 = [(s, c) for _, _, s, c in BATCH_OCCUPANCY.samples()]
    sum0 = occ0[0][0] if occ0 else 0.0
    cnt0 = occ0[0][1] if occ0 else 0

    results = [None] * 8
    errors = []

    def worker(i):
        try:
            results[i] = serving.score_rows(binom_model, [SCORE_ROWS[i % 5]])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    barrier_start = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    occ1 = [(s, c) for _, _, s, c in BATCH_OCCUPANCY.samples()]
    dsum, dcnt = occ1[0][0] - sum0, occ1[0][1] - cnt0
    assert dsum == 8  # every request accounted for
    assert dcnt < 8  # ...in fewer dispatches than requests
    assert dsum / dcnt > 1.0  # mean occupancy > 1
    # per-request results match the inline (window=0) path bitwise
    monkeypatch.setenv("H2O3_TPU_SCORE_BATCH_WINDOW_MS", "0")
    for i, res in enumerate(results):
        want = serving.score_rows(binom_model, [SCORE_ROWS[i % 5]])
        for k in want:
            np.testing.assert_array_equal(np.asarray(res[k]),
                                          np.asarray(want[k]))
    assert time.monotonic() - barrier_start < 30


def test_deadline_shed(binom_model, monkeypatch):
    from h2o3_tpu import serving

    monkeypatch.setenv("H2O3_TPU_SCORE_BATCH_WINDOW_MS", "120")
    monkeypatch.setenv("H2O3_TPU_SCORE_DEADLINE_MS", "1")
    with pytest.raises(serving.ShedError) as ei:
        serving.score_rows(binom_model, [SCORE_ROWS[0]])
    assert ei.value.status == 504


def test_queue_full_shed(binom_model, monkeypatch):
    from h2o3_tpu import serving
    from h2o3_tpu.serving.batcher import batcher_for

    monkeypatch.setenv("H2O3_TPU_SCORE_BATCH_WINDOW_MS", "150")
    monkeypatch.setenv("H2O3_TPU_SCORE_QUEUE_MAX", "3")
    done = threading.Event()

    def filler():
        try:
            serving.score_rows(binom_model, SCORE_ROWS[:3])  # 3 rows queue up
        finally:
            done.set()

    t = threading.Thread(target=filler)
    t.start()
    # wait until the filler's rows are actually queued
    b = batcher_for(binom_model)
    t0 = time.monotonic()
    while b._rows_queued < 3 and time.monotonic() - t0 < 5:
        time.sleep(0.005)
    assert b._rows_queued >= 3
    with pytest.raises(serving.ShedError) as ei:
        serving.score_rows(binom_model, [SCORE_ROWS[0]])
    assert ei.value.status == 429
    done.wait(timeout=30)
    t.join(timeout=5)


# ---------------------------------------------------------------------------
# REST surface


@pytest.fixture(scope="module")
def server():
    from h2o3_tpu.api.server import start_server

    return start_server(port=0)


def _post_json(server, path, payload):
    req = urllib.request.Request(
        server.url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_rows_route_over_rest(binom_model, server):
    out = _post_json(server, "/3/Predictions/rows",
                     {"model": binom_model.key, "rows": SCORE_ROWS})
    assert out["rows"] == len(SCORE_ROWS)
    preds = out["predictions"]
    want, labels = _frame_path_probs(binom_model)
    dom = binom_model.output["response_domain"]
    for k, d in enumerate(dom):
        # json round-trips float32 exactly through float(); compare exact
        assert preds[str(d)] == [float(v) for v in want[:, k]]
    assert preds["predict"] == list(labels)


def test_rows_route_client(binom_model, server):
    from h2o3_tpu.client import connect

    conn = connect(server.url)
    preds = conn.predict_rows(binom_model.key, SCORE_ROWS[:2])
    want, _ = _frame_path_probs(binom_model, SCORE_ROWS[:2])
    dom = binom_model.output["response_domain"]
    assert preds[str(dom[1])] == [float(v) for v in want[:, 1]]


def test_rows_route_errors(binom_model, server):
    import urllib.error

    with pytest.raises(urllib.error.HTTPError) as ei:
        _post_json(server, "/3/Predictions/rows", {"rows": SCORE_ROWS})
    assert ei.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post_json(server, "/3/Predictions/rows",
                   {"model": "no_such_model", "rows": SCORE_ROWS})
    assert ei.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post_json(server, "/3/Predictions/rows",
                   {"model": binom_model.key, "rows": []})
    assert ei.value.code == 400


def test_prediction_frame_retention(binom_model, server, monkeypatch):
    """Hammering /3/Predictions with generated dest keys must not grow the
    DKV beyond the retention bound (the serving-load DKV leak fix)."""
    monkeypatch.setenv("H2O3_TPU_PREDICTIONS_RETAIN", "4")
    before = _mx.counter_value("rest_prediction_frames_evicted_total")
    path = (f"/3/Predictions/models/{binom_model.key}"
            f"/frames/serve_train")
    made = []
    for _ in range(10):
        out = _post_json(server, path, {})
        made.append(out["predictions_frame"]["name"])
    live = [k for k in made if DKV.get(k) is not None]
    assert len(live) <= 4, f"retention bound leaked: {live}"
    # the newest frames survive (a client polling its own result in time
    # still finds it)
    assert DKV.get(made[-1]) is not None
    assert _mx.counter_value(
        "rest_prediction_frames_evicted_total") >= before + 6
    # an explicitly-named dest is NEVER auto-evicted
    out = _post_json(server, path, {"predictions_frame": "my_kept_preds"})
    for _ in range(6):
        _post_json(server, path, {})
    assert DKV.get("my_kept_preds") is not None
    DKV.remove("my_kept_preds")


# ---------------------------------------------------------------------------
# persistent compilation cache: cross-process zero-compile proof


_CACHE_PROBE = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
# the cache is placed from OUTSIDE (JAX_COMPILATION_CACHE_DIR in the env —
# same machine, so the AOT feature-mismatch hazard that keeps CPU runs off
# the default <checkout>/.jax_cache does not apply): no directory is set in
# code here or in init()
assert jax.config.jax_compilation_cache_dir == sys.argv[1]
import numpy as np, pandas as pd
import h2o3_tpu
h2o3_tpu.init()
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import GBM
from h2o3_tpu import serving
from h2o3_tpu.utils import metrics as mx
rng = np.random.default_rng(11)
n = 400
df = pd.DataFrame({"a": rng.normal(size=n), "b": rng.normal(size=n),
                   "y": np.where(rng.random(n) < 0.5, "p", "q")})
m = GBM(ntrees=4, max_depth=3, seed=5).train(
    y="y", training_frame=Frame.from_pandas(df, destination_frame="cc"))
out = serving.score_rows(m, [{"a": 0.1, "b": -0.2}, {"a": None, "b": 3.0}])
print(json.dumps({
    "p_q": [float(v) for v in out["q"]],
    "cache_hits": mx.counter_value("compile_cache_hits_total"),
}))
"""


def _cache_files(d):
    out = set()
    for root, _dirs, files in os.walk(d):
        out.update(os.path.join(root, f) for f in files)
    return out


def test_compile_cache_cross_process(tmp_path):
    """A second process training + scoring the SAME shape bucket compiles
    zero new programs: the persistent XLA cache, placed by
    ``JAX_COMPILATION_CACHE_DIR``, serves every program, proven by the
    cache dir gaining no new entries while the run still produces identical
    predictions."""
    cache = str(tmp_path / "xla_cache")
    os.makedirs(cache)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_COMPILATION_CACHE_DIR"] = cache

    def run():
        p = subprocess.run(
            [sys.executable, "-c", _CACHE_PROBE, cache],
            capture_output=True, text=True, timeout=420, env=env, cwd=ROOT)
        assert p.returncode == 0, p.stderr[-3000:]
        return json.loads(p.stdout.strip().splitlines()[-1])

    first = run()
    files_after_first = _cache_files(cache)
    assert files_after_first, "first process persisted no cache entries"
    second = run()
    files_after_second = _cache_files(cache)
    new = files_after_second - files_after_first
    assert not new, f"second process compiled {len(new)} new programs"
    # identical predictions from the cache-served programs
    assert second["p_q"] == first["p_q"]
    # the registry surfaces cache effectiveness (jax monitoring bridge)
    assert second["cache_hits"] > first["cache_hits"]
