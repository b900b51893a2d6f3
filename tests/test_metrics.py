"""Observability layer tests: the metrics registry (Prometheus exposition,
histogram bucket semantics), span tracing (nesting under concurrent jobs,
Chrome-trace serving), the BUILD_STATS back-compat alias, the /3/Metrics +
/3/Logs + /3/Jobs/{key}/trace routes, job timing fields, the /3/Timeline
merge, and the persist retry counter."""

import json
import re
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pandas as pd
import pytest

import h2o3_tpu
from h2o3_tpu.api.server import start_server
from h2o3_tpu.utils import metrics


@pytest.fixture(scope="module")
def server():
    return start_server(port=0)


def _get_json(server, path):
    with urllib.request.urlopen(server.url + path) as r:
        return json.loads(r.read())


def _get_text(server, path):
    with urllib.request.urlopen(server.url + path) as r:
        return r.read().decode(), r.headers.get("Content-Type", "")


def _post(server, path, payload):
    data = urllib.parse.urlencode(payload).encode()
    req = urllib.request.Request(server.url + path, data=data, method="POST")
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def _wait_job(server, job_key, timeout=120.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        j = _get_json(server, f"/3/Jobs/{job_key}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED", "CANCELLED"):
            return j
    raise TimeoutError(job_key)


def _upload_frame(n=600, seed=0, key="metrics_train"):
    rng = np.random.default_rng(seed)
    df = pd.DataFrame({
        "a": rng.normal(size=n),
        "b": rng.normal(size=n),
        "y": np.where(rng.random(n) < 0.5, "dog", "cat"),
    })
    return h2o3_tpu.upload_file(df, destination_frame=key)


# ---------------------------------------------------------------------------
# registry semantics


def test_prometheus_exposition_parses_names_types_and_escaping():
    c = metrics.counter("px_demo_total", 'demo with "quotes"\nand newline')
    c.inc(3, route='/3/"x"\\y', method="GET")
    g = metrics.gauge("px_gauge", "a gauge")
    g.set(2.5)
    text = metrics.REGISTRY.to_prometheus()

    # TYPE lines present and correct
    assert "# TYPE px_demo_total counter" in text
    assert "# TYPE px_gauge gauge" in text
    # HELP newline is escaped — the exposition stays line-oriented
    help_line = next(
        ln for ln in text.splitlines() if ln.startswith("# HELP px_demo_total")
    )
    assert "\\n" in help_line and "\n" not in help_line[1:]
    # label values escape backslash and double-quote
    sample = next(
        ln for ln in text.splitlines()
        if ln.startswith("px_demo_total{") and ln.endswith(" 3")
    )
    assert '\\"x\\"' in sample and "\\\\y" in sample
    # every non-comment line is `name{labels} value` or `name value`
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        assert re.match(
            r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? [0-9eE+.inf-]+$", ln
        ), ln


def test_histogram_buckets_are_cumulative():
    h = metrics.histogram("hb_seconds", "x", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    [(labels, cum, s, n)] = h.samples()
    assert labels == {}
    assert cum == [2, 3, 4, 5]  # le=0.1, le=1, le=10, +Inf — prefix sums
    assert n == 5 and s == pytest.approx(55.6)
    # rendered form repeats the cumulative contract with an +Inf bucket
    text = metrics.REGISTRY.to_prometheus()
    assert 'hb_seconds_bucket{le="+Inf"} 5' in text
    assert "hb_seconds_count 5" in text


def test_build_stats_alias_stays_in_sync_with_registry():
    from h2o3_tpu.models.tree import shared_tree as st

    st.reset_build_stats()
    st.BUILD_STATS["dispatches"] += 2
    assert metrics.counter_value("tree_dispatches_total") == 2
    # registry-side bump is visible through the alias too — one source of truth
    metrics.counter("tree_dispatches_total").inc(1)
    assert st.BUILD_STATS["dispatches"] == 3
    snap = st.reset_build_stats()
    assert snap["dispatches"] == 3
    assert st.BUILD_STATS["dispatches"] == 0
    assert metrics.counter_value("tree_dispatches_total") == 0


def test_span_nesting_reconstructs_tree_under_concurrent_jobs():
    metrics.reset_spans()

    def work(trace_id, tag):
        with metrics.trace(trace_id):
            with metrics.span(f"outer.{tag}"):
                with metrics.span(f"mid.{tag}"):
                    with metrics.span(f"leaf.{tag}"):
                        time.sleep(0.01)
                with metrics.span(f"leaf2.{tag}"):
                    pass

    threads = [
        threading.Thread(target=work, args=(f"job_t{i}", f"t{i}"))
        for i in range(3)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for i in range(3):
        evs = metrics.trace_events(f"job_t{i}")
        by_name = {e["name"]: e for e in evs}
        # only this job's spans — no cross-thread contamination
        assert set(by_name) == {f"outer.t{i}", f"mid.t{i}",
                                f"leaf.t{i}", f"leaf2.t{i}"}
        assert by_name[f"outer.t{i}"]["parent"] is None
        assert by_name[f"mid.t{i}"]["parent"] == by_name[f"outer.t{i}"]["id"]
        assert by_name[f"leaf.t{i}"]["parent"] == by_name[f"mid.t{i}"]["id"]
        # sibling after a closed child re-parents to mid's PARENT level
        assert by_name[f"leaf2.t{i}"]["parent"] == by_name[f"outer.t{i}"]["id"]
        assert by_name[f"leaf.t{i}"]["dur_s"] >= 0.01


def test_metrics_disabled_is_inert():
    metrics.set_enabled(False)
    try:
        c = metrics.counter("gated_total", "x")
        base = c.value()
        c.inc(5)
        assert c.value() == base
        with metrics.span("gated.span"):
            pass
        assert all(
            e["name"] != "gated.span" for e in metrics.recent_spans(1000)
        )
        # always-on counters (the BUILD_STATS contract) keep counting
        from h2o3_tpu.models.tree import shared_tree as st

        st.reset_build_stats()
        st.BUILD_STATS["trees_built"] += 4
        assert st.reset_build_stats()["trees_built"] == 4
    finally:
        metrics.set_enabled(True)


# ---------------------------------------------------------------------------
# REST serving


def test_metrics_endpoint_prometheus_and_json(server):
    fr = _upload_frame(key="metrics_train_a")
    # touch GLM + GBM + persist + cluster so families from every subsystem
    # exist (the live-endpoint acceptance: >= 10 families across REST,
    # tree-build, GLM, persist, cluster)
    from h2o3_tpu.models.glm import GLM
    from h2o3_tpu.models.tree import GBM

    GBM(ntrees=2, max_depth=3, seed=1).train(y="y", training_frame=fr)
    GLM(family="binomial", lambda_=1e-4, max_iterations=3).train(
        y="y", training_frame=fr)
    _get_json(server, "/3/Cloud")

    text, ctype = _get_text(server, "/3/Metrics")
    assert ctype.startswith("text/plain")
    families = {
        m.group(1): m.group(2)
        for m in re.finditer(r"^# TYPE ([a-zA-Z0-9_:]+) (\w+)$", text, re.M)
    }
    for fam in ("rest_requests_total", "rest_request_seconds",
                "rest_requests_in_flight", "tree_dispatches_total",
                "tree_trees_built_total", "tree_programs_compiled_total",
                "glm_irls_iterations_total", "glm_irls_iteration_seconds",
                "persist_retries_total", "cloud_healthy", "jobs_total",
                "span_seconds", "mrtask_dispatches_total",
                "models_built_total"):
        assert fam in families, f"{fam} missing from /3/Metrics"
    assert len(families) >= 10
    assert families["rest_request_seconds"] == "histogram"
    assert families["rest_requests_in_flight"] == "gauge"
    # sample values present for the instrumented request counter
    assert re.search(r'^rest_requests_total\{.*route=.*\} \d+$', text, re.M)

    j = _get_json(server, "/3/Metrics?format=json")
    assert j["__meta"]["schema_type"] == "Metrics"
    assert "rest_requests_total" in j["families"]
    assert j["families"]["rest_requests_total"]["type"] == "counter"


def test_job_trace_endpoint_serves_chrome_trace_with_nested_builds(server):
    _upload_frame(key="metrics_train_b")
    resp = _post(server, "/3/ModelBuilders/gbm", {
        "training_frame": "metrics_train_b", "response_column": "y",
        "ntrees": 3, "max_depth": 3, "seed": 7,
    })
    key = resp["job"]["key"]["name"]
    j = _wait_job(server, key)
    assert j["status"] == "DONE", j

    # the per-job resource ledger rides the /3/Jobs wire schema (the
    # budget signal a fleet scheduler reads): device-seconds and the
    # tree-dispatch counts this build just spent
    led = j.get("ledger")
    assert led, "no ledger block on /3/Jobs"
    assert led["device_seconds"] > 0
    assert led["dispatches"].get("tree", 0) >= 1

    trace = _get_json(server, f"/3/Jobs/{key}/trace")
    evs = trace["traceEvents"]
    assert isinstance(evs, list) and evs, trace
    complete = [e for e in evs if e.get("ph") == "X"]
    for e in complete:
        assert {"name", "ts", "dur", "pid", "tid", "args"} <= set(e)
    names = {e["name"] for e in complete}
    assert "job" in names
    assert "gbm.build_tree" in names, names
    # nesting reconstructs: every build span's parent chain reaches the root
    ids = {e["args"]["span_id"]: e for e in complete}
    build = next(e for e in complete if e["name"] == "gbm.build_tree")
    seen = set()
    cur = build
    while cur["args"]["parent_id"] is not None:
        assert cur["args"]["parent_id"] in ids, "broken parent chain"
        assert cur["args"]["parent_id"] not in seen, "parent cycle"
        seen.add(cur["args"]["parent_id"])
        cur = ids[cur["args"]["parent_id"]]
    assert cur["name"] == "job"

    # 404 for unknown jobs
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get_json(server, "/3/Jobs/nope_123/trace")
    assert ei.value.code == 404


def test_job_schema_reports_stable_duration(server):
    _upload_frame(key="metrics_train_c")
    resp = _post(server, "/3/ModelBuilders/gbm", {
        "training_frame": "metrics_train_c", "response_column": "y",
        "ntrees": 2, "max_depth": 2, "seed": 3,
    })
    key = resp["job"]["key"]["name"]
    j1 = _wait_job(server, key)
    assert j1["status"] == "DONE"
    assert j1["started_at"] > 0
    assert j1["duration_ms"] > 0
    time.sleep(0.05)
    j2 = _get_json(server, f"/3/Jobs/{key}")["jobs"][0]
    # finished: duration frozen at end_time, identical across polls
    assert j2["duration_ms"] == j1["duration_ms"]
    assert j2["started_at"] == j1["started_at"]
    # the per-phase rollup covers the build
    assert "span_summary" in j2 and "job" in j2["span_summary"]
    assert j2["span_summary"]["job"]["total_ms"] > 0


def test_logs_route_tails_and_filters_by_level(server):
    from h2o3_tpu.utils.log import Log

    Log.warn("metrics-test warn line")
    Log.info("metrics-test info line")
    out = _get_json(server, "/3/Logs?n=200")
    assert out["count"] == len(out["lines"]) > 0
    assert any("metrics-test info line" in ln for ln in out["lines"])
    warn_only = _get_json(server, "/3/Logs?n=200&level=WARN")
    assert any("metrics-test warn line" in ln for ln in warn_only["lines"])
    assert not any("metrics-test info line" in ln for ln in warn_only["lines"])
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get_json(server, "/3/Logs?level=NOPE")
    assert ei.value.code == 400


def test_timeline_merges_span_events(server):
    from h2o3_tpu.utils import telemetry

    telemetry.record("test", "timeline merge marker")
    with metrics.span("timeline.merge.probe"):
        pass
    tl = _get_json(server, "/3/Timeline?n=500")
    kinds = {e["kind"] for e in tl["events"]}
    assert "span" in kinds
    assert isinstance(tl["compile_count"], int)
    assert tl["span_count"] >= 1
    span_evs = [e for e in tl["events"] if e["kind"] == "span"]
    assert any(e["msg"] == "timeline.merge.probe" for e in span_evs)
    assert all("dur_ms" in e for e in span_evs)


def test_timeline_compile_count_consistent_under_concurrent_records():
    """The satellite-fix regression: timeline() counting from the live deque
    while another thread records raced (RuntimeError: deque mutated during
    iteration). Hammer it."""
    from h2o3_tpu.utils import telemetry

    stop = threading.Event()
    errors = []

    def recorder():
        while not stop.is_set():
            telemetry.record("compile", "x")

    def reader():
        try:
            for _ in range(300):
                tl = telemetry.timeline(50)
                assert tl["compile_count"] >= 0
        except Exception as e:  # the pre-fix failure mode
            errors.append(e)

    t1 = threading.Thread(target=recorder)
    t2 = threading.Thread(target=reader)
    t1.start(); t2.start()
    t2.join(); stop.set(); t1.join()
    assert not errors, errors


def test_persist_retry_bumps_counter_and_logs(monkeypatch, tmp_path):
    from h2o3_tpu import persist
    from h2o3_tpu.utils.log import Log

    monkeypatch.setenv("H2O3_TPU_PERSIST_RETRIES", "3")
    monkeypatch.setenv("H2O3_TPU_PERSIST_BACKOFF", "0.0")
    before = metrics.counter_value("persist_retries_total", op="write")
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise OSError("transient blip")
        return "done"

    assert persist._with_retries(flaky, "write /tmp/flaky-probe") == "done"
    after = metrics.counter_value("persist_retries_total", op="write")
    assert after - before == 2
    tail = "\n".join(Log.tail(50, level="WARN"))
    assert "flaky-probe" in tail and "retrying" in tail


# ---------------------------------------------------------------------------
# model metrics: the device-statistics path (models/metrics.py) against the
# exact host path. On an accelerator every tree model and GLM report the
# device path's numbers; here its functions are called directly on the CPU.

_N_PARITY = 200_000


@pytest.fixture(scope="module")
def logit_sample():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(_N_PARITY, 3))
    eta = x @ np.array([1.2, -0.8, 0.4]) - 0.2
    p = (1.0 / (1.0 + np.exp(-eta))).astype(np.float32)
    y = (rng.random(_N_PARITY) < p).astype(np.float64)
    return {"x": x, "eta": eta, "p": p, "y": y,
            "w": rng.uniform(0.5, 2.0, _N_PARITY)}


def _parity_inputs(s, case):
    """(actual, weights, binomial probability) of one case."""
    y, w, p = s["y"].copy(), None, s["p"].copy()
    if case != "unweighted":
        w = s["w"].copy()
    if case == "nan_labels_zero_weights":
        y[::17] = np.nan
        w[::13] = 0.0
    elif case == "saturated":  # a saturated sigmoid, right and wrong
        p[:100] = 0.0
        p[100:200] = 1.0
    elif case == "constant":
        p[:] = 0.3
    return y, w, p


def _rel(a, b):
    return abs(a - b) / max(abs(a), 1e-30)


@pytest.mark.parametrize("kind,case", [
    ("binomial", "unweighted"), ("binomial", "weighted"),
    ("binomial", "nan_labels_zero_weights"), ("binomial", "saturated"),
    ("binomial", "constant"),
    ("regression", "unweighted"), ("regression", "weighted"),
    ("regression", "nan_labels_zero_weights"),
    ("multinomial", "unweighted"), ("multinomial", "weighted"),
    ("multinomial", "nan_labels_zero_weights"),
])
def test_device_statistics_match_the_host_path(logit_sample, kind, case):
    from h2o3_tpu.models import metrics as MM

    s = logit_sample
    y, w, p = _parity_inputs(s, case)
    sw = float(np.sum((np.ones_like(y) if w is None else w)[~np.isnan(y)]))
    if kind == "binomial":
        host = MM.binomial_metrics(y, p, w)
        dev = MM._binomial_metrics_device(y, p, w, ("0", "1"))
        assert np.isfinite(dev.logloss)
        assert np.sum(dev.confusion_matrix) == pytest.approx(sw, rel=1e-6)
        if case == "constant":
            # every threshold up to the score predicts the same: which one
            # stands for them (and PR-AUC's tie order) is each path's own
            assert dev.ks == 0.0 and dev.auc == 0.5
        else:
            assert abs(dev.auc - host.auc) < 1e-4
            assert abs(dev.pr_auc - host.pr_auc) < 1e-3
            assert abs(dev.ks - host.ks) < 1e-3
            assert (abs(dev.default_threshold - host.default_threshold)
                    <= 1 / 400 + 1 / 1024)
        scalars = ("logloss", "mse")
    elif kind == "regression":
        a = np.where(np.isnan(y), np.nan, np.random.default_rng(3).poisson(
            np.exp(0.3 * s["eta"])).astype(np.float64))
        mu = np.exp(0.3 * s["eta"]).astype(np.float32)
        host = MM.regression_metrics(a, mu, w, "poisson")
        dev = MM._regression_metrics_device(a, mu, w, "poisson")
        scalars = ("mse", "mae", "rmsle", "r2", "mean_residual_deviance")
    else:
        z = np.stack([s["eta"], -s["eta"], 0.5 * s["x"][:, 0]], axis=1)
        P = np.exp(z - z.max(axis=1, keepdims=True))
        P = (P / P.sum(axis=1, keepdims=True)).astype(np.float32)
        cls = (np.random.default_rng(4).random(_N_PARITY)[:, None]
               > np.cumsum(P, axis=1)).sum(axis=1).clip(0, 2)
        yk = np.where(np.isnan(y), -1, cls).astype(np.int64)
        host = MM.multinomial_metrics(yk, P, w)
        dev = MM._multinomial_metrics_device(yk, P, w, ())
        assert np.sum(dev.confusion_matrix) == pytest.approx(sw, rel=1e-6)
        np.testing.assert_allclose(dev.confusion_matrix, host.confusion_matrix,
                                   rtol=1e-5)
        np.testing.assert_allclose(dev.hit_ratios, host.hit_ratios, rtol=2e-6)
        scalars = ("logloss", "mse", "classification_error",
                   "mean_per_class_error")
    assert dev.nobs == host.nobs
    for name in scalars:
        assert _rel(host.value(name), dev.value(name)) < 2e-6, name


def test_device_log_is_float32_accurate():
    """The device statistics take their logs from a polynomial (the TPU's
    own ``log`` reads low by 2e-6 in the mean): an ulp or two from float64
    over every magnitude a clipped probability has, exact at 1."""
    from h2o3_tpu.models import metrics as MM

    x = np.concatenate([
        np.logspace(-15, 0, 20001), 1 - np.logspace(-7.2, -0.3, 5001),
        np.random.default_rng(0).random(50000), [1e-15, 0.5, np.sqrt(0.5), 1.0],
    ]).astype(np.float32)
    got = np.asarray(MM._log_f32(x), np.float64)
    want = np.log(x.astype(np.float64))
    assert got[-1] == 0.0
    assert np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-7)) < 3e-7
    assert abs(np.mean(got - want)) < 2e-9
