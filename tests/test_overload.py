"""Overload-survival plane tests (ISSUE 19): memory-aware admission with
per-job HBM reservations, streamed-lane auto-routing, the REST memory gate
and admission storm behavior, RESOURCE_EXHAUSTED catch-and-degrade, the
dispatch hang watchdog, and the H2O3_TPU_OVERLOAD=0 pre-overload pin.

The CPU proxy's devices report no ``memory_stats``, so every headroom-
dependent check injects synthetic stats through ``devmem._stats_fn`` (the
one real call site) and force-polls — no mocks of the plane itself."""

import contextlib
import json
import os
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pandas as pd
import pytest

from h2o3_tpu.cluster import cloud, recovery
from h2o3_tpu.frame import chunkstore as cs
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models import GBM
from h2o3_tpu.utils import devmem, faults, flightrec, overload
from h2o3_tpu.utils import metrics as mx

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean(monkeypatch, tmp_path):
    monkeypatch.setenv("H2O3_TPU_INCIDENT_DIR", str(tmp_path / "incidents"))
    monkeypatch.setenv("H2O3_TPU_RECOVERY", "1")
    monkeypatch.setenv("H2O3_TPU_RECOVERY_BACKOFF", "0.01")
    monkeypatch.setenv("H2O3_TPU_OVERLOAD", "1")
    flightrec._reset_incidents_for_tests()
    overload._reset_for_tests()
    cloud.clear_degraded()
    yield
    faults.reset()
    overload._reset_for_tests()
    flightrec._HUNG_SPANS.clear()  # synthetic ring spans must not leak into
    for k in list(devmem.reservations()):  # the live span-id sequence
        devmem.release(k)
    cloud.clear_degraded()


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


@contextlib.contextmanager
def _synthetic_stats(in_use, limit):
    """Route devmem's one memory_stats call site through synthetic numbers
    (per local device), force-poll, and restore the proxy's honest None."""
    orig = devmem._stats_fn
    devmem._stats_fn = lambda d: {"bytes_in_use": int(in_use),
                                  "bytes_limit": int(limit)}
    devmem.poll(force=True)
    try:
        yield
    finally:
        devmem._stats_fn = orig
        devmem.poll(force=True)


def _df(n=800, seed=3):
    rng = np.random.default_rng(seed)
    df = pd.DataFrame({
        "a": rng.normal(size=n),
        "b": rng.normal(size=n),
        "c": rng.choice(["x", "y", "z"], n),
    })
    eta = df["a"] * 1.5 + (df["c"] == "x") * 2 - df["b"]
    df["y"] = np.where(eta + rng.normal(size=n) > 0, "p", "n")
    return df


# ---------------------------------------------------------------------------
# admission preflight: resident / streamed / shed routing + reservations


def test_capacity_model_shapes():
    # the admission preflight and tools/tpu_mem_analysis.py share one model
    assert overload.per_row_device_bytes(32, "gbm", compressed=True) == \
        32 + overload.STATE_BYTES
    assert overload.per_row_device_bytes(32, "gbm", compressed=False) == \
        32 * 5 + overload.STATE_BYTES
    assert overload.per_row_device_bytes(10, "glm") == (10 + 3) * 4
    fr = Frame.from_pandas(_df(200))
    est = overload.estimate_build_bytes(fr, "gbm")
    assert est >= fr.npad  # at least one byte/row of binned codes


def test_admit_routes_and_reservation_ledger():
    # per device: limit 1 GiB, in_use 0.5 GiB -> 8 x 0.5 GiB = 4 GiB headroom
    with _synthetic_stats(in_use=1 << 29, limit=1 << 30):
        head = devmem.headroom()
        assert head == pytest.approx(8 * (1 << 29))
        avail = head * 0.7
        # small footprint: resident, full-footprint reservation
        assert overload.admit("job_small", 1 << 20, "gbm") == "resident"
        assert devmem.reservations()["job_small"] == float(1 << 20)
        # huge footprint + compression on: streamed with a headroom window
        with _env(H2O3_TPU_FRAME_COMPRESS="1"):
            assert overload.admit("job_big", 100 << 30, "gbm") == "streamed"
        win = devmem.reservations()["job_big"]
        assert 4 << 20 <= win <= avail
        # reservation gauge publishes per-job series
        snap = mx.REGISTRY.snapshot()["hbm_reserved_bytes"]
        jobs = {v["labels"].get("job") for v in snap["values"]}
        assert {"job_small", "job_big"} <= jobs
        # fits nowhere (streaming unavailable): shed with honest Retry-After
        with _env(H2O3_TPU_FRAME_COMPRESS="0"):
            with pytest.raises(overload.Shed) as ei:
                overload.admit("job_doomed", 100 << 30, "gbm")
        assert ei.value.retry_after >= 1.0
        assert "job_doomed" not in devmem.reservations()
        # release: sums return to zero and the gauge series disappear
        overload.finish("job_small")
        overload.finish("job_big")
        overload.finish("job_big")  # idempotent
        assert devmem.reservations() == {}
        assert devmem.reserved_total() == 0.0
        snap = mx.REGISTRY.snapshot()["hbm_reserved_bytes"]
        assert not [v for v in snap["values"]
                    if v["labels"].get("job") in ("job_small", "job_big")]


def test_admit_unmeasured_headroom_still_reserves():
    # CPU proxy devices report no stats: admitted resident, but the
    # reservation (and so the hold-time estimator) still works
    assert devmem.headroom() is None
    assert overload.admit("job_cpu", 123456, "gbm") == "resident"
    assert devmem.reservations() == {"job_cpu": 123456.0}
    with overload.job_scope("job_other"):
        pass  # scope releases on exit
    overload.finish("job_cpu")
    assert devmem.reservations() == {}


def test_retry_after_scales_with_queue_depth():
    # no completed holds yet: the 5 s prior, clamped to >= 1
    assert overload.retry_after_estimate() == pytest.approx(5.0)
    # finish() feeds the measured hold time into the estimator
    overload._reserve("held", 1)
    time.sleep(0.02)
    overload.finish("held")
    with overload._HOLD_LOCK:
        assert len(overload._HOLDS) == 1 and overload._HOLDS[0] >= 0.02
        overload._HOLDS[0] = 2.0  # deterministic mean for the math below
    assert overload.retry_after_estimate() == pytest.approx(2.0)
    # a deeper live reservation queue means a longer advertised wait
    devmem.reserve("q1", 1)
    devmem.reserve("q2", 1)
    devmem.reserve("q3", 1)
    assert overload.retry_after_estimate() == pytest.approx(6.0)
    # and the estimate clamps into [1, 120]
    with overload._HOLD_LOCK:
        overload._HOLDS[0] = 90.0
    assert overload.retry_after_estimate() == pytest.approx(120.0)
    for k in ("q1", "q2", "q3"):
        devmem.release(k)


def test_job_scope_releases_on_error():
    with pytest.raises(RuntimeError):
        with overload.job_scope("job_err"):
            devmem.reserve("job_err", 7)
            raise RuntimeError("boom")
    assert "job_err" not in devmem.reservations()


# ---------------------------------------------------------------------------
# streamed-lane routing: plan_window + ChunkStore.plan


def test_plan_window_autoroutes_and_excludes_own_reservation():
    with _synthetic_stats(in_use=1 << 29, limit=1 << 30):
        head = devmem.headroom()
        avail = head * 0.7
        # fits the usable share: no override, resident lane
        assert overload.plan_window(avail * 0.5, 0) is None
        # exceeds it: headroom-derived window, at least the 4 MiB floor
        win = overload.plan_window(avail * 4, 0)
        assert win is not None and win >= 4 << 20 and win <= avail
        # an operator window always wins over the auto-route
        assert overload.plan_window(avail * 4, 8 << 20) is None
        # another job's reservation shrinks the share ...
        devmem.reserve("hog", int(avail))
        assert overload.plan_window(avail * 0.5, 0) is not None
        # ... but a job's OWN reservation must not push it to streaming
        with overload.job_scope("hog"):
            assert overload.plan_window(avail * 0.5, 0) is None
        assert devmem.reservations() == {}  # job_scope released "hog"


def test_plan_window_degrade_scope_halves():
    need = 100 << 20
    with overload.degrade_scope():
        assert overload.degrade_active()
        # previously streaming: half the static window
        assert overload.plan_window(need, 8 << 20) == 4 << 20
        # previously resident: half the frame's own footprint
        assert overload.plan_window(need, 0) == need // 2
    assert not overload.degrade_active()
    # outside the scope, no headroom measured: legacy static policy
    assert overload.plan_window(need, 8 << 20) is None


def test_chunkstore_plan_consults_overload_window():
    with _synthetic_stats(in_use=1 << 29, limit=1 << 30):
        avail = devmem.headroom() * 0.7
        npad = 1 << 20
        bpr = max(int(avail * 4 // npad), 8)  # footprint ~4x the usable share
        with _env(H2O3_TPU_HBM_WINDOW_BYTES="0", H2O3_TPU_FRAME_COMPRESS="1"):
            # no static knob: the auto-route streams through a measured-
            # headroom window instead of OOMing resident
            st = cs.ChunkStore.plan(npad, bpr)
            assert st is not None and st.n_blocks > 1
            assert st.window <= avail
            # plane off: the same frame runs resident, exactly as before
            with _env(H2O3_TPU_OVERLOAD="0"):
                assert cs.ChunkStore.plan(npad, bpr) is None


def test_plan_window_disabled_pins_legacy():
    with _env(H2O3_TPU_OVERLOAD="0"):
        assert overload.admit("job_off", 1 << 40, "gbm") == "off"
        assert devmem.reservations() == {}
        with _synthetic_stats(in_use=1 << 29, limit=1 << 30):
            assert overload.plan_window(1 << 40, 0) is None
        with overload.degrade_scope():
            assert overload.plan_window(1 << 40, 0) is None
        assert overload.watchdog_pass() == []


# ---------------------------------------------------------------------------
# REST admission: inflight storm + the memory gate


def _post_status(url, path, payload):
    """POST form-encoded; return (status, retry_after, reason)."""
    data = urllib.parse.urlencode(payload or {}).encode()
    req = urllib.request.Request(url + path, data=data, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, None, None
    except urllib.error.HTTPError as e:
        ra = e.headers.get("Retry-After")
        try:
            reason = json.loads(e.read()).get("reason")
        except Exception:  # noqa: BLE001 — status is the assertion target
            reason = None
        return e.code, (float(ra) if ra else None), reason


def test_rest_admission_storm_sheds_and_recovers():
    from h2o3_tpu.api.server import start_server

    srv = start_server(port=0)
    with _env(H2O3_TPU_MAX_INFLIGHT="2"):
        faults.configure(slow={"rest": 0.6})
        try:
            results = []
            bar = threading.Barrier(6)

            def _one(i):
                bar.wait()
                results.append(_post_status(
                    srv.url, "/3/CreateFrame",
                    {"dest": f"ovst_{i}", "rows": 50, "cols": 2, "seed": i}))

            ts = [threading.Thread(target=_one, args=(i,)) for i in range(6)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
        finally:
            faults.reset()
        statuses = [s for s, _, _ in results]
        assert statuses.count(200) >= 1      # capacity serves
        shed = [r for r in results if r[0] != 200]
        assert shed                           # excess is shed, not queued
        for s, ra, reason in shed:
            assert s in (429, 503)
            assert ra is not None and ra >= 1.0
            assert reason in ("inflight_full", "queue_full", "memory",
                              "draining", "job_queue_full")
    # the storm leaves no reservation behind and the server still serves
    assert devmem.reservations() == {}
    s, _, _ = _post_status(srv.url, "/3/CreateFrame",
                           {"dest": "ovst_after", "rows": 50, "cols": 2})
    assert s == 200


def test_rest_memory_gate_closes_and_reopens():
    from h2o3_tpu.api.server import start_server

    srv = start_server(port=0)
    payload = {"dest": "ovmem", "rows": 50, "cols": 2}
    with _env(H2O3_TPU_ADMIT_MIN_HEADROOM_BYTES=str(64 << 20)):
        # zero measured headroom: every mutating request sheds 503 "memory"
        with _synthetic_stats(in_use=8 << 30, limit=8 << 30):
            s, ra, reason = _post_status(srv.url, "/3/CreateFrame", payload)
            assert s == 503 and reason == "memory"
            assert ra is not None and ra >= 1.0
            assert mx.counter_value("rest_rejected_total", method="POST",
                                    route="/3/CreateFrame",
                                    reason="memory") >= 1
        # stats gone (unmeasured headroom): the gate must not trip on stale
        # numbers — the CPU proxy is never memory-gated
        s, _, _ = _post_status(srv.url, "/3/CreateFrame", payload)
        assert s == 200


def test_client_retries_memory_shed_with_retry_after_floor():
    from h2o3_tpu.api.server import start_server
    from h2o3_tpu.client import H2OClientError, H2OConnection

    srv = start_server(port=0)
    # a short measured hold keeps the computed Retry-After at the 1 s clamp
    with overload._HOLD_LOCK:
        overload._HOLDS.append(0.5)
    orig = devmem._stats_fn
    with _env(H2O3_TPU_ADMIT_MIN_HEADROOM_BYTES=str(64 << 20)):
        devmem._stats_fn = lambda d: {"bytes_in_use": 8 << 30,
                                      "bytes_limit": 8 << 30}
        devmem.poll(force=True)
        try:
            # the machine-readable shed surfaces on a no-retry client
            conn = H2OConnection(srv.url, retries=0)
            with pytest.raises(H2OClientError) as ei:
                conn.post("/3/CreateFrame",
                          {"dest": "cm0", "rows": 50, "cols": 2})
            err = ei.value
            assert err.status == 503 and err.reason == "memory"
            assert err.retry_after is not None and err.retry_after >= 1.0
            # the computed Retry-After floors the client's tiny backoff
            conn.retries = 8
            conn.retry_backoff = 0.01
            assert conn._backoff_delay("/x", 0,
                                       err.retry_after) >= err.retry_after
            # gate reopens while the client backs off: the retry lands
            def _reopen():
                time.sleep(0.3)
                devmem._stats_fn = orig
                devmem.poll(force=True)

            threading.Thread(target=_reopen, daemon=True).start()
            out = conn.post("/3/CreateFrame",
                            {"dest": "cm1", "rows": 50, "cols": 2})
            assert out.get("key") or out.get("job")  # served post-reopen
        finally:
            devmem._stats_fn = orig
            devmem.poll(force=True)


# ---------------------------------------------------------------------------
# OOM catch-and-degrade: one supervised retry under the degrade scope


def test_oom_degrades_once_and_matches_clean_run(tmp_path):
    fr = Frame.from_pandas(_df())
    kw = dict(max_depth=3, seed=11, learn_rate=0.2, score_tree_interval=2)
    full = GBM(ntrees=6, **kw).train(y="y", training_frame=fr)

    ckdir = str(tmp_path / "oomck")
    g0 = cloud.generation()
    retried0 = mx.counter_value("oom_degrades_total", site="tree",
                                outcome="retried")
    recovered0 = mx.counter_value("oom_degrades_total", site="tree",
                                  outcome="recovered")

    def _launch(ckpt):
        kw2 = dict(kw, export_checkpoints_dir=ckdir)
        if ckpt:
            kw2["checkpoint"] = ckpt
        return GBM(ntrees=6, **kw2).train(y="y", training_frame=fr)

    with faults.inject(oom={"tree"}):
        healed = recovery.run_supervised(_launch, ckdir=ckdir, algo="gbm",
                                         description="oom degrade drill")
    # degrade-once, NOT a reform: generation must not tick
    assert cloud.generation() == g0
    assert cloud.degraded_reason() is None
    assert healed.output["ntrees_actual"] == 6
    np.testing.assert_allclose(healed.training_metrics.logloss,
                               full.training_metrics.logloss, atol=1e-6)
    pa = full.predict(fr).vec("p").to_numpy()
    pb = healed.predict(fr).vec("p").to_numpy()
    np.testing.assert_allclose(pa, pb, atol=1e-5)
    assert mx.counter_value("oom_degrades_total", site="tree",
                            outcome="retried") == retried0 + 1
    assert mx.counter_value("oom_degrades_total", site="tree",
                            outcome="recovered") == recovered0 + 1
    # the incident bundle froze the dying state and names the OOM site
    path = flightrec.last_incident()
    assert path and os.path.exists(path)
    with open(path) as f:
        bundle = json.load(f)
    assert bundle["trigger"] == "oom"
    assert "'tree'" in bundle["reason"]
    # the ring kept the classification and the degrade record
    assert [e for e in flightrec.events(kind="oom") if e["site"] == "tree"]
    assert [e for e in flightrec.events(kind="oom_degrade")
            if e.get("site") == "tree"]


def test_oom_disabled_plane_surfaces_error(tmp_path):
    fr = Frame.from_pandas(_df(300, seed=9))

    def _launch(ckpt):
        return GBM(ntrees=4, max_depth=2, seed=1,
                   score_tree_interval=2).train(y="y", training_frame=fr)

    with _env(H2O3_TPU_OVERLOAD="0"):
        with faults.inject(oom={"tree"}):
            with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
                recovery.run_supervised(_launch, description="oom off")
    assert cloud.degraded_reason() is None  # no latch: plain job failure


def test_is_oom_classification():
    assert overload.is_oom(RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    assert overload.is_oom(RuntimeError("Resource_Exhausted allocating"))
    assert not overload.is_oom(RuntimeError("invalid argument"))
    assert overload.oom_site(RuntimeError("invalid argument")) is None


# ---------------------------------------------------------------------------
# dispatch hang watchdog: ring-driven trips with an injectable clock


def _seed_site(site, n, dur_ms, span0=0):
    for i in range(n):
        flightrec.record("dispatch_start", site=site, span=span0 + i)
        flightrec.record("dispatch_end", site=site, span=span0 + i,
                         dur_ms=dur_ms)


def test_watchdog_trips_overdue_dispatch_once():
    overload.uninstall_watchdog()  # the ring walk below owns the clock
    flightrec.reset()
    _seed_site("wd_site", 3, dur_ms=100.0)       # baseline mean 0.1 s
    flightrec.record("dispatch_start", site="wd_site", span=991)
    with _env(H2O3_TPU_HANG_MIN_SECS="0.5", H2O3_TPU_HANG_FACTOR="8"):
        hangs0 = mx.counter_value("dispatch_hangs_total", site="wd_site")
        trips = overload.watchdog_pass(now=time.time() + 5.0)
        assert len(trips) == 1
        t = trips[0]
        assert t["site"] == "wd_site" and t["span"] == 991
        assert t["budget_s"] == pytest.approx(0.8, abs=0.01)  # 8 x 0.1 s
        assert t["age_s"] > t["budget_s"]
        # the trip's full blast radius: counter, gauge, ring, latch, bundle
        assert mx.counter_value("dispatch_hangs_total",
                                site="wd_site") == hangs0 + 1
        snap = mx.REGISTRY.snapshot()["dispatch_hung"]
        hung = {v["labels"].get("site"): v["value"] for v in snap["values"]}
        assert hung["wd_site"] > 0
        assert [e for e in flightrec.events(kind="watchdog_trip")
                if e["site"] == "wd_site"]
        reason = cloud.degraded_reason()
        assert reason and "wd_site" in reason and "wedged" in reason
        with open(flightrec.last_incident()) as f:
            assert json.load(f)["trigger"] == "hang"
        # same pass again: the span trips exactly once
        assert overload.watchdog_pass(now=time.time() + 6.0) == []
        assert mx.counter_value("dispatch_hangs_total",
                                site="wd_site") == hangs0 + 1
        # the span closes (late unwedge): the hung gauge clears to 0
        flightrec.record("dispatch_end", site="wd_site", span=991,
                         dur_ms=5000.0, error="RuntimeError")
        overload.watchdog_pass(now=time.time() + 7.0)
        snap = mx.REGISTRY.snapshot()["dispatch_hung"]
        hung = {v["labels"].get("site"): v["value"] for v in snap["values"]}
        assert hung["wd_site"] == 0.0
    flightrec.reset()


def test_watchdog_floor_guards_first_compile():
    overload.uninstall_watchdog()  # the ring walk below owns the clock
    flightrec.reset()
    # < 3 completed dispatches: the rolling mean is untrusted — only the
    # floor applies, so a legitimately long first compile never false-trips
    _seed_site("wd_new", 2, dur_ms=10.0)
    flightrec.record("dispatch_start", site="wd_new", span=992)
    with _env(H2O3_TPU_HANG_MIN_SECS="120", H2O3_TPU_HANG_FACTOR="8"):
        assert overload.watchdog_pass(now=time.time() + 60.0) == []
        # a seasoned site with the same tiny baseline WOULD have tripped,
        # but still not before the floor
        _seed_site("wd_old", 3, dur_ms=10.0, span0=100)
        flightrec.record("dispatch_start", site="wd_old", span=993)
        assert overload.watchdog_pass(now=time.time() + 60.0) == []
        # past the floor both trip — the floor is the young site's only guard
        trips = overload.watchdog_pass(now=time.time() + 125.0)
        assert {t["site"] for t in trips} == {"wd_old", "wd_new"}
    flightrec.reset()


def test_hung_span_fail_stops_at_dispatch_exit():
    # a dispatch the watchdog declared wedged must not return its late
    # result: the exit raises the degraded fail-stop the supervisor owns
    d = flightrec.dispatch("wd_failstop")
    with pytest.raises(RuntimeError, match="fail-stop"):
        with d:
            # the open dispatch is the active span (metrics.OpenSpan)
            flightrec.mark_span_hung(mx.current_span())
    ends = [e for e in flightrec.events(kind="dispatch_end")
            if e["site"] == "wd_failstop"]
    assert ends  # the span still closed in the ring


def test_watchdog_thread_install_uninstall_idempotent():
    overload.install_watchdog()
    overload.install_watchdog()
    names = [t.name for t in threading.enumerate()]
    assert names.count("h2o3-hang-watchdog") == 1
    overload.uninstall_watchdog()
    overload.uninstall_watchdog()
    assert "h2o3-hang-watchdog" not in [t.name for t in threading.enumerate()]


# ---------------------------------------------------------------------------
# the overload metric families bypass the H2O3_TPU_METRICS gate


def test_overload_metrics_record_while_metrics_disabled():
    gated = mx.counter("overload_test_gated", "a normal gated counter")
    mx.set_enabled(False)
    try:
        gated.inc(k="v")
        overload.count_degrade("mx_site", "retried")
        devmem.reserve("mx_job", 42)
        snap = mx.REGISTRY.snapshot()
        # the gated counter recorded nothing while disabled ...
        assert all(v["value"] == 0.0
                   for v in snap["overload_test_gated"]["values"])
        # ... while the always-on overload families kept counting
        assert mx.counter_value("oom_degrades_total", site="mx_site",
                                outcome="retried") == 1
        res = {v["labels"].get("job"): v["value"]
               for v in snap["hbm_reserved_bytes"]["values"]}
        assert res["mx_job"] == 42.0
    finally:
        mx.set_enabled(True)
        devmem.release("mx_job")
