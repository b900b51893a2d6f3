"""Device-memory ledger + incident flight recorder (ISSUE 13,
utils/devmem.py + utils/flightrec.py).

The acceptance pins:
- ledger attribution sums stay consistent under CONCURRENT frame-stream +
  serving-paging load (each owner's claim returns to its prior level, the
  window claim never exceeds the window, no owner goes negative);
- the ring is bounded and ordered under multithreaded append, and its
  append stays O(µs) (the ≤2% fused-tree span-overhead contract is a bench
  pin; the per-event cost bound here is its unit-level guard);
- an injected cloud death (faults ``die:`` at a collective boundary)
  produces an incident bundle containing the dying dispatch and the
  failing generation, with the bundle path surfaced in the job's recovery
  block — and the supervised run still heals;
- ``H2O3_TPU_METRICS=0`` keeps the ring recording and bundles writing
  (the histogram alone goes quiet);
- the attribution identity Σ owned + unattributed = in_use holds when the
  backend reports memory_stats (synthetic stats on the CPU proxy);
- ChunkStore stats land in the REGISTRY at close() (the LAST_STORE_STATS
  clobber fix) and /3/FlightRecorder serves the ring + devmem snapshot.
"""

import contextlib
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pandas as pd
import pytest

from h2o3_tpu.cluster import cloud, recovery
from h2o3_tpu.frame import chunkstore as cs
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.parallel.mesh import pad_to_shards
from h2o3_tpu.utils import devmem, faults, flightrec
from h2o3_tpu.utils import metrics as mx


@pytest.fixture(autouse=True)
def _clean(monkeypatch, tmp_path):
    monkeypatch.setenv("H2O3_TPU_INCIDENT_DIR", str(tmp_path / "incidents"))
    monkeypatch.setenv("H2O3_TPU_RECOVERY", "1")
    monkeypatch.setenv("H2O3_TPU_RECOVERY_BACKOFF", "0.01")
    flightrec._reset_incidents_for_tests()
    cloud.clear_degraded()
    yield
    faults.reset()
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


def _df(n=1500, seed=3):
    rng = np.random.default_rng(seed)
    df = pd.DataFrame({
        "a": rng.normal(size=n),
        "b": rng.normal(size=n),
        "c": rng.normal(size=n),
    })
    eta = df["a"] * 1.5 - df["b"]
    df["y"] = np.where(eta + rng.normal(size=n) > 0, "p", "n")
    return df


class _FakeScorer:
    """Minimal pageable-payload scorer for ResidencyManager tests."""

    def __init__(self, key: str, kb: int = 8):
        self.model_key = key
        self._host_args = {"w": np.ones(kb * 256, np.float32)}


# ---------------------------------------------------------------------------
# the owner ledger


def test_adjust_tracks_live_and_peak():
    o0 = devmem.owned().get("frame_resident", 0.0)
    devmem.adjust("frame_resident", 5000)
    devmem.adjust("frame_resident", -2000)
    assert devmem.owned()["frame_resident"] == pytest.approx(o0 + 3000)
    assert devmem.peaks()["frame_resident"] >= o0 + 5000
    assert mx.counter_value("hbm_owned_bytes", owner="frame_resident") == (
        pytest.approx(o0 + 3000))
    devmem.adjust("frame_resident", -3000)


def test_ledger_attribution_under_concurrent_load():
    """Frame streaming (ChunkStore window) and serving paging
    (ResidencyManager LRU) hammer the ledger from two threads: the window
    claim stays <= the window the whole time, the serving claim stays
    <= the device-LRU total, and both return their bytes at the end."""
    from h2o3_tpu.serving.residency import ResidencyManager

    base_win = devmem.owned().get("frame_window", 0.0)
    base_srv = devmem.owned().get("serving", 0.0)
    window = 16 * 1024
    npad = pad_to_shards(4096)
    errs: list = []
    over: list = []

    def _stream():
        try:
            store = cs.ChunkStore(npad, 8.0, window=window, prefetch=1)
            store.add("x", np.zeros((npad,), np.float32))
            store.add("n", np.zeros((npad,), np.int32))
            for _ in range(3):
                for _bi, blk in store.stream(("x", "n")):
                    claim = devmem.owned().get("frame_window", 0.0)
                    if claim - base_win > window + 1:
                        over.append(claim)
            store.close()
        except Exception as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    def _page():
        try:
            mgr = ResidencyManager()
            scorers = [_FakeScorer(f"m{i}") for i in range(6)]
            with _env(H2O3_TPU_SERVE_HBM_BYTES=str(3 * 8 * 1024)):
                for _ in range(4):
                    for s in scorers:
                        with mgr.hold(s):
                            pass
            for s in scorers:
                mgr.release(s)
        except Exception as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    ts = [threading.Thread(target=_stream), threading.Thread(target=_page)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not errs, errs
    assert not over, f"frame_window claim exceeded the window: {over[:3]}"
    # both planes returned their bytes: the ledger is live residency
    assert devmem.owned().get("frame_window", 0.0) == pytest.approx(
        base_win, abs=1.0)
    assert devmem.owned().get("serving", 0.0) == pytest.approx(
        base_srv, abs=1.0)
    # and the gauges never went negative
    for owner, v in devmem.owned().items():
        assert v >= -1.0, (owner, v)


def test_attribution_identity_with_synthetic_stats(monkeypatch):
    """Sigma owned + unattributed = in_use (the CPU proxy's devices report
    memory_stats()=None, so the identity is pinned with injected stats)."""
    devmem.adjust("serving", 10_000)
    try:
        owned_total = sum(devmem.owned().values())
        fake = {"bytes_in_use": int(owned_total + 70_000),
                "peak_bytes_in_use": int(owned_total + 90_000),
                "bytes_limit": int(owned_total + 1_000_000)}
        monkeypatch.setattr(devmem, "_stats_fn",
                            lambda d: fake if d.id == 0 else None)
        devmem.poll(force=True)
        s = devmem.status()
        assert s["in_use_bytes"] == fake["bytes_in_use"]
        assert s["unattributed_bytes"] == pytest.approx(70_000, abs=1)
        assert s["unattributed_bytes"] + s["owned_total_bytes"] == (
            s["in_use_bytes"])
        assert mx.counter_value(
            "hbm_owned_bytes", owner="unattributed") == pytest.approx(
                70_000, abs=1)
        assert mx.counter_value(
            "device_hbm_bytes", device="0", kind="in_use") == (
                fake["bytes_in_use"])
        assert devmem.headroom() == pytest.approx(
            fake["bytes_limit"] - fake["bytes_in_use"], abs=1)
    finally:
        devmem.adjust("serving", -10_000)
        monkeypatch.undo()
        devmem.poll(force=True)


def test_cluster_info_routes_through_devmem(monkeypatch):
    """/3/Cloud's node table reads the ledger's cached poll — ONE
    memory_stats reader — and keeps the probe-failure health semantics."""
    calls = []

    def _probe(d):
        calls.append(d.id)
        if d.id == 1:
            raise RuntimeError("probe died")
        return {"bytes_in_use": 11, "bytes_limit": 22}

    monkeypatch.setattr(devmem, "_stats_fn", _probe)
    devmem.poll(force=True)
    n_calls = len(calls)
    info = cloud.cluster_info()
    # served from the cache: cluster_info itself did not re-probe
    assert len(calls) == n_calls
    nodes = {n["id"]: n for n in info["nodes"]}
    assert nodes[0]["healthy"] and nodes[0]["mem_in_use"] == 11
    assert not nodes[1]["healthy"]
    assert not info["cloud_healthy"]
    monkeypatch.undo()
    devmem.poll(force=True)


# ---------------------------------------------------------------------------
# the ring


def test_ring_bounded_and_ordered_under_multithreaded_append():
    flightrec.reset()
    n_threads, per = 8, 1500

    def _spam(tid):
        for i in range(per):
            flightrec.record("spam", tid=tid, i=i)

    ts = [threading.Thread(target=_spam, args=(t,)) for t in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    evs = flightrec.events()
    assert len(evs) <= flightrec._SIZE
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # the newest events survived (it is a ring, not a sieve)
    assert evs[-1]["kind"] == "spam"
    st = flightrec.ring_status()
    assert st["next_seq"] >= n_threads * per
    assert st["dropped"] >= n_threads * per - flightrec._SIZE


def test_ring_append_stays_microseconds():
    """The O(µs) hot-path budget, unit level (the end-to-end ≤2%
    fused-tree overhead bound is the bench contract)."""
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        flightrec.record("bench", i=i)
    per_event = (time.perf_counter() - t0) / n
    assert per_event < 100e-6, f"{per_event * 1e6:.1f}µs per append"


def test_dispatch_feeds_histogram_and_ring():
    flightrec.reset()
    fam = mx.REGISTRY.histogram("dispatch_device_seconds")
    before = sum(n for _l, _c, _s, n in fam.samples()
                 if _l.get("site") == "probe_site")
    with flightrec.dispatch("probe_site", program="k1", block=2):
        time.sleep(0.002)
    evs = flightrec.events()
    kinds = [e["kind"] for e in evs]
    assert "dispatch_start" in kinds and "dispatch_end" in kinds
    end = [e for e in evs if e["kind"] == "dispatch_end"][-1]
    assert end["site"] == "probe_site" and end["dur_ms"] >= 1.0
    after = sum(n for _l, _c, _s, n in fam.samples()
                if _l.get("site") == "probe_site")
    assert after == before + 1


def test_training_dispatches_land_in_ring_and_histogram():
    """The wired hot sites: a GBM train stamps ``site=tree`` dispatch
    events (program key included) and the dispatch_device_seconds series."""
    from h2o3_tpu.models.tree import GBM

    flightrec.reset()
    fr = Frame.from_pandas(_df())
    fam = mx.REGISTRY.histogram("dispatch_device_seconds")
    before = sum(n for _l, _c, _s, n in fam.samples()
                 if _l.get("site") == "tree")
    GBM(ntrees=3, max_depth=3, seed=7).train(y="y", training_frame=fr)
    tree_evs = [e for e in flightrec.events(kind="dispatch_end")
                if e["site"] == "tree"]
    assert tree_evs, "no tree dispatch events recorded"
    starts = [e for e in flightrec.events(kind="dispatch_start")
              if e["site"] == "tree"]
    assert any("program" in e for e in starts)
    after = sum(n for _l, _c, _s, n in fam.samples()
                if _l.get("site") == "tree")
    assert after > before


# ---------------------------------------------------------------------------
# incident bundles


class _JobShim:
    def __init__(self):
        self.recovery = None
        self.restarts = 0

    def set_recovery(self, info):
        self.recovery = {**(self.recovery or {}), **info}


def test_incident_bundle_on_injected_cloud_death(tmp_path):
    """The recovery drill with forensics: a die: fault mid-GBM produces a
    bundle whose ring holds the dying dispatch and the failing
    generation, the bundle path lands in the job's recovery block, the
    bundle was written atomically through persist, and the supervised
    run still heals to the uninterrupted result."""
    flightrec.reset()
    fr = Frame.from_pandas(_df())
    kw = dict(max_depth=3, seed=11, learn_rate=0.2, score_tree_interval=2)
    from h2o3_tpu.models.tree import GBM

    full = GBM(ntrees=6, **kw).train(y="y", training_frame=fr)
    ckdir = str(tmp_path / "heal")
    g0 = cloud.generation()
    job = _JobShim()

    def _launch(ckpt):
        kw2 = dict(kw, export_checkpoints_dir=ckdir)
        if ckpt:
            kw2["checkpoint"] = ckpt
        return GBM(ntrees=6, **kw2).train(y="y", training_frame=fr)

    with faults.inject(die={"gbm"}):
        healed = recovery.run_supervised(
            _launch, ckdir=ckdir, algo="gbm", description="forensics drill",
            job=job)
    # healed (the PR-10 contract holds with forensics attached)
    np.testing.assert_allclose(
        healed.training_metrics.logloss, full.training_metrics.logloss,
        atol=1e-6)
    assert cloud.generation() == g0 + 1
    # the bundle path surfaced in the recovery block — and survived the
    # post-resume checkpoint updates (set_recovery merges)
    assert job.recovery and "incident_bundle" in job.recovery
    path = job.recovery["incident_bundle"]
    assert os.path.exists(path)
    assert path == flightrec.last_incident()
    with open(path) as f:
        bundle = json.load(f)
    # captured BEFORE the reform: the failing generation, not the new one
    assert bundle["generation"] == g0
    kinds = {e["kind"] for e in bundle["events"]}
    # the dying dispatch is in the ring...
    assert any(e["kind"] == "dispatch_start" and e["site"] == "tree"
               for e in bundle["events"])
    # ...with the failing episode's generation marker
    assert "cloud_failure" in kinds
    cf = [e for e in bundle["events"] if e["kind"] == "cloud_failure"][-1]
    assert cf["generation"] == g0
    # the full forensics payload is present
    assert bundle["devmem"]["owned_bytes"] is not None
    assert isinstance(bundle["metrics"], dict) and bundle["metrics"]
    assert isinstance(bundle["log_tail"], list)
    assert mx.counter_value("incident_bundles_total", trigger="retry") >= 1


def test_incident_capture_dedups_per_episode():
    flightrec._reset_incidents_for_tests()
    p1 = flightrec.capture_incident("first failure", trigger="degraded")
    p2 = flightrec.capture_incident("same episode", trigger="reform")
    assert p1 is not None and p2 == p1  # one bundle per degraded episode


def test_metrics_off_keeps_ring_and_bundles(tmp_path):
    """H2O3_TPU_METRICS=0 contract: the ring keeps recording (always-on),
    bundles still write; only the gated histogram goes quiet."""
    mx.set_enabled(False)
    try:
        flightrec.reset()
        flightrec._reset_incidents_for_tests()
        fam = mx.REGISTRY.histogram("dispatch_device_seconds")
        before = sum(n for _l, _c, _s, n in fam.samples())
        with flightrec.dispatch("gated_site"):
            pass
        evs = flightrec.events()
        assert [e["kind"] for e in evs[-2:]] == [
            "dispatch_start", "dispatch_end"]
        after = sum(n for _l, _c, _s, n in fam.samples())
        assert after == before  # the histogram IS gated
        path = flightrec.capture_incident("metrics-off incident")
        assert path is not None and os.path.exists(path)
    finally:
        mx.set_enabled(True)


# ---------------------------------------------------------------------------
# ChunkStore registry stats (the LAST_STORE_STATS clobber fix)


def test_chunkstore_close_publishes_registry_stats():
    npad = pad_to_shards(4096)
    window = 16 * 1024
    ev0 = mx.counter_value("frame_window_evictions_total")
    # the ledger is process-wide: a store another test file left unclosed
    # on this worker (test_elastic's and test_oocore's refused stores keep
    # 2048 bytes claimed) is not this store's claim to return
    base_win = devmem.owned().get("frame_window", 0.0)
    store = cs.ChunkStore(npad, 8.0, window=window, prefetch=1)
    store.add("x", np.zeros((npad,), np.float32))
    store.add("n", np.zeros((npad,), np.int32))
    for _bi, _blk in store.stream(("x", "n")):
        pass
    store.close()
    assert mx.counter_value("frame_window_peak_bytes") == (
        store.peak_hbm)
    assert mx.counter_value("frame_window_peak_bytes") <= window
    assert mx.counter_value("frame_window_evictions_total") - ev0 == (
        store.evictions)
    # the deprecated dict alias still mirrors the same run
    assert cs.LAST_STORE_STATS["peak_hbm"] == store.peak_hbm
    # chunk fetch/evict traffic reached the ring
    assert flightrec.events(kind="chunk_fetch")
    # and the window returned its own ledger claim
    assert devmem.owned().get("frame_window", 0.0) == pytest.approx(
        base_win, abs=1.0)


def test_oversized_streamed_train_bounds_ledger_claims():
    """The acceptance geometry on the proxy: an oversized streamed GBM
    concurrent with serving paging keeps hbm_owned_bytes{frame_window}
    <= the window and {serving} <= the serve budget while both run."""
    from h2o3_tpu.models.tree import GBM
    from h2o3_tpu.serving.residency import ResidencyManager

    window = 24 * 1024
    serve_budget = 3 * 8 * 1024
    base_win = devmem.owned().get("frame_window", 0.0)
    base_srv = devmem.owned().get("serving", 0.0)
    samples: list = []
    stop = threading.Event()
    errs: list = []

    def _serve():
        try:
            mgr = ResidencyManager()
            scorers = [_FakeScorer(f"ov{i}") for i in range(6)]
            with _env(H2O3_TPU_SERVE_HBM_BYTES=str(serve_budget)):
                while not stop.is_set():
                    for s in scorers:
                        with mgr.hold(s):
                            pass
                    samples.append((
                        devmem.owned().get("frame_window", 0.0) - base_win,
                        devmem.owned().get("serving", 0.0) - base_srv,
                    ))
            for s in scorers:
                mgr.release(s)
        except Exception as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    t = threading.Thread(target=_serve)
    t.start()
    try:
        with _env(H2O3_TPU_HBM_WINDOW_BYTES=str(window)):
            fr = _frame_oversized()
            m = GBM(ntrees=3, max_depth=3, seed=5).train(
                y="label", training_frame=fr)
    finally:
        stop.set()
        t.join(timeout=120)
    assert not errs, errs
    assert cs.LAST_STORE_STATS["n_blocks"] > 1  # really streamed
    assert samples, "no concurrent samples taken"
    for win_claim, srv_claim in samples:
        assert win_claim <= window + 1
        assert srv_claim <= serve_budget + 1
    assert float(m.training_metrics.auc) > 0.6


def _frame_oversized(n=6000, c=6, seed=23):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, c)).astype(np.float32)
    eta = X[:, 0] - 0.5 * X[:, 1]
    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(c)])
    y = rng.random(n) < 1.0 / (1.0 + np.exp(-eta))
    df["label"] = np.where(y, "s", "b")
    return Frame.from_pandas(df)


# ---------------------------------------------------------------------------
# the REST surface


def test_flight_recorder_route():
    from h2o3_tpu.api import server as srv_mod

    existing = srv_mod._SERVER
    srv = srv_mod.start_server(port=0)
    try:
        flightrec.record("route_probe", x=1)
        with urllib.request.urlopen(
                srv.url + "/3/FlightRecorder?n=64", timeout=10) as r:
            out = json.loads(r.read())
        assert out["ring"]["size"] == flightrec._SIZE
        assert any(e["kind"] == "route_probe" for e in out["events"])
        assert "owned_bytes" in out["devmem"]
        with urllib.request.urlopen(
                srv.url + "/3/FlightRecorder?kind=route_probe",
                timeout=10) as r:
            filt = json.loads(r.read())
        assert filt["events"] and all(
            e["kind"] == "route_probe" for e in filt["events"])
    finally:
        if existing is None:
            srv.stop()
