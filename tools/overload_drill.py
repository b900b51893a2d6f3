#!/usr/bin/env python
"""Overload-survival drill (ISSUE 19): prove the overload plane's three
survival paths end-to-end and emit one gated artifact
(``OVERLOAD_DRILL_<stamp>.json``; tools/latest_bench_ok.py checks its pins).

Scenarios:

1. **storm** — an admission storm at 4x capacity: 16 concurrent mutating
   REST requests against ``H2O3_TPU_MAX_INFLIGHT=4`` while a ``slow:rest``
   fault holds every handler open. The pins: some requests land 200, the
   rest shed 429/503 with an honest numeric Retry-After (>= 1 s), the
   server answers normally the moment the storm ends (zero server deaths),
   and the reservation ledger sums back to zero. A second wave drives the
   ISSUE-19 memory gate: with synthetic device stats reporting no headroom
   and ``H2O3_TPU_ADMIT_MIN_HEADROOM_BYTES`` armed, mutating requests shed
   503 ``reason=memory`` — and admit again once headroom returns.

2. **oom** — a ``RESOURCE_EXHAUSTED`` at the ``tree`` dispatch site (the
   one-shot ``oom:tree`` fault raises the real XlaRuntimeError signature
   inside the flight-recorder span): ``recovery.run_supervised`` retries
   the job exactly ONCE under ``overload.degrade_scope`` (streamed /
   halved window), the healed model lands within 1e-6 logloss of the
   resident control, the incident bundle names the OOM dispatch site, and
   the cloud generation does NOT tick — an OOM degrade is not a reform.

3. **hang** — a wedged dispatch (``hang:tree`` sleeps inside the open
   span, armed only after an interval snapshot exists): the watchdog trips
   ``dispatch_hangs_total{site=tree}`` within its budget, captures the
   incident, latches the cloud degraded; the unwedged dispatch fail-stops
   at its own exit and the supervisor reforms + resumes from the latest
   snapshot to a model within 1e-6 of the uninterrupted reference.

Runs on the CPU proxy too (CI's tests/test_overload.py is the assert-only
version of the same drill).
"""

from __future__ import annotations

import argparse
import glob as _glob
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU proxy runs the drill on the same 8-device sharded mesh the bench
# artifacts use (real accelerators keep their native device count)
if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu" and \
        "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()


def _frame(n=4000, seed=3):
    import numpy as np
    import pandas as pd

    from h2o3_tpu.frame.frame import Frame

    rng = np.random.default_rng(seed)
    df = pd.DataFrame({
        "a": rng.normal(size=n),
        "b": rng.normal(size=n),
        "c": rng.choice(["x", "y", "z"], n),
    })
    eta = df["a"] * 1.5 + (df["c"] == "x") * 2 - df["b"]
    df["y"] = np.where(eta + rng.normal(size=n) > 0, "p", "n")
    return Frame.from_pandas(df)


# -- scenario 1: admission storm ---------------------------------------------

def _post(url, path, payload):
    """POST form-encoded; returns (status, retry_after_or_None, reason)."""
    import urllib.error
    import urllib.parse
    import urllib.request

    data = urllib.parse.urlencode(payload).encode()
    req = urllib.request.Request(url + path, data=data, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, None, None
    except urllib.error.HTTPError as e:
        ra = e.headers.get("Retry-After")
        try:
            reason = json.loads(e.read()).get("reason")
        except Exception:  # noqa: BLE001 — shed body parse is best-effort
            reason = None
        return e.code, ra, reason


def _drill_storm():
    from h2o3_tpu.api.server import start_server
    from h2o3_tpu.utils import devmem, faults

    cap, waves = 4, 16
    saved = {k: os.environ.get(k) for k in (
        "H2O3_TPU_MAX_INFLIGHT", "H2O3_TPU_ADMIT_MIN_HEADROOM_BYTES")}
    os.environ["H2O3_TPU_MAX_INFLIGHT"] = str(cap)
    srv = start_server(port=0)
    orig_stats = devmem._stats_fn
    try:
        # ---- wave 1: 4x capacity with every handler held open ----
        faults.configure(slow={"rest": 1.0})
        barrier = threading.Barrier(waves)
        out: list[tuple] = [None] * waves

        def _one(i):
            barrier.wait()
            out[i] = _post(srv.url, "/3/CreateFrame",
                           {"dest": f"storm_{i}", "rows": 200, "cols": 3,
                            "seed": i})

        threads = [threading.Thread(target=_one, args=(i,))
                   for i in range(waves)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        faults.reset()

        assert all(r is not None for r in out), "a storm request never returned"
        ok = [r for r in out if r[0] == 200]
        shed = [r for r in out if r[0] in (429, 503)]
        assert ok, "storm starved every request (no 200s at all)"
        assert shed, f"{waves} concurrent vs capacity {cap} shed nothing"
        assert len(ok) + len(shed) == waves, \
            f"unexpected statuses in {sorted(r[0] for r in out)}"
        for status, ra, reason in shed:
            assert ra is not None and float(ra) >= 1, \
                f"shed {status} carried a dishonest Retry-After {ra!r}"
            assert reason in ("inflight_full", "queue_full", "memory",
                              "draining"), f"shed {status} reason {reason!r}"
        # zero server deaths: the server answers normally post-storm
        st, _, _ = _post(srv.url, "/3/CreateFrame",
                         {"dest": "storm_after", "rows": 50, "cols": 2})
        assert st == 200, f"server did not survive the storm (post-storm {st})"
        assert devmem.reservations() == {}, \
            f"reservations leaked: {devmem.reservations()}"

        # ---- wave 2: the memory gate (synthetic zero headroom) ----
        devmem._stats_fn = lambda d: {"bytes_in_use": 8 << 30,
                                      "bytes_limit": 8 << 30}
        devmem.poll(force=True)
        os.environ["H2O3_TPU_ADMIT_MIN_HEADROOM_BYTES"] = str(64 << 20)
        st, ra, reason = _post(srv.url, "/3/CreateFrame",
                               {"dest": "storm_mem", "rows": 50, "cols": 2})
        assert st == 503 and reason == "memory", \
            f"memory gate did not shed (status={st} reason={reason!r})"
        assert ra is not None and float(ra) >= 1, \
            f"memory shed carried a dishonest Retry-After {ra!r}"
        mem_shed = {"status": st, "reason": reason, "retry_after": float(ra)}
        # headroom returns -> the valve opens again
        devmem._stats_fn = orig_stats
        devmem.poll(force=True)
        os.environ["H2O3_TPU_ADMIT_MIN_HEADROOM_BYTES"] = "0"
        st, _, _ = _post(srv.url, "/3/CreateFrame",
                         {"dest": "storm_mem_after", "rows": 50, "cols": 2})
        assert st == 200, f"server kept shedding after headroom returned ({st})"

        return {"sent": waves, "capacity": cap, "ok": len(ok),
                "shed": len(shed),
                "shed_statuses": sorted({r[0] for r in shed}),
                "retry_after_min": min(float(r[1]) for r in shed),
                "retry_after_max": max(float(r[1]) for r in shed),
                "memory_shed": mem_shed,
                "reservations_after": 0, "server_alive": True}
    finally:
        faults.reset()
        devmem._stats_fn = orig_stats
        devmem.poll(force=True)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        srv.stop()


# -- scenario 2: OOM catch-and-degrade ---------------------------------------

def _drill_oom(fr, ckdir):
    import numpy as np

    from h2o3_tpu.cluster import cloud, recovery
    from h2o3_tpu.models import GBM
    from h2o3_tpu.utils import faults, flightrec
    from h2o3_tpu.utils import metrics as mx

    kw = dict(ntrees=16, max_depth=4, seed=11, learn_rate=0.2,
              score_tree_interval=4)
    full = GBM(**kw).train(y="y", training_frame=fr)
    gen0 = cloud.generation()

    def _launch(ckpt):
        kw2 = dict(kw, export_checkpoints_dir=ckdir)
        if ckpt:
            kw2["checkpoint"] = ckpt
        return GBM(**kw2).train(y="y", training_frame=fr)

    t0 = time.perf_counter()
    with faults.inject(oom={"tree"}):
        healed = recovery.run_supervised(_launch, ckdir=ckdir, algo="gbm",
                                         description="oom drill")
    wall = time.perf_counter() - t0

    delta = abs(healed.training_metrics.logloss - full.training_metrics.logloss)
    assert delta <= 1e-6, f"oom degrade parity violated: {delta}"
    assert healed.output["ntrees_actual"] == kw["ntrees"]
    pa = full.predict(fr).vec("p").to_numpy()
    pb = healed.predict(fr).vec("p").to_numpy()
    # an OOM degrade is NOT a reform: the cloud was healthy the whole time
    assert cloud.generation() == gen0, "oom degrade ticked the generation"
    bundle_path = flightrec.last_incident()
    assert bundle_path, "no incident bundle captured for the OOM"
    with open(bundle_path) as f:
        bundle = json.load(f)
    assert bundle["trigger"] == "oom", f"trigger {bundle['trigger']!r}"
    assert "'tree'" in bundle["reason"], \
        f"incident does not name the OOM dispatch site: {bundle['reason']!r}"
    fam = json.dumps(mx.REGISTRY.snapshot().get("oom_degrades_total"))
    assert "retried" in fam and "recovered" in fam, \
        f"oom_degrades_total missing outcomes: {fam}"
    return {"logloss_delta": delta, "wall_s": wall,
            "pred_max_delta": float(np.max(np.abs(pa - pb))),
            "incident": bundle_path, "incident_trigger": "oom",
            "generation_ticked": 0}


# -- scenario 3: dispatch hang -> watchdog trip -> supervised resume ----------

def _drill_hang(fr, ckdir):
    from h2o3_tpu.cluster import cloud, recovery
    from h2o3_tpu.models import GBM
    from h2o3_tpu.utils import faults, flightrec, overload
    from h2o3_tpu.utils import metrics as mx

    saved = {k: os.environ.get(k) for k in (
        "H2O3_TPU_HANG_MIN_SECS", "H2O3_TPU_HANG_POLL_SECS",
        "H2O3_TPU_HANG_FACTOR")}
    # the tree site dispatches once per score interval and its rolling mean
    # is compile-inflated (~2.4s with the 8s first-chunk trace on the CPU
    # proxy), so the drill pins factor=2 to keep budget x sleep inside a
    # CI-sized wall; poll fast enough to trip mid-sleep
    os.environ["H2O3_TPU_HANG_MIN_SECS"] = "0.6"
    os.environ["H2O3_TPU_HANG_POLL_SECS"] = "0.1"
    os.environ["H2O3_TPU_HANG_FACTOR"] = "2"

    kw = dict(ntrees=24, max_depth=4, seed=11, learn_rate=0.2,
              score_tree_interval=4)
    full = GBM(**kw).train(y="y", training_frame=fr)
    gen0 = cloud.generation()
    armed_after_snapshot = threading.Event()

    def _armer():
        # arm the wedge only once an interval snapshot exists, so the
        # supervised resume has something real to resume from; once the
        # watchdog trips, raise the floor back up so the resumed run's
        # recompile can never false-trip
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60:
            if _glob.glob(os.path.join(ckdir, "gbm_ckpt_*")):
                faults.configure(hang={"tree": 8.0})
                armed_after_snapshot.set()
                break
            time.sleep(0.002)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60:
            if flightrec.events(kind="watchdog_trip"):
                os.environ["H2O3_TPU_HANG_MIN_SECS"] = "120"
                break
            time.sleep(0.01)

    overload.install_watchdog()
    armer = threading.Thread(target=_armer, daemon=True)
    try:
        def _launch(ckpt):
            kw2 = dict(kw, export_checkpoints_dir=ckdir)
            if ckpt:
                kw2["checkpoint"] = ckpt
            return GBM(**kw2).train(y="y", training_frame=fr)

        t0 = time.perf_counter()
        armer.start()
        healed = recovery.run_supervised(_launch, ckdir=ckdir, algo="gbm",
                                         description="hang drill")
        wall = time.perf_counter() - t0
    finally:
        armer.join(timeout=10)
        faults.reset()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    assert armed_after_snapshot.is_set(), \
        "the hang was never armed (no snapshot appeared) — drill vacuous"
    trips = flightrec.events(kind="watchdog_trip")
    assert trips and any(e.get("site") == "tree" for e in trips), \
        f"watchdog never tripped on the wedged tree dispatch: {trips}"
    fam = json.dumps(mx.REGISTRY.snapshot().get("dispatch_hangs_total"))
    assert "tree" in fam, f"dispatch_hangs_total missing the site: {fam}"
    bundle_path = flightrec.last_incident()
    assert bundle_path, "no incident bundle captured for the hang"
    with open(bundle_path) as f:
        bundle = json.load(f)
    assert bundle["trigger"] == "hang", f"trigger {bundle['trigger']!r}"
    # the fail-stop handed the job to the supervisor: reform ticked the
    # generation and the resumed run completed from the interval snapshot
    assert cloud.generation() > gen0, "supervisor never re-formed the cloud"
    assert cloud.degraded_reason() is None, "cloud left degraded"
    delta = abs(healed.training_metrics.logloss - full.training_metrics.logloss)
    assert delta <= 1e-6, f"hang resume parity violated: {delta}"
    assert healed.output["ntrees_actual"] == kw["ntrees"]
    return {"logloss_delta": delta, "wall_s": wall,
            "trips": [{"site": e.get("site"), "age_s": e.get("age_s"),
                       "budget_s": e.get("budget_s")} for e in trips],
            "incident": bundle_path, "incident_trigger": "hang",
            "generations_ticked": cloud.generation() - gen0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="artifact path (default: "
                    "OVERLOAD_DRILL_<stamp>.json in the repo root)")
    ap.add_argument("--scenarios", default="storm,oom,hang")
    args = ap.parse_args(argv)

    os.environ.setdefault("H2O3_TPU_RECOVERY", "1")
    os.environ.setdefault("H2O3_TPU_RECOVERY_BACKOFF", "0.05")
    os.environ.setdefault("H2O3_TPU_OVERLOAD", "1")

    import jax

    import h2o3_tpu
    from h2o3_tpu.cluster import cloud
    from h2o3_tpu.utils import flightrec, overload
    from h2o3_tpu.utils import metrics as mx

    h2o3_tpu.init()
    scen = [s.strip() for s in args.scenarios.split(",") if s.strip()]
    results = {}
    if "storm" in scen:
        results["storm"] = _drill_storm()
        print(f"storm: ok={results['storm']['ok']} "
              f"shed={results['storm']['shed']} "
              f"retry_after=[{results['storm']['retry_after_min']}, "
              f"{results['storm']['retry_after_max']}] server alive")
    fr = _frame()
    if "oom" in scen:
        flightrec._reset_incidents_for_tests()
        with tempfile.TemporaryDirectory(prefix="ovl_oom_") as ckdir:
            results["oom"] = _drill_oom(fr, ckdir)
        assert cloud.degraded_reason() is None, "cloud left degraded"
        print(f"oom: logloss_delta={results['oom']['logloss_delta']:.2e} "
              f"incident={os.path.basename(results['oom']['incident'])}")
    if "hang" in scen:
        flightrec._reset_incidents_for_tests()
        try:
            with tempfile.TemporaryDirectory(prefix="ovl_hang_") as ckdir:
                results["hang"] = _drill_hang(fr, ckdir)
        finally:
            overload.uninstall_watchdog()
        assert cloud.degraded_reason() is None, "cloud left degraded"
        print(f"hang: trips={len(results['hang']['trips'])} "
              f"logloss_delta={results['hang']['logloss_delta']:.2e} "
              f"generations={results['hang']['generations_ticked']}")

    snap = mx.REGISTRY.snapshot()
    fam = {name: snap.get(name) for name in (
        "oom_degrades_total", "dispatch_hangs_total", "dispatch_hung",
        "hbm_reserved_bytes", "rest_rejected_total")}
    artifact = {
        "kind": "overload_drill",
        "stamp": time.strftime("%Y%m%dT%H%M%SZ", time.gmtime()),
        "backend": jax.devices()[0].platform,
        "n_devices": len(jax.devices()),
        "results": results,
        "overload_metrics": fam,
        "ok": True,
    }
    out = args.out or f"OVERLOAD_DRILL_{artifact['stamp']}.json"
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps(artifact))
    return 0


if __name__ == "__main__":
    sys.exit(main())
