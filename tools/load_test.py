#!/usr/bin/env python
"""Open-loop load harness for the scoring tier (ISSUE 7): Poisson arrivals
against ``POST /3/Predictions/rows``, swept over offered QPS, measuring
p50/p99 latency, shed rate, and the server's batch-occupancy histogram.

Open loop is the point: arrivals are scheduled by a Poisson process at the
OFFERED rate regardless of completions (a closed loop self-throttles and
hides saturation — the classic coordinated-omission trap). Each mode runs
against a fresh server SUBPROCESS so client and server never share a GIL and
the A/B is honest:

- ``batched``  — the coalescing tier at its default window
  (H2O3_TPU_SCORE_BATCH_WINDOW_MS), one device dispatch per micro-batch;
- ``control``  — the same route with the window forced to 0: one device
  dispatch per request, the pre-tier behavior.

Artifact (one JSON line on stdout, also written to --out): per-step
latency/shed/occupancy numbers plus a summary with each mode's sustained
QPS (highest offered rate with shed+error rate <= 1% and achieved >= 90% of
offered), the p99 at that rate, and a batched-vs-control byte-parity probe.
``tools/latest_bench_ok.py`` sanity-checks the newest artifact.

Usage::

    python tools/load_test.py                          # spawn servers, both modes
    python tools/load_test.py --mode batched --qps 200,800
    python tools/load_test.py --url http://host:54321 --model gbm_x  # external

The committed CPU-proxy artifact runs with JAX_PLATFORMS=cpu and
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the same 8-device mesh
the tier-1 suite uses).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the scoring workload: a fixed synthetic model + row pool, deterministic on
# both sides of the subprocess boundary


def _train_df(n: int = 40_000, seed: int = 9):
    import pandas as pd

    rng = np.random.default_rng(seed)
    df = pd.DataFrame({
        "a": rng.normal(size=n), "b": rng.normal(size=n),
        "c": rng.normal(size=n), "d": rng.normal(size=n),
        "e": rng.normal(size=n),
        "f": rng.choice(["u", "v", "w"], n),
    })
    logit = df["a"] * 0.8 - df["b"] * 0.5 + (df["f"] == "v") * 0.7
    df["y"] = np.where(
        rng.random(n) < 1 / (1 + np.exp(-logit)), "pos", "neg")
    df.loc[::31, "a"] = np.nan
    return df


def _row_pool(n: int = 512, seed: int = 123) -> list[dict]:
    rng = np.random.default_rng(seed)
    pool = []
    for i in range(n):
        row = {
            "a": None if i % 29 == 0 else float(rng.normal()),
            "b": float(rng.normal()), "c": float(rng.normal()),
            "d": float(rng.normal()), "e": float(rng.normal()),
            "f": ["u", "v", "w", "NEW_LEVEL"][int(rng.integers(0, 4))],
        }
        pool.append(row)
    return pool


def _serve_forever() -> None:
    """Park the server subprocess until the parent's SIGTERM; leave through
    SystemExit so interpreter shutdown (the jax backend's teardown, which
    releases the chip) runs — the default SIGTERM action skips it."""
    import signal

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))
    while True:
        time.sleep(3600)


def _stop_server(proc) -> None:
    """End a server subprocess so the chip is free for the next one:
    SIGTERM + wait; SIGKILL only for one that does not leave."""
    proc.terminate()
    try:
        proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=30)


def _serve(args) -> None:
    """Server-subprocess mode: boot a cloud, train the workload model,
    serve REST, print the READY line the parent parses."""
    import h2o3_tpu
    from h2o3_tpu.api.server import start_server
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models import GBM

    h2o3_tpu.init(log_level="WARN")
    fr = Frame.from_pandas(_train_df(), destination_frame="load_train")
    model = GBM(ntrees=20, max_depth=5, seed=1).train(
        y="y", training_frame=fr)
    # warm the scorer program for the single-row bucket so the first
    # measured request doesn't pay the compile
    from h2o3_tpu import serving

    serving.scorer_for(model)
    serving.score_rows(model, [_row_pool(1)[0]])
    srv = start_server(port=args.port)
    print(f"READY {srv.url} {model.key}", flush=True)
    _serve_forever()


def _serve_fleet(args) -> None:
    """Fleet server-subprocess mode (--fleet): train M models, export each
    through serialize_model into the watch dir (H2O3_TPU_SERVE_WATCH_DIR —
    set by the parent), let the serving REGISTRY load them (the real
    rollout path), size the HBM budget to H2O3_TPU_FLEET_OVERSUB× less
    than the fleet's total scorer bytes (0 = unbounded, the all-resident
    control), and serve REST."""
    import h2o3_tpu
    from h2o3_tpu import persist, serving
    from h2o3_tpu.api.server import start_server
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models import GBM
    from h2o3_tpu.serving.registry import REGISTRY
    from h2o3_tpu.serving.residency import MANAGER

    h2o3_tpu.init(log_level="WARN")
    watch = os.environ["H2O3_TPU_SERVE_WATCH_DIR"]
    oversub = int(os.environ.get("H2O3_TPU_FLEET_OVERSUB", "0"))
    fr = Frame.from_pandas(_train_df(), destination_frame="fleet_train")
    keys = []
    for i in range(args.models):
        m = GBM(ntrees=8, max_depth=4, seed=100 + i).train(
            y="y", training_frame=fr)
        persist.save_model(m, os.path.join(watch, f"fleet_model_{i:03d}"))
        keys.append(m.key)
    loaded = REGISTRY.poll_once()
    assert loaded == args.models, (loaded, args.models)
    # stack every registry-served model's HOST payload first (scorer_for
    # uploads nothing), size the budget from the measured fleet bytes,
    # THEN warm-score — so every device upload happens under the budget
    # and hbm_peak_bytes is an honest bound
    for k in keys:
        serving.scorer_for(REGISTRY.resolve(k))
    total = MANAGER.status()["host_bytes"]
    if oversub > 0:
        os.environ["H2O3_TPU_SERVE_HBM_BYTES"] = str(
            max(total // oversub, 1))
    probe = _row_pool(1)[0]
    for k in keys:
        serving.score_rows(REGISTRY.resolve(k), [probe])
    srv = start_server(port=args.port)
    print(f"READY {srv.url} {','.join(keys)} total_bytes={total} "
          f"budget={os.environ.get('H2O3_TPU_SERVE_HBM_BYTES', '0')}",
          flush=True)
    _serve_forever()


# ---------------------------------------------------------------------------
# client side


def _post_rows(url: str, model_key: str, rows: list[dict],
               timeout: float = 15.0):
    body = json.dumps({"model": model_key, "rows": rows}).encode()
    req = urllib.request.Request(
        url + "/3/Predictions/rows", data=body,
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _scrape_hist(url: str, family: str, labels: dict | None = None):
    """(buckets, sum, count) of one histogram child. ``labels`` selects the
    child whose labels contain them (dispatch_device_seconds{site=...});
    None keeps the old first-child behavior (unlabeled families)."""
    try:
        with urllib.request.urlopen(url + "/3/Metrics?format=json",
                                    timeout=10) as r:
            fam = json.loads(r.read())["families"].get(family)
        if not fam or not fam["values"]:
            return {}, 0.0, 0
        v = None
        if labels is None:
            v = fam["values"][0]
        else:
            for cand in fam["values"]:
                if all(cand["labels"].get(k) == lv
                       for k, lv in labels.items()):
                    v = cand
                    break
        if v is None:
            return {}, 0.0, 0
        return dict(v["buckets"]), float(v["sum"]), int(v["count"])
    except Exception as e:  # noqa: BLE001 — metrics are best-effort here
        _log(f"metrics scrape failed: {e!r}")
        return {}, 0.0, 0


def _leg_stats(h0, h1) -> dict:
    """Per-step delta stats for one latency leg (two _scrape_hist results):
    request count, mean ms, and the bucket upper bound covering p99 —
    bucket-resolution, which is what the batch-window tuner needs."""
    b0, s0, c0 = h0
    b1, s1, c1 = h1
    n = c1 - c0
    if n <= 0:
        return {"count": 0}
    out = {"count": n, "mean_ms": round((s1 - s0) / n * 1e3, 3)}
    prev1 = prev0 = 0
    acc = 0.0
    for le in b1:
        c0le = b0.get(le, 0) if b0 else 0
        acc += (b1[le] - prev1) - (c0le - prev0)
        prev1, prev0 = b1[le], c0le
        if acc >= 0.99 * n:
            out["p99_le_ms"] = (None if le == "+Inf"
                                else round(float(le) * 1e3, 3))
            break
    return out


def _run_step(url: str, model_key: str, qps: float, duration: float,
              rows_per_req: int, threads: int, pool: list[dict],
              model_pick=None) -> dict:
    """One offered-QPS step. ``model_pick`` (fleet mode) is a deterministic
    per-arrival model-key array — Zipf-distributed traffic over the fleet
    instead of one hot key."""
    rng = np.random.default_rng(int(qps * 1000) ^ 0x5EED)
    gaps = rng.exponential(1.0 / qps, size=int(qps * duration * 1.2) + 8)
    arrivals = np.cumsum(gaps)
    arrivals = arrivals[arrivals < duration]
    occ0 = _scrape_hist(url, "serving_batch_occupancy")
    rows0 = _scrape_hist(url, "serving_batch_rows")
    # per-request latency legs, from the tracing plane: time queued in the
    # batcher, device time in the coalesced dispatch, residency page-ins
    qw0 = _scrape_hist(url, "job_queue_wait_seconds")
    dd0 = _scrape_hist(url, "dispatch_device_seconds",
                       {"site": "serving_batch"})
    pi0 = _scrape_hist(url, "serving_page_in_seconds")

    idx_lock = threading.Lock()
    nxt = [0]
    lat_ms: list[float] = []
    shed = [0]
    errors = [0]
    unsent = [0]
    last_done = [0.0]  # span of actual completions — the throughput base
    lat_lock = threading.Lock()
    t0 = time.monotonic()
    # hard wall for the step: an overloaded server must not let the client
    # spend minutes draining its arrival backlog — arrivals the client could
    # not even ISSUE inside the window are unsustained offered load and are
    # counted against the rate like sheds
    cutoff = t0 + duration + 2.0

    def worker():
        import urllib.error

        while True:
            with idx_lock:
                i = nxt[0]
                if i >= len(arrivals):
                    return
                nxt[0] += 1
            if time.monotonic() > cutoff:
                with lat_lock:
                    unsent[0] += 1
                continue
            delay = t0 + arrivals[i] - time.monotonic()
            if delay > 0:
                time.sleep(delay)  # behind schedule -> fire immediately
            rows = [pool[(i * rows_per_req + j) % len(pool)]
                    for j in range(rows_per_req)]
            mk = (model_key if model_pick is None
                  else model_pick[i % len(model_pick)])
            r0 = time.monotonic()
            try:
                _post_rows(url, mk, rows)
                done = time.monotonic()
                with lat_lock:
                    lat_ms.append((done - r0) * 1e3)
                    last_done[0] = max(last_done[0], done - t0)
            except urllib.error.HTTPError as e:
                with lat_lock:
                    if e.code in (429, 503, 504):
                        shed[0] += 1
                    else:
                        errors[0] += 1
            except Exception:  # noqa: BLE001 — timeouts/conn resets
                with lat_lock:
                    errors[0] += 1

    ts = [threading.Thread(target=worker, daemon=True)
          for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=duration + 90)
    wall = max(last_done[0], duration)

    occ1 = _scrape_hist(url, "serving_batch_occupancy")
    rows1 = _scrape_hist(url, "serving_batch_rows")
    d_occ_count = occ1[2] - occ0[2]
    d_occ_sum = occ1[1] - occ0[1]
    hist = {}
    if rows1[0]:
        # de-cumulate the Prometheus buckets into per-bucket deltas
        prev1 = prev0 = 0
        for le in rows1[0]:
            c1 = rows1[0][le]
            c0 = rows0[0].get(le, 0) if rows0[0] else 0
            hist[le] = (c1 - prev1) - (c0 - prev0)
            prev1, prev0 = c1, c0
        hist = {k: v for k, v in hist.items() if v}
    sent = len(arrivals)
    ok = len(lat_ms)
    lat = np.sort(np.asarray(lat_ms)) if lat_ms else np.asarray([])

    def pct(p):
        return round(float(lat[min(int(len(lat) * p), len(lat) - 1)]), 3) \
            if len(lat) else None

    step = {
        "offered_qps": qps, "duration_s": duration, "sent": sent,
        "ok": ok, "shed": shed[0], "errors": errors[0],
        "unsent": unsent[0],
        "achieved_qps": round(ok / wall, 1) if wall > 0 else 0.0,
        "shed_rate": round(
            (shed[0] + errors[0] + unsent[0]) / max(sent, 1), 4),
        "p50_ms": pct(0.50), "p90_ms": pct(0.90), "p99_ms": pct(0.99),
        "mean_batch_occupancy": (
            round(d_occ_sum / d_occ_count, 2) if d_occ_count else None),
        "batch_rows_hist": hist,
        "latency_breakdown": {
            "queue_wait": _leg_stats(
                qw0, _scrape_hist(url, "job_queue_wait_seconds")),
            "dispatch": _leg_stats(
                dd0, _scrape_hist(url, "dispatch_device_seconds",
                                  {"site": "serving_batch"})),
            "page_in": _leg_stats(
                pi0, _scrape_hist(url, "serving_page_in_seconds")),
        },
    }
    return step


def _spawn_server(mode: str, window_ms: str | None) -> tuple:
    # One process for each chip: this parent never imports jax, so the
    # device is free for the server child, and children run one at a time —
    # each mode's server is stopped (_stop_server) before the next spawns.
    env = dict(os.environ)
    env.setdefault("H2O3_TPU_LOG_LEVEL", "WARN")
    if mode == "control":
        env["H2O3_TPU_SCORE_BATCH_WINDOW_MS"] = "0"
    elif window_ms is not None:
        env["H2O3_TPU_SCORE_BATCH_WINDOW_MS"] = window_ms
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--serve", "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env, cwd=ROOT)
    deadline = time.monotonic() + 600
    while time.monotonic() < deadline:
        line = p.stdout.readline()
        if not line:
            raise RuntimeError(f"{mode} server died (rc={p.poll()})")
        if line.startswith("READY "):
            _, url, model_key = line.split()
            _log(f"{mode} server up at {url} (model {model_key})")
            return p, url, model_key
    _stop_server(p)
    raise RuntimeError(f"{mode} server never became ready")


def _spawn_fleet_server(mode: str, args, watch_dir: str) -> tuple:
    """mode 'oversub' bounds HBM to total/oversub; 'resident' leaves the
    budget unbounded (the all-resident control)."""
    # one process for each chip, as in _spawn_server: the parent stays off
    # jax and the two fleet servers run in turn, never together
    env = dict(os.environ)
    env.setdefault("H2O3_TPU_LOG_LEVEL", "WARN")
    env["H2O3_TPU_SERVE_WATCH_DIR"] = watch_dir
    env["H2O3_TPU_FLEET_OVERSUB"] = (
        str(args.oversub) if mode == "oversub" else "0")
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--serve-fleet",
         "--port", "0", "--models", str(args.models)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env, cwd=ROOT)
    deadline = time.monotonic() + 900
    while time.monotonic() < deadline:
        line = p.stdout.readline()
        if not line:
            raise RuntimeError(f"fleet {mode} server died (rc={p.poll()})")
        if line.startswith("READY "):
            parts = line.split()
            url, keys = parts[1], parts[2].split(",")
            extra = dict(kv.split("=") for kv in parts[3:])
            _log(f"fleet {mode} server up at {url}: {len(keys)} models, "
                 f"total_bytes={extra.get('total_bytes')} "
                 f"budget={extra.get('budget')}")
            return p, url, keys, extra
    _stop_server(p)
    raise RuntimeError(f"fleet {mode} server never became ready")


def _scrape_registry(url: str) -> dict:
    try:
        with urllib.request.urlopen(url + "/3/ServingRegistry",
                                    timeout=10) as r:
            return json.loads(r.read())
    except Exception as e:  # noqa: BLE001 — observability is best-effort
        _log(f"registry scrape failed: {e!r}")
        return {}


def _zipf_pick(keys: list[str], n: int, s: float, seed: int) -> list[str]:
    """Deterministic Zipf-ranked model choice: p_i ∝ 1/(i+1)^s."""
    rng = np.random.default_rng(seed)
    w = 1.0 / np.power(np.arange(1, len(keys) + 1, dtype=np.float64), s)
    w /= w.sum()
    idx = rng.choice(len(keys), size=n, p=w)
    return [keys[i] for i in idx]


def _run_fleet(args, stamp: str) -> int:
    """The fleet A/B (ISSUE 12 acceptance): Zipf traffic over M models at
    K× HBM oversubscription vs the all-resident control — sustained QPS,
    eviction/page-in counters, the peak-bytes-under-budget pin, and
    byte-parity per model before/after the sweep AND across modes."""
    import tempfile

    qps_list = [float(q) for q in args.qps.split(",") if q.strip()]
    pool = _row_pool()
    probe_rows = pool[:8]
    artifact = {
        "schema": "fleet-loadtest/v1", "stamp": stamp,
        "models": args.models, "oversub": args.oversub,
        "zipf_s": args.zipf, "rows_per_request": args.rows,
        "duration_s_per_step": args.duration, "steps": [],
        "env": {
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", ""),
            "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""),
        },
    }
    parity: dict[str, dict] = {}
    registry_stats: dict[str, dict] = {}
    budgets: dict[str, int] = {}

    for mode in ("oversub", "resident"):
        watch = tempfile.mkdtemp(prefix=f"fleet_store_{mode}_")
        proc, url, keys, extra = _spawn_fleet_server(mode, args, watch)
        budgets[mode] = int(extra.get("budget") or 0)
        try:
            # ordered by training seed, NOT keyed by model key: keys are
            # per-process uuids, but seed i's model is identical across the
            # two servers (deterministic training)
            before = [_post_rows(url, k, probe_rows)["predictions"]
                      for k in keys]
            for q in qps_list:
                pick = _zipf_pick(keys, max(int(q * args.duration * 2), 64),
                                  args.zipf, seed=int(q))
                step = _run_step(url, keys[0], q, args.duration, args.rows,
                                 args.threads, pool, model_pick=pick)
                step["mode"] = mode
                artifact["steps"].append(step)
                _log(f"[fleet {mode}] offered={q:>7.0f}/s achieved="
                     f"{step['achieved_qps']:>7.1f}/s shed_rate="
                     f"{step['shed_rate']:.3f} p50={step['p50_ms']}ms "
                     f"p99={step['p99_ms']}ms")
            # byte-parity per model across the whole sweep's page-out/in
            after = [_post_rows(url, k, probe_rows)["predictions"]
                     for k in keys]
            parity[mode] = {"before": before, "after": after,
                            "stable": before == after}
            registry_stats[mode] = _scrape_registry(url)
        finally:
            _stop_server(proc)

    summary: dict = {}
    for mode in ("oversub", "resident"):
        steps = [s for s in artifact["steps"] if s["mode"] == mode]
        best = _sustained(steps)
        summary[f"{mode}_sustained_qps"] = best["offered_qps"] if best else 0.0
        summary[f"{mode}_p99_ms_at_sustained"] = (best["p99_ms"] if best
                                                  else None)
        if best:
            summary[f"{mode}_breakdown_at_sustained"] = best.get(
                "latency_breakdown")
        res = (registry_stats.get(mode) or {}).get("residency") or {}
        summary[f"{mode}_hbm_peak_bytes"] = res.get("hbm_peak_bytes")
        summary[f"{mode}_evictions"] = res.get("evictions")
        summary[f"{mode}_page_ins"] = res.get("page_ins")
        summary[f"{mode}_parity_stable"] = parity[mode]["stable"]
    summary["hbm_budget_bytes"] = budgets["oversub"]
    peak = summary.get("oversub_hbm_peak_bytes") or 0
    summary["peak_within_budget"] = bool(
        budgets["oversub"] and peak <= budgets["oversub"])
    # cross-mode parity: same seeds, same data -> same models; paging must
    # not perturb a single bit
    summary["parity_across_modes"] = (
        parity["oversub"]["after"] == parity["resident"]["after"])
    c = summary.get("resident_sustained_qps") or 0.0
    b = summary.get("oversub_sustained_qps") or 0.0
    summary["qps_ratio_vs_resident"] = round(b / c, 3) if c else None
    artifact["summary"] = summary
    artifact["registry"] = {
        m: (registry_stats.get(m) or {}).get("residency")
        for m in registry_stats
    }

    out_path = args.out or os.path.join(ROOT, f"FLEET_{stamp}.json")
    line = json.dumps(artifact)
    with open(out_path, "w") as f:
        f.write(line + "\n")
    print(line)
    _log(f"fleet artifact written to {out_path}")
    ok = (summary["peak_within_budget"]
          and summary["parity_across_modes"]
          and summary["oversub_parity_stable"]
          and (summary["qps_ratio_vs_resident"] or 0) >= 0.5)
    _log(f"fleet acceptance {'OK' if ok else 'NOT MET'}: {summary}")
    return 0


def _sustained(steps: list[dict]) -> dict | None:
    """Highest offered rate the tier sustains: <= 1% of the offered load was
    shed, errored, or left unissued inside the step window (shed_rate
    already folds all three in). Judged against what was actually SENT, not
    the nominal rate — Poisson draws undershoot the nominal by a few
    percent and must not fail a healthy step."""
    best = None
    for s in steps:
        if s["shed_rate"] <= 0.01:
            if best is None or s["offered_qps"] > best["offered_qps"]:
                best = s
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--serve", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--serve-fleet", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--mode", default="both",
                    choices=("both", "batched", "control"))
    ap.add_argument("--fleet", action="store_true",
                    help="fleet A/B: Zipf traffic over --models models at "
                         "--oversub x HBM oversubscription through the "
                         "serving registry, vs the all-resident control")
    ap.add_argument("--models", type=int, default=10,
                    help="fleet mode: how many models to train/serve")
    ap.add_argument("--oversub", type=int, default=10,
                    help="fleet mode: HBM budget = fleet bytes / this")
    ap.add_argument("--zipf", type=float, default=1.2,
                    help="fleet mode: Zipf skew of the per-model traffic")
    ap.add_argument("--qps", default="25,50,100,200,400,800,1600,3200",
                    help="comma list of offered QPS steps")
    ap.add_argument("--duration", type=float, default=6.0,
                    help="seconds per step")
    ap.add_argument("--rows", type=int, default=1,
                    help="rows per request (1 = the per-user pattern)")
    ap.add_argument("--threads", type=int, default=48)
    ap.add_argument("--window-ms", default=None,
                    help="override the batched server's coalescing window")
    ap.add_argument("--url", default=None,
                    help="drive an existing server instead of spawning")
    ap.add_argument("--model", default=None,
                    help="model key on the existing server (--url)")
    ap.add_argument("--out", default=None,
                    help="artifact path (default LOADTEST_<stamp>.json)")
    args = ap.parse_args(argv)

    if args.serve_fleet:
        _serve_fleet(args)
        return 0
    if args.serve:
        _serve(args)
        return 0

    stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    if args.fleet:
        return _run_fleet(args, stamp)
    qps_list = [float(q) for q in args.qps.split(",") if q.strip()]
    pool = _row_pool()
    modes = (["batched", "control"] if args.mode == "both" else [args.mode])
    artifact = {
        "schema": "loadtest/v1", "stamp": stamp, "rows_per_request": args.rows,
        "duration_s_per_step": args.duration, "modes": modes, "steps": [],
        "env": {
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", ""),
            "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""),
            "window_ms": args.window_ms
            or os.environ.get("H2O3_TPU_SCORE_BATCH_WINDOW_MS", "(default)"),
        },
    }
    parity_probe = pool[:16]
    parity: dict[str, list] = {}

    for mode in modes:
        if args.url:
            proc, url, model_key = None, args.url.rstrip("/"), args.model
            if not model_key:
                _log("--url needs --model")
                return 2
        else:
            proc, url, model_key = _spawn_server(mode, args.window_ms)
        try:
            # parity probe: the same 16 rows through each mode's server —
            # batched and per-request answers must be byte-identical
            resp = _post_rows(url, model_key, parity_probe)
            parity[mode] = resp["predictions"].get(
                "pos", resp["predictions"].get("predict"))
            for q in qps_list:
                step = _run_step(url, model_key, q, args.duration,
                                 args.rows, args.threads, pool)
                step["mode"] = mode
                artifact["steps"].append(step)
                _log(f"[{mode}] offered={q:>7.0f}/s achieved="
                     f"{step['achieved_qps']:>7.1f}/s shed_rate="
                     f"{step['shed_rate']:.3f} p50={step['p50_ms']}ms "
                     f"p99={step['p99_ms']}ms occupancy="
                     f"{step['mean_batch_occupancy']}")
        finally:
            if proc is not None:
                _stop_server(proc)

    summary: dict = {}
    for mode in modes:
        steps = [s for s in artifact["steps"] if s["mode"] == mode]
        best = _sustained(steps)
        summary[f"{mode}_sustained_qps"] = best["offered_qps"] if best else 0.0
        summary[f"{mode}_p99_ms_at_sustained"] = best["p99_ms"] if best else None
        if best:
            summary[f"{mode}_breakdown_at_sustained"] = best.get(
                "latency_breakdown")
        if mode == "batched" and best:
            summary["batched_occupancy_at_sustained"] = best[
                "mean_batch_occupancy"]
    if len(modes) == 2:
        c = summary.get("control_sustained_qps") or 0.0
        b = summary.get("batched_sustained_qps") or 0.0
        summary["speedup"] = round(b / c, 2) if c else None
        summary["parity_byte_equal"] = (parity.get("batched")
                                        == parity.get("control"))
        # the operational comparison: serve >= 3x the control's capacity —
        # what does each mode's tail look like AT THAT RATE?
        target = 3 * c
        cand = sorted(
            (s for s in artifact["steps"] if s["offered_qps"] >= target),
            key=lambda s: s["offered_qps"])
        by_mode = {}
        for s in cand:
            by_mode.setdefault(s["mode"], s)
        if "batched" in by_mode and "control" in by_mode:
            summary["p99_at_3x_control"] = {
                "offered_qps": by_mode["batched"]["offered_qps"],
                "batched_ms": by_mode["batched"]["p99_ms"],
                "control_ms": by_mode["control"]["p99_ms"],
            }
    artifact["summary"] = summary

    out_path = args.out or os.path.join(ROOT, f"LOADTEST_{stamp}.json")
    line = json.dumps(artifact)
    with open(out_path, "w") as f:
        f.write(line + "\n")
    print(line)
    _log(f"artifact written to {out_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
