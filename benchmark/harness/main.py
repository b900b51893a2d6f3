"""The one command's flow: resolve the cell to its files, set up, warm up,
measure the window, free the program's state, decide ``correct`` against the
plain reference, print the result line."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmark/
ROOT = os.path.dirname(HERE)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(workload: str, rows: int | None = None) -> tuple:
    """A cell's files, found by the names in BENCHMARK.json: (benchmark,
    cell, configuration, traffic, the configuration's module)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"benchmark: no workload {workload!r} in BENCHMARK.json")
    cfg = load_json(HERE, "configs", cell["config"] + ".json")
    if rows:
        cfg["rows"] = rows
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    mod = importlib.import_module(f"benchmark.configs.{cell['config']}")
    return bench, cell, cfg, traffic, mod


def metric_reader(name: str):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


def reports(metric: dict, cell: dict, end_to_end: set | None = None) -> bool:
    """Does ``cell`` report ``metric``? By the metric's ``workloads`` key;
    without one an end-to-end metric is reported in every cell, and a
    per-layer metric wherever the end-to-end metric it moves is."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return end_to_end is None or metric["moves"] in end_to_end


def main(argv: list, t_start: float) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run off the chip to check the plumbing; writes no device metric")
    ap.add_argument("--rows", type=int, default=None,
                    help="rehearsals only: a smaller frame")
    args = ap.parse_args(argv)
    if args.rows is not None and not args.rehearse:
        raise SystemExit("benchmark: --rows is for --rehearse only")

    bench, cell, cfg, traffic, mod = resolve(args.workload, args.rows)
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]

    import jax

    from . import peaks as _peaks
    from . import trace as _trace
    from . import window as _window

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    peaks = None
    if not args.rehearse:
        if device["platform"] != "tpu" or len(devs) < cell["chips"]:
            raise SystemExit(
                f"benchmark: {cell['name']} needs {cell['chips']} TPU chip(s); jax "
                f"reports {device}. It measures the chip and does not fall back.")
        peaks = _peaks.peaks_for(device["kind"])

    import h2o3_tpu

    h2o3_tpu.init()
    compiles = _window.CompileCounter()
    data = mod.make_frame(cfg, args.seed)
    _window.block_on([data.columns, data.label])
    _, warm_s, _ = _window.one_call(mod, cfg, data)  # the cell's own call
    log(f"warm-up call: {warm_s:.3f} s")
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s, {compiles.count} programs compiled or loaded")

    # ---- the measured window ----
    e2e = [m for m in bench["end_to_end"] if reports(m, cell)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if reports(m, cell, reported)]
    readers = {m["name"]: metric_reader(m["name"]) for m in layer} if args.trace else {}
    counter_names = sorted({c for r in readers.values()
                            for c in getattr(r, "COUNTERS", ())})
    run = _window.KINDS[traffic["kind"]](
        mod, cfg, data, traffic, seconds, bool(args.trace), counter_names, compiles)
    calls = run["calls"]
    total_passes = sum(c["passes"] for c in calls)
    log(f"window {run['window_s']:.3f} s: {len(calls)} calls, {total_passes} passes, "
        f"{run['failed']} failed, {run['compiles_in_window']} programs compiled "
        f"inside it; calls {[round(c['wall_s'], 3) for c in calls]}")
    # the peak on the fullest chip: what the chip held at its fullest after
    # a call of the window, live buffers plus what the runtime keeps reserved
    # for compiled programs' temporaries; or JAX's own peak of live buffers
    # where that is more. The two parts stand beside it, apart.
    live_peak = max((int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in jax.local_devices()), default=0)
    held_live, held_reserved = run["device_bytes"]
    device["memory_peak_bytes"] = max(live_peak, held_live + held_reserved)
    device["memory_live_peak_bytes"] = live_peak
    device["memory_reserved_bytes"] = held_reserved

    # ---- free the program's state, then decide `correct` on the host ----
    import numpy as np

    models = run.pop("models")
    checks, correct = {}, False
    if models:
        pick = int(np.random.default_rng(args.seed).integers(len(models)))
        model = mod.outputs(models[pick])
        X, y = data.host()
        del models
        data.drop()
        t0 = time.perf_counter()
        compared = mod.compare(cfg, X, y, model)
        got = compared["program"]
        log(f"reference over model {pick + 1} of {len(calls)}: "
            f"{time.perf_counter() - t0:.3f} s")
        if compared.get("diagnostic"):  # read by no one but the reader of a refusal
            log(f"diagnostic: {json.dumps(compared['diagnostic'])}")
        checks = {k: {"value": float(v), "limit": float(cfg["limits"][k])}
                  for k, v in got.items()}
        correct = run["failed"] == 0 and all(
            c["value"] <= c["limit"] for c in checks.values())  # NaN fails

    # ---- the metrics ----
    metrics: dict = {}
    values = {"setup_s": setup_s,
              "train_rows_per_s": cfg["rows"] * total_passes / run["window_s"]}
    breakdown = None
    if args.trace:
        traced = run["traced"]
        tr = _trace.load(traced["dir"]) if traced else {"device": {}}
        if traced:
            shutil.rmtree(traced["dir"], ignore_errors=True)
        busy = _trace.busy_seconds(tr)
        if busy is not None:
            device["busy_s"], device["window_s"] = busy, traced["wall_s"]
            breakdown = {
                "device_ops": _trace.device_ops(tr),
                "idle_gaps": _trace.idle_gaps(tr, traced["wall_s"], traced["where"])}
        ctx = {"trace": tr, "busy_s": busy, "call": traced, "cfg": cfg,
               "config": mod, "peaks": peaks, "log": log}
        for m in layer:
            v = readers[m["name"]].read(ctx) if traced else None
            if v is not None:  # a reader with nothing to read is left out
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    result = {"correct": bool(correct), "attempted": len(calls) + run["failed"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    if args.rehearse:  # a CPU run gives counts, never a device metric
        result["rehearsal"] = {
            "metrics": metrics, "calls": len(calls), "passes": total_passes,
            "rows": cfg["rows"], "compiles_in_window": run["compiles_in_window"]}
        result["metrics"] = {}
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']:.6g} (limit {c['limit']:.6g})"
            + ("" if c["value"] <= c["limit"] else "  <-- over the limit"))
    print(json.dumps(result), flush=True)
    return 0
