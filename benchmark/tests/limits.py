"""Readings that the limits of ``correct`` are set from, taken in one process
on the chip at the cell's own size (or rehearsed on the CPU with --rehearse
--rows): for each seed one whole ``train`` call through the window's own
entry, then the comparison with the plain reference; for the first
--control seeds the control (the reference one precision down) as well, and
for the first --faults seeds each planted fault.

    python3 benchmark/tests/limits.py --workload gbm_higgs.train \
        --seeds 11,12,13 --control 3 --faults 3 --out chiprun_out/limits.jsonl
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args()

    import jax

    import h2o3_tpu
    from benchmark.configs.higgs_data import make_frame
    from benchmark.harness import window
    from benchmark.harness.main import resolve
    from benchmark.tests.faults import PLANTED

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("limits.py: no TPU (use --rehearse --rows to rehearse)")
    _, cell, cfg, _, mod = resolve(args.workload, args.rows)
    h2o3_tpu.init()
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        data = mod.make_frame(cfg, seed)
        est, call_s, n_passes = window.one_call(mod, cfg, data)
        model = mod.outputs(est)
        peak = sum(window.device_bytes())
        X, y = data.host()
        half = None
        if i < args.faults:
            part = make_frame(cfg["rows"], cfg["cols"], seed, first_rows=cfg["rows"] // 2)
            est2, _, _ = window.one_call(mod, cfg, part)
            half = mod.outputs(est2)
            part.drop()
        del est
        data.drop()
        # the program keeps every finished Job (and through it the frame) in
        # its DKV; a client that changes frames would run out of memory
        from h2o3_tpu.cluster.registry import DKV

        for k in DKV.keys("job*"):
            DKV.remove(k)
        got = mod.compare(cfg, X, y, model, control=i < args.control)
        line = {"workload": args.workload, "seed": seed, "rows": cfg["rows"],
                "call_s": call_s, "passes": n_passes, "memory_peak_bytes": peak, "program": got["program"],
                "control": got["control"], "reference": got.get("reference"),
                "diagnostic": got.get("diagnostic")}
        if i < args.faults:
            f = mod.compare(cfg, X, y, half)
            line["faults"] = {"half_batch": {**f["program"], **(f.get("diagnostic") or {})}}
            for name, plant in PLANTED[cell["config"]].items():
                f = mod.compare(cfg, X, y, plant(model))
                line["faults"][name] = {**f["program"], **(f.get("diagnostic") or {})}
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
