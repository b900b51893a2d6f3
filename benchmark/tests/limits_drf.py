"""Readings that the forest cell's limits are set from, ``limits.py``'s way
(which knows only ``faults.py``'s plants and is not edited): in one process
on the chip at the cell's own size (or rehearsed on the CPU with --rehearse
--rows), for each seed one whole ``train`` call through the window's own
entry, then the comparison with the plain reference; for the first --control
seeds the control (the reference one precision down) as well, and for the
first --faults seeds each fault of ``faults_drf.py``.

    python3 benchmark/tests/limits_drf.py --seeds 11,12,13 --control 3 \
        --faults 3 --out chiprun_out/limits_drf.jsonl
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
    ap.add_argument("--workload", default="drf_higgs.train")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args()

    import jax

    import h2o3_tpu
    from benchmark.harness import window
    from benchmark.harness.main import resolve
    from benchmark.tests.faults_drf import PLANTED
    from h2o3_tpu.cluster.registry import DKV

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("limits_drf.py: no TPU (use --rehearse --rows to rehearse)")
    _, cell, cfg, _, mod = resolve(args.workload, args.rows)
    h2o3_tpu.init()
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        data = mod.make_frame(cfg, seed)
        est, call_s, n_passes = window.one_call(mod, cfg, data)
        model = mod.outputs(est)
        leaves = [g[0].n_leaves for g in est.model.output["trees"]]
        peak = sum(window.device_bytes())
        X, y = data.host()
        del est
        data.drop()
        for k in DKV.keys("job*"):  # finished jobs pin their frames
            DKV.remove(k)
        got = mod.compare(cfg, X, y, model, control=i < args.control)
        line = {"workload": args.workload, "seed": seed, "rows": cfg["rows"],
                "call_s": call_s, "passes": n_passes, "leaves": leaves,
                "memory_peak_bytes": peak, "program": got["program"],
                "control": got["control"], "reference": got.get("reference"),
                "diagnostic": got.get("diagnostic")}
        if i < args.faults:
            line["faults"] = {
                name: mod.compare(cfg, X, y, plant(model, X, y, cfg))["program"]
                for name, plant in PLANTED[cell["config"]].items()}
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
