"""Readings that the network cell's limits are set from, ``limits.py``'s way
(which knows only ``faults.py``'s plants and is not edited): in one process
on the chip at the cell's own size (or rehearsed on the CPU with --rehearse
--rows), for each seed one whole ``train`` call through the window's own
entry; then, in a child process that never imports jax, the comparison with
the plain reference, for the first --control seeds the control (the
reference one precision down) as well, and for the first --faults seeds each
fault of ``faults_dl.py``. The child keeps the reference's host memory apart
from the process that holds the chip.

    python3 benchmark/tests/limits_dl.py --seeds 11,12,13 --control 3 \
        --faults 3 --out limits_dl.jsonl
"""

import argparse
import json
import os
import pickle
import resource
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def max_rss_bytes() -> int:
    """This process's peak resident memory (Linux keeps it over an ``exec``:
    the child's reading is at least its parent's at the fork)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def compare_saved(path: str, workload: str, rows: int | None) -> int:
    """The child: the comparisons of one saved training, one line out."""
    from benchmark.harness.main import resolve
    from benchmark.tests.faults_dl import PLANTED

    _, cell, cfg, _, mod = resolve(workload, rows)
    with open(path, "rb") as f:  # written by this script's parent
        saved = pickle.load(f)
    X, y, model, line = saved["X"], saved["y"], saved["model"], saved["line"]
    memo: dict = {}  # the design and the forward, once for the seed's weights
    got = mod.compare(cfg, X, y, model, control=saved["control"], memo=memo)
    line.update(program=got["program"], control=got["control"],
                reference=got.get("reference"), diagnostic=got.get("diagnostic"))
    if saved["faults"]:
        line["faults"] = {
            name: mod.compare(cfg, X, y, plant(model, X, y, cfg), memo=memo)["program"]
            for name, plant in PLANTED[cell["config"]].items()}
    line["reference_max_rss_bytes"] = max_rss_bytes()
    print(json.dumps(line), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="dl_higgs.train")
    ap.add_argument("--seeds")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--compare-saved", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.compare_saved:
        return compare_saved(args.compare_saved, args.workload, args.rows)

    import jax

    import h2o3_tpu
    from benchmark.harness import window
    from benchmark.harness.main import resolve
    from h2o3_tpu.cluster.registry import DKV

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("limits_dl.py: no TPU (use --rehearse --rows to rehearse)")
    _, cell, cfg, _, mod = resolve(args.workload, args.rows)
    h2o3_tpu.init()
    out = open(args.out, "a") if args.out else None
    with tempfile.TemporaryDirectory() as tmp:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            data = mod.make_frame(cfg, seed)
            est, call_s, n_passes = window.one_call(mod, cfg, data)
            model = mod.outputs(est)
            peak = sum(window.device_bytes())
            X, y = data.host()
            del est
            data.drop()
            for k in DKV.keys("job*"):  # finished jobs pin their frames
                DKV.remove(k)
            path = os.path.join(tmp, f"{seed}.pkl")
            line = {"workload": args.workload, "seed": seed, "rows": cfg["rows"],
                    "call_s": call_s, "passes": n_passes, "memory_peak_bytes": peak,
                    "train_max_rss_bytes": max_rss_bytes()}
            with open(path, "wb") as f:
                pickle.dump({"X": X, "y": y, "model": model, "line": line,
                             "control": i < args.control, "faults": i < args.faults},
                            f, protocol=pickle.HIGHEST_PROTOCOL)
            del X, y, model
            child = [sys.executable, os.path.abspath(__file__), "--workload",
                     args.workload, "--compare-saved", path]
            if args.rows:
                child += ["--rows", str(args.rows)]
            done = subprocess.run(child, capture_output=True, text=True,
                                  env={**os.environ, "JAX_PLATFORMS": "cpu"})
            os.remove(path)
            if done.returncode != 0:
                print(done.stderr[-3000:], file=sys.stderr, flush=True)
                raise SystemExit(f"limits_dl.py: the comparison of seed {seed} failed")
            got = json.loads(done.stdout.strip().splitlines()[-1])
            got["seconds"] = time.perf_counter() - t0
            print(json.dumps(got), flush=True)
            if out:
                out.write(json.dumps(got) + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
