#!/usr/bin/env python
"""Stage-level wall-time attribution for one bench-config GBM train on the
real TPU: where do the ~0.13 s/tree of non-fused-builder time go?

Monkeypatches timers around fit_bins / bin_frame / build_trees_scanned /
trees_from_stacked / metrics and prints one JSON line. Run on the chip:

    python tools/profile_train_stages.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STAGES: dict[str, float] = {}


def _wrap(mod, name, label):
    fn = getattr(mod, name)

    def timed(*a, **k):
        t0 = time.perf_counter()
        out = fn(*a, **k)
        try:  # block so the timer sees device completion, not dispatch
            import jax

            jax.tree.map(
                lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
                out,
            )
        except Exception:
            pass
        dt = time.perf_counter() - t0
        STAGES[label] = STAGES.get(label, 0.0) + dt
        try:  # stamp the flight ring so stage walls cross-reference the
            # dispatch_device_seconds events by timestamp (ISSUE 13)
            from h2o3_tpu.utils import flightrec

            flightrec.record("stage", stage=label, dur_ms=round(dt * 1e3, 3))
        except Exception:
            pass
        return out

    setattr(mod, name, timed)
    return fn


def main() -> None:
    import bench
    import h2o3_tpu

    h2o3_tpu.init(log_level="WARN")

    from h2o3_tpu.models.tree import binning, gbm, shared_tree

    # gbm binds fit_bins/bin_frame at module import (patch gbm's refs) but
    # imports the scanned builder at call time (patch shared_tree's attrs)
    _wrap(gbm, "fit_bins", "fit_bins")
    _wrap(gbm, "bin_frame", "bin_frame")
    _wrap(shared_tree, "build_trees_scanned", "fused_builder")
    _wrap(shared_tree, "trees_from_stacked", "record_unpack")
    _wrap(gbm, "_metrics_from_F", "metrics")

    df = bench.make_data()
    fr = h2o3_tpu.upload_file(df)
    from h2o3_tpu.models.tree import GBM

    kw = dict(max_depth=6, learn_rate=0.1, min_rows=10.0,
              score_tree_interval=1000, seed=42, ntrees=20)
    GBM(**kw).train(y="label", training_frame=fr)  # warmup/compile
    STAGES.clear()
    t0 = time.time()
    GBM(**kw).train(y="label", training_frame=fr)
    total = time.time() - t0
    other = total - sum(STAGES.values())
    print(json.dumps({"total_s": round(total, 4), "unattributed_s": round(other, 4),
                      **{k: round(v, 4) for k, v in STAGES.items()}}))


if __name__ == "__main__":
    main()
