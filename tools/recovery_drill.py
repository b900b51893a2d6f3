#!/usr/bin/env python
"""Self-healing recovery drill (ISSUE 10): kill a worker mid-GBM and prove
the supervised recovery loop — detection → reform → resume — completes with
NO operator action and reproduces the uninterrupted run.

What it does, per algo (gbm / glm / dl / automl):

1. builds the uninterrupted reference model;
2. re-runs with ``export_checkpoints_dir`` under
   :func:`h2o3_tpu.cluster.recovery.run_supervised` with a one-shot
   ``die:<algo>`` fault armed — the worker "dies" at a collective boundary
   right after an interval snapshot, exactly what a preempted v5e host does;
3. asserts the healed run's metrics land within the PR-2 1e-6 resume pin of
   the reference and the cloud ended healthy with the generation ticked;
4. emits one JSON artifact line with the metric deltas, restart counts, and
   the ``recovery_seconds`` histogram snapshot from the registry.

``--elastic`` (ISSUE 17) is the topology-chaos variant: the kill is a
``reshape:RxC`` fault, so the formation "comes back different" and the
snapshot must resume on a CHANGED mesh shape. Each algo is killed on a
different transition of the shape-change matrix (8->4 scale-down, 4->8
scale-up, 2x4->4x2 transpose, 1-D->2-D) with the same 1e-6 final-metric
pin plus splits/coefs parity; emits ``ELASTIC_DRILL_<stamp>.json``.

Runs on the CPU proxy too (that is what CI exercises via
tests/test_recovery.py — this tool is the measured-artifact version of the
same drill).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU proxy runs drill the same 8-device sharded mesh the bench artifacts
# use (real accelerators keep their native device count)
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


def _drill_gbm(fr, ckdir):
    import numpy as np

    from h2o3_tpu.cluster import recovery
    from h2o3_tpu.models import GBM
    from h2o3_tpu.utils import faults

    kw = dict(ntrees=16, max_depth=4, seed=11, learn_rate=0.2,
              score_tree_interval=4)
    full = GBM(**kw).train(y="y", training_frame=fr)

    def _launch(ckpt):
        kw2 = dict(kw, export_checkpoints_dir=ckdir)
        if ckpt:
            kw2["checkpoint"] = ckpt
        return GBM(**kw2).train(y="y", training_frame=fr)

    t0 = time.perf_counter()
    with faults.inject(die={"gbm"}):
        healed = recovery.run_supervised(_launch, ckdir=ckdir, algo="gbm",
                                         description="gbm drill")
    wall = time.perf_counter() - t0
    delta = abs(healed.training_metrics.logloss - full.training_metrics.logloss)
    assert delta <= 1e-6, f"gbm resume pin violated: {delta}"
    assert healed.output["ntrees_actual"] == kw["ntrees"]
    pa = full.predict(fr).vec("p").to_numpy()
    pb = healed.predict(fr).vec("p").to_numpy()
    return {"logloss_delta": delta, "wall_s": wall,
            "pred_max_delta": float(np.max(np.abs(pa - pb)))}


def _drill_glm(fr, ckdir):
    import numpy as np

    from h2o3_tpu.cluster import recovery
    from h2o3_tpu.models import GLM
    from h2o3_tpu.utils import faults

    kw = dict(family="binomial", max_iterations=25, seed=1)
    full = GLM(**kw).train(y="y", training_frame=fr)

    def _launch(ckpt):
        kw2 = dict(kw, export_checkpoints_dir=ckdir)
        if ckpt:
            kw2["checkpoint"] = ckpt
        return GLM(**kw2).train(y="y", training_frame=fr)

    t0 = time.perf_counter()
    with faults.inject(die={"glm"}):
        healed = recovery.run_supervised(_launch, ckdir=ckdir, algo="glm",
                                         description="glm drill")
    wall = time.perf_counter() - t0
    beta_delta = float(np.max(np.abs(
        np.asarray(healed.output["beta_std"]) - np.asarray(full.output["beta_std"]))))
    delta = abs(healed.training_metrics.logloss - full.training_metrics.logloss)
    assert delta <= 1e-6, f"glm resume pin violated: {delta}"
    return {"logloss_delta": delta, "beta_max_delta": beta_delta,
            "wall_s": wall}


def _drill_dl(fr, ckdir):
    import numpy as np

    from h2o3_tpu.cluster import recovery
    from h2o3_tpu.models import DeepLearning
    from h2o3_tpu.utils import faults

    kw = dict(hidden=[8], seed=4, mini_batch_size=64, epochs=4)
    full = DeepLearning(**kw).train(y="y", training_frame=fr)

    def _launch(ckpt):
        kw2 = dict(kw, export_checkpoints_dir=ckdir)
        if ckpt:
            kw2["checkpoint"] = ckpt
        return DeepLearning(**kw2).train(y="y", training_frame=fr)

    t0 = time.perf_counter()
    with faults.inject(die={"deeplearning"}):
        healed = recovery.run_supervised(_launch, ckdir=ckdir,
                                         algo="deeplearning",
                                         description="dl drill")
    wall = time.perf_counter() - t0
    delta = abs(healed.training_metrics.logloss - full.training_metrics.logloss)
    assert delta <= 1e-6, f"dl resume pin violated: {delta}"
    assert healed.output["epochs_trained"] == kw["epochs"]
    pa = full.predict(fr).vec("p").to_numpy()
    pb = healed.predict(fr).vec("p").to_numpy()
    return {"logloss_delta": delta, "wall_s": wall,
            "pred_max_delta": float(np.max(np.abs(pa - pb)))}


def _drill_automl(fr, ckdir):
    from h2o3_tpu.cluster import recovery
    from h2o3_tpu.automl import AutoML
    from h2o3_tpu.utils import faults

    spec = dict(max_models=3, nfolds=2, seed=11, max_runtime_secs=0.0,
                include_algos=["GBM", "GLM"], project_name="drill")

    def lb(aml):
        return sorted(
            (r["model_id"].split("_")[0], round(float(r["auc"]), 10))
            for r in aml.leaderboard.as_table())

    full = AutoML(**spec)
    full.train(y="y", training_frame=fr)
    assert full.leaderboard.models, "drill spec built no models"

    def _launch(_ckpt):
        aml = AutoML(export_checkpoints_dir=ckdir, **spec)
        aml.train(y="y", training_frame=fr)
        return aml

    t0 = time.perf_counter()
    with faults.inject(die={"automl"}):
        healed = recovery.run_supervised(_launch, description="automl drill")
    wall = time.perf_counter() - t0
    assert lb(healed) == lb(full), "automl resume leaderboard diverged"
    recovered = sum(1 for e in healed.event_log if e["stage"] == "recover")
    assert recovered >= 1, "resume recovered no steps — the drill was vacuous"
    return {"leaderboard_equal": True, "steps_recovered": recovered,
            "wall_s": wall}


# ---------------------------------------------------------------------------
# elastic drills (ISSUE 17): kill mid-train with a reshape:RxC fault and
# resume the snapshot on a DIFFERENT mesh shape. Each algo is killed on a
# different transition so one artifact covers the whole shape-change matrix
# (scale-down, scale-up, 2-D transpose, 1-D <-> 2-D) on 8 devices.

ELASTIC_MATRIX = (
    ("gbm", (1, 8), (1, 4), "8->4"),
    ("glm", (1, 4), (1, 8), "4->8"),
    ("deeplearning", (2, 4), (4, 2), "2x4->4x2"),
    ("gbm", (1, 8), (2, 4), "1d->2d"),
)


def _elastic_case(algo, start, end, fr):
    """Reference run on ``start``; killed run re-forms onto ``end`` mid-train
    and resumes its snapshot there. Returns the parity record (pins at the
    PR-2 1e-6 resume contract — docs/RECOVERY.md 'Elastic resume')."""
    import tempfile

    import numpy as np

    from h2o3_tpu.cluster import cloud, recovery
    from h2o3_tpu.models import GBM, GLM, DeepLearning
    from h2o3_tpu.parallel import mesh
    from h2o3_tpu.utils import faults

    cls, kw = {
        "gbm": (GBM, dict(ntrees=16, max_depth=4, seed=11, learn_rate=0.2,
                          score_tree_interval=4)),
        "glm": (GLM, dict(family="binomial", max_iterations=25, seed=1)),
        "deeplearning": (DeepLearning, dict(hidden=[8], seed=4,
                                            mini_batch_size=64, epochs=4)),
    }[algo]

    mesh.reform_mesh(start)
    full = cls(**kw).train(y="y", training_frame=fr)
    ref_ll = full.training_metrics.logloss
    ref_pred = full.predict(fr).vec("p").to_numpy().copy()

    with tempfile.TemporaryDirectory(prefix=f"elastic_{algo}_") as ckdir:
        def _launch(ckpt):
            kw2 = dict(kw, export_checkpoints_dir=ckdir)
            if ckpt:
                kw2["checkpoint"] = ckpt
            return cls(**kw2).train(y="y", training_frame=fr)

        t0 = time.perf_counter()
        with faults.inject(reshape=end):
            healed = recovery.run_supervised(
                _launch, ckdir=ckdir, algo=algo,
                description=f"elastic {algo} {start}->{end}")
        wall = time.perf_counter() - t0

    got = dict(mesh.get_mesh().shape)
    assert got.get("rows", 1) * got.get("cols", 1) == end[0] * end[1], \
        f"resume did not land on {end}: mesh is {got}"
    assert cloud.degraded_reason() is None, "cloud left degraded"

    delta = abs(healed.training_metrics.logloss - ref_ll)
    assert delta <= 1e-6, f"{algo} elastic resume pin violated: {delta}"
    rec = {"algo": algo, "from": f"{start[0]}x{start[1]}",
           "to": f"{end[0]}x{end[1]}", "logloss_delta": delta,
           "recovery_seconds": wall}
    # splits/coefs parity: trees predict identically (split-for-split), GLM
    # coefficients match, DL predictions match — all within f32 resolution
    pred = healed.predict(fr).vec("p").to_numpy()
    rec["pred_max_delta"] = float(np.max(np.abs(ref_pred - pred)))
    assert rec["pred_max_delta"] <= 1e-5, \
        f"{algo} elastic pred parity violated: {rec['pred_max_delta']}"
    if algo == "gbm":
        assert healed.output["ntrees_actual"] == kw["ntrees"]
    elif algo == "deeplearning":
        assert healed.output["epochs_trained"] == kw["epochs"]
    elif algo == "glm":
        rec["beta_max_delta"] = float(np.max(np.abs(
            np.asarray(healed.output["beta_std"])
            - np.asarray(full.output["beta_std"]))))
        assert rec["beta_max_delta"] <= 1e-5, \
            f"glm elastic coef parity violated: {rec['beta_max_delta']}"
    return rec


def _run_elastic(out_path):
    import jax

    import h2o3_tpu
    from h2o3_tpu.cluster import cloud
    from h2o3_tpu.parallel import mesh
    from h2o3_tpu.utils import metrics as mx

    h2o3_tpu.init()
    if len(jax.devices()) < 8:
        print(f"elastic drill needs >= 8 devices (have {len(jax.devices())})",
              file=sys.stderr)
        return 2
    fr = _frame()
    gen0 = cloud.generation()
    results = []
    try:
        for algo, start, end, label in ELASTIC_MATRIX:
            rec = _elastic_case(algo, start, end, fr)
            rec["transition"] = label
            results.append(rec)
            print(f"elastic {label} ({algo}): logloss_delta="
                  f"{rec['logloss_delta']:.2e} "
                  f"recovery_seconds={rec['recovery_seconds']:.2f}")
    finally:
        mesh.reform_mesh()  # re-plan onto every live device for whoever's next

    snap = mx.REGISTRY.snapshot()
    fam = {name: snap.get(name) for name in (
        "recovery_seconds", "recovery_attempts_total", "cloud_generation")}
    artifact = {
        "kind": "elastic_drill",
        "stamp": time.strftime("%Y%m%dT%H%M%SZ", time.gmtime()),
        "backend": jax.devices()[0].platform,
        "n_devices": len(jax.devices()),
        "generations_ticked": cloud.generation() - gen0,
        "results": results,
        "recovery_seconds": max(r["recovery_seconds"] for r in results),
        "recovery_metrics": fam,
        "ok": True,
    }
    out = out_path or f"ELASTIC_DRILL_{artifact['stamp']}.json"
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps(artifact))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="artifact path (default: "
                    "RECOVERY_DRILL_<stamp>.json in the repo root)")
    ap.add_argument("--algos", default="gbm,glm,automl")
    ap.add_argument("--elastic", action="store_true",
                    help="topology-chaos mode (ISSUE 17): each algo is "
                    "killed mid-train by a reshape:RxC fault and resumes "
                    "its snapshot on a DIFFERENT mesh shape; emits "
                    "ELASTIC_DRILL_<stamp>.json")
    args = ap.parse_args(argv)

    os.environ.setdefault("H2O3_TPU_RECOVERY", "1")
    os.environ.setdefault("H2O3_TPU_RECOVERY_BACKOFF", "0.05")

    if args.elastic:
        return _run_elastic(args.out)

    import tempfile

    import jax

    import h2o3_tpu
    from h2o3_tpu.cluster import cloud
    from h2o3_tpu.utils import metrics as mx

    h2o3_tpu.init()
    fr = _frame()
    drills = {"gbm": _drill_gbm, "glm": _drill_glm, "dl": _drill_dl,
              "automl": _drill_automl}
    gen0 = cloud.generation()
    results = {}
    for algo in args.algos.split(","):
        algo = algo.strip()
        with tempfile.TemporaryDirectory(prefix=f"drill_{algo}_") as ckdir:
            results[algo] = drills[algo](fr, ckdir)
        assert cloud.degraded_reason() is None, "cloud left degraded"

    # the recovery_seconds histogram snapshot: detection → resume dispatch
    snap = mx.REGISTRY.snapshot()
    fam = {name: snap.get(name) for name in (
        "recovery_seconds", "recovery_attempts_total",
        "cloud_generation", "cloud_health_transitions_total")}
    artifact = {
        "kind": "recovery_drill",
        "stamp": time.strftime("%Y%m%dT%H%M%SZ", time.gmtime()),
        "backend": jax.devices()[0].platform,
        "n_devices": len(jax.devices()),
        "generations_ticked": cloud.generation() - gen0,
        "results": results,
        "recovery_metrics": fam,
        "ok": True,
    }
    out = args.out or f"RECOVERY_DRILL_{artifact['stamp']}.json"
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps(artifact))
    return 0


if __name__ == "__main__":
    sys.exit(main())
