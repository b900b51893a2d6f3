#!/usr/bin/env python
"""Weak-scaling measurement for the histogram hot loop on a virtual CPU mesh
(VERDICT r3 item 4 / SURVEY §4 "real stack, local topology").

Fixed rows PER SHARD; mesh sizes 1/2/4/8. On this box the virtual devices
share the physical cores, so wall time CANNOT weak-scale by construction;
the honest signal (VERDICT r4 weak #3) is ``psum_share`` — the fraction the
cross-shard reduction adds over the local pass — reported as median with a
min-max band over repetitions. Writes WEAKSCALING_r05.json at the repo root.

    python tools/bench_weak_scaling.py
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent

ROWS_PER_SHARD = 262_144
N_COLS = 28
N_NODES = 32
N_BINS = 255


def main() -> None:
    if os.environ.get("_H2O3_WS_CHILD") != "1":
        env = dict(
            os.environ,
            _H2O3_WS_CHILD="1",
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=(
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip(),
        )
        os.execve(sys.executable, [sys.executable, os.path.abspath(__file__)], env)

    sys.path.insert(0, str(ROOT))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from h2o3_tpu.ops.histogram import histogram_in_jit
    from h2o3_tpu.parallel.mesh import shard_map

    devices = jax.devices()
    rng = np.random.default_rng(0)
    results = []
    for k in (1, 2, 4, 8):
        if k > len(devices):
            break
        mesh = Mesh(np.array(devices[:k]), ("rows",))
        sh = NamedSharding(mesh, P("rows"))
        n = ROWS_PER_SHARD * k
        bins = jax.device_put(
            rng.integers(0, N_BINS, (n, N_COLS)).astype(np.uint8), sh
        )
        nid = jax.device_put(rng.integers(0, N_NODES, n).astype(np.int32), sh)
        w = jax.device_put(np.ones(n, np.float32), sh)
        wy = jax.device_put(rng.normal(size=n).astype(np.float32), sh)

        fn = jax.jit(
            lambda b, i, w_, wy_: histogram_in_jit(
                b, i, (w_, wy_, w_), N_NODES, N_BINS, mesh=mesh
            )
        )
        def timed(f, *a, reps=5):
            """Per-rep wall times (median/min/max downstream, not a mean)."""
            jax.block_until_ready(f(*a))  # warm
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(f(*a))
                ts.append(time.perf_counter() - t0)
            return ts

        ts = timed(fn, bins, nid, w, wy)

        # local-only variant (no psum) isolates the reduction share
        from h2o3_tpu.ops.histogram import _select_local

        local = _select_local()
        loc_fn = jax.jit(
            shard_map(
                lambda b, i, w_, wy_: local(
                    b, i, jnp.stack([w_, wy_, w_], 1), N_NODES, N_BINS),
                mesh=mesh,
                in_specs=(P("rows"),) * 4,
                out_specs=P("rows"),
                check_vma=False,
            )
        )
        ts_local = timed(loc_fn, bins, nid, w, wy)

        med = lambda xs: sorted(xs)[len(xs) // 2]
        # run-order-matched pairs: rep i of the full pass against rep i of
        # the local pass, so each share reflects one machine state. Sorting
        # the two lists independently pairs fastest-with-fastest, which
        # understates the band whenever noise hits the two passes on
        # different reps.
        shares = [
            max(t - tl, 0.0) / t for t, tl in zip(ts, ts_local) if t > 0
        ]
        results.append({
            "mesh_shards": k,
            "rows_total": n,
            "rows_per_shard": ROWS_PER_SHARD,
            "hist_s_median": round(med(ts), 4),
            "hist_s_minmax": [round(min(ts), 4), round(max(ts), 4)],
            "hist_local_s_median": round(med(ts_local), 4),
            "hist_local_s_minmax": [
                round(min(ts_local), 4), round(max(ts_local), 4)
            ],
            "psum_share_median": round(med(shares), 4) if shares else None,
            "psum_share_minmax": [round(min(shares), 4), round(max(shares), 4)]
            if shares else None,
        })
        print(results[-1], flush=True)

    payload = {
        "workload": f"histogram pass, {N_COLS} cols x {N_BINS} bins x {N_NODES} nodes, "
                    f"{ROWS_PER_SHARD} rows/shard (weak scaling)",
        "backend": "cpu x 8 virtual devices (XLA_FLAGS force_host_platform_device_count)",
        "note": "virtual devices share this box's physical cores, so wall "
                "time grows ~linearly with shards BY CONSTRUCTION and no "
                "efficiency number is reported from this box (VERDICT r4 "
                "weak #3). The scaling-relevant measurement is psum_share "
                "— the fraction the cross-shard reduction adds over the "
                "local pass, computed per run-order-matched rep pair (rep i "
                "full vs rep i local; independent sorting would pair "
                "fastest-with-fastest and understate the band) — reported "
                "as median with min-max over 5 reps. "
                "On real chips each shard has its own compute, leaving "
                "psum as the only scaling cost. The mesh_shards=1 row has "
                "NO reduction at all: its delta is the replicated-output "
                "layout/transpose cost and bounds the measurement noise.",
        "results": results,
    }
    out = ROOT / "WEAKSCALING_r05.json"
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
