"""Diagnose the 10M-row GBM RESOURCE_EXHAUSTED on the TPU — and
model the out-of-core data plane's capacity math (``--oocore``).

The 20260731T0101Z bench lost every entry after the headline to an OOM
cascade that started in the 10M build; an isolated 10M run reproduces it
even with ~15 GB HBM allocatable (probed) and an estimated ~3 GB working
set. CPU memory_analysis of the same program shows 13.4 GB temp at 10M —
but that's the scatter path; the TPU program (Pallas kernel) should be far
smaller. This tool gets the REAL number from the TPU compiler:

  1. AOT-compile the scanned-tree program for 1M/4M/10M rows on the TPU
     backend and print XLA's memory_analysis (temp/argument/output bytes).
  2. If the analysis looks fine, run an actual GBM train at increasing row
     counts (each in THIS process — run the tool fresh per investigation)
     to find where execution, as opposed to allocation plan, fails.

Usage (on the chip): python tools/tpu_mem_analysis.py [--train]
       python tools/tpu_mem_analysis.py --oocore [--out FILE]
          # analytic capacity model of compressed/binned frames + the HBM
          # window (ISSUE 11): largest trainable rows per pod bracket
          # before/after compression, and the streamed geometry that makes
          # Higgs-1B trainable through a fixed window. Pure host math —
          # runs anywhere, artifact committed alongside the PR.
       python tools/tpu_mem_analysis.py --live [URL]
          # read the devmem ledger + flight-recorder ring from a RUNNING
          # server (GET /3/Metrics?format=json + /3/FlightRecorder,
          # default http://127.0.0.1:54321) and print the measured
          # attribution table — per-owner live/peak bytes, per-device
          # in_use/limit, the unattributed (XLA program/temp) share —
          # next to the static capacity model, flagging an unattributed
          # share > 25% of in_use (the OOM-forensics threshold).
"""

from __future__ import annotations

import sys
import time

sys.path.insert(0, ".")

import numpy as np


def oocore_model(out_path: str | None = None) -> dict:
    """Largest-trainable-rows per bracket, resident f32 vs compressed
    (binned uint8) vs streamed through an HBM window (frame/chunkstore.py).

    Per-row device bytes during a GBM build:
    - resident f32 frame: C*4 (columns) + C (bins_u8) + 24 (w/y/F/wy/wh f32
      + nid i32) — the pre-ISSUE-11 layout keeps BOTH the f32 columns and
      the binned matrix resident;
    - compressed (H2O3_TPU_FRAME_COMPRESS): C (bins_u8) + 24 — the f32
      columns are released to the host tier after binning;
    - streamed (H2O3_TPU_HBM_WINDOW_BYTES): device holds only the window;
      rows are bounded by HOST RAM at (C + 24 + C*4) bytes/row host tier
      (the f32 mirrors + lanes), not by HBM.
    ``usable`` reserves HBM for compiled programs/temporaries (the 10M-row
    RESOURCE_EXHAUSTED above is exactly what ignoring that costs).

    The per-row math and the usable fraction live in
    ``h2o3_tpu/utils/overload.py`` (ISSUE 19): the SAME model the runtime's
    memory-aware admission preflight checks against measured
    ``devmem.headroom()`` — this offline table and the live gate cannot
    drift apart.
    """
    import json

    from h2o3_tpu.utils import overload as _ov

    GiB = 1 << 30
    C = 28  # Higgs feature width
    usable = _ov.USABLE_FRACTION
    state = _ov.STATE_BYTES  # per-row f32 lanes + nid
    brackets = [
        ("v5e-1", 1), ("v5e-4", 4), ("v5e-8", 8), ("v5e-16", 16),
        ("v5e-32", 32),
    ]
    hbm_per_chip = 16 * GiB
    per_row_res = _ov.per_row_device_bytes(C, "gbm", compressed=False)
    per_row_cmp = _ov.per_row_device_bytes(C, "gbm", compressed=True)
    rows_resident = lambda hbm: int(usable * hbm // per_row_res)
    rows_compressed = lambda hbm: int(usable * hbm // per_row_cmp)
    out = {"phase": "oocore_mem_model", "cols": C, "usable_fraction": usable,
           "hbm_per_chip_gib": hbm_per_chip / GiB, "brackets": []}
    for name, chips in brackets:
        hbm = chips * hbm_per_chip
        r_res, r_cmp = rows_resident(hbm), rows_compressed(hbm)
        out["brackets"].append({
            "bracket": name, "chips": chips, "hbm_gib": hbm / GiB,
            "max_rows_resident_f32": r_res,
            "max_rows_compressed_u8": r_cmp,
            "compression_capacity_ratio": round(r_cmp / max(r_res, 1), 2),
            "higgs_1b_fits_resident": r_res >= 1_000_000_000,
            "higgs_1b_fits_compressed": r_cmp >= 1_000_000_000,
        })
    # streamed geometry: Higgs-1B through a fixed per-chip window
    window = int(0.25 * usable * hbm_per_chip)
    host_bytes_per_row = C * 4 + C + state  # f32 mirrors + lanes, host tier
    out["streamed"] = {
        "window_bytes_per_chip": window,
        "bytes_per_row_device_lanes": C + state,
        "block_rows_per_chip_window": int(window // (2 * (C + state))),
        "higgs_1b_host_tier_gib": round(1e9 * host_bytes_per_row / GiB, 1),
        "note": "rows are host-RAM bound, not HBM bound: the device holds "
                "only the LRU window; Higgs-1B streams through any bracket "
                "whose hosts carry the spill tier",
    }
    # compiled-munging exchange geometry (ISSUE 20): the radix join's
    # all_to_all moves, per side, an i32 key lane + a bool validity lane
    # out and an i32 gid lane back, through (nd, cap) bucket buffers whose
    # cap the skew guard bounds at 4x the balanced share — so the exchange
    # working set is the padding factor times the row bytes, NOT the raw
    # frame. The sort lane moves no rows at all (one replicated order
    # vector + the payload gather).
    jx_bytes_per_row = 4 + 4  # key out + gid back (empty slots carry the
    # canonical-NaN key code, so no validity plane rides the exchange)
    skew_pad_max = 4.0            # tuple_gids_exchange's cap guard
    per_row_join = int(2 * jx_bytes_per_row * skew_pad_max + 8)  # both
    # sides' buckets live at once + the i64 staging codes
    out["munge_exchange"] = {
        "join_exchange_bytes_per_row_balanced": 2 * jx_bytes_per_row,
        "join_exchange_bytes_per_row_skew_capped": per_row_join,
        "sort_exchange_bytes_per_row": 4,  # replicated order i32 only
        "brackets": [{
            "bracket": name, "chips": chips,
            "max_join_rows_per_side": int(
                usable * chips * hbm_per_chip // per_row_join),
        } for name, chips in brackets],
        "note": "join capacity is exchange-buffer bound (cap*nd padding), "
                "not key bound: the skew guard falls back to the lexsort "
                "lane before the padded buckets can exceed 4x the data",
    }
    print(json.dumps(out), flush=True)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(out, f, indent=1)
    return out


def live_attribution(url: str = "http://127.0.0.1:54321") -> dict:
    """The measured twin of :func:`oocore_model`: pull the devmem ledger
    and the flight-recorder ring off a running server and print the
    attribution table. Returns the combined dict (and exits nonzero from
    __main__ when the unattributed share exceeds 25% — that much
    unclaimed HBM means XLA temps/programs, not the residency planes,
    are what an OOM investigation should chase)."""
    import json
    import urllib.request

    def _get(path):
        with urllib.request.urlopen(url.rstrip("/") + path, timeout=10) as r:
            return json.loads(r.read())

    fr = _get("/3/FlightRecorder?n=64")
    dm = fr.get("devmem", {})
    owned = dm.get("owned_bytes", {})
    peaks = dm.get("peak_owned_bytes", {})
    in_use = dm.get("in_use_bytes")
    unattr = dm.get("unattributed_bytes")

    print(f"== live HBM attribution ({url}) ==")
    print(f"{'owner':16s} {'live_bytes':>14s} {'peak_bytes':>14s}")
    for owner in sorted(set(owned) | set(peaks)):
        print(f"{owner:16s} {owned.get(owner, 0):>14,} "
              f"{peaks.get(owner, 0):>14,}")
    print(f"{'TOTAL owned':16s} {sum(owned.values()):>14,}")
    if in_use is not None:
        share = (unattr or 0) / max(in_use, 1)
        print(f"{'device in_use':16s} {in_use:>14,}")
        print(f"{'unattributed':16s} {unattr or 0:>14,}  "
              f"({share:.0%} of in_use — XLA program/temp share)")
        if share > 0.25:
            print("FLAG: unattributed share > 25% — the residency planes "
                  "are not what is eating HBM; dump the flight ring and "
                  "check compiled-program temps (memory_analysis)")
    else:
        print("device in_use: unavailable (backend reports no "
              "memory_stats — CPU proxy); per-owner ledger only")
    for d in dm.get("devices", []):
        if "in_use" in d or d.get("error"):
            print(f"  device {d['id']}: in_use={d.get('in_use')} "
                  f"limit={d.get('limit')} peak={d.get('peak')} "
                  f"err={d.get('error')}")
    ring = fr.get("ring", {})
    print(f"flight ring: {ring.get('next_seq', 0)} events recorded, "
          f"size {ring.get('size')}, last incident: "
          f"{fr.get('last_incident')}")
    for ev in fr.get("events", [])[-8:]:
        print(f"  [{ev['seq']}] {ev['kind']}: "
              + ", ".join(f"{k}={v}" for k, v in ev.items()
                          if k not in ("seq", "ts", "kind")))
    print()
    print("== static capacity model (for comparison) ==")
    model = oocore_model(None)
    out = {"live": dm, "ring": ring, "static_model": model}
    out["unattributed_flag"] = bool(
        in_use is not None and (unattr or 0) / max(in_use, 1) > 0.25)
    return out


def main() -> None:
    import jax
    import jax.numpy as jnp
    import jax.random as jr

    import h2o3_tpu
    from h2o3_tpu.models.tree import shared_tree as st
    from h2o3_tpu.models.tree.distributions import grad_hess

    h2o3_tpu.init(log_level="WARN")
    print("backend:", jax.default_backend(), jax.devices()[0].device_kind, flush=True)

    C, n_trees, depth, n_bins = 28, 5, 6, 256
    kw = dict(
        grad_fn=lambda F_, y_, w_: grad_hess("bernoulli", F_, y_, w_, 0.0),
        grad_key=("memdiag", "bernoulli"),
        sample_rate=1.0, n_bins=n_bins, is_cat_cols=np.zeros(C, bool),
        max_depth=depth, min_rows=10.0, min_split_improvement=1e-5,
        learn_rates=np.full(n_trees, 0.1, np.float32),
        max_abs_leaf=float("inf"), col_sample_rate=1.0,
        col_sample_rate_per_tree=1.0,
    )
    t0 = time.time()
    st.build_trees_scanned(
        jnp.zeros((512, C), jnp.uint8), jnp.ones(512), jnp.zeros(512),
        jnp.zeros(512), jnp.zeros(C), jr.PRNGKey(0), n_trees, **kw,
    )
    print("warm trace+exec", round(time.time() - t0, 1), "s", flush=True)
    prog = [v for k, v in st._STEP_CACHE.items() if k[0] == "scan"][-1]

    for n in (1_048_576, 4_194_304, 10_485_760):
        bins = jax.ShapeDtypeStruct((n, C), jnp.uint8)
        f32 = jax.ShapeDtypeStruct((n,), jnp.float32)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        t0 = time.time()
        try:
            c = prog.lower(
                bins, f32, f32, f32,
                jax.ShapeDtypeStruct((C,), jnp.float32), key, key,
                jnp.int32(0), jax.ShapeDtypeStruct((n_trees,), jnp.float32),
                jax.ShapeDtypeStruct((C,), jnp.bool_), jnp.float32(10.0),
                jnp.float32(1e-5), jnp.float32(np.inf), jnp.float32(1.0),
                None,
            ).compile()
            ma = c.memory_analysis()
            print(
                f"rows={n}: temp={ma.temp_size_in_bytes / 2**30:.3f} GB "
                f"args={ma.argument_size_in_bytes / 2**30:.3f} GB "
                f"out={ma.output_size_in_bytes / 2**30:.3f} GB "
                f"(compile {time.time() - t0:.1f} s)",
                flush=True,
            )
        except Exception as e:
            print(f"rows={n}: compile FAILED: {e!r}"[:500], flush=True)

    if "--train" not in sys.argv:
        return
    # execution-level bisect: fresh data per size, freed before the next
    import bench
    from h2o3_tpu.cluster.registry import DKV
    from h2o3_tpu.models.tree import GBM

    for n in (2_000_000, 5_000_000, 10_000_000):
        fr = bench._make_data_device(n)
        m = None
        try:
            t0 = time.time()
            m = GBM(ntrees=5, max_depth=depth, learn_rate=0.1, min_rows=10.0,
                    score_tree_interval=1000, seed=42).train(
                y="label", training_frame=fr)
            print(f"train rows={n}: OK {time.time() - t0:.1f} s "
                  f"auc={float(m.training_metrics.auc):.4f}", flush=True)
        except Exception as e:
            print(f"train rows={n}: FAILED {e!r}"[:300], flush=True)
            break
        finally:
            bench._drop_models(m)
            DKV.remove(fr.key)
            del fr


if __name__ == "__main__":
    if "--live" in sys.argv:
        i = sys.argv.index("--live")
        url = (sys.argv[i + 1] if i + 1 < len(sys.argv)
               and not sys.argv[i + 1].startswith("--")
               else "http://127.0.0.1:54321")
        res = live_attribution(url)
        sys.exit(1 if res.get("unattributed_flag") else 0)
    elif "--oocore" in sys.argv:
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        oocore_model(out)
    else:
        main()
