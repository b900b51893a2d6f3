"""Exit 0 iff the newest BENCH_builder_*.json captured a real headline value
AND at least one post-headline phase.

The 'did the bench run actually measure anything' signal for committed
artifacts. Requiring a post-headline phase matters: round 4's
failure mode was exactly 'headline measured, every scale phase dead in a
RESOURCE_EXHAUSTED cascade', and standing down on a headline alone would
forfeit the later windows this round exists to use.
"""

import glob
import json
import os
import sys

# keep in sync with bench.py _PHASES (minus headline)
POST_HEADLINE = (
    "scale_10m", "cat_1m", "join_10m", "glm_1m", "hash_1m", "dl_100k",
    "automl_50k",
)

RECENT_S = 6 * 3600  # this window's artifacts only — stale full runs from
                     # an earlier round must not stand the watcher down


def _stamp_age_s(path: str, now: float) -> float | None:
    """Age from the UTC stamp IN THE FILENAME (BENCH_builder_<stamp>*.json).

    mtime is useless here: these artifacts are git-committed and a fresh
    checkout re-stamps them to checkout time, which would let a previous
    round's success stand the watcher down. Old-style names without a
    stamp are by definition not from this window."""
    import re
    from datetime import datetime, timezone

    m = re.search(r"(\d{8}T\d{6})Z", os.path.basename(path))
    if not m:
        return None
    t = datetime.strptime(m.group(1), "%Y%m%dT%H%M%S").replace(
        tzinfo=timezone.utc
    )
    return now - t.timestamp()


def _loadtest_ok(here: str, now: float):
    """Sanity-check the newest recent LOADTEST_*.json (tools/load_test.py,
    the serving-tier A/B). Returns None when no recent artifact exists (no
    opinion), else True/False. Checks: non-empty steps each carrying a p99,
    non-zero achieved throughput somewhere, and shed rate <= 1% on every
    step offered at or below half the mode's sustained capacity — a tier
    shedding sub-capacity traffic is broken, not overloaded."""
    recent = []
    for p in glob.glob(os.path.join(here, "LOADTEST_*.json")):
        age = _stamp_age_s(p, now)
        if age is not None and 0 <= age < RECENT_S:
            recent.append((age, p))
    if not recent:
        return None
    path = sorted(recent)[0][1]
    name = os.path.basename(path)
    try:
        with open(path) as f:
            d = json.loads(f.readline())
        steps = d.get("steps") or []
        summary = d.get("summary") or {}
        if not steps:
            print(f"{name}: NO steps")
            return False
        if not all(s.get("p99_ms") is not None or s.get("ok", 0) == 0
                   for s in steps):
            print(f"{name}: step missing p99")
            return False
        if not any(float(s.get("achieved_qps") or 0) > 0 for s in steps):
            print(f"{name}: zero throughput everywhere")
            return False
        for s in steps:
            cap = summary.get(f"{s.get('mode')}_sustained_qps") or 0
            if cap and s["offered_qps"] <= 0.5 * cap and s["shed_rate"] > 0.01:
                print(f"{name}: shed at sub-capacity load "
                      f"({s['mode']} offered={s['offered_qps']} "
                      f"shed_rate={s['shed_rate']})")
                return False
        parity = summary.get("parity_byte_equal")
        if parity is False:
            print(f"{name}: batched/control predictions DIVERGED")
            return False
        # span-sourced latency breakdown (ISSUE 18) is OPTIONAL — older
        # artifacts predate it — but when a step carries one, every leg
        # that counted requests must carry a finite non-negative mean, or
        # the breakdown the batch-window tuning relies on is garbage
        for s in steps:
            for leg, st in (s.get("latency_breakdown") or {}).items():
                if not st.get("count"):
                    continue
                try:
                    v = float(st.get("mean_ms"))
                    sane = v >= 0 and v == v and v != float("inf")
                except (TypeError, ValueError):
                    sane = False
                if not sane:
                    print(f"{name}: breakdown leg {leg} mean_ms INSANE "
                          f"({st.get('mean_ms')!r})")
                    return False
        print(f"{name}: steps=ok p99=ok throughput=ok"
              f" speedup={summary.get('speedup')}"
              f" parity={'ok' if parity else 'n/a'}")
        return True
    except OSError as e:
        print(f"{name}: unreadable ({e.strerror or e})")
        return False
    except Exception as e:  # torn/garbage JSON
        print(f"{name}: unparseable ({type(e).__name__})")
        return False


def _quant_ab_ok(here: str, now: float):
    """Sanity-check the newest recent QUANT_AB_*.jsonl (bench_kernel_sweep
    --quant-ab, the quantized-collective-lane A/B). Returns None when no
    recent artifact exists (no opinion), else True/False. Checks the
    acceptance pins: modeled hist_reduce bytes ratio >= 2 (the lane's
    reason to exist), GBM AUC delta <= 1e-3 and a finite small GLM
    coefficient delta (accuracy envelopes) — a summary violating them
    means the lane regressed and the window's numbers are noise."""
    recent = []
    for p in glob.glob(os.path.join(here, "QUANT_AB_*.jsonl")):
        age = _stamp_age_s(p, now)
        if age is not None and 0 <= age < RECENT_S:
            recent.append((age, p))
    if not recent:
        return None
    path = sorted(recent)[0][1]
    name = os.path.basename(path)
    try:
        summary = None
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                d = json.loads(line)
                if "quant_ab" in d:
                    summary = d["quant_ab"]
        if not summary:
            print(f"{name}: NO quant_ab summary line")
            return False
        ratio = float(summary.get("hist_bytes_ratio_exact_over_quant") or 0)
        auc_d = float(summary.get("gbm_auc_delta", float("nan")))
        coef_d = float(summary.get("glm_coef_max_delta", float("nan")))
        if not ratio >= 2.0:
            print(f"{name}: hist_reduce byte ratio {ratio} < 2x")
            return False
        if not auc_d <= 1e-3:
            print(f"{name}: GBM AUC delta {auc_d} > 1e-3")
            return False
        if not coef_d <= 1e-2:
            print(f"{name}: GLM coef delta {coef_d} > 1e-2")
            return False
        print(f"{name}: bytes-ratio={ratio} auc-delta={auc_d} "
              f"coef-delta={coef_d} ok")
        return True
    except OSError as e:
        print(f"{name}: unreadable ({e.strerror or e})")
        return False
    except Exception as e:  # torn/garbage JSON
        print(f"{name}: unparseable ({type(e).__name__})")
        return False


def _oocore_ab_ok(here: str, now: float):
    """Sanity-check the newest recent OOCORE_AB_*.jsonl (bench_kernel_sweep
    --oocore-ab, the out-of-core streaming A/B). Returns None when no
    recent artifact exists (no opinion), else True/False. Checks the
    acceptance pins: the streamed mode really streamed at rows >= 10x the
    window with its peak frame device bytes bounded by the window (the
    fixed-footprint claim), the COMPRESS=0 control stayed resident (the
    kill switch works), and the AUC delta stays inside the f32
    block-summation envelope."""
    recent = []
    for p in glob.glob(os.path.join(here, "OOCORE_AB_*.jsonl")):
        age = _stamp_age_s(p, now)
        if age is not None and 0 <= age < RECENT_S:
            recent.append((age, p))
    if not recent:
        return None
    path = sorted(recent)[0][1]
    name = os.path.basename(path)
    try:
        summary = None
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                d = json.loads(line)
                if "oocore_ab" in d:
                    summary = d["oocore_ab"]
        if not summary:
            print(f"{name}: NO oocore_ab summary line")
            return False
        if not summary.get("streamed_engaged"):
            print(f"{name}: streamed mode never streamed")
            return False
        if not summary.get("compress0_stayed_resident"):
            print(f"{name}: COMPRESS=0 control STREAMED (kill switch broken)")
            return False
        if not summary.get("peak_within_window"):
            print(f"{name}: peak frame device bytes EXCEEDED the window")
            return False
        if not float(summary.get("rows_over_window") or 0) >= 10.0:
            print(f"{name}: rows_over_window "
                  f"{summary.get('rows_over_window')} < 10x")
            return False
        auc_d = float(summary.get("auc_delta", float("nan")))
        if not auc_d <= 5e-3:
            print(f"{name}: streamed AUC delta {auc_d} > 5e-3")
            return False
        print(f"{name}: streamed=ok peak-in-window=ok "
              f"rows/window={summary['rows_over_window']} "
              f"auc-delta={auc_d} ok")
        return True
    except OSError as e:
        print(f"{name}: unreadable ({e.strerror or e})")
        return False
    except Exception as e:  # torn/garbage JSON
        print(f"{name}: unparseable ({type(e).__name__})")
        return False


def _fallback_ab_ok(here: str, now: float):
    """Sanity-check the newest recent FALLBACK_AB_*.jsonl
    (bench_kernel_sweep --fallback-ab, the ISSUE-15 fallback-matrix
    closure A/B). Returns None when no recent artifact exists (no
    opinion), else True/False. Checks the acceptance pins: multinomial GLM coef
    parity <= 2e-3, dropout-DL trajectory parity <= 1e-4 vs the same-masks
    ctl control, the multinomial dispatch drop >= 3x, and the fused lanes'
    wall no worse than the fallback they replace (1.10x proxy-noise
    allowance)."""
    recent = []
    for p in glob.glob(os.path.join(here, "FALLBACK_AB_*.jsonl")):
        age = _stamp_age_s(p, now)
        if age is not None and 0 <= age < RECENT_S:
            recent.append((age, p))
    if not recent:
        return None
    path = sorted(recent)[0][1]
    name = os.path.basename(path)
    try:
        summary = None
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                d = json.loads(line)
                if "fallback_ab" in d:
                    summary = d["fallback_ab"]
        if not summary:
            print(f"{name}: NO fallback_ab summary line")
            return False
        glm_d = float(summary.get("glm_coef_max_delta", float("nan")))
        dl_d = float(summary.get("dl_ctl_pred_max_delta", float("nan")))
        if not glm_d <= 2e-3:
            print(f"{name}: multinomial coef delta {glm_d} > 2e-3")
            return False
        if not dl_d <= 1e-4:
            print(f"{name}: dropout-DL ctl pred delta {dl_d} > 1e-4")
            return False
        gr = float(summary.get("glm_dispatch_ratio_fallback_over_fused")
                   or 0)
        if not gr >= 3.0:
            print(f"{name}: multinomial dispatch ratio {gr} < 3x")
            return False
        for k in ("glm_time_ratio_fused_over_fallback",
                  "dl_time_ratio_fused_over_fallback"):
            r = float(summary.get(k) or 0)
            if not 0 < r <= 1.10:
                print(f"{name}: {k}={r} outside (0, 1.10]")
                return False
        print(f"{name}: glm-delta={glm_d} "
              f"dl-delta={dl_d} glm-dispatch-ratio={gr} ok")
        return True
    except OSError as e:
        print(f"{name}: unreadable ({e.strerror or e})")
        return False
    except Exception as e:  # torn/garbage JSON
        print(f"{name}: unparseable ({type(e).__name__})")
        return False


def _wave2_ab_ok(here: str, now: float):
    """Sanity-check the newest recent WAVE2_AB_*.jsonl (bench_kernel_sweep
    --wave2-ab, the ISSUE-16 tree-kernel wave-2 A/B). Returns None when no
    recent artifact exists (no opinion), else True/False. Checks the
    acceptance pins: GOSS at a=0.2,b=0.1 streams >=2x fewer row stats per
    level at AUC delta <=1e-3, EFB shrinks the histogram C dimension
    >=1.5x with bit-equal split structure on the integer-exact parity
    frame, the u8-code cache cuts rebin HBM traffic >=2x across repeated
    builds, the int16 lane holds a 1.10x RMSE envelope, lossguide honors
    its leaf budget, and EVERY knob-off control is bit-identical."""
    recent = []
    for p in glob.glob(os.path.join(here, "WAVE2_AB_*.jsonl")):
        age = _stamp_age_s(p, now)
        if age is not None and 0 <= age < RECENT_S:
            recent.append((age, p))
    if not recent:
        return None
    path = sorted(recent)[0][1]
    name = os.path.basename(path)
    try:
        summary = None
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                d = json.loads(line)
                if "wave2_ab" in d:
                    summary = d["wave2_ab"]
        if not summary:
            print(f"{name}: NO wave2_ab summary line")
            return False
        goss_r = float(summary.get("goss_row_stats_ratio") or 0)
        if not goss_r >= 2.0:
            print(f"{name}: GOSS row-stats ratio {goss_r} < 2x")
            return False
        goss_d = float(summary.get("goss_auc_delta", float("nan")))
        if not goss_d <= 1e-3:
            print(f"{name}: GOSS AUC delta {goss_d} > 1e-3")
            return False
        efb_s = float(summary.get("efb_c_shrink") or 0)
        if not efb_s >= 1.5:
            print(f"{name}: EFB C shrink {efb_s} < 1.5x")
            return False
        u8_r = float(summary.get("u8_rebin_bytes_ratio") or 0)
        if not u8_r >= 2.0:
            print(f"{name}: u8 rebin-bytes ratio {u8_r} < 2x")
            return False
        i16_r = float(summary.get("i16_rmse_ratio", float("nan")))
        if not 0 < i16_r <= 1.10:
            print(f"{name}: i16 RMSE ratio {i16_r} outside (0, 1.10]")
            return False
        for k in ("efb_splits_bit_equal", "goss_off_bit_identical",
                  "u8_off_bit_identical", "i16_off_bit_identical",
                  "lossguide_leaves_bounded",
                  "lossguide_unbound_bit_identical"):
            if summary.get(k) is not True:
                print(f"{name}: {k}={summary.get(k)!r} (want true)")
                return False
        print(f"{name}: goss-ratio={goss_r} goss-auc-delta={goss_d} "
              f"efb-shrink={efb_s} u8-ratio={u8_r} i16-rmse={i16_r} "
              f"controls=bit-identical ok")
        return True
    except OSError as e:
        print(f"{name}: unreadable ({e.strerror or e})")
        return False
    except Exception as e:  # torn/garbage JSON
        print(f"{name}: unparseable ({type(e).__name__})")
        return False


def _munge_ab_ok(here: str, now: float):
    """Sanity-check the newest recent MUNGE_AB_*.jsonl (bench_kernel_sweep
    --munge-ab, the ISSUE-20 compiled-munging-plane A/B). Returns None
    when no recent artifact exists (no opinion), else True/False. Checks
    the acceptance pins: fused wall <= 0.5x eager for group-by AND join,
    sort no worse than ~1.1x, the 10-op expression chain's dispatch count
    cut >= 5x, and every parity pin green (joins/sort/chain bit-equal,
    group-by counts exact + float sums allclose)."""
    recent = []
    for p in glob.glob(os.path.join(here, "MUNGE_AB_*.jsonl")):
        age = _stamp_age_s(p, now)
        if age is not None and 0 <= age < RECENT_S:
            recent.append((age, p))
    if not recent:
        return None
    path = sorted(recent)[0][1]
    name = os.path.basename(path)
    try:
        summary = None
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                d = json.loads(line)
                if "munge_ab" in d:
                    summary = d["munge_ab"]
        if not summary:
            print(f"{name}: NO munge_ab summary line")
            return False
        gb_r = float(summary.get("groupby_wall_ratio_fused_over_eager",
                                 float("nan")))
        if not gb_r <= 0.5:
            print(f"{name}: group-by fused/eager wall {gb_r} > 0.5x")
            return False
        jn_r = float(summary.get("join_wall_ratio_fused_over_eager",
                                 float("nan")))
        if not jn_r <= 0.5:
            print(f"{name}: join fused/eager wall {jn_r} > 0.5x")
            return False
        so_r = float(summary.get("sort_wall_ratio_fused_over_eager",
                                 float("nan")))
        if not so_r <= 1.1:
            print(f"{name}: sort fused/eager wall {so_r} > 1.1x")
            return False
        disp_r = float(summary.get("chain_dispatch_ratio") or 0)
        if not disp_r >= 5.0:
            print(f"{name}: chain dispatch ratio {disp_r} < 5x")
            return False
        if summary.get("parity_ok") is not True:
            bad = [k for k in ("groupby_parity_ok", "join_bit_equal",
                               "sort_bit_equal", "chain_bit_equal")
                   if summary.get(k) is not True]
            print(f"{name}: parity pins failed: {bad}")
            return False
        print(f"{name}: groupby={gb_r}x join={jn_r}x sort={so_r}x "
              f"chain-dispatches=1/{disp_r} parity=ok")
        return True
    except OSError as e:
        print(f"{name}: unreadable ({e.strerror or e})")
        return False
    except Exception as e:  # torn/garbage JSON
        print(f"{name}: unparseable ({type(e).__name__})")
        return False


def _mesh2d_ab_ok(here: str, now: float):
    """Sanity-check the newest recent MESH2D_AB_*.jsonl (bench_kernel_sweep
    --mesh2d-ab, the 1-D vs 2-D pod-mesh A/B, ISSUE 14). Returns None when
    no recent artifact exists (no opinion), else True/False. Checks the
    acceptance pins: collective bytes recorded BY PHASE on every mesh shape
    (a zero phase means the 2-D tally broke), the winner gather shrank with
    the cols width, and 2x4 fused_tree_s held within 1.10x of the 1-D mesh
    — 'no worse' up to proxy noise: on the one-host CPU proxy the stage-1
    rows psum is pure emulation overhead with none of the ICI placement
    payoff, so a small regression is expected there and the real
    ICI-vs-DCN number is the queued v5e-16 pod bracket's."""
    recent = []
    for p in glob.glob(os.path.join(here, "MESH2D_AB_*.jsonl")):
        age = _stamp_age_s(p, now)
        if age is not None and 0 <= age < RECENT_S:
            recent.append((age, p))
    if not recent:
        return None
    path = sorted(recent)[0][1]
    name = os.path.basename(path)
    try:
        summary = None
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                d = json.loads(line)
                if "mesh2d_ab" in d:
                    summary = d["mesh2d_ab"]
        if not summary:
            print(f"{name}: NO mesh2d_ab summary line")
            return False
        if not summary.get("phases_recorded_all_modes"):
            print(f"{name}: a mesh shape recorded ZERO bytes for a phase")
            return False
        ratio = float(summary.get("time_ratio_2x4_over_1d") or 0)
        if not 0 < ratio <= 1.10:
            print(f"{name}: 2x4 fused_tree_s ratio {ratio} outside (0, 1.10]")
            return False
        wg = float(summary.get("winner_gather_ratio_1d_over_2x4") or 0)
        if not wg >= 1.5:
            print(f"{name}: winner gather did not shrink with cols ({wg})")
            return False
        print(f"{name}: phases=ok 2x4-time-ratio={ratio} "
              f"winner-gather-ratio={wg} ok")
        return True
    except OSError as e:
        print(f"{name}: unreadable ({e.strerror or e})")
        return False
    except Exception as e:  # torn/garbage JSON
        print(f"{name}: unparseable ({type(e).__name__})")
        return False


def _fleet_ok(here: str, now: float):
    """Sanity-check the newest recent FLEET_*.json (tools/load_test.py
    --fleet, the serving-plane oversubscription A/B). Returns None when no
    recent artifact exists (no opinion), else True/False. Checks the
    ISSUE-12 acceptance pins: resident model bytes stayed under
    H2O3_TPU_SERVE_HBM_BYTES at oversubscription, paging actually happened
    (evictions > 0), every model's scores were byte-stable across
    page-out/page-in AND across the resident control, and the oversub
    tier's sustained QPS held >= 0.5x the all-resident run."""
    recent = []
    for p in glob.glob(os.path.join(here, "FLEET_*.json")):
        age = _stamp_age_s(p, now)
        if age is not None and 0 <= age < RECENT_S:
            recent.append((age, p))
    if not recent:
        return None
    path = sorted(recent)[0][1]
    name = os.path.basename(path)
    try:
        with open(path) as f:
            d = json.loads(f.readline())
        s = d.get("summary") or {}
        if not d.get("steps"):
            print(f"{name}: NO steps")
            return False
        if not s.get("peak_within_budget"):
            print(f"{name}: resident model bytes EXCEEDED the HBM budget "
                  f"(peak {s.get('oversub_hbm_peak_bytes')} > "
                  f"{s.get('hbm_budget_bytes')})")
            return False
        if not (s.get("oversub_evictions") or 0) > 0:
            print(f"{name}: oversubscription never paged (evictions=0)")
            return False
        if not (s.get("oversub_parity_stable")
                and s.get("parity_across_modes")):
            print(f"{name}: paging perturbed scores (parity_stable="
                  f"{s.get('oversub_parity_stable')}, across_modes="
                  f"{s.get('parity_across_modes')})")
            return False
        ratio = s.get("qps_ratio_vs_resident")
        if ratio is not None and ratio < 0.5:
            print(f"{name}: oversub sustained QPS ratio {ratio} < 0.5x "
                  "resident")
            return False
        print(f"{name}: peak-in-budget=ok evictions="
              f"{s.get('oversub_evictions')} parity=ok qps-ratio={ratio} ok")
        return True
    except OSError as e:
        print(f"{name}: unreadable ({e.strerror or e})")
        return False
    except Exception as e:  # torn/garbage JSON
        print(f"{name}: unparseable ({type(e).__name__})")
        return False


def _elastic_drill_ok(here: str, now: float):
    """Sanity-check the newest recent ELASTIC_DRILL_*.json
    (tools/recovery_drill.py --elastic, the ISSUE-17 topology-chaos drill).
    Returns None when no recent artifact exists (no opinion), else
    True/False. Checks the elastic acceptance pins: every shape transition
    in the matrix completed with the 1e-6 final-metric parity, the resumes
    actually re-formed the cloud (generations ticked), and the
    recovery_seconds measurement is present."""
    recent = []
    for p in glob.glob(os.path.join(here, "ELASTIC_DRILL_*.json")):
        age = _stamp_age_s(p, now)
        if age is not None and 0 <= age < RECENT_S:
            recent.append((age, p))
    if not recent:
        return None
    path = sorted(recent)[0][1]
    name = os.path.basename(path)
    try:
        with open(path) as f:
            d = json.load(f)  # indented JSON (same format as RECOVERY_DRILL)
        if not d.get("ok"):
            print(f"{name}: ok flag not set")
            return False
        results = d.get("results") or []
        if len(results) < 3:
            print(f"{name}: only {len(results)} transitions drilled "
                  "(want the full shape-change matrix)")
            return False
        algos = {r.get("algo") for r in results}
        if not {"gbm", "glm", "deeplearning"} <= algos:
            print(f"{name}: matrix missing algos (have {sorted(algos)})")
            return False
        for r in results:
            label = f"{r.get('algo')} {r.get('from')}->{r.get('to')}"
            if not (0 <= float(r.get("logloss_delta", 1)) <= 1e-6):
                print(f"{name}: {label} parity pin violated "
                      f"(logloss_delta={r.get('logloss_delta')})")
                return False
            if r.get("recovery_seconds") is None:
                print(f"{name}: {label} has no recovery_seconds")
                return False
        if not (d.get("generations_ticked") or 0) >= len(results):
            print(f"{name}: generations_ticked="
                  f"{d.get('generations_ticked')} < {len(results)} resumes "
                  "— the drill never actually re-formed")
            return False
        if d.get("recovery_seconds") is None:
            print(f"{name}: no headline recovery_seconds")
            return False
        print(f"{name}: {len(results)} transitions, parity<=1e-6, "
              f"generations={d.get('generations_ticked')} "
              f"recovery_seconds={d.get('recovery_seconds'):.2f} ok")
        return True
    except OSError as e:
        print(f"{name}: unreadable ({e.strerror or e})")
        return False
    except Exception as e:  # torn/garbage JSON
        print(f"{name}: unparseable ({type(e).__name__})")
        return False


def _overload_drill_ok(here: str, now: float):
    """Sanity-check the newest recent OVERLOAD_DRILL_*.json
    (tools/overload_drill.py, the ISSUE-19 overload-survival drill).
    Returns None when no recent artifact exists (no opinion), else
    True/False. Checks the acceptance pins: the admission storm at 4x
    capacity landed some requests AND shed the rest with only 429/503 and
    an honest Retry-After >= 1 s while the server survived and the
    reservation ledger returned to zero (memory gate shed reason=memory);
    the induced OOM auto-degraded to a model within 1e-6 of the resident
    control with an incident naming the dispatch and NO generation tick;
    the induced hang tripped the watchdog past its budget, captured a
    hang incident, and the supervisor re-formed and resumed to the 1e-6
    pin."""
    recent = []
    for p in glob.glob(os.path.join(here, "OVERLOAD_DRILL_*.json")):
        age = _stamp_age_s(p, now)
        if age is not None and 0 <= age < RECENT_S:
            recent.append((age, p))
    if not recent:
        return None
    path = sorted(recent)[0][1]
    name = os.path.basename(path)
    try:
        with open(path) as f:
            d = json.load(f)  # indented JSON, same format as the drills
        if not d.get("ok"):
            print(f"{name}: ok flag not set")
            return False
        r = d.get("results") or {}
        storm, oom, hang = r.get("storm"), r.get("oom"), r.get("hang")
        if not (storm and oom and hang):
            print(f"{name}: scenarios missing (have {sorted(r)})")
            return False
        if not (storm.get("ok", 0) >= 1 and storm.get("shed", 0) >= 1):
            print(f"{name}: storm did not both admit and shed "
                  f"(ok={storm.get('ok')} shed={storm.get('shed')})")
            return False
        if not set(storm.get("shed_statuses") or ()) <= {429, 503}:
            print(f"{name}: storm shed with non-backpressure statuses "
                  f"{storm.get('shed_statuses')}")
            return False
        if not float(storm.get("retry_after_min") or 0) >= 1:
            print(f"{name}: dishonest Retry-After "
                  f"({storm.get('retry_after_min')})")
            return False
        if not (storm.get("server_alive")
                and storm.get("reservations_after") == 0):
            print(f"{name}: storm killed the server or leaked reservations")
            return False
        if (storm.get("memory_shed") or {}).get("reason") != "memory":
            print(f"{name}: memory gate never shed reason=memory "
                  f"({storm.get('memory_shed')})")
            return False
        if not (0 <= float(oom.get("logloss_delta", 1)) <= 1e-6):
            print(f"{name}: oom degrade parity pin violated "
                  f"(logloss_delta={oom.get('logloss_delta')})")
            return False
        if oom.get("incident_trigger") != "oom" or not oom.get("incident"):
            print(f"{name}: oom incident missing/mistriggered")
            return False
        if oom.get("generation_ticked") != 0:
            print(f"{name}: oom degrade re-formed the cloud "
                  f"(generation_ticked={oom.get('generation_ticked')})")
            return False
        trips = hang.get("trips") or []
        if not trips or not all(
                float(t.get("budget_s") or 0) > 0
                and float(t.get("age_s") or 0) >= float(t["budget_s"])
                for t in trips):
            print(f"{name}: watchdog trips missing/under-budget ({trips})")
            return False
        if hang.get("incident_trigger") != "hang" or not hang.get("incident"):
            print(f"{name}: hang incident missing/mistriggered")
            return False
        if not (hang.get("generations_ticked") or 0) >= 1:
            print(f"{name}: hang never handed the job to the supervisor "
                  f"(generations_ticked={hang.get('generations_ticked')})")
            return False
        if not (0 <= float(hang.get("logloss_delta", 1)) <= 1e-6):
            print(f"{name}: hang resume parity pin violated "
                  f"(logloss_delta={hang.get('logloss_delta')})")
            return False
        print(f"{name}: storm ok={storm['ok']}/shed={storm['shed']} "
              f"oom-delta={oom['logloss_delta']:.1e} "
              f"hang-trips={len(trips)} "
              f"hang-delta={hang['logloss_delta']:.1e} ok")
        return True
    except OSError as e:
        print(f"{name}: unreadable ({e.strerror or e})")
        return False
    except Exception as e:  # torn/garbage JSON
        print(f"{name}: unparseable ({type(e).__name__})")
        return False


def _ledger_sane(led: dict) -> bool:
    """One per-job ledger's totals: finite non-negative numbers, counts
    non-negative ints. Shared by the TRACE gate and the BENCH jobs block."""
    try:
        for k in ("device_seconds", "queue_wait_seconds"):
            v = float(led.get(k, 0) or 0)
            if not (v >= 0 and v == v and v != float("inf")):
                return False
        for v in (led.get("dispatches") or {}).values():
            if not (isinstance(v, int) and v >= 0):
                return False
        for v in list((led.get("collective_bytes") or {}).values()) + [
                led.get("window_bytes", 0) or 0]:
            v = float(v)
            if not (v >= 0 and v == v and v != float("inf")):
                return False
    except (TypeError, ValueError):
        return False
    return True


def _trace_ok(here: str, now: float):
    """Sanity-check the newest recent TRACE_*.json (the traced-headline-GBM
    capture, ISSUE 18). Returns None when no recent
    artifact exists (no opinion), else True/False. Checks the acceptance
    pins: the Perfetto export carries a span for EVERY site the job's
    ledger says it dispatched (a missing site means the trace plane lost a
    dispatch path), and the ledger totals are finite with device-seconds
    bounded by the measured wall-clock — attribution that exceeds the wall
    is double-counting, not measurement."""
    recent = []
    for p in glob.glob(os.path.join(here, "TRACE_*.json")):
        age = _stamp_age_s(p, now)
        if age is not None and 0 <= age < RECENT_S:
            recent.append((age, p))
    if not recent:
        return None
    path = sorted(recent)[0][1]
    name = os.path.basename(path)
    try:
        with open(path) as f:
            d = json.load(f)
        led = d.get("ledger") or {}
        evs = (d.get("trace") or {}).get("traceEvents") or []
        if not evs:
            print(f"{name}: trace export has NO events")
            return False
        span_names = {e.get("name") for e in evs if e.get("ph") == "X"}
        missing = [site for site in (led.get("dispatches") or {})
                   if f"dispatch:{site}" not in span_names]
        if missing:
            print(f"{name}: ledger dispatched {missing} but the trace "
                  "has no spans for them")
            return False
        if not led.get("dispatches"):
            print(f"{name}: traced GBM job recorded ZERO dispatches")
            return False
        bad = [j for j, lj in (d.get("jobs") or {}).items()
               if not _ledger_sane(lj)]
        if bad:
            print(f"{name}: ledger totals INSANE for {bad}")
            return False
        wall = float(d.get("wall_s") or 0)
        ds = float(led.get("device_seconds") or 0)
        if not (wall > 0 and 0 <= ds <= wall):
            print(f"{name}: ledger device-seconds {ds} outside "
                  f"[0, wall={wall}]")
            return False
        print(f"{name}: spans-per-site=ok dispatches={led['dispatches']} "
              f"device_s={ds} wall_s={wall} ok")
        return True
    except OSError as e:
        print(f"{name}: unreadable ({e.strerror or e})")
        return False
    except Exception as e:  # torn/garbage JSON
        print(f"{name}: unparseable ({type(e).__name__})")
        return False


def main() -> int:
    import time

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    now = time.time()
    # serving-tier artifact gate: when a recent load-test artifact exists it
    # must be sane, or the window's serving A/B numbers are untrustworthy
    lt = _loadtest_ok(here, now)
    if lt is False:
        return 1
    # quantized-collective-lane gate (ISSUE 9): same contract — a recent
    # --quant-ab artifact must satisfy the acceptance pins or the window
    # stands
    qa = _quant_ab_ok(here, now)
    if qa is False:
        return 1
    # out-of-core streaming gate (ISSUE 11): a recent --oocore-ab artifact
    # must satisfy the fixed-footprint acceptance pins or the window stands
    oo = _oocore_ab_ok(here, now)
    if oo is False:
        return 1
    # fleet serving gate (ISSUE 12): a recent --fleet artifact must satisfy
    # the oversubscription acceptance pins or the window stands
    fl = _fleet_ok(here, now)
    if fl is False:
        return 1
    # 2-D pod-mesh gate (ISSUE 14): a recent --mesh2d-ab artifact must
    # satisfy the no-regression + per-phase-bytes pins or the window stands
    m2 = _mesh2d_ab_ok(here, now)
    if m2 is False:
        return 1
    # fallback-matrix closure gate (ISSUE 15): a recent --fallback-ab
    # artifact must satisfy the parity + dispatch + no-worse-wall pins
    fb = _fallback_ab_ok(here, now)
    if fb is False:
        return 1
    # tree-kernel wave-2 gate (ISSUE 16): a recent --wave2-ab artifact
    # must satisfy the sampling/bundling/quantization pins + bit-identical
    # knob-off controls or the window stands
    w2 = _wave2_ab_ok(here, now)
    if w2 is False:
        return 1
    # compiled-munging-plane gate (ISSUE 20): a recent --munge-ab artifact
    # must satisfy the wall-ratio + dispatch-cut + parity pins or the
    # window stands
    mu = _munge_ab_ok(here, now)
    if mu is False:
        return 1
    # elastic-recovery gate (ISSUE 17): a recent --elastic drill artifact
    # must satisfy the shape-change parity pins or the window stands
    el = _elastic_drill_ok(here, now)
    if el is False:
        return 1
    # job-scoped tracing gate (ISSUE 18): a recent traced-GBM capture must
    # carry a span per dispatched site and a wall-bounded ledger
    tr = _trace_ok(here, now)
    if tr is False:
        return 1
    # overload-survival gate (ISSUE 19): a recent overload drill must
    # satisfy the shed-honesty + OOM-degrade + hang-watchdog pins or the
    # window stands
    ov = _overload_drill_ok(here, now)
    if ov is False:
        return 1
    # ANY qualifying artifact from this window counts: the backlog writes
    # headline-only A/B controls (_adapt/_nbins127/_matmul) AFTER the full
    # run, so "the newest file" is usually a control and judging only it
    # would loop the watcher forever on a fully successful window
    recent = []
    try:
        candidates = glob.glob(os.path.join(here, "BENCH_builder_*.json"))
    except OSError as e:  # unreadable repo dir: clean message, not traceback
        print(f"cannot list bench artifacts under {here}: {e}")
        return 1
    for p in candidates:
        age = _stamp_age_s(p, now)
        if age is not None and 0 <= age < RECENT_S:
            recent.append((age, p))
    recent = [p for _, p in sorted(recent)]
    if not recent:
        print("no recent BENCH_builder artifacts")
        return 1
    for path in recent:
        headline_ok = phases_ok = registry_ok = False
        psum_note = ""
        note = ""
        try:
            with open(path) as f:
                d = json.loads(f.readline())
            if isinstance(d, dict):
                headline_ok = float(d.get("value") or 0) > 0
                phases_ok = any(
                    isinstance(d.get(p), dict) for p in POST_HEADLINE
                )
                # the registry-snapshot block: bench counters sourced from
                # the live /3/Metrics registry — an artifact without it was
                # produced by a pre-observability bench and cannot be
                # cross-checked against the endpoint
                reg = d.get("metrics_registry")
                registry_ok = isinstance(reg, dict) and len(reg) > 0
                # psum_bytes_per_tree (split-pipeline traffic, ISSUE 5) is
                # OPTIONAL — older artifacts predate it — but when present
                # it must be a sane number: a negative/NaN/garbage value
                # means the byte tally broke and the A/B replay would be
                # comparing noise, so the artifact does not count
                if "psum_bytes_per_tree" in d:
                    try:
                        v = float(d["psum_bytes_per_tree"])
                        sane = v >= 0 and v == v and v != float("inf")
                    except (TypeError, ValueError):
                        sane = False
                    psum_note = (
                        f" psum-bytes/tree={d['psum_bytes_per_tree']}"
                        if sane else " psum-bytes/tree=INSANE"
                    )
                    if not sane:
                        headline_ok = False
                # hist_hbm_bytes_per_tree (fused split pipeline, ISSUE 6) is
                # OPTIONAL like psum above, but when present it must be a
                # sane non-negative finite number or the fused-vs-unfused
                # A/B would be comparing noise
                if "hist_hbm_bytes_per_tree" in d:
                    try:
                        v = float(d["hist_hbm_bytes_per_tree"])
                        sane = v >= 0 and v == v and v != float("inf")
                    except (TypeError, ValueError):
                        sane = False
                    psum_note += (
                        f" hist-hbm-bytes/tree={d['hist_hbm_bytes_per_tree']}"
                        if sane else " hist-hbm-bytes/tree=INSANE"
                    )
                    if not sane:
                        headline_ok = False
                # tracked GLM/DL/AutoML summary keys (ISSUE 8) are OPTIONAL
                # — artifacts from partial runs lack them — but when
                # present they must be finite positives or the per-round
                # trend they exist to track is garbage
                for k in ("glm_iters_per_s", "dl_epoch_s",
                          "automl_total_s"):
                    if k not in d:
                        continue
                    try:
                        v = float(d[k])
                        sane = v > 0 and v == v and v != float("inf")
                    except (TypeError, ValueError):
                        sane = False
                    psum_note += (
                        f" {k}={d[k]}" if sane else f" {k}=INSANE"
                    )
                    if not sane:
                        headline_ok = False
                # devmem attribution block (ISSUE 13) is OPTIONAL — older
                # artifacts predate the ledger — but when present every
                # per-owner byte count must be a finite non-negative int
                # and each peak must be >= its live value, or the HBM
                # attribution the TPU-window A/Bs rely on is garbage
                if "devmem" in d:
                    dv = d["devmem"]
                    sane = isinstance(dv, dict)
                    if sane:
                        own = dv.get("owned_bytes", {})
                        pk = dv.get("peak_owned_bytes", {})
                        try:
                            for o, v in {**own, **pk}.items():
                                v = float(v)
                                if not (v >= 0 and v == v
                                        and v != float("inf")):
                                    sane = False
                            for o, v in own.items():
                                if float(pk.get(o, v)) < float(v):
                                    sane = False
                        except (TypeError, ValueError):
                            sane = False
                    psum_note += (" devmem=ok" if sane
                                  else " devmem=INSANE")
                    if not sane:
                        headline_ok = False
                # per-job ledger block (ISSUE 18) is OPTIONAL — older
                # artifacts predate jobacct — but when present every
                # job's totals must be finite non-negative numbers or
                # the device-time attribution is garbage
                if "jobs" in d:
                    jb = d["jobs"]
                    sane = isinstance(jb, dict) and all(
                        isinstance(lj, dict) and _ledger_sane(lj)
                        for lj in jb.values())
                    psum_note += (" jobs=ok" if sane else " jobs=INSANE")
                    if not sane:
                        headline_ok = False
        except OSError as e:  # vanished/unreadable between glob and open
            note = f" (unreadable: {e.strerror or e})"
        except Exception as e:  # torn/empty/garbage JSON is a MISSING, not a crash
            note = f" (unparseable: {type(e).__name__})"
        print(
            f"{os.path.basename(path)}: "
            f"headline={'ok' if headline_ok else 'MISSING'}"
            f" post-headline-phases={'ok' if phases_ok else 'MISSING'}"
            f" registry-snapshot={'ok' if registry_ok else 'MISSING'}"
            f"{psum_note}{note}"
        )
        if headline_ok and phases_ok and registry_ok:
            return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
