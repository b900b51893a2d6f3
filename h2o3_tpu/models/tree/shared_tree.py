"""Level-wise distributed tree builder — successor of ``hex.tree.SharedTree``
/ ``DTree`` (``UndecidedNode``/``DecidedNode``, ``findBestSplitPoint``) /
``ScoreBuildHistogram2`` [UNVERIFIED upstream paths, SURVEY.md §2.2 §3.3].

Per level (SURVEY §3.3 call stack, TPU-native form), ALL fused into ONE
compiled device program (`_level_step`):
1. histogram pass — the ScoreBuildHistogram successor: {w,wy,wh} into
   (node,col,bin) cells per row shard, psum across the mesh (the wy² lane
   of upstream's DHistogram cancels in the gain — see _split_scan)
   (:mod:`h2o3_tpu.ops.histogram`).
2. split scan — DTree.findBestSplitPoint vectorized over all (node, col)
   pairs: SE-reduction gain over bin prefixes, NA-direction both ways
   (DHistogram's NA trick), categorical bins in mean-sorted order
   (DHistogram's categorical bin-sort).
3. leaf decision + child id assignment (compacted via device cumsum — the
   active-leaf frontier, NOT full 2^d indexing, so depth-20 DRF stays
   bounded by ``node_cap``).
4. partition update — the DecidedNode re-labeling: rows map to child nids;
   rows landing in finalized leaves add the leaf value to the running
   prediction and retire with nid=-1.
5. variable-importance scatter (per-split gain by column).

Device-residency is the design point: the driving host loop only *dispatches*
one program per level and never blocks on device→host transfers (on a
networked TPU a single transfer costs ~100ms — the former per-level host
round-trips dominated build time ~30:1 over compute). Recorded per-level
arrays stay on device; prediction replays them without ever touching host.
The only syncs are an occasional early-exit poll for deep trees and the
final scoring pulls.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_NEG = -1e30


# ---------------------------------------------------------------------------
# build telemetry: host dispatches and program-cache traffic. The whole-tree
# design's contract is O(1) dispatches per tree (vs O(depth) for the
# host-driven level loop) and one compile per shape signature — these
# counters are how tests assert it and how bench.py reports it. The counts
# now live in the cluster metrics registry (utils/metrics.py, served over
# GET /3/Metrics); BUILD_STATS stays as a dict-shaped back-compat alias
# whose reads and writes go straight through to the registry counters
# (always=True: the accounting is a test contract, not optional telemetry,
# so H2O3_TPU_METRICS=0 does not switch it off).

from h2o3_tpu.utils import jobacct as _jobacct
from h2o3_tpu.utils import metrics as _metrics

_BUILD_COUNTERS = {
    # alias key -> registry counter
    "dispatches": _metrics.counter(
        "tree_dispatches_total",
        "device-program launches issued by the tree builders", always=True),
    "trees_built": _metrics.counter(
        "tree_trees_built_total", "trees those dispatches produced",
        always=True),
    "tree_programs_compiled": _metrics.counter(
        "tree_programs_compiled_total",
        "whole-tree/chunk program cache misses", always=True),
    "tree_program_cache_hits": _metrics.counter(
        "tree_program_cache_hits_total",
        "whole-tree/chunk program cache hits (same shape, no recompile)",
        always=True),
    # saturated-region while_loop iterations that actually EXECUTED (the
    # on-device early exit can skip the rest): read back per dispatch and
    # used to scale the sat-region byte tallies to actual volume
    "sat_levels_executed": _metrics.counter(
        "tree_sat_levels_total",
        "node_cap-saturated tree levels actually executed by the fused "
        "builds' while_loop (post-early-exit)", always=True),
}

# Collective observability for the split pipeline (labeled by phase:
# hist_reduce = the histogram psum / psum_scatter, winner_gather = the
# sharded scan's per-block winner all-gather). Bytes use the replication-
# volume model (see ops/histogram.py record_collective): what the collective
# leaves on each device — the O(C·N·B·S) vs O(C·N·B·S/P) quantity the
# sharded pipeline shrinks — tallied from the traced program structure and
# replayed per dispatch, so bench's psum_bytes_per_tree is derived from what
# actually ran, not asserted. Seconds are filled by bench.py's collective
# calibration microbench (collectives inside a fused program cannot be
# host-timed individually).
_COLL_BYTES = _metrics.counter(
    "tree_collective_bytes_total",
    "per-device collective payload bytes moved by tree builds (replication-"
    "volume model), by phase", always=True)
_COLL_SECONDS = _metrics.counter(
    "tree_collective_seconds_total",
    "measured seconds of representative tree-phase collectives (bench "
    "calibration microbench), by phase", always=True)

# HBM-traffic model of the histogram+split phases, by pipeline path
# (``path``: pallas_unfused = Pallas histogram + two HBM unscramble
# transposes + dense XLA scan; dense = scatter/matmul histogram + dense
# scan; rebin = a binning pass, tallied by binning.bin_frame). Same
# traced-structure tally mechanism as the collective bytes
# (ops/histogram.record_hbm): one write per materialized intermediate + one
# read per consumed one, recorded at trace time and replayed per dispatch.
# Terminal force-leaf levels skip the scan read the model counts: an upper
# bound, like the saturated-region collective tally.
_HIST_HBM_BYTES = _metrics.counter(
    "tree_hist_hbm_bytes_total",
    "modeled per-device HBM bytes moved by the histogram+split phases of "
    "tree builds, by pipeline path", always=True)

# Wave-2 arithmetic-reduction observability (ISSUE 16). Rows-sampled is the
# MODELED kept-row volume of GOSS builds ((a+b) · padded rows · trees —
# the expected fraction, same modeled-volume convention as the HBM bytes);
# cols-bundled counts real feature columns EFB eliminated from the
# histogram grid, per build.
_ROWS_SAMPLED = _metrics.counter(
    "tree_rows_sampled_total",
    "modeled rows kept by GOSS one-side sampling across tree builds "
    "(expected (a+b) fraction of the padded row count, per tree)",
    always=True)
_COLS_BUNDLED = _metrics.counter(
    "tree_cols_bundled_total",
    "feature columns removed from the histogram C dimension by exclusive "
    "feature bundling, per build", always=True)

# Partition passes (ISSUE 29): one for every tree level a program executes —
# ``_partition_update`` is the partition of every builder and the replay op.
# A pass traced into a tree program is tallied at trace time and replayed at
# each dispatch like the byte counters (``_run_counted``: the saturated
# region by its executed iterations); a direct call counts itself; a program
# dispatched outside ``_run_counted`` (``replay_batch``, uplift, the REST
# scorer) counts its levels where it dispatches. ``path`` names the
# formulation: one, ``dense`` — no per-row gather at any frontier width.
_PART_LEVELS = _metrics.counter(
    "tree_partition_levels_total",
    "partition / replay passes over the row lanes executed by tree "
    "programs, by formulation", always=True)


# Node tiles (ISSUE 32): the histogram op's unit of work at a level is a tile
# of ``hist_pallas.NODE_TILE`` (64) node slots over every row — one kernel
# grid pass. ``histogram_in_jit`` tallies ceil(nodes / 64) where it is traced
# into a program and ``_run_counted`` replays the tally at each dispatch like
# the HBM bytes (a scanned chunk by its trees, the saturated region by the
# levels that ran), so kernel seconds / this counter is the cost of a node
# tile: 20 ms at one node, 144 ms at 64 (PERF.md §5).
_NODE_TILES = _metrics.counter(
    "tree_node_tiles_total",
    "node tiles of 64 node slots the tree programs asked the histogram op "
    "to build (one pass over the rows each)", always=True)

# The grouped histogram (ISSUE 33): a level wider than one node tile reads its
# rows in node order and contracts a row chunk only against the node tiles
# whose rows it holds. Levels are tallied where ``histogram_in_jit`` is traced
# with an order and replayed like the node tiles; the grid steps that
# contracted a chunk are counted on the device (they depend on the data) and
# come back with the executed saturated levels. steps / (the grouped levels'
# node tiles x row chunks x column tiles) is the share of the dense pass still
# run: 1 / n_nt where the order holds, 1 where a level fell back to dense.
_GROUPED_LEVELS = _metrics.counter(
    "tree_hist_grouped_levels_total",
    "tree levels whose histogram ran grouped over rows in node order",
    always=True)
_CHUNK_VISITS = _metrics.counter(
    "tree_hist_chunk_visits_total",
    "kernel grid steps that contracted a row chunk in grouped histogram "
    "levels, over all shards and column tiles", always=True)


def count_partition_levels(n: int) -> None:
    """``n`` partition passes of a program dispatched outside
    :func:`_run_counted`."""
    _PART_LEVELS.inc(n, path="dense")


def _count_partition_level(nid) -> None:
    """One pass: into the dispatcher's tally while a program is traced,
    straight into the counter for a direct call."""
    if isinstance(nid, jax.core.Tracer):
        from h2o3_tpu.ops.collectives import record_collective

        record_collective("part/dense", 1)
    else:
        count_partition_levels(1)


# program-key registry + per-program collective tallies: _run_counted
# captures a program's ((phase, lane, group) -> bytes) tally during its
# first (tracing) dispatch and replays it on every later one.
_PROG_KEY: dict[int, tuple] = {}
_PROG_COLL: dict = {}


def _run_counted(fn, args, mult: int = 1, counts_from=None):
    """Dispatch ``fn(*args)`` with collective byte accounting.

    ``mult`` scales the traced tally per dispatch (a scanned chunk's body
    traces once but executes once per tree). Entries recorded under
    ``tally_group("sat")`` — the node_cap-saturated while_loop body, traced
    once but executed a data-dependent number of times — are instead
    scaled by the EXECUTED iteration count, extracted from the program's
    output via ``counts_from(out)`` (the fused programs return int32
    ``[executed saturated levels, grouped histogram grid steps]``), so the
    counters report actual volume, not the old n_sat trace-time upper
    bound. Reading the pair syncs the dispatch — one 8-byte pull, and
    only for programs that traced a saturated region or a grouped level
    (deep builds whose per-level cost dwarfs it; GBM-typical shallow trees
    never pay)."""
    from h2o3_tpu.ops.collectives import collective_tally
    from h2o3_tpu.utils import flightrec as _fr

    key = _PROG_KEY.get(id(fn), id(fn))
    # the flight-recorder dispatch event: the cached-program key already
    # carries shape bucket + mesh key + lane knobs (the jit cache key)
    _disp = _fr.dispatch("tree", program=str(key)[:160], mult=mult)
    agg = _PROG_COLL.get(key)
    if agg is None:
        entries: list = []
        with _disp, collective_tally(entries):
            out = fn(*args)
        agg = {}
        for ph, lane, grp, b in entries:
            k = (ph, lane, grp)
            agg[k] = agg.get(k, 0.0) + b
        _PROG_COLL[key] = agg
    else:
        with _disp:
            out = fn(*args)
    if agg:
        # per-dispatch collective phase tallies ride the ring too, so an
        # incident bundle shows what the dying dispatch was reducing
        by_phase: dict = {}
        for (ph, _lane, _grp), b in agg.items():
            by_phase[ph] = by_phase.get(ph, 0) + int(b)
        _fr.record("collectives", **by_phase)
    sat_n = 0
    if counts_from is not None and any(
            grp == "sat" or ph == "hist_grouped" for ph, _lane, grp in agg):
        sat_n, steps = (int(c) for c in jax.device_get(counts_from(out)))
        BUILD_STATS["sat_levels_executed"] += sat_n
        _CHUNK_VISITS.inc(steps)
    for (ph, lane, grp), b in agg.items():
        m = sat_n if grp == "sat" else mult
        if not b or not m:
            continue
        if ph.startswith("hbm/"):
            _HIST_HBM_BYTES.inc(b * m, path=ph[4:])
        elif ph.startswith("part/"):
            _PART_LEVELS.inc(b * m, path=ph[5:])
        elif ph == "node_tiles":
            _NODE_TILES.inc(b * m)
        elif ph == "hist_grouped":
            _GROUPED_LEVELS.inc(b * m)
        else:
            _COLL_BYTES.inc(b * m, phase=ph)
            _COLL_BYTES.inc(b * m, phase=ph, lane=lane)
            # per-job attribution: the replayed tally charges the job whose
            # trace this dispatch ran under (utils/jobacct.py), lane-split
            _jobacct.on_collective_bytes(
                _metrics.current_trace(), b * m, lane=lane)
    return out


class _BuildStatsAlias:
    """Mapping view of the tree-build registry counters.

    ``BUILD_STATS["dispatches"] += 1`` and ``dict(BUILD_STATS)`` behave
    exactly as they did when this was a module-global dict — existing tests
    and bench code keep working — but the single source of truth is the
    registry, so /3/Metrics and bench artifacts cannot disagree."""

    def __getitem__(self, k: str) -> int:
        return int(_BUILD_COUNTERS[k].value())

    def __setitem__(self, k: str, v) -> None:
        _BUILD_COUNTERS[k].set_(float(v))

    def __iter__(self):
        return iter(_BUILD_COUNTERS)

    def __len__(self) -> int:
        return len(_BUILD_COUNTERS)

    def __contains__(self, k) -> bool:
        return k in _BUILD_COUNTERS

    def keys(self):
        return _BUILD_COUNTERS.keys()

    def items(self):
        return [(k, self[k]) for k in _BUILD_COUNTERS]

    def values(self):
        return [self[k] for k in _BUILD_COUNTERS]

    def __repr__(self) -> str:
        return repr(dict(self.items()))


BUILD_STATS = _BuildStatsAlias()


def reset_build_stats() -> dict:
    """Zero the counters and return the pre-reset snapshot."""
    snap = dict(BUILD_STATS.items())
    for k in BUILD_STATS:
        BUILD_STATS[k] = 0
    return snap


def _cached_program(key, make):
    """_STEP_CACHE lookup with compile/hit accounting for tree programs."""
    fn = _STEP_CACHE.get(key)
    if fn is None:
        BUILD_STATS["tree_programs_compiled"] += 1
        fn = make()
        _STEP_CACHE[key] = fn
    else:
        BUILD_STATS["tree_program_cache_hits"] += 1
    _PROG_KEY[id(fn)] = key
    return fn


# ---------------------------------------------------------------------------
# split finding (pure function, traced inside the level step)


def _split_scan(hist, is_cat, col_mask, min_rows, min_split_improvement, cat_cols=(),
                mono=None, node_lo=None, node_hi=None, node_totals=None):
    """Best split per node from hist (N, C, B, 3). Returns per-node arrays.

    Stats axis: 0=w, 1=wy, 2=wh. Bin 0 is the NA bin.

    DHistogram's squared-error gain is (wy2 - wy^2/w)_parent - (...)_L -
    (...)_R; since L, R and the NA side PARTITION the node's rows, the wy2
    terms cancel EXACTLY and the gain equals wy_L^2/w_L + wy_R^2/w_R -
    wy_tot^2/w_tot. The histogram therefore never accumulates a wy2 lane —
    a 25% MXU/HBM saving in the dominant phase at identical math (float
    rounding aside; ``fit`` below is the wy2-free per-side term).

    ``cat_cols`` is the STATIC tuple of categorical column indices: the
    mean-sorted categorical branch (two argsorts over (N, C, B-1) — by far
    the most expensive part of this scan on TPU) runs only on that column
    subset, and disappears entirely for all-numeric frames.

    ``mono`` (optional, (C,) int {-1,0,1}) activates monotone-constraint
    feasibility: numeric candidates whose bound-clamped child Newton values
    violate the direction are masked BEFORE the column argmax (so a feasible
    categorical or other-numeric split wins on merit), and the result gains
    ``mid``/``mono_col`` for child-bound propagation. The unconstrained path
    is untouched (this branch doesn't trace when mono is None).

    ``node_totals`` ((N, 3), optional) overrides the per-node {w, wy, wh}
    totals that feed ``parent_fit`` and the node stats. The replicated path
    derives them from column 0's bin sum ("any column sums to the node
    totals" — every row lights exactly one bin per column); the sharded
    path passes GLOBAL column 0's totals in, because a different column's
    bin partition sums the same rows in a different grouping and the float
    result can differ in the last bits — which would make per-block gains
    incomparable with the replicated scan's.
    """
    N, C, B, _ = hist.shape
    na = hist[:, :, 0, :]  # (N, C, 3)
    data = hist[:, :, 1:, :]  # (N, C, B-1, 3)

    def fit(s):  # SE with the cancelling wy2 term dropped: -wy^2/w
        w = s[..., 0]
        return -jnp.where(w > 0, s[..., 1] ** 2 / jnp.maximum(w, 1e-30), 0.0)

    if node_totals is None:
        node_totals = hist.sum(axis=2)[:, 0, :]  # (N, 3), from column 0
    parent_fit = fit(node_totals[:, None, :]).squeeze(1)  # same for every col: (N,)

    def gain_with_na(L, R):
        gl = fit(L)
        gr = fit(R)
        ok = (L[..., 0] >= min_rows) & (R[..., 0] >= min_rows)
        g = parent_fit[:, None, None] - gl - gr
        return jnp.where(ok, g, _NEG)

    # ---- numeric: prefix split over natural bin order ----
    cum = jnp.cumsum(data, axis=2)  # (N, C, B-1, 3)
    tot_nonna = cum[:, :, -1:, :]
    left_n = cum[:, :, :-1, :]  # split after data-bin t: left = bins 1..t+1
    right_n = tot_nonna - left_n

    g_naleft = gain_with_na(left_n + na[:, :, None, :], right_n)
    g_naright = gain_with_na(left_n, right_n + na[:, :, None, :])
    if mono is not None:

        def child_val(s):  # Newton child value wy/wh, clamped to node bounds
            v = jnp.where(s[..., 2] > 0, s[..., 1] / jnp.maximum(s[..., 2], 1e-30), 0.0)
            return jnp.clip(v, node_lo[:, None, None], node_hi[:, None, None])

        m = mono[None, :, None]
        na_b = na[:, :, None, :]
        ok_nl = (m == 0) | (m * (child_val(right_n) - child_val(left_n + na_b)) >= 0)
        ok_nr = (m == 0) | (m * (child_val(right_n + na_b) - child_val(left_n)) >= 0)
        g_naleft = jnp.where(ok_nl, g_naleft, _NEG)
        g_naright = jnp.where(ok_nr, g_naright, _NEG)
    g_num = jnp.maximum(g_naleft, g_naright)  # (N, C, B-2)
    num_best_t = jnp.argmax(g_num, axis=2)  # (N, C)
    num_best_gain = jnp.take_along_axis(g_num, num_best_t[:, :, None], 2).squeeze(2)
    num_na_left = (
        jnp.take_along_axis(g_naleft, num_best_t[:, :, None], 2).squeeze(2)
        >= jnp.take_along_axis(g_naright, num_best_t[:, :, None], 2).squeeze(2)
    )

    if cat_cols:
        # ---- categorical: prefix split in mean-sorted bin order, on the
        # categorical column subset only ----
        cat_idx = jnp.asarray(np.asarray(cat_cols, np.int32))
        Cc = len(cat_cols)
        data_c = data[:, cat_idx, :, :]  # (N, Cc, B-1, 3)
        na_c = na[:, cat_idx, :]
        w_bins = data_c[..., 0]
        mean = jnp.where(w_bins > 0, data_c[..., 1] / jnp.maximum(w_bins, 1e-30), jnp.inf)
        order = jnp.argsort(mean, axis=2)  # (N, Cc, B-1) empty bins (inf) last
        sdata = jnp.take_along_axis(data_c, order[..., None], axis=2)
        scum = jnp.cumsum(sdata, axis=2)
        s_tot = scum[:, :, -1:, :]
        s_left = scum[:, :, :-1, :]
        s_right = s_tot - s_left
        gc_naleft = gain_with_na(s_left + na_c[:, :, None, :], s_right)
        gc_naright = gain_with_na(s_left, s_right + na_c[:, :, None, :])
        g_cat = jnp.maximum(gc_naleft, gc_naright)
        cat_best_k = jnp.argmax(g_cat, axis=2)  # (N, Cc) prefix length-1
        cat_best_gain_c = jnp.take_along_axis(g_cat, cat_best_k[:, :, None], 2).squeeze(2)
        cat_na_left_c = (
            jnp.take_along_axis(gc_naleft, cat_best_k[:, :, None], 2).squeeze(2)
            >= jnp.take_along_axis(gc_naright, cat_best_k[:, :, None], 2).squeeze(2)
        )
        # scatter subset results back to full column axis
        cat_best_gain = jnp.full((N, C), _NEG, hist.dtype).at[:, cat_idx].set(cat_best_gain_c)
        col_gain = jnp.where(is_cat[None, :], cat_best_gain, num_best_gain)
    else:
        col_gain = num_best_gain

    # ---- choose best column per node ----
    col_gain = jnp.where(col_mask > 0, col_gain, _NEG)
    best_col = jnp.argmax(col_gain, axis=1)  # (N,)
    best_gain = jnp.take_along_axis(col_gain, best_col[:, None], 1).squeeze(1)

    take = lambda a: jnp.take_along_axis(a, best_col[:, None], 1).squeeze(1)
    bc_t = take(num_best_t)
    # split_bin: numeric → left iff 1 <= bin <= t+1
    split_bin = bc_t + 1

    if cat_cols:
        # position of each full col in the cat subset (0 for non-cat; gated
        # by bc_is_cat downstream so the garbage value is never used)
        pos_of_col = np.zeros(C, np.int32)
        pos_of_col[list(cat_cols)] = np.arange(Cc, dtype=np.int32)
        bc_is_cat = is_cat[best_col]
        best_pos = jnp.asarray(pos_of_col)[best_col]  # (N,)
        take_c = lambda a: jnp.take_along_axis(a, best_pos[:, None], 1).squeeze(1)
        bc_k = take_c(cat_best_k)
        bc_na_left = jnp.where(bc_is_cat, take_c(cat_na_left_c), take(num_na_left))
        # cat membership mask over ALL B bins (bin 0 NA handled separately):
        # rank of data-bin j (order position) <= k  → left
        ranks = jnp.argsort(order, axis=2)  # (N, Cc, B-1) rank of each data bin
        idx = jnp.broadcast_to(best_pos[:, None, None], (N, 1, ranks.shape[2]))
        best_ranks = jnp.take_along_axis(ranks, idx, axis=1).squeeze(1)  # (N, B-1)
        cat_left = best_ranks <= bc_k[:, None]  # (N, B-1) for data bins 1..B-1
        cat_mask = jnp.concatenate(
            [bc_na_left[:, None], cat_left], axis=1
        )  # (N, B): bin0 = NA direction
        # canonical form: numeric winners record an all-False mask (every
        # consumer gates on is_cat, and a garbage mask would differ between
        # the replicated and column-sharded scans)
        cat_mask = jnp.where(bc_is_cat[:, None], cat_mask, False)
    else:
        bc_is_cat = jnp.zeros(N, bool)
        bc_na_left = take(num_na_left)
        cat_mask = jnp.zeros((N, B), bool)

    node_w = node_totals[:, 0]
    node_wy = node_totals[:, 1]
    node_wh = node_totals[:, 2]
    ok_split = best_gain >= min_split_improvement

    # Chosen-split child stats {w, wy, wh} (N, 3) for the left/right
    # children, NA direction folded in. These feed (a) sibling subtraction —
    # next level builds only the smaller child's histogram and derives the
    # other as parent − built (the DHistogram/LightGBM work-halving trick) —
    # and (b) the final level's leaf values, which then need no histogram
    # pass at all.
    na_best = jnp.take_along_axis(na, best_col[:, None, None], 1).squeeze(1)  # (N,3)
    gidx = best_col[:, None, None, None]
    gnum = lambda arr: jnp.take_along_axis(
        jnp.take_along_axis(arr, gidx, 1).squeeze(1), bc_t[:, None, None], 1
    ).squeeze(1)  # (N, 3)
    Lraw, Rraw = gnum(left_n), gnum(right_n)
    if cat_cols:
        gidx_c = best_pos[:, None, None, None]
        gcat = lambda arr: jnp.take_along_axis(
            jnp.take_along_axis(arr, gidx_c, 1).squeeze(1), bc_k[:, None, None], 1
        ).squeeze(1)
        Lraw = jnp.where(bc_is_cat[:, None], gcat(s_left), Lraw)
        Rraw = jnp.where(bc_is_cat[:, None], gcat(s_right), Rraw)
    nl = bc_na_left[:, None]
    Lst = Lraw + jnp.where(nl, na_best, 0.0)
    Rst = Rraw + jnp.where(~nl, na_best, 0.0)

    out = {
        "Lst": Lst,
        "Rst": Rst,
        "gain": best_gain,
        "ok": ok_split,
        "col": best_col,
        "is_cat": bc_is_cat,
        "split_bin": split_bin,
        "na_left": bc_na_left,
        "cat_mask": cat_mask,
        "node_w": node_w,
        "node_wy": node_wy,
        "node_wh": node_wh,
    }
    if mono is not None:
        # chosen split's clamped child values -> mid for bound propagation
        # (categorical winners carry mono_col 0, so their mid is never used)
        vL = jnp.clip(
            jnp.where(Lst[:, 2] > 0, Lst[:, 1] / jnp.maximum(Lst[:, 2], 1e-30), 0.0),
            node_lo, node_hi,
        )
        vR = jnp.clip(
            jnp.where(Rst[:, 2] > 0, Rst[:, 1] / jnp.maximum(Rst[:, 2], 1e-30), 0.0),
            node_lo, node_hi,
        )
        out["mid"] = 0.5 * (vL + vR)
        out["mono_col"] = jnp.where(bc_is_cat, 0, mono[best_col])
    return out


# ---------------------------------------------------------------------------
# column-sharded split pipeline (H2O3_TPU_SPLIT_SHARD): the histogram
# reduction ends in a reduce-scatter over contiguous column blocks
# (histogram_in_jit col_sharded=True — each device keeps only its C/P
# columns, 1/P of the all-reduce's replication volume), the split scan runs
# on the local block only (FLOPs / P), and a tiny all-gather of per-block
# winner tuples feeds a merge that reproduces jnp.argmax's
# lowest-global-index tie-breaking bit-exactly.


def _split_shard_on() -> bool:
    """Single policy for the sharded split pipeline: on by default whenever
    the mesh deals >1 COLUMN block (``H2O3_TPU_SPLIT_SHARD=0`` restores the
    replicated scan). On the legacy 1-D mesh that is any >1-device mesh; on
    a 2-D rows×cols mesh the block count is the ``cols`` axis — an R×1 mesh
    has nothing to shard columns over and scans replicated (its histogram
    still reduces over the rows axis)."""
    from h2o3_tpu import config
    from h2o3_tpu.parallel.mesh import n_col_shards

    return config.get_bool("H2O3_TPU_SPLIT_SHARD") and n_col_shards() > 1


def _kernel_key() -> tuple:
    """Program-cache component for everything that changes the TRACED
    kernels without changing any call-site argument: the Pallas tile triple
    and the local-histogram override. Without these a cached program
    compiled under one setting would silently serve another."""
    from h2o3_tpu import config
    from h2o3_tpu.ops.hist_pallas import _tiles

    # the RAW spec rides along because 'auto' (the tile autotuner) resolves
    # shape-dependent tiles inside the trace — _tiles() alone could not
    # distinguish 'auto' from the '' defaults; HIST_I16 changes the traced
    # local accumulation (ops/histogram._maybe_i16)
    return (_tiles(),
            config.get("H2O3_TPU_PALLAS_TILES").strip(),
            config.get("H2O3_TPU_HIST"),
            config.get_bool("H2O3_TPU_HIST_I16"))


def _split_scan_sharded(
    hist, is_cat, col_mask, min_rows, min_split_improvement,
    any_cat: bool, mono=None, node_lo=None, node_hi=None, mesh=None,
):
    """Blockwise :func:`_split_scan` over a column-sharded histogram, merged
    bit-exactly against the replicated scan's ``jnp.argmax``.

    ``hist`` is (N, Cp, B, S) with the column axis sharded over the mesh
    (``histogram_in_jit(..., col_sharded=True)``'s layout; Cp = C padded to
    a multiple of the shard count). Each device scans ONLY its contiguous
    block of Cp/P columns, then every device gathers the per-block winner
    tuples — O(N·P) scalars, not the O(C·N·B·S) histogram — and merges them
    identically (replicated output).

    Bit-exactness, piece by piece:
    - each block's histogram cells equal the replicated reduction's
      (reduce-scatter and all-reduce combine shards in the same order);
    - every block computes gains against GLOBAL column 0's node totals
      (gathered once, (N, S)), because a different column's bin partition
      can change the float total in the last bits (``node_totals`` in
      :func:`_split_scan`) — so per-(node, col) gains are the identical
      floats the replicated scan compares;
    - the block-local argmax picks the lowest LOCAL index among ties, the
      merge's argmax over the gathered (P, N) gains picks the lowest BLOCK,
      and blocks are contiguous ascending column ranges — lexicographic
      (block, local) is exactly lowest-global-index.

    When the frame has categorical columns (``any_cat``), every block runs
    the mean-sort categorical branch on ALL its local columns (block
    membership is dynamic, the traced program is one-per-mesh) and selects
    per-column by the sliced ``is_cat`` — same per-column floats, so parity
    holds for categorical winners too; the winner tuple then carries the
    (N, B) membership mask, making the gather O(N·B·P) instead of O(N·P).
    """
    import jax.tree_util as jtu

    from h2o3_tpu.ops.histogram import record_collective
    from h2o3_tpu.parallel.mesh import (
        col_axis_name, get_mesh, n_col_shards, shard_map,
    )
    from jax.sharding import PartitionSpec as P

    mesh = mesh or get_mesh()
    n_dev = n_col_shards(mesh)
    cax = col_axis_name(mesh)
    N, Cp, B, S = hist.shape
    Cb = Cp // n_dev
    C = is_cat.shape[0]
    if Cp > C:  # histogram divisibility padding: zero hists, masked columns
        is_cat = jnp.pad(is_cat, (0, Cp - C))
        col_mask = jnp.pad(col_mask, ((0, 0), (0, Cp - C)))
        if mono is not None:
            mono = jnp.pad(mono, (0, Cp - C))
    local_cats = tuple(range(Cb)) if any_cat else ()

    # winner-gather payload per device (trace-time byte tally): the scalar
    # tuple + the block-0 node-totals broadcast, + the membership mask when
    # categorical columns exist
    if n_dev > 1:
        per_dev = N * (4 + 4 + 4 + 1 + 1 + 12 + 12 + 4 * S)
        if any_cat:
            per_dev += N * B
        if mono is not None:
            per_dev += N * 8
        record_collective("winner_gather", n_dev * per_dev)

    def body(h_blk, cm, ic, mono_g, lo, hi):
        d = jax.lax.axis_index(cax)
        col0 = (d * Cb).astype(jnp.int32)
        # node totals from GLOBAL column 0 = block 0's local column 0
        tot_loc = h_blk[:, 0, :, :].sum(axis=1)  # (N, S)
        tot0 = jax.lax.all_gather(tot_loc, cax)[0]
        cm_blk = jax.lax.dynamic_slice_in_dim(cm, col0, Cb, axis=1)
        ic_blk = jax.lax.dynamic_slice_in_dim(ic, col0, Cb, axis=0)
        mono_blk = (
            None if mono_g is None
            else jax.lax.dynamic_slice_in_dim(mono_g, col0, Cb, axis=0)
        )
        sp = _split_scan(
            h_blk, ic_blk, cm_blk, min_rows, min_split_improvement,
            local_cats, mono=mono_blk, node_lo=lo, node_hi=hi,
            node_totals=tot0,
        )
        win = {
            "gain": sp["gain"],
            "col": col0 + sp["col"].astype(jnp.int32),
            "split_bin": sp["split_bin"],
            "na_left": sp["na_left"],
            "is_cat": sp["is_cat"],
            "Lst": sp["Lst"],
            "Rst": sp["Rst"],
        }
        if any_cat:
            win["cat_mask"] = sp["cat_mask"]
        if mono_g is not None:
            win["mid"] = sp["mid"]
            win["mono_col"] = sp["mono_col"]
        g = jtu.tree_map(lambda a: jax.lax.all_gather(a, cax), win)
        # the merge, computed identically on every device: argmax over the
        # gathered block axis — first max wins, i.e. the LOWEST block
        bb = jnp.argmax(g["gain"], axis=0)  # (N,)

        def pick(a):
            idx = bb.reshape((1,) + bb.shape + (1,) * (a.ndim - 2))
            return jnp.take_along_axis(a, idx, axis=0).squeeze(0)

        out = {k: pick(v) for k, v in g.items()}
        out["ok"] = out["gain"] >= min_split_improvement
        out["node_w"] = tot0[:, 0]
        out["node_wy"] = tot0[:, 1]
        out["node_wh"] = tot0[:, 2]
        if not any_cat:
            out["cat_mask"] = jnp.zeros((N, B), bool)
        return out

    if mono is None:
        return shard_map(
            lambda h, cm, ic: body(h, cm, ic, None, None, None),
            mesh=mesh,
            in_specs=(P(None, cax), P(), P()),
            out_specs=P(),
            check_vma=False,
        )(hist, col_mask, is_cat)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, cax), P(), P(), P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )(hist, col_mask, is_cat, mono, node_lo, node_hi)


# ---------------------------------------------------------------------------
# partition update (DecidedNode re-labeling + leaf retirement)
# — also the prediction-replay op, so it keeps its own jit wrapper.
#
# A TPU executes a per-row gather element by element (86 ms to fetch one
# byte for each of 4M rows, PERF.md §6 PR 29), so the routing is written
# with none: a row's entries of the level's node tables are ONE contraction
# of its one-hot node indicator with the tables, and the split column's
# code is a compare-select-reduce over the C columns. Every per-row
# intermediate is (n,) or (k, n) with the rows along the lanes; nothing is
# shaped (n, 1).


def _byte_lanes(word, n_bytes: int):
    """The low ``n_bytes`` bytes of an int32 table (n_pad,), as
    (n_bytes, n_pad) bfloat16 lanes (0..255 is exact there)."""
    u = jax.lax.bitcast_convert_type(word.astype(jnp.int32), jnp.uint32)
    return jnp.stack(
        [((u >> (8 * i)) & 255).astype(jnp.bfloat16) for i in range(n_bytes)])


def _from_bytes(rows):
    """Inverse of :func:`_byte_lanes` on the contracted (k, n) int32 rows."""
    word = rows[0]
    for i in range(1, rows.shape[0]):
        word = word | (rows[i] << (8 * i))
    return word


def _n_bytes(max_value: int) -> int:
    return max(1, (int(max_value).bit_length() + 7) // 8)


@partial(jax.jit, static_argnames=("any_cat",))
def _partition_dense(
    bins_u8, nid, preds, split_col, split_bin, is_cat, cat_mask, na_left,
    leaf_now, leaf_val, child_base, *, any_cat: bool,
):
    n, C = bins_u8.shape
    n_pad = split_col.shape[0]
    i32, bf16 = jnp.int32, jnp.bfloat16
    active = nid >= 0
    node = jnp.where(active, nid, 0)

    # the node tables as (k, n_pad) bfloat16 lanes, every entry an integer
    # in 0..256 (exact in bfloat16): three flags, the threshold + 1 (codes
    # are 0..255, so a threshold outside [-1, 255] routes as its clip), and
    # the bytes of the column, the child base and the leaf value's bits.
    # Their widths come from the static shapes: a column is < C, a child
    # id < 2 * n_pad.
    flags = (na_left.astype(i32) | (is_cat.astype(i32) << 1)
             | (leaf_now.astype(i32) << 2))
    tables = [
        flags.astype(bf16)[None],
        (jnp.clip(split_bin.astype(i32), -1, 255) + 1).astype(bf16)[None],
        _byte_lanes(split_col, _n_bytes(C - 1)),
        _byte_lanes(child_base, _n_bytes(2 * n_pad - 1)),
        _byte_lanes(
            jax.lax.bitcast_convert_type(leaf_val.astype(jnp.float32), i32), 4),
    ]
    if any_cat:
        # the membership mask, eight bins to a byte: (ceil(B / 8), n_pad)
        nb_mask = -(-cat_mask.shape[1] // 8)
        bits = jnp.pad(
            cat_mask, ((0, 0), (0, 8 * nb_mask - cat_mask.shape[1]))
        ).reshape(n_pad, nb_mask, 8).astype(i32)
        tables.append(
            (bits << jnp.arange(8, dtype=i32)).sum(axis=2).T.astype(bf16))

    # one 0/1 operand and one nonzero product a row: the f32 accumulator
    # holds each table entry exactly. XLA fuses the indicator into the
    # contraction; (n_pad, n) is never materialised.
    hot = (node[None, :] == jnp.arange(n_pad, dtype=i32)[:, None]).astype(bf16)
    rows = jax.lax.dot_general(
        jnp.concatenate(tables), hot, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(i32)  # (k, n)
    row_flags, thr, col, child, leaf, *mask = jnp.split(
        rows, np.cumsum([t.shape[0] for t in tables])[:-1].tolist())
    row_flags, thr = row_flags[0], thr[0] - 1
    col, child = _from_bytes(col), _from_bytes(child)
    leaf = jax.lax.bitcast_convert_type(_from_bytes(leaf), jnp.float32)

    # the split column's code: one pass over the codes
    b = jnp.sum(
        jnp.where(jax.lax.broadcast_in_dim(col, (n, C), (0,))
                  == jnp.arange(C, dtype=i32)[None, :],
                  bins_u8.astype(i32), 0),
        axis=1,
    )
    go_left = b <= thr
    if any_cat:
        byte = jnp.sum(
            jnp.where((b >> 3)[None, :] == jnp.arange(nb_mask, dtype=i32)[:, None],
                      mask[0], 0),
            axis=0,
        )
        go_left = jnp.where(
            (row_flags & 2) != 0, ((byte >> (b & 7)) & 1) != 0, go_left)
    go_left = jnp.where(b == 0, (row_flags & 1) != 0, go_left)
    retired = (row_flags & 4) != 0
    new_nid = jnp.where(
        active, jnp.where(retired, -1, child + jnp.where(go_left, 0, 1)), -1)
    with jax.named_scope("ph_pred"):  # the prediction update, under ph_part
        new_preds = preds + jnp.where(active & retired, leaf, 0.0)
    # The barrier keeps the node ids a 1-D lane. Until ISSUE 31 the
    # histogram kernel took them as an (n, 1) operand in (8, 128) tiles —
    # 128 lanes a row — and XLA's layout assignment carried that tiling back
    # through every elementwise producer: the selects above and the next
    # level's pair bookkeeping then ran on 2 GB arrays (a layout constraint
    # does the same on one device, but the SPMD partitioner gathers its
    # operand across a row-sharded mesh). The kernel now takes (1, n), a
    # bitcast of this lane; whether the program is as fast without the
    # barrier has not been measured on a chip (ROADMAP A5).
    new_nid = jax.lax.optimization_barrier(new_nid.astype(jnp.int32))
    return new_nid, new_preds


def _partition_update(
    bins_u8, nid, preds, split_col, split_bin, is_cat, cat_mask, na_left,
    leaf_now, leaf_val, child_base, any_cat: bool | None = None,
):
    """Route every row one level down and retire the rows whose node went
    leaf (``genmodel.goes_left`` is the host twin of the rule).

    ``any_cat`` says whether a node of the level may split on a categorical
    column; False drops the membership-mask term from the program. Callers
    pass what they know statically (the builders' ``cat_cols``); left None
    it is read off a host-resident ``is_cat`` and assumed otherwise.
    """
    if any_cat is None:
        any_cat = bool(is_cat.any()) if isinstance(is_cat, np.ndarray) else True
    _count_partition_level(nid)
    return _partition_dense(
        bins_u8, nid, preds, split_col, split_bin, is_cat, cat_mask, na_left,
        leaf_now, leaf_val, child_base, any_cat=any_cat,
    )


# ---------------------------------------------------------------------------
# the fused level step


def _leaf_decide(
    ok, gain, node_w, node_wy, node_wh, split_col, split_bin,
    is_cat_n, cat_mask, na_left, learn_rate, max_abs_leaf, n_pad,
    node_lo=None, node_hi=None, reg_lambda=None, reg_alpha=None,
    *, col_offer,
):
    """Leaf decision + child-id assignment + the replayable record — the
    partition-free head of :func:`_finish_level`, shared with the
    out-of-core streamed driver (:func:`build_trees_streamed`), which runs
    the partition update per row block instead of over one resident array.

    ``reg_lambda``/``reg_alpha`` (XGBoost leaf regularization, traced
    scalars): leaf = soft_threshold(Σwy, α) / (Σwh + λ) — xgboost's
    w* = −soft(G, α)/(H + λ) with our sign convention (wy ≡ −G, wh ≡ H).
    None keeps the unregularized trace byte-identical (the H2O GBM path).

    ``col_offer`` ((n_pad, C) 0/1, :func:`_offered_columns`' mask as the
    split scan saw it; all zero on a level that scans nothing) is kept in
    the record beside ``split_col``: which columns each node was offered.
    """
    leaf_now = ~ok
    if reg_lambda is not None:
        num = jnp.sign(node_wy) * jnp.maximum(jnp.abs(node_wy) - reg_alpha, 0.0)
        den = node_wh + reg_lambda
        leaf_val = jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0)
    else:
        leaf_val = jnp.where(node_wh > 0, node_wy / jnp.maximum(node_wh, 1e-30), 0.0)
    if node_lo is not None:
        leaf_val = jnp.clip(leaf_val, node_lo, node_hi)  # monotone bound clamp
    leaf_val = jnp.clip(leaf_val, -max_abs_leaf, max_abs_leaf) * learn_rate
    leaf_val = jnp.where(leaf_now, leaf_val, 0.0).astype(jnp.float32)

    cs = jnp.cumsum(ok.astype(jnp.int32))
    child_base = jnp.where(ok, 2 * (cs - 1), 0).astype(jnp.int32)
    n_split = cs[-1] if n_pad else jnp.int32(0)

    record = {
        "node_w": node_w.astype(jnp.float32),
        "split_col": split_col.astype(jnp.int32),
        "split_bin": split_bin.astype(jnp.int32),
        "is_cat": is_cat_n,
        "cat_mask": cat_mask,
        "na_left": na_left,
        "leaf_now": leaf_now,
        "leaf_val": leaf_val,
        "child_base": child_base,
        "gain": gain,
        "col_offer": col_offer > 0,
    }
    return leaf_now, leaf_val, child_base, cs, n_split, record


def _finish_level(
    bins_u8, nid, preds, varimp, ok, gain, node_w, node_wy, node_wh,
    split_col, split_bin, is_cat_n, cat_mask, na_left,
    learn_rate, max_abs_leaf, n_pad, node_lo=None, node_hi=None,
    reg_lambda=None, reg_alpha=None, any_cat: bool = True, col_offer=None,
):
    """Shared tail of every level: leaf decision, child-id assignment,
    varimp scatter, partition update, and the replayable record.

    ``node_lo``/``node_hi`` (monotone-constraint bound state) clamp leaf
    values when given; None leaves the unconstrained trace byte-identical.
    ``any_cat`` False (a frame with no categorical column, or the all-leaf
    terminal level) keeps the membership-mask term out of the partition.
    ``col_offer`` None (a level that scans nothing) records no column offered.
    """
    if col_offer is None:
        col_offer = jnp.zeros((n_pad, bins_u8.shape[1]), jnp.float32)
    with jax.named_scope("ph_leaf"):
        leaf_now, leaf_val, child_base, cs, n_split, record = _leaf_decide(
            ok, gain, node_w, node_wy, node_wh, split_col, split_bin,
            is_cat_n, cat_mask, na_left, learn_rate, max_abs_leaf, n_pad,
            node_lo=node_lo, node_hi=node_hi,
            reg_lambda=reg_lambda, reg_alpha=reg_alpha, col_offer=col_offer,
        )

    varimp = varimp.at[split_col].add(jnp.where(ok, gain, 0.0).astype(varimp.dtype))

    # ph_part: phase tag (utils/telemetry.summarize, tools/profile_fused.py)
    with jax.named_scope("ph_part"):
        nid, preds = _partition_update(
            bins_u8, nid, preds, split_col, split_bin, is_cat_n, cat_mask,
            na_left, leaf_now, leaf_val, child_base, any_cat=any_cat,
        )
    return nid, preds, varimp, n_split, record, cs


def _child_bounds(ok, child_base, mono_col, mid, node_lo, node_hi,
                  n_pad_next: int):
    """Monotone child-bound propagation: children of a constrained split
    tighten to the parent's ``mid`` on the constrained side (left child at
    ``child_base``, right at ``child_base+1``; leaves drop out-of-bounds).
    Factored out of the per-level mono step so the streamed decide and the
    per-level loop scatter the SAME ops. Returns ``(new_lo, new_hi)`` sized
    ``n_pad_next``."""
    new_lo = jnp.full(n_pad_next, -jnp.inf, jnp.float32)
    new_hi = jnp.full(n_pad_next, jnp.inf, jnp.float32)
    inc = mono_col > 0
    dec = mono_col < 0
    l_lo = jnp.where(dec, mid, node_lo)
    l_hi = jnp.where(inc, mid, node_hi)
    r_lo = jnp.where(inc, mid, node_lo)
    r_hi = jnp.where(dec, mid, node_hi)
    li = jnp.where(ok, child_base, n_pad_next)  # OOB drop for leaves
    ri = jnp.where(ok, child_base + 1, n_pad_next)
    new_lo = new_lo.at[li].set(l_lo, mode="drop")
    new_lo = new_lo.at[ri].set(r_lo, mode="drop")
    new_hi = new_hi.at[li].set(l_hi, mode="drop")
    new_hi = new_hi.at[ri].set(r_hi, mode="drop")
    return new_lo, new_hi


def _offered_columns(key, cols_enabled, col_sample_rate, n_pad: int,
                     n_cols_real: int | None = None):
    """The columns each node of a level may split on: ``(n_pad, C)`` float
    0/1 — exactly ``k = max(1, round(col_sample_rate * C_real))`` distinct
    columns a node (H2O's ``mtries`` / ``col_sample_rate`` per split), drawn
    uniformly without replacement from the tree's enabled columns: the ``k``
    smallest of one random word a column. The words are made distinct (the
    column's index in their low bits), so a node is never offered ``k + 1``
    on a tie; fewer than ``k`` enabled columns offers them all; rate 1.0
    offers every enabled column. The one draw of every builder
    (:func:`_level_core`, the monotone step, the streamed decide).

    The draw runs at the REAL column count (``n_cols_real``) so
    shape-bucketed column padding cannot perturb which columns a node
    samples — bucketed builds stay bit-identical. ``col_sample_rate`` may be
    a traced scalar.
    """
    C = cols_enabled.shape[0]
    Cr = n_cols_real or C
    k = jnp.clip(jnp.round(col_sample_rate * Cr), 1, Cr).astype(jnp.int32)
    low = max(1, (Cr - 1).bit_length())
    col = jnp.arange(Cr, dtype=jnp.uint32)
    word = jax.random.bits(key, (n_pad, Cr), jnp.uint32)
    word = (word >> (low + 1) << low) | col  # distinct; the top bit is free
    enabled = cols_enabled[:Cr] > 0
    word = jnp.where(enabled, word, word | jnp.uint32(1 << 31))  # disabled last
    kth = jnp.take_along_axis(
        jnp.sort(word, axis=1), jnp.broadcast_to(k - 1, (n_pad, 1)), axis=1)
    keep = (word <= kth) & enabled
    if Cr < C:
        keep = jnp.pad(keep, ((0, 0), (0, C - Cr)))
    return keep.astype(cols_enabled.dtype)


def _level_core(
    hist, bins_u8, nid, preds, varimp, key, cols_enabled, is_cat,
    min_rows, min_split_improvement, learn_rate, max_abs_leaf, col_sample_rate,
    leaf_reg=None,
    *, n_pad: int, n_pad_next: int, cat_cols: tuple = (),
    n_cols_real: int | None = None, split_shard: bool = False,
    leaf_budget=None,
):
    """Split scan → decisions → partition for one level, given its histogram.

    ``split_shard`` selects the column-sharded scan: ``hist`` then arrives
    column-sharded (and possibly padded past the real column count — the
    sharded scan masks the pad), and the scan+merge reproduces the
    replicated path's decisions bit-exactly (:func:`_split_scan_sharded`).

    ``leaf_budget`` (traced int32 scalar, ISSUE 16 ``grow_policy=lossguide``)
    rations this level's splits by gain rank: only the ``leaf_budget``
    highest-gain candidates split (each split adds one net leaf), and the
    return appends the decremented budget for the caller's carry. The
    ranking argsort is stable, so ties break toward the lower node slot —
    deterministic across backends. A budget ≥ the candidate count leaves
    the level's decisions bit-identical to depth-wise growth.

    Returns ``(nid, preds, varimp, n_split, record, pair_info)``.
    ``pair_info`` carries, per next-level child PAIR slot (``n_pad_next//2``
    slots; pair *i* holds children ``2i``/``2i+1``), everything sibling
    subtraction at the next level needs: ``parent_idx`` (which of this
    level's nodes split into that pair), ``valid`` (the slot is a real
    split), ``build_left`` (the lighter child — the one whose histogram is
    worth building), and the chosen split's exact left/right child stats
    ``Lst``/``Rst`` (so the final level derives leaf values with no
    histogram at all).

    Empty/padding nodes need no masking anywhere: their histograms are all
    zero, so every candidate split fails the min_rows check and they retire
    as zero-valued leaves that no row is assigned to.
    """
    col_mask = _offered_columns(
        key, cols_enabled, col_sample_rate, n_pad, n_cols_real)
    # ph_split: phase tag (utils/telemetry.summarize, tools/profile_fused.py)
    with jax.named_scope("ph_split"):
        if split_shard:
            sp = _split_scan_sharded(
                hist, is_cat, col_mask, min_rows, min_split_improvement,
                any_cat=bool(cat_cols),
            )
        else:
            sp = _split_scan(
                hist, is_cat, col_mask, min_rows, min_split_improvement,
                cat_cols,
            )
    ok = sp["ok"]
    # frontier cap: children must fit n_pad_next; later nodes go leaf
    fits = 2 * jnp.cumsum(ok.astype(jnp.int32)) <= n_pad_next
    ok = ok & fits
    new_budget = None
    if leaf_budget is not None:
        # loss-guide ration: keep only the budget's worth of highest-gain
        # candidates (stable argsort — ties go to the lower node slot)
        order = jnp.argsort(jnp.where(ok, -sp["gain"], jnp.inf))
        rank = jnp.zeros(n_pad, jnp.int32).at[order].set(
            jnp.arange(n_pad, dtype=jnp.int32)
        )
        ok = ok & (rank < leaf_budget)
        new_budget = (leaf_budget - ok.sum()).astype(jnp.int32)
    gain = jnp.where(ok, jnp.maximum(sp["gain"], 0.0), 0.0)

    rl, ra = (None, None) if leaf_reg is None else leaf_reg
    nid, preds, varimp, n_split, record, cs = _finish_level(
        bins_u8, nid, preds, varimp, ok, gain,
        sp["node_w"], sp["node_wy"], sp["node_wh"],
        sp["col"], sp["split_bin"], sp["is_cat"], sp["cat_mask"], sp["na_left"],
        learn_rate, max_abs_leaf, n_pad,
        reg_lambda=rl, reg_alpha=ra, any_cat=bool(cat_cols),
        col_offer=col_mask,
    )

    half = n_pad_next // 2
    pidx = jnp.where(ok, cs - 1, half)  # OOB drop for non-splitting nodes
    scat = lambda init, vals: init.at[pidx].set(vals, mode="drop")
    pair_info = {
        "valid": scat(jnp.zeros(half, bool), jnp.ones(n_pad, bool)),
        "parent_idx": scat(
            jnp.zeros(half, jnp.int32), jnp.arange(n_pad, dtype=jnp.int32)
        ),
        "build_left": scat(jnp.zeros(half, bool), sp["Lst"][:, 0] <= sp["Rst"][:, 0]),
        "Lst": scat(jnp.zeros((half, 3), sp["Lst"].dtype), sp["Lst"]),
        "Rst": scat(jnp.zeros((half, 3), sp["Rst"].dtype), sp["Rst"]),
    }
    out = (nid, preds, varimp, n_split, record, pair_info)
    return out if leaf_budget is None else out + (new_budget,)


def _force_leaf_from_stats(
    bins_u8, nid, preds, varimp, node_w, node_wy, node_wh,
    learn_rate, max_abs_leaf, n_pad, n_bins, leaf_reg=None,
):
    """Terminal level: every active node becomes a leaf (no split scan)."""
    ok = jnp.zeros(n_pad, bool)
    zi = jnp.zeros(n_pad, jnp.int32)
    rl, ra = (None, None) if leaf_reg is None else leaf_reg
    nid, preds, varimp, n_split, record, _ = _finish_level(
        bins_u8, nid, preds, varimp, ok, jnp.zeros(n_pad, jnp.float32),
        node_w, node_wy, node_wh, zi, zi, jnp.zeros(n_pad, bool),
        jnp.zeros((n_pad, n_bins), bool), jnp.zeros(n_pad, bool),
        learn_rate, max_abs_leaf, n_pad,
        reg_lambda=rl, reg_alpha=ra, any_cat=False,
    )
    return nid, preds, varimp, n_split, record


def _level_step_fn(
    bins_u8, nid, preds, varimp, w, wy, wh, key, cols_enabled, is_cat,
    min_rows, min_split_improvement, learn_rate, max_abs_leaf, col_sample_rate,
    leaf_reg=None,
    *, n_pad: int, n_pad_next: int, n_bins: int, force_leaf: bool,
    cat_cols: tuple = (), split_shard: bool = False,
):
    """One whole tree level on device (histogram built from scratch).

    The per-level dispatch form: used by the CPU loop and as the building
    block the fused/subtraction path (:func:`_fused_levels`) specializes.
    Returns (nid, preds, varimp, n_split, record).
    """
    from h2o3_tpu.ops.histogram import histogram_in_jit

    hist = histogram_in_jit(
        bins_u8, nid, (w, wy, wh), n_pad, n_bins, col_sharded=split_shard,
    )

    if force_leaf:
        tot = hist[:, 0, :, :].sum(axis=1)  # (n_pad, 3); col 0 ≡ any col
        return _force_leaf_from_stats(
            bins_u8, nid, preds, varimp, tot[:, 0], tot[:, 1], tot[:, 2],
            learn_rate, max_abs_leaf, n_pad, n_bins, leaf_reg,
        )
    out = _level_core(
        hist, bins_u8, nid, preds, varimp, key, cols_enabled, is_cat,
        min_rows, min_split_improvement, learn_rate, max_abs_leaf,
        col_sample_rate, leaf_reg, n_pad=n_pad, n_pad_next=n_pad_next,
        cat_cols=cat_cols, split_shard=split_shard,
    )
    return out[:5]


def _sat_region(max_depth: int, node_cap: int) -> tuple:
    """(start, count) of the node_cap-SATURATED level run rolled into a
    ``lax.while_loop``: levels where the frontier is pinned at ``node_cap``
    (so every iteration has identical shapes). Unrolling those levels instead
    would compile O(depth) copies of the most expensive level body — the
    while_loop form compiles ONE body and early-exits on device the moment a
    level produces no splits (the deep-DRF regime where most levels are
    dead)."""
    for d in range(1, max_depth):
        if min(1 << d, node_cap) == node_cap:
            if max_depth - d >= 2:
                return d, max_depth - d
            break
    return None, 0


def _fused_levels(
    bins_u8, preds, varimp, w, wy, wh, tkey, cols_enabled, is_cat,
    min_rows, min_split_improvement, learn_rate, max_abs_leaf, col_sample_rate,
    leaf_reg=None,
    *, max_depth: int, n_bins: int, node_cap: int, cat_cols: tuple,
    subtract: bool = True, n_cols_real: int | None = None,
    split_shard: bool = False, max_leaves: int = 0, efb=None, bins_b=None,
):
    """All levels of one tree, traced into a single program, with the two
    histogram work reductions the reference's hot loop embodies
    (``DHistogram``'s build-smaller-child + derive-sibling, SURVEY §2.2):

    - levels 1..D-1 build histograms only for the LIGHTER child of each
      split pair (``n_pad//2`` node slots — the dense one-hot histogram's
      cost is ∝ node count); the heavier sibling is ``parent − built``.
      Building the lighter child keeps the subtraction cancellation error
      small relative to the surviving (heavier) histogram.
    - the terminal level needs NO histogram: every node's {w, wy, wh} totals
      are exactly its parent's chosen-split child stats, recorded by
      :func:`_level_core`.

    At depth 6 that is 1+1+2+4+8+16+0 = 32 node-histogram units vs 127 for
    the direct scheme — ~4× fewer MXU FLOPs in the phase that dominates
    tree time. ``subtract=False`` recovers the direct scheme (the reference
    the parity tests compare against, ``H2O3_TPU_HIST_SUBTRACT=0``).

    Level structure (one compiled program, zero host round-trips):
    frontier-GROWTH levels (node count 1, 2, 4, … < node_cap) unroll — each
    has its own shapes; the node_cap-SATURATED run rolls into a
    ``lax.while_loop`` whose predicate early-exits on device once a level
    splits nothing (see :func:`_sat_region`); the terminal level force-leafs.
    Skipped (post-exit) levels keep their pre-initialized placeholder records
    — all-leaf, zero-valued, reachable by no row — so replay, export and the
    level masks need no notion of "how deep did this tree actually go".

    ``max_leaves`` > 0 (ISSUE 16 ``grow_policy=lossguide``) threads an
    int32 remaining-leaf budget through the level-to-level carry (including
    the saturated while_loop's): each level rations its splits by gain rank
    (:func:`_level_core`) and decrements the budget, so the finished tree
    has at most ``max_leaves`` leaves.

    ``efb``/``bins_b`` (ISSUE 16 exclusive feature bundling) accumulate
    every level's histogram from the BUNDLED code matrix ``bins_b``
    ((npad, Cb), Cb < C) and expand it back to real columns immediately
    after accumulation (:func:`~h2o3_tpu.models.tree.binning.expand_hist`),
    so subtraction, the split scans and the partition walk are untouched —
    the O(rows · C) accumulation is the only thing that shrinks. EFB rides
    the replicated lane only (callers force ``split_shard=False``).

    Returns ``(nid, preds, varimp, records, counts)``, ``counts`` the int32
    pair [executed saturated levels, grid steps of the grouped histogram
    levels] that :func:`_run_counted` reads back.
    """
    from h2o3_tpu.ops.histogram import (
        histogram_in_jit, order_codes_in_jit, restore_rows_in_jit,
        row_order_in_jit)

    efb_expand = None
    if efb is not None:
        from h2o3_tpu.models.tree.binning import expand_arrays, expand_hist

        assert not split_shard, "EFB is replicated-lane only"
        _efb_arrs = expand_arrays(efb, bins_u8.shape[1], n_bins)
        efb_expand = lambda h: expand_hist(_efb_arrs, h)

    # pair bookkeeping (children 2i/2i+1 share pair slot i) needs an even
    # frontier; round an odd node_cap down rather than trace-crash on the
    # stack/reshape interleave
    node_cap = max(2, node_cap - (node_cap % 2))
    nid = jnp.zeros(bins_u8.shape[0], jnp.int32)
    # lossguide: remaining net-leaf budget (root is 1 leaf; a split adds 1)
    leaf_budget = jnp.int32(max_leaves - 1) if max_leaves else None
    recs = []
    parent_hist = None
    pair_info = None
    n_split = None
    sat_start, n_sat = _sat_region(max_depth, node_cap)
    bins_h = bins_b if efb_expand else bins_u8  # what the histograms read
    # The rows in node order (ISSUE 33), made before the first level whose
    # histogram is wider than one node tile. From there on the TREE lives in
    # that order — codes, node ids and predictions are permuted once, every
    # later level partitions and histograms them as they lie, and the ids
    # and predictions go back to the frame's order at the tree's end: no
    # level pays a pass to bring its ids into the order. Children are
    # numbered in their parents' order (``_leaf_decide``'s child_base), so the
    # descendants of a sorted node keep its segment of the rows and a
    # contiguous node range: the rows stay in node-tile order at the next
    # level exactly, and later but for the segments whose range straddles a
    # tile boundary. The histogram reads the tiles' chunk ranges from the ids
    # themselves; records, varimp and the metrics never see a row's place.
    order = None
    n_rows = bins_u8.shape[0]

    def built_nodes(depth):
        """Node slots the level's histogram builds: the lighter child of
        each pair under subtraction, else the frontier."""
        n_pad = min(1 << depth, node_cap)
        return n_pad if depth == 0 or not subtract else n_pad // 2

    def hist_of(nid_h, n_nodes):
        """``(hist, steps)`` of ``histogram_in_jit``, ``steps`` 0 where the
        level runs dense."""
        h = histogram_in_jit(
            bins_h, nid_h, (w, wy, wh), n_nodes, n_bins,
            col_sharded=split_shard, order=order,
        )
        h, steps = (h, jnp.int32(0)) if order is None else h
        return (efb_expand(h) if efb_expand else h), steps

    def level_hist(depth, nid, pair_info, parent_hist):
        """One level's histogram — direct or sibling-sub — and the grid steps
        its grouped pass took. Under ``split_shard`` the column axis comes
        back sharded (and padded to the shard count); subtraction and the
        parent carry are columnwise ops, so they stay block-local and never
        transpose in HBM."""
        n_pad = min(1 << depth, node_cap)
        if depth == 0 or not subtract:
            return hist_of(nid, n_pad)
        half = n_pad // 2
        row_pair = jnp.maximum(nid, 0) >> 1  # pair = nid//2 (child_base even)
        row_left = (nid & 1) == 0
        bl = pair_info["build_left"]
        build_row = (nid >= 0) & (row_left == bl[row_pair])
        nid_build = jnp.where(build_row, row_pair, -1)
        # (half, C, B, 3) — EFB accumulates bundled, expands to real C
        built, steps = hist_of(nid_build, half)
        psel = jnp.where(
            pair_info["valid"][:, None, None, None],
            parent_hist[pair_info["parent_idx"]],
            0.0,
        )
        sib = psel - built
        blb = bl[:, None, None, None]
        return jnp.stack(
            [jnp.where(blb, built, sib), jnp.where(blb, sib, built)], axis=1
        ).reshape(n_pad, *built.shape[1:]), steps

    depth = 0
    sat_iters = jnp.int32(0)  # executed saturated-region levels (0 if none)
    hist_steps = jnp.int32(0)  # grid steps of the grouped histogram levels
    while depth <= max_depth:
        n_pad = min(1 << depth, node_cap)
        n_pad_next = min(2 * n_pad, node_cap)
        force_leaf = depth == max_depth
        builds_hist = not (force_leaf and subtract and pair_info is not None)
        if order is None and builds_hist:
            ordered = row_order_in_jit(
                bins_h, nid, (w, wy, wh), built_nodes(depth), n_bins,
                carry=(nid, preds) + ((bins_u8,) if efb_expand else ()))
            if ordered is not None:
                order, (nid, preds, *codes) = ordered
                bins_u8 = codes[0] if codes else order_codes_in_jit(
                    order, bins_u8.shape[1])

        if depth == sat_start:
            # ---- saturated run: ONE compiled body, on-device early exit ----
            if subtract and parent_hist.shape[0] < node_cap:
                # first iteration's parent frontier may be node_cap/2 wide;
                # zero-pad so the carry shape is loop-invariant (the pad rows
                # are gated off by pair_info["valid"])
                parent_hist = jnp.pad(
                    parent_hist,
                    ((0, node_cap - parent_hist.shape[0]),) + ((0, 0),) * 3,
                )
            zf = jnp.zeros((n_sat, node_cap), jnp.float32)
            zi = jnp.zeros((n_sat, node_cap), jnp.int32)
            zb = jnp.zeros((n_sat, node_cap), bool)
            bufs = {
                "node_w": zf, "split_col": zi, "split_bin": zi,
                "is_cat": zb, "cat_mask": jnp.zeros((n_sat, node_cap, n_bins), bool),
                "na_left": zb, "leaf_now": jnp.ones((n_sat, node_cap), bool),
                "leaf_val": zf, "child_base": zi, "gain": zf,
                "col_offer": jnp.zeros(
                    (n_sat, node_cap, bins_u8.shape[1]), bool),
            }

            def sat_cond(carry):
                return (carry[0] < n_sat) & (carry[4] > 0)

            def sat_body(carry):
                (i, nid_c, preds_c, vi_c, _, phist, pinfo, bufs_c,
                 steps_c) = carry[:9]
                bgt_c = carry[9] if max_leaves else None
                d = sat_start + i
                lkey = jax.random.fold_in(tkey, d)
                hist, steps = level_hist(sat_start, nid_c, pinfo, phist)
                out = _level_core(
                    hist, bins_u8, nid_c, preds_c, vi_c, lkey, cols_enabled,
                    is_cat, min_rows, min_split_improvement, learn_rate,
                    max_abs_leaf, col_sample_rate, leaf_reg,
                    n_pad=node_cap, n_pad_next=node_cap, cat_cols=cat_cols,
                    n_cols_real=n_cols_real, split_shard=split_shard,
                    leaf_budget=bgt_c,
                )
                nid_c, preds_c, vi_c, nsp, rec, pinfo = out[:6]
                bufs_c = {k: bufs_c[k].at[i].set(rec[k]) for k in bufs_c}
                # direct mode threads a fixed dummy parent carry instead
                base = (i + 1, nid_c, preds_c, vi_c, nsp,
                        hist if subtract else phist, pinfo, bufs_c,
                        steps_c + steps)
                return base + ((out[-1],) if max_leaves else ())

            if not subtract:
                # the direct scheme needs no parent-histogram/pair carry;
                # thread dummies of fixed shape so one body serves both
                parent_hist = jnp.zeros((node_cap, 1, 1, 1), jnp.float32)
                pair_info = pair_info or {}
            from h2o3_tpu.ops.collectives import tally_group

            # the saturated body traces ONCE but executes a data-dependent
            # number of times (on-device early exit): its tally entries are
            # tagged and scaled at DISPATCH time by the executed iteration
            # count returned below (_run_counted), so the byte counters
            # report actual volume, not the n_sat upper bound
            carry0 = (jnp.int32(0), nid, preds, varimp, n_split, parent_hist,
                      pair_info, bufs, hist_steps)
            if max_leaves:
                carry0 = carry0 + (leaf_budget,)
            with tally_group("sat"):
                out = jax.lax.while_loop(sat_cond, sat_body, carry0)
            (sat_iters, nid, preds, varimp, n_split, parent_hist,
             pair_info, bufs, hist_steps) = out[:9]
            if max_leaves:
                leaf_budget = out[-1]
            for j in range(n_sat):
                recs.append({k: bufs[k][j] for k in bufs})
            depth = max_depth
            continue

        lkey = jax.random.fold_in(tkey, depth)

        if force_leaf and subtract and pair_info is not None:
            # leaf stats straight from the parents' chosen splits
            node_stats = jnp.stack(
                [pair_info["Lst"], pair_info["Rst"]], axis=1
            ).reshape(n_pad, 3)
            nid, preds, varimp, _, rec = _force_leaf_from_stats(
                bins_u8, nid, preds, varimp,
                node_stats[:, 0], node_stats[:, 1], node_stats[:, 2],
                learn_rate, max_abs_leaf, n_pad, n_bins, leaf_reg,
            )
            recs.append(rec)
            break

        hist, steps = level_hist(depth, nid, pair_info, parent_hist)
        hist_steps = hist_steps + steps

        if force_leaf:
            tot = hist[:, 0, :, :].sum(axis=1)
            nid, preds, varimp, _, rec = _force_leaf_from_stats(
                bins_u8, nid, preds, varimp, tot[:, 0], tot[:, 1], tot[:, 2],
                learn_rate, max_abs_leaf, n_pad, n_bins, leaf_reg,
            )
        else:
            out = _level_core(
                hist, bins_u8, nid, preds, varimp, lkey, cols_enabled, is_cat,
                min_rows, min_split_improvement, learn_rate, max_abs_leaf,
                col_sample_rate, leaf_reg, n_pad=n_pad, n_pad_next=n_pad_next,
                cat_cols=cat_cols, n_cols_real=n_cols_real,
                split_shard=split_shard, leaf_budget=leaf_budget,
            )
            nid, preds, varimp, n_split, rec, pair_info = out[:6]
            if max_leaves:
                leaf_budget = out[-1]
            parent_hist = hist
        recs.append(rec)
        depth += 1
    if order is not None:
        nid, preds = restore_rows_in_jit(order, (nid, preds), n_rows)
    return nid, preds, varimp, tuple(recs), jnp.stack([sat_iters, hist_steps])


def _subtract_enabled() -> bool:
    from h2o3_tpu import config

    return config.get_bool("H2O3_TPU_HIST_SUBTRACT")


def use_fused_trees(max_depth: int) -> bool:
    """Single policy for every fused/scanned-tree selector (build_tree, GBM
    and DRF scan paths): the device-resident whole-tree program on EVERY
    backend up to H2O3_TPU_FUSED_MAX_DEPTH. One dispatch per tree beats
    per-level dispatch gaps everywhere (host dispatch overhead × levels ×
    trees, and a device left idle between levels), and the
    saturated-level ``lax.while_loop`` (see :func:`_fused_levels`) keeps the
    compile bounded at any depth — deep levels compile ONE body and early-
    exit on device. ``H2O3_TPU_WHOLE_TREE=0`` restores the host-driven
    per-level dispatch loop (debug/bisect escape hatch)."""
    from h2o3_tpu import config

    return (
        config.get_bool("H2O3_TPU_WHOLE_TREE")
        and max_depth <= config.get_int("H2O3_TPU_FUSED_MAX_DEPTH")
    )


# ---------------------------------------------------------------------------
# GOSS — gradient-based one-side sampling (ISSUE 16, after arXiv:1706.08359):
# keep the top-a fraction of rows by |gradient| exactly, sample a b fraction
# of the rest uniformly, and amplify the sampled rest by (1-a)/b so the
# histogram stat sums stay unbiased. Rows drop out the same way sample_rate
# rows do — weight 0 — so every downstream lane (hists, partition, streamed
# blocks, the 2-D mesh row axis) composes with no new code paths.


def bootstrap_mask(row_key, tree_index, sample_rate: float, shape):
    """The rows in tree ``tree_index``'s bag: a Bernoulli(``sample_rate``)
    draw a row (``sample_rate`` without replacement), keyed by the builder's
    row key and the tree's global index alone. The one function behind every
    resident build's row sample — the chunk program calls it traced, DRF's
    per-tree loop and ``DRFModel.inbag_rows`` call it directly — so a fitted
    model can say which rows each of its trees saw."""
    return jax.random.bernoulli(
        jax.random.fold_in(jax.random.fold_in(row_key, tree_index), 1 << 29),
        sample_rate, shape)


def _goss_ab() -> tuple[float, float] | None:
    """Parse ``H2O3_TPU_TREE_GOSS='a,b'``; None (knob empty) = GOSS off."""
    from h2o3_tpu import config

    raw = config.get("H2O3_TPU_TREE_GOSS").strip()
    if not raw:
        return None
    try:
        a_s, b_s = raw.split(",")
        a, b = float(a_s), float(b_s)
    except ValueError:
        raise ValueError(
            f"H2O3_TPU_TREE_GOSS must be 'a,b' (two floats), got {raw!r}"
        ) from None
    if not (0.0 <= a < 1.0):
        raise ValueError(f"GOSS top fraction a must be in [0, 1), got {a}")
    if not (0.0 < b <= 1.0 - a):
        raise ValueError(f"GOSS rest fraction b must be in (0, 1-a], got {b}")
    return a, b


def _goss_factor(w_tree, wy, gkey, a: float, b: float):
    """Traced per-row GOSS factor: 1 for the top-a rows by |weighted
    gradient|, (1-a)/b for the kept b-sample of the rest, 0 otherwise.

    The top set is selected by a rank-k threshold over the VALID rows
    (``w_tree > 0`` — bootstrap/sample_rate dropouts and row padding never
    count toward the top fraction), with ties at the threshold all kept
    (the cheap, deterministic resolution — the set can exceed a·n by the
    tie count). ``a == 0`` degrades to plain amplified row sampling at
    rate ``b``."""
    valid = w_tree > 0
    gmag = jnp.where(valid, jnp.abs(wy), -jnp.inf)
    n_valid = valid.sum()
    k = jnp.round(a * n_valid).astype(jnp.int32)
    srt = jnp.sort(gmag)[::-1]  # descending; invalid (-inf) rows sort last
    thr = srt[jnp.maximum(k - 1, 0)]
    top = valid & (gmag >= thr) & (k > 0)
    rest = valid & ~top
    keep_rest = rest & jax.random.bernoulli(gkey, b / (1.0 - a), w_tree.shape)
    amp = jnp.float32((1.0 - a) / b)
    return jnp.where(
        top, 1.0, jnp.where(keep_rest, amp, 0.0)
    ).astype(w_tree.dtype)


# ---------------------------------------------------------------------------
# monotone-constraint variant of the level step (GBM monotone_constraints).
# Kept separate so the unconstrained hot path compiles byte-identical; used
# only via build_tree's per-level loop when constraints are present.


def _level_step_mono_fn(
    bins_u8, nid, preds, varimp, w, wy, wh, key, cols_enabled, is_cat,
    min_rows, min_split_improvement, learn_rate, max_abs_leaf, col_sample_rate,
    mono, node_lo, node_hi, leaf_reg=None,
    *, n_pad: int, n_pad_next: int, n_bins: int, force_leaf: bool,
    cat_cols: tuple = (), split_shard: bool = False,
):
    """Monotone variant of _level_step_fn: leaf values clamp to the node's
    [lo, hi] bounds; children of a constrained split get tightened bounds."""
    from h2o3_tpu.ops.histogram import histogram_in_jit

    hist = histogram_in_jit(
        bins_u8, nid, (w, wy, wh), n_pad, n_bins, col_sharded=split_shard
    )

    if force_leaf:
        tot = hist[:, 0, :, :].sum(axis=1)
        node_w, node_wy, node_wh = tot[:, 0], tot[:, 1], tot[:, 2]
        ok = jnp.zeros(n_pad, bool)
        gain = jnp.zeros(n_pad, jnp.float32)
        split_col = jnp.zeros(n_pad, jnp.int32)
        split_bin = jnp.zeros(n_pad, jnp.int32)
        is_cat_n = jnp.zeros(n_pad, bool)
        cat_mask = jnp.zeros((n_pad, n_bins), bool)
        na_left = jnp.zeros(n_pad, bool)
        mid = jnp.zeros(n_pad, jnp.float32)
        mono_col = jnp.zeros(n_pad, jnp.int32)
    else:
        col_mask = _offered_columns(key, cols_enabled, col_sample_rate, n_pad)
        if split_shard:
            sp = _split_scan_sharded(
                hist, is_cat, col_mask, min_rows, min_split_improvement,
                any_cat=bool(cat_cols),
                mono=mono, node_lo=node_lo, node_hi=node_hi,
            )
        else:
            sp = _split_scan(
                hist, is_cat, col_mask, min_rows, min_split_improvement,
                cat_cols, mono=mono, node_lo=node_lo, node_hi=node_hi,
            )
        ok = sp["ok"]
        fits = 2 * jnp.cumsum(ok.astype(jnp.int32)) <= n_pad_next
        ok = ok & fits
        gain = jnp.where(ok, jnp.maximum(sp["gain"], 0.0), 0.0)
        node_w, node_wy, node_wh = sp["node_w"], sp["node_wy"], sp["node_wh"]
        split_col, split_bin = sp["col"], sp["split_bin"]
        is_cat_n, cat_mask, na_left = sp["is_cat"], sp["cat_mask"], sp["na_left"]
        mid, mono_col = sp["mid"], sp["mono_col"]

    rl, ra = (None, None) if leaf_reg is None else leaf_reg
    col_offer = None if force_leaf else col_mask
    nid, preds, varimp, n_split, record, cs = _finish_level(
        bins_u8, nid, preds, varimp, ok, gain, node_w, node_wy, node_wh,
        split_col, split_bin, is_cat_n, cat_mask, na_left,
        learn_rate, max_abs_leaf, n_pad, node_lo=node_lo, node_hi=node_hi,
        reg_lambda=rl, reg_alpha=ra,
        any_cat=bool(cat_cols) and not force_leaf, col_offer=col_offer,
    )
    # child bounds scatter: left child at child_base, right at child_base+1
    new_lo, new_hi = _child_bounds(
        ok, record["child_base"], mono_col, mid, node_lo, node_hi, n_pad_next
    )
    return nid, preds, varimp, n_split, record, new_lo, new_hi


def _mesh_key():
    """Program-cache component for the process mesh: the traced collectives
    (and the sharded split's block layout) bake the mesh in at trace time,
    so a program compiled for one mesh must never serve another (tests swap
    sub-meshes of different sizes within one process)."""
    from h2o3_tpu.parallel.mesh import mesh_key

    return mesh_key()


def _level_step_mono(n_pad, n_pad_next, n_bins, force_leaf, cat_cols=(),
                     split_shard=False):
    # _kernel_key: the Pallas tile/override knobs change the traced
    # histogram kernel
    key = ("mono", n_pad, n_pad_next, n_bins, force_leaf, cat_cols,
           split_shard, _kernel_key(), _mesh_key(), jax.default_backend())
    fn = _STEP_CACHE.get(key)
    if fn is None:
        fn = jax.jit(
            partial(
                _level_step_mono_fn,
                n_pad=n_pad, n_pad_next=n_pad_next, n_bins=n_bins,
                force_leaf=force_leaf, cat_cols=cat_cols,
                split_shard=split_shard,
            )
        )
        _STEP_CACHE[key] = fn
    _PROG_KEY[id(fn)] = key
    return fn


_STEP_CACHE: dict = {}


def _level_step(
    n_pad: int, n_pad_next: int, n_bins: int, force_leaf: bool,
    cat_cols: tuple = (), split_shard: bool = False,
):
    key = (n_pad, n_pad_next, n_bins, force_leaf, cat_cols, split_shard,
           _kernel_key(), _mesh_key(), jax.default_backend())
    fn = _STEP_CACHE.get(key)
    if fn is None:
        fn = jax.jit(
            partial(
                _level_step_fn,
                n_pad=n_pad, n_pad_next=n_pad_next,
                n_bins=n_bins, force_leaf=force_leaf, cat_cols=cat_cols,
                split_shard=split_shard,
            )
        )
        _STEP_CACHE[key] = fn
    _PROG_KEY[id(fn)] = key
    return fn


def _clamp_node_cap(node_cap: int, npad: int, min_rows) -> int:
    """node_cap can't usefully exceed the next power of two ≥ the row count:
    with min_rows ≥ 1 a split needs two rows, so the live frontier is bounded
    by the rows and every slot past that bound is provably-dead padding the
    fused program would still trace and execute. Capping it keeps small-frame
    whole-tree programs (tests, AutoML folds) proportionate. The split chain
    is unchanged by construction; only the RNG-draw width at depths past the
    clamped cap differs from an uncapped build."""
    if float(min_rows) < 1.0:
        return node_cap
    cap_rows = 1 << max(1, int(npad - 1).bit_length())
    return max(2, min(node_cap, cap_rows))


def _tree_program(
    max_depth: int, n_bins: int, node_cap: int, cat_cols: tuple,
    n_cols_real: int | None = None, n_cols_pad: int | None = None,
    max_leaves: int = 0, efb=None,
):
    """One jitted program building a WHOLE tree (growth levels unrolled, the
    saturated run as a lax.while_loop — see :func:`_fused_levels`).

    Per-level dispatch leaves the device idle between levels while the
    host pulls the split records and launches the next program; one
    dispatch per tree removes those gaps. ``preds``/``varimp`` are DONATED: tree t+1's dispatch
    reuses tree t's output buffers in place, so nothing is copied and no
    host sync sits between pipelined trees. ``n_cols_pad`` (shape bucketing)
    pads the column axis INSIDE the program — callers pass real-width arrays
    and get a real-width varimp back.
    """
    subtract = _subtract_enabled()
    # EFB rides the replicated lane only: the bundled C axis is too small
    # to shard profitably, and the replicated scan is decision-equal to the
    # sharded one by construction
    split_shard = efb is None and _split_shard_on()
    key = ("tree", max_depth, n_bins, node_cap, cat_cols, subtract,
           n_cols_real, n_cols_pad, split_shard,
           int(max_leaves), None if efb is None else efb.key,
           _kernel_key(), _mesh_key(), jax.default_backend())

    def make():
        def whole_tree(
            bins_u8, preds, varimp, w, wy, wh, key_, cols_enabled, is_cat,
            min_rows, min_split_improvement, learn_rate, max_abs_leaf,
            col_sample_rate, leaf_reg=None, bins_b=None,
        ):
            C = bins_u8.shape[1]
            Cp = n_cols_pad or C
            if Cp > C:  # bucketed column pad: code 0 (NA), masked everywhere
                bins_u8 = jnp.pad(bins_u8, ((0, 0), (0, Cp - C)))
                is_cat = jnp.pad(is_cat, (0, Cp - C))
                varimp = jnp.pad(varimp, (0, Cp - C))
                cols_enabled = jnp.pad(cols_enabled, (0, Cp - C))
            nid, preds_, varimp_, records, counts = _fused_levels(
                bins_u8, preds, varimp, w, wy, wh, key_, cols_enabled, is_cat,
                min_rows, min_split_improvement, learn_rate, max_abs_leaf,
                col_sample_rate, leaf_reg,
                max_depth=max_depth, n_bins=n_bins, node_cap=node_cap,
                cat_cols=cat_cols, subtract=subtract, n_cols_real=n_cols_real,
                split_shard=split_shard,
                max_leaves=max_leaves, efb=efb, bins_b=bins_b,
            )
            return nid, preds_, varimp_[:C], records, counts

        return jax.jit(whole_tree, donate_argnums=(1, 2))

    return _cached_program(key, make)


def build_trees_scanned(
    bins_u8,
    w,
    y,
    preds,
    varimp,
    base_key,
    n_trees: int,
    *,
    row_key=None,
    tree_offset: int = 0,
    grad_fn,
    grad_key,
    sample_rate: float,
    n_bins: int,
    is_cat_cols,
    max_depth: int,
    min_rows: float,
    min_split_improvement: float,
    learn_rates,
    max_abs_leaf: float,
    col_sample_rate: float,
    col_sample_rate_per_tree: float,
    node_cap: int = 2048,
    reg_lambda: float = 0.0,
    reg_alpha: float = 0.0,
    max_leaves: int = 0,
    efb=None,
    bins_b=None,
):
    """Build ``n_trees`` trees in ONE device dispatch (lax.scan over trees).

    One dispatch and one record pull per scoring interval instead of one
    per tree: fewer dispatches, fewer host syncs.

    ``grad_fn(F, y, w_tree) -> (t, h)`` supplies per-tree pseudo-residuals
    and hessians (distribution-specific, traced); ``grad_key`` is a hashable
    cache token identifying it. ``learn_rates`` is a host array of length
    ``n_trees`` (annealing). ``row_key`` (defaults to ``base_key``) seeds the
    per-tree row bootstrap separately so DRF's K class-trees can share one
    bootstrap while drawing distinct column/level randomness. ``tree_offset``
    is the global index of the chunk's first tree, keeping per-tree key
    folds stable across chunk boundaries. Returns ``(preds, varimp,
    stacked)`` where ``stacked`` is a tuple over levels of record dicts with
    a leading ``n_trees`` axis — convert with :func:`trees_from_stacked`.
    """
    from h2o3_tpu.models.tree.binning import bucket_cols, bucket_nbins

    C = bins_u8.shape[1]
    Cp = bucket_cols(C)  # shape-bucketed column padding (inert, see binning)
    n_bins = bucket_nbins(n_bins)  # padded bins are empty → argmax-inert
    node_cap = _clamp_node_cap(node_cap, bins_u8.shape[0], min_rows)
    is_cat_np = np.asarray(is_cat_cols, bool)
    cat_cols = tuple(int(i) for i in np.nonzero(is_cat_np)[0])
    is_cat_dev = jnp.asarray(is_cat_np)

    subtract = _subtract_enabled()
    split_shard = efb is None and _split_shard_on()  # EFB: replicated only
    goss = _goss_ab()
    # the float rates are baked into the traced closure, so they MUST be part
    # of the cache key (a boolean would silently reuse another model's rates);
    # C (the real column count) likewise — it sizes the traced RNG draws;
    # goss (a, b floats) and the EFB plan fingerprint bake in the same way
    key = (
        "scan", n_trees, max_depth, n_bins, node_cap, cat_cols, grad_key, C,
        float(sample_rate), float(col_sample_rate_per_tree), subtract,
        split_shard, goss,
        int(max_leaves), None if efb is None else efb.key, _kernel_key(),
        _mesh_key(), jax.default_backend(),
    )

    def make():
        def whole_chunk(
            bins_u8, w, y, preds, varimp, base_key, row_key_, offset, lrs, is_cat,
            min_rows_, msi_, max_abs_leaf_, col_rate_, leaf_reg_,
            bins_b=None,
        ):
            if Cp > C:  # bucketed column pad: code 0 (NA) everywhere, masked
                bins_u8 = jnp.pad(bins_u8, ((0, 0), (0, Cp - C)))
                is_cat = jnp.pad(is_cat, (0, Cp - C))
                varimp = jnp.pad(varimp, (0, Cp - C))

            def body(carry, per_tree):
                F, vi = carry
                i, lr = per_tree
                m = i + offset
                tkey = jax.random.fold_in(base_key, m)
                if sample_rate < 1.0:
                    mask = bootstrap_mask(row_key_, m, sample_rate, w.shape)
                    w_tree = w * mask.astype(w.dtype)
                else:
                    w_tree = w
                # ph_grad: phase tag for tools/profile_fused.py
                with jax.named_scope("ph_grad"):
                    t, h = grad_fn(F, y, w_tree)
                    wy = w_tree * t
                    wh = jnp.where(w_tree > 0, h, 0.0)
                if goss is not None:
                    gf = _goss_factor(
                        w_tree, wy, jax.random.fold_in(tkey, 1 << 28), *goss
                    )
                    w_tree = w_tree * gf
                    wy = wy * gf
                    wh = wh * gf
                # the per-tree column draw runs at the REAL column count C,
                # so bucketed padding cannot perturb the sampled columns
                if col_sample_rate_per_tree < 1.0:
                    keep = (
                        jax.random.uniform(jax.random.fold_in(tkey, 1 << 30), (C,))
                        < col_sample_rate_per_tree
                    )
                    keep = jnp.where(keep.any(), keep, True)
                    cols_enabled = keep.astype(jnp.float32)
                else:
                    cols_enabled = jnp.ones(C, jnp.float32)
                if Cp > C:
                    cols_enabled = jnp.pad(cols_enabled, (0, Cp - C))

                _, F, vi, recs, counts = _fused_levels(
                    bins_u8, F, vi, w_tree, wy, wh, tkey, cols_enabled,
                    is_cat, min_rows_, msi_, lr, max_abs_leaf_, col_rate_,
                    leaf_reg_,
                    max_depth=max_depth, n_bins=n_bins, node_cap=node_cap,
                    cat_cols=cat_cols, subtract=subtract, n_cols_real=C,
                    split_shard=split_shard,
                    max_leaves=max_leaves, efb=efb, bins_b=bins_b,
                )
                return (F, vi), (recs, counts)

            (preds, varimp), (stacked, counts_per_tree) = jax.lax.scan(
                body, (preds, varimp), (jnp.arange(n_trees), lrs)
            )
            # total executed saturated-region levels across the chunk's
            # trees — the dispatch-time weight for the sat byte tallies —
            # and their grouped histogram steps
            return preds, varimp[:C], stacked, counts_per_tree.sum(axis=0)

        # preds/varimp donated: chunk t+1 reuses chunk t's output buffers in
        # place — the running prediction never copies between dispatches
        return jax.jit(whole_chunk, donate_argnums=(3, 4))

    prog = _cached_program(key, make)

    lrs = jnp.asarray(np.asarray(learn_rates, np.float32))
    leaf_reg = (
        None
        if reg_lambda == 0.0 and reg_alpha == 0.0
        else (jnp.float32(reg_lambda), jnp.float32(reg_alpha))
    )
    BUILD_STATS["dispatches"] += 1
    BUILD_STATS["trees_built"] += n_trees
    # the scan body traces once but runs once per tree: mult=n_trees; the
    # saturated-region tallies instead scale by the chunk's total EXECUTED
    # sat levels, returned as the program's last output
    if goss is not None:
        # modeled expected kept-row volume, same convention as the HBM
        # byte tallies (host-side: the factor never leaves the program)
        _ROWS_SAMPLED.inc((goss[0] + goss[1]) * bins_u8.shape[0] * n_trees)
    if efb is not None:
        _COLS_BUNDLED.inc(C - efb.n_cols_b)
    out = _run_counted(
        prog,
        (
            bins_u8, w, y, preds, varimp, base_key,
            base_key if row_key is None else row_key,
            jnp.int32(tree_offset), lrs, is_cat_dev,
            jnp.float32(min_rows), jnp.float32(min_split_improvement),
            jnp.float32(max_abs_leaf), jnp.float32(col_sample_rate), leaf_reg,
            bins_b,
        ),
        mult=n_trees,
        counts_from=lambda o: o[3],
    )
    return out[:3]


def scan_chunk_cap(
    max_depth: int, n_bins: int, node_cap: int = 2048, budget_bytes: int = 256 << 20
) -> int:
    """Max trees per scanned dispatch so stacked records fit the budget
    (cat_mask (T, N, B) dominates; deep DRF trees are ~6 MB each)."""
    per_tree = 0
    for depth in range(max_depth + 1):
        n = min(1 << depth, node_cap)
        per_tree += n * (n_bins + 40)
    return max(1, int(budget_bytes // max(per_tree, 1)))


# Record fields in pack order. The whole stacked chunk flattens into ONE
# uint8 buffer = ONE device→host transfer: a naive device_get(stacked) pulls
# ~70 leaves, each its own host round-trip, which made record download cost
# more than building the trees. f32/i32 fields are bitcast to 4
# uint8 lanes (exact, any magnitude); bools ship as 1 byte each, so the
# payload stays byte-sized for cat_mask — the dominant field.
_PACK_I32 = ("split_col", "split_bin", "child_base")
_PACK_BOOL = ("is_cat", "na_left", "leaf_now", "cat_mask", "col_offer")
_PACK_F32 = ("node_w", "leaf_val", "gain")
_PACK_FIELDS = _PACK_F32 + _PACK_I32 + _PACK_BOOL


@jax.jit
def _pack_stacked(stacked):
    parts = []
    for lvl in stacked:
        assert set(lvl) == set(_PACK_FIELDS), sorted(set(lvl) ^ set(_PACK_FIELDS))
        T = lvl["node_w"].shape[0]
        for k in _PACK_FIELDS:
            v = lvl[k]
            if k in _PACK_BOOL:
                parts.append(v.astype(jnp.uint8).reshape(T, -1))
            else:
                parts.append(jax.lax.bitcast_convert_type(v, jnp.uint8).reshape(T, -1))
    return jnp.concatenate(parts, axis=1)


def trees_from_stacked(stacked, n_trees: int) -> list["Tree"]:
    """ONE device→host transfer for a whole chunk → numpy-backed Trees."""
    packed = np.asarray(jax.device_get(_pack_stacked(stacked)))  # (T, X) u8
    out = [Tree() for _ in range(n_trees)]
    off = 0
    for lvl in stacked:
        fields = {}
        for k in _PACK_FIELDS:
            shape = lvl[k].shape[1:]  # per-tree shape
            size = int(np.prod(shape)) if shape else 1
            nbytes = size if k in _PACK_BOOL else size * 4
            # contiguous per-field copy: the view below then holds only this
            # field's bytes, not the whole chunk buffer
            raw = np.ascontiguousarray(packed[:, off : off + nbytes])
            if k in _PACK_BOOL:
                v = raw.view(np.bool_).reshape(n_trees, *shape)
            elif k in _PACK_I32:
                v = raw.view(np.int32).reshape(n_trees, *shape)
            else:
                v = raw.view(np.float32).reshape(n_trees, *shape)
            fields[k] = v
            off += nbytes
        for ti in range(n_trees):
            out[ti].levels.append(TreeLevel(**{k: v[ti] for k, v in fields.items()}))
    return out


def replay_batch(bins_u8, stacked, preds):
    """Replay a whole stacked chunk of trees in ONE dispatch.

    ``stacked`` is the (device or host) tuple-over-levels of record dicts
    with leading tree axis, as returned by :func:`build_trees_scanned`.
    """
    n_levels = len(stacked)
    key = ("replay", n_levels, jax.default_backend())
    prog = _STEP_CACHE.get(key)
    if prog is None:

        def run(bins_u8, stacked, preds):
            def body(preds, tree_recs):
                nid = jnp.zeros(bins_u8.shape[0], jnp.int32)
                for rec in tree_recs:
                    nid, preds = _partition_update(
                        bins_u8, nid, preds, rec["split_col"], rec["split_bin"],
                        rec["is_cat"], rec["cat_mask"], rec["na_left"],
                        rec["leaf_now"], rec["leaf_val"], rec["child_base"],
                    )
                return preds, None

            preds, _ = jax.lax.scan(body, preds, stacked)
            return preds

        # preds donated: score-keeper replays pipeline behind the next
        # chunk's build without copying the running prediction
        prog = jax.jit(run, donate_argnums=(2,))
        _STEP_CACHE[key] = prog
    if n_levels:
        count_partition_levels(n_levels * stacked[0]["split_col"].shape[0])
    return prog(bins_u8, stacked, preds)


# ---------------------------------------------------------------------------
# recorded tree (for prediction replay; fields are DEVICE arrays)


@dataclass
class TreeLevel:
    split_col: jnp.ndarray
    split_bin: jnp.ndarray
    is_cat: jnp.ndarray
    cat_mask: jnp.ndarray
    na_left: jnp.ndarray
    leaf_now: jnp.ndarray
    leaf_val: jnp.ndarray
    child_base: jnp.ndarray
    gain: jnp.ndarray | None = None  # per-node split gain (varimp source)
    node_w: jnp.ndarray | None = None  # per-node weighted cover (TreeSHAP)
    # (n_pad, C) bool: the columns each node was offered (mtries /
    # col_sample_rate per split; all False on a level that scanned nothing)
    col_offer: jnp.ndarray | None = None


@dataclass
class Tree:
    levels: list[TreeLevel] = field(default_factory=list)

    def real_level_masks(self) -> list[np.ndarray]:
        """Boolean mask of REAL node slots per level, derived exactly from
        the split chain: level 0 has one real node; level i+1 has
        2 * (# real non-leaf nodes at level i) real slots (children are
        compacted to the front by child_base). Padding slots carry
        leaf_now=True with zero stats and must not count as leaves."""
        host = self.to_host() if any(
            not isinstance(lv.leaf_now, np.ndarray) for lv in self.levels
        ) else self
        masks = []
        n_real = 1
        for lv in host.levels:
            width = len(lv.leaf_now)
            m = np.arange(width) < n_real
            masks.append(m)
            n_real = 2 * int(np.sum(~lv.leaf_now & m))
        return masks

    @property
    def n_leaves(self) -> int:
        host = self.to_host() if any(
            not isinstance(lv.leaf_now, np.ndarray) for lv in self.levels
        ) else self
        return int(sum(
            int(np.sum(lv.leaf_now & m))
            for lv, m in zip(host.levels, host.real_level_masks())
        ))

    @property
    def depth(self) -> int:
        """Depth of the deepest REAL node (the recorded level count can
        exceed it when every branch retired early)."""
        host = self.to_host() if any(
            not isinstance(lv.leaf_now, np.ndarray) for lv in self.levels
        ) else self
        d = 0
        for li, m in enumerate(host.real_level_masks()):
            if m.any():
                d = li
        return d

    def replay(self, bins_u8, nid, preds):
        """Accumulate this tree's contribution into preds (device walk)."""
        for lv in self.levels:
            nid, preds = _partition_update(
                bins_u8, nid, preds,
                lv.split_col, lv.split_bin, lv.is_cat, lv.cat_mask,
                lv.na_left, lv.leaf_now, lv.leaf_val, lv.child_base,
            )
        return nid, preds

    def to_host(self) -> "Tree":
        """Pull every level to numpy (for export/inspection paths)."""
        out = Tree()
        import dataclasses as _dc

        fields = tuple(f.name for f in _dc.fields(TreeLevel))
        pulled = jax.device_get([[getattr(lv, f) for f in fields] for lv in self.levels])
        for vals in pulled:
            out.levels.append(TreeLevel(*[np.asarray(v) for v in vals]))
        return out


# ---------------------------------------------------------------------------
# the level-wise builder


def build_tree(
    bins_u8,
    w,
    t,
    h,
    *,
    n_bins: int,
    is_cat_cols,
    max_depth: int,
    min_rows: float,
    min_split_improvement: float,
    learn_rate: float,
    preds,
    key,
    varimp,
    col_sample_rate: float = 1.0,
    col_sample_rate_per_tree: float = 1.0,
    cols_enabled=None,
    max_abs_leaf: float = np.inf,
    node_cap: int = 2048,
    monotone=None,  # (C,) int {-1,0,1} per-column constraint directions
    reg_lambda: float = 0.0,
    reg_alpha: float = 0.0,
    max_leaves: int = 0,
    efb=None,
    bins_b=None,
):
    """Build one tree without any host↔device traffic in the level loop.

    Inputs are row-sharded device arrays: ``bins_u8`` (npad,C), per-row
    weight ``w`` (0 = out of this tree), target ``t`` (residual), hessian
    ``h``; ``key`` a jax PRNG key (column sampling), ``varimp`` a device (C,)
    accumulator. Returns ``(Tree, preds, varimp)`` — all device-resident.

    ALL rows walk the tree (sampled-out rows contribute nothing to hists via
    w=0, but must still receive leaf predictions — GBM's next-iteration
    gradients depend on F for every row).
    """
    from h2o3_tpu.models.tree.binning import bucket_cols, bucket_nbins

    C = bins_u8.shape[1]
    Cp = bucket_cols(C)  # shape-bucketed column padding (inert, see binning)
    n_bins = bucket_nbins(n_bins)  # padded bins are empty → argmax-inert
    node_cap = _clamp_node_cap(node_cap, bins_u8.shape[0], min_rows)
    is_cat_dev = jnp.asarray(np.asarray(is_cat_cols, bool))
    wy = w * t
    wh = jnp.where(w > 0, h, 0.0)  # sampled-out rows carry no hessian either
    goss = _goss_ab()
    if goss is not None:
        # GOSS composes with every build lane from here: the factor folds
        # into the row weights before any histogram sees them
        gf = _goss_factor(w, wy, jax.random.fold_in(key, 1 << 28), *goss)
        w = w * gf
        wy = wy * gf
        wh = wh * gf
        _ROWS_SAMPLED.inc((goss[0] + goss[1]) * w.shape[0])
    if efb is not None:
        _COLS_BUNDLED.inc(C - efb.n_cols_b)
    if cols_enabled is not None:
        cols_enabled_dev = jnp.asarray(np.asarray(cols_enabled, np.float32))
    elif col_sample_rate_per_tree < 1.0:
        # per-tree column subsample drawn on device (no host rng → no upload)
        keep = jax.random.uniform(jax.random.fold_in(key, 1 << 30), (C,)) < col_sample_rate_per_tree
        keep = jnp.where(keep.any(), keep, True)
        cols_enabled_dev = keep.astype(jnp.float32)
    else:
        cols_enabled_dev = jnp.ones(C, jnp.float32)

    cat_cols = tuple(int(i) for i in np.nonzero(np.asarray(is_cat_cols, bool))[0])
    tree = Tree()
    leaf_reg = (
        None
        if reg_lambda == 0.0 and reg_alpha == 0.0
        else (jnp.float32(reg_lambda), jnp.float32(reg_alpha))
    )

    # Monotone constraints carry per-node [lo, hi] bound state level to
    # level — a separate per-level loop (constrained builds trade the
    # whole-tree dispatch for it; the default path is untouched).
    split_shard = _split_shard_on()
    if monotone is not None and np.any(np.asarray(monotone) != 0):
        mono_dev = jnp.asarray(np.asarray(monotone, np.int32))
        nid = jnp.zeros(bins_u8.shape[0], jnp.int32)
        node_lo = jnp.full(1, -jnp.inf, jnp.float32)
        node_hi = jnp.full(1, jnp.inf, jnp.float32)
        for depth in range(max_depth + 1):
            n_pad = min(1 << depth, node_cap)
            n_pad_next = min(2 * n_pad, node_cap)
            force_leaf = depth == max_depth
            step = _level_step_mono(
                n_pad, n_pad_next, n_bins, force_leaf, cat_cols, split_shard
            )
            lkey = jax.random.fold_in(key, depth)
            BUILD_STATS["dispatches"] += 1
            nid, preds, varimp, n_split, rec, node_lo, node_hi = _run_counted(
                step,
                (
                    bins_u8, nid, preds, varimp, w, wy, wh, lkey,
                    cols_enabled_dev, is_cat_dev,
                    jnp.float32(min_rows), jnp.float32(min_split_improvement),
                    jnp.float32(learn_rate), jnp.float32(max_abs_leaf),
                    jnp.float32(col_sample_rate),
                    mono_dev, node_lo, node_hi, leaf_reg,
                ),
            )
            tree.levels.append(TreeLevel(**rec))
            if force_leaf:
                break
            if jax.default_backend() == "cpu" and int(n_split) == 0:
                break
        BUILD_STATS["trees_built"] += 1
        return tree, preds, varimp

    fused = use_fused_trees(max_depth)
    if (max_leaves or efb is not None) and not fused:
        raise ValueError(
            "grow_policy=lossguide / EFB need the fused whole-tree program "
            "(H2O3_TPU_WHOLE_TREE=1 within the fused depth cap)"
        )
    if fused:
        prog = _tree_program(
            max_depth, n_bins, node_cap, cat_cols, n_cols_real=C,
            n_cols_pad=Cp, max_leaves=max_leaves, efb=efb,
        )
        BUILD_STATS["dispatches"] += 1
        BUILD_STATS["trees_built"] += 1
        _, preds, varimp, records, _sat = _run_counted(
            prog,
            (
                bins_u8, preds, varimp, w, wy, wh, key, cols_enabled_dev,
                is_cat_dev,
                jnp.float32(min_rows), jnp.float32(min_split_improvement),
                jnp.float32(learn_rate), jnp.float32(max_abs_leaf),
                jnp.float32(col_sample_rate), leaf_reg, bins_b,
            ),
            counts_from=lambda o: o[4],
        )
        for rec in records:
            tree.levels.append(TreeLevel(**rec))
        return tree, preds, varimp

    nid = jnp.zeros(bins_u8.shape[0], jnp.int32)
    for depth in range(max_depth + 1):
        n_pad = min(1 << depth, node_cap)
        n_pad_next = min(2 * n_pad, node_cap)
        force_leaf = depth == max_depth
        step = _level_step(
            n_pad, n_pad_next, n_bins, force_leaf, cat_cols, split_shard
        )
        lkey = jax.random.fold_in(key, depth)
        BUILD_STATS["dispatches"] += 1
        nid, preds, varimp, n_split, rec = _run_counted(
            step,
            (
                bins_u8, nid, preds, varimp, w, wy, wh, lkey,
                cols_enabled_dev, is_cat_dev,
                jnp.float32(min_rows), jnp.float32(min_split_improvement),
                jnp.float32(learn_rate), jnp.float32(max_abs_leaf),
                jnp.float32(col_sample_rate), leaf_reg,
            ),
        )
        tree.levels.append(TreeLevel(**rec))
        if force_leaf:
            break
        # Early-exit polling trades a blocking device→host pull against
        # dispatching useless empty levels. On a local CPU mesh the pull is
        # ~free, poll every level; past GBM-typical depths poll sparsely.
        if jax.default_backend() == "cpu":
            if int(n_split) == 0:
                break
        elif depth >= 8 and depth % 4 == 0 and int(n_split) == 0:
            break

    BUILD_STATS["trees_built"] += 1
    return tree, preds, varimp


# ---------------------------------------------------------------------------
# out-of-core streamed forest build (ISSUE 11, frame/chunkstore.py): the
# level math as a BLOCK-ACCUMULATE outer loop over a ChunkStore's row
# blocks. Histogram accumulation is associative over row blocks, so one
# level = Σ_blocks histogram_in_jit(block) (the existing fused histogram
# program — incl. its hist_reduce psum and the PR-9 collective lane — runs
# untouched inside each block), then ONE replicated split-scan/decide
# dispatch on the accumulated (n_pad, C, B, S) tensor (node-frontier sized,
# tiny next to the data), then one _partition_update per block. Per-row
# state (running score F, node ids) lives in the store's host tier between
# touches, so the device footprint is the HBM window, not the frame.
# Frames that fit the window never get here (ChunkStore.plan routes them
# to the resident whole-tree programs — bit-parity by construction).


def _stream_hist_prog(n_pad: int, n_bins: int):
    """One block's histogram contribution, accumulated in place: the
    donated ``acc`` buffer pipelines across block dispatches with no
    copies. Dense replicated mode — the streamed decide needs the full
    (n_pad, C, B, S) tensor on every device anyway, and it is bounded by
    the node frontier, not the rows."""
    from h2o3_tpu.ops.histogram import histogram_in_jit

    key = ("stream_hist", n_pad, n_bins, _kernel_key(), _mesh_key(),
           jax.default_backend())

    def make():
        def run(bins_u8, nid, wt, wy, wh, acc):
            return acc + histogram_in_jit(
                bins_u8, nid, (wt, wy, wh), n_pad, n_bins
            )

        return jax.jit(run, donate_argnums=(5,))

    return _cached_program(key, make)


def _stream_decide_prog(n_pad: int, n_pad_next: int, n_bins: int,
                        cat_cols: tuple, force_leaf: bool, n_cols: int,
                        mono: bool = False):
    """Split scan + leaf decision on the block-accumulated histogram —
    ``_level_core``'s math with the partition update factored out (it runs
    per block). Returns ``(varimp, n_split, record)``; with ``mono`` the
    inputs grow (mono_vec, node_lo, node_hi) and the return appends
    ``(new_lo, new_hi)`` — the constraint state is per-NODE, so it rides
    the host level loop untouched by the block structure (the ISSUE-15
    streamed-GBM gate fix)."""
    key = ("stream_decide", n_pad, n_pad_next, n_bins, cat_cols, force_leaf,
           n_cols, bool(mono), _mesh_key(), jax.default_backend())

    def make():
        def run(hist, key_, cols_enabled, is_cat, varimp, min_rows, msi,
                learn_rate, max_abs_leaf, col_sample_rate, leaf_reg=None,
                mono_vec=None, node_lo=None, node_hi=None):
            rl, ra = (None, None) if leaf_reg is None else leaf_reg
            if force_leaf:
                tot = hist[:, 0, :, :].sum(axis=1)  # col 0 ≡ any col
                ok = jnp.zeros(n_pad, bool)
                gain = jnp.zeros(n_pad, jnp.float32)
                zi = jnp.zeros(n_pad, jnp.int32)
                _, _, _, _, n_split, rec = _leaf_decide(
                    ok, gain, tot[:, 0], tot[:, 1], tot[:, 2], zi, zi,
                    jnp.zeros(n_pad, bool),
                    jnp.zeros((n_pad, n_bins), bool),
                    jnp.zeros(n_pad, bool), learn_rate, max_abs_leaf,
                    n_pad, node_lo=node_lo, node_hi=node_hi,
                    reg_lambda=rl, reg_alpha=ra,
                    col_offer=jnp.zeros((n_pad, n_cols), jnp.float32),
                )
                if mono:
                    return (varimp, n_split, rec,
                            jnp.full(n_pad_next, -jnp.inf, jnp.float32),
                            jnp.full(n_pad_next, jnp.inf, jnp.float32))
                return varimp, n_split, rec
            # the streamed path never column-pads: n_cols is the real count
            col_mask = _offered_columns(
                key_, cols_enabled, col_sample_rate, n_pad)
            sp = _split_scan(hist, is_cat, col_mask, min_rows, msi, cat_cols,
                             mono=mono_vec, node_lo=node_lo, node_hi=node_hi)
            ok = sp["ok"]
            fits = 2 * jnp.cumsum(ok.astype(jnp.int32)) <= n_pad_next
            ok = ok & fits
            gain = jnp.where(ok, jnp.maximum(sp["gain"], 0.0), 0.0)
            _, _, _, _, n_split, rec = _leaf_decide(
                ok, gain, sp["node_w"], sp["node_wy"], sp["node_wh"],
                sp["col"], sp["split_bin"], sp["is_cat"], sp["cat_mask"],
                sp["na_left"], learn_rate, max_abs_leaf, n_pad,
                node_lo=node_lo, node_hi=node_hi,
                reg_lambda=rl, reg_alpha=ra, col_offer=col_mask,
            )
            varimp = varimp.at[sp["col"]].add(
                jnp.where(ok, gain, 0.0).astype(varimp.dtype))
            if mono:
                new_lo, new_hi = _child_bounds(
                    ok, rec["child_base"], sp["mono_col"], sp["mid"],
                    node_lo, node_hi, n_pad_next,
                )
                return varimp, n_split, rec, new_lo, new_hi
            return varimp, n_split, rec

        return jax.jit(run)

    return _cached_program(key, make)


_STREAM_GRAD_CACHE: dict = {}


def _stream_grad_prog(grad_fn, grad_key, sample: bool, goss=None):
    """Per-block pseudo-residuals/hessians (+ the per-tree row bootstrap
    when sampling): (F, y, w, key, rate) -> (w_tree, wy, wh).

    ``goss`` ((a, b) floats) applies GOSS per BLOCK: the top-a threshold is
    taken over each block's rows rather than the whole frame — a documented
    approximation of the resident lanes' global threshold (same expected
    kept volume and amplification; the out-of-core frame never holds the
    global gradient ranking)."""
    key = ("stream_grad", grad_key, sample, goss, jax.default_backend())
    fn = _STREAM_GRAD_CACHE.get(key)
    if fn is None:

        def run(F, y, w, skey, rate):
            if sample:
                mask = jax.random.bernoulli(skey, rate, w.shape)
                wt = w * mask.astype(w.dtype)
            else:
                wt = w
            t, h = grad_fn(F, y, wt)
            wy = wt * t
            wh = jnp.where(wt > 0, h, 0.0)
            if goss is not None:
                gf = _goss_factor(
                    wt, wy, jax.random.fold_in(skey, 1 << 28), *goss
                )
                wt, wy, wh = wt * gf, wy * gf, wh * gf
            return wt, wy, wh

        fn = jax.jit(run)
        _STREAM_GRAD_CACHE[key] = fn
    return fn


def build_trees_streamed(
    store,
    n_trees: int,
    *,
    base_key,
    row_key=None,
    tree_offset: int = 0,
    grad_fn,
    grad_key,
    sample_rate: float,
    n_bins: int,
    is_cat_cols,
    max_depth: int,
    min_rows: float,
    min_split_improvement: float,
    learn_rates,
    max_abs_leaf: float,
    col_sample_rate: float,
    col_sample_rate_per_tree: float,
    varimp,
    node_cap: int = 2048,
    reg_lambda: float = 0.0,
    reg_alpha: float = 0.0,
    monotone=None,
):
    """Build ``n_trees`` trees over a :class:`~h2o3_tpu.frame.chunkstore.
    ChunkStore` whose rows exceed the HBM window.

    Lanes consumed: ``bins`` (uint8 (npad, C)), ``y``/``w``/``F`` (f32 —
    ``F`` is the running score, updated in place per level) plus the
    driver-owned scratch lanes ``wt``/``wy``/``wh`` (f32) and ``nid``
    (int32). Per tree: one gradient pass over the blocks, then per level
    one histogram-accumulate pass, one decide dispatch, one partition
    pass — O(levels · blocks) dispatches, the irreducible cost of touching
    every row per level out of core. The per-tree column subsample and the
    per-(node,col) draw use the scanned path's exact key folds; the row
    bootstrap additionally folds the block index (a per-block draw — the
    resident and streamed bootstraps are different RNG streams, same
    marginal rate).

    Returns ``(trees, varimp)`` with host-resident tree records (streamed
    frames are too big to keep per-level device state around).

    ``monotone`` ((C,) int {-1,0,1}) accepts constrained builds in the
    streamed lane (ISSUE 15): the per-node [lo, hi] bound state is
    frontier-sized — it rides the host level loop and the decide dispatch,
    untouched by the row-block structure.
    """
    from h2o3_tpu.models.tree.binning import bucket_nbins

    n_bins = bucket_nbins(n_bins)
    node_cap = _clamp_node_cap(node_cap, store.npad, min_rows)
    is_cat_np = np.asarray(is_cat_cols, bool)
    cat_cols = tuple(int(i) for i in np.nonzero(is_cat_np)[0])
    is_cat_dev = jnp.asarray(is_cat_np)
    C = len(is_cat_np)
    if row_key is None:
        row_key = base_key
    lrs = np.asarray(learn_rates, np.float32)
    leaf_reg = (
        None if reg_lambda == 0.0 and reg_alpha == 0.0
        else (jnp.float32(reg_lambda), jnp.float32(reg_alpha))
    )
    goss = _goss_ab()
    gprog = _stream_grad_prog(grad_fn, grad_key, sample_rate < 1.0, goss)
    if goss is not None:
        _ROWS_SAMPLED.inc((goss[0] + goss[1]) * store.npad * n_trees)
    mono_dev = None
    if monotone is not None and np.any(np.asarray(monotone) != 0):
        mono_dev = jnp.asarray(np.asarray(monotone, np.int32))
    trees: list[Tree] = []
    for m in range(n_trees):
        g = m + tree_offset
        tkey = jax.random.fold_in(base_key, g)
        if col_sample_rate_per_tree < 1.0:
            keep = (
                jax.random.uniform(jax.random.fold_in(tkey, 1 << 30), (C,))
                < col_sample_rate_per_tree
            )
            keep = jnp.where(keep.any(), keep, True)
            cols_enabled = keep.astype(jnp.float32)
        else:
            cols_enabled = jnp.ones(C, jnp.float32)
        skey = jax.random.fold_in(jax.random.fold_in(row_key, g), 1 << 29)

        # gradient/bootstrap pass
        for bi, blk in store.stream(("F", "y", "w")):
            BUILD_STATS["dispatches"] += 1
            wt, wy, wh = gprog(
                blk["F"], blk["y"], blk["w"],
                jax.random.fold_in(skey, bi), jnp.float32(sample_rate),
            )
            store.update(bi, wt=wt, wy=wy, wh=wh)
        store.fill("nid", 0)

        tree = Tree()
        node_lo = node_hi = None
        if mono_dev is not None:
            node_lo = jnp.full(1, -jnp.inf, jnp.float32)
            node_hi = jnp.full(1, jnp.inf, jnp.float32)
        for depth in range(max_depth + 1):
            n_pad = min(1 << depth, node_cap)
            n_pad_next = min(2 * n_pad, node_cap)
            force_leaf = depth == max_depth
            hist = jnp.zeros((n_pad, C, n_bins, 3), jnp.float32)
            hprog = _stream_hist_prog(n_pad, n_bins)
            for bi, blk in store.stream(("bins", "nid", "wt", "wy", "wh")):
                BUILD_STATS["dispatches"] += 1
                hist = _run_counted(
                    hprog,
                    (blk["bins"], blk["nid"], blk["wt"], blk["wy"],
                     blk["wh"], hist),
                )
            dprog = _stream_decide_prog(
                n_pad, n_pad_next, n_bins, cat_cols, force_leaf, C,
                mono=mono_dev is not None,
            )
            BUILD_STATS["dispatches"] += 1
            dout = dprog(
                hist, jax.random.fold_in(tkey, depth), cols_enabled,
                is_cat_dev, varimp, jnp.float32(min_rows),
                jnp.float32(min_split_improvement), jnp.float32(lrs[m]),
                jnp.float32(max_abs_leaf), jnp.float32(col_sample_rate),
                leaf_reg, mono_dev, node_lo, node_hi,
            )
            if mono_dev is not None:
                varimp, n_split, rec, node_lo, node_hi = dout
            else:
                varimp, n_split, rec = dout
            for bi, blk in store.stream(("bins", "nid", "F")):
                BUILD_STATS["dispatches"] += 1
                nid_b, F_b = _partition_update(
                    blk["bins"], blk["nid"], blk["F"], rec["split_col"],
                    rec["split_bin"], rec["is_cat"], rec["cat_mask"],
                    rec["na_left"], rec["leaf_now"], rec["leaf_val"],
                    rec["child_base"],
                )
                store.update(bi, nid=nid_b, F=F_b)
            rec_host = jax.device_get(rec)
            tree.levels.append(
                TreeLevel(**{k: np.asarray(v) for k, v in rec_host.items()})
            )
            if force_leaf or int(n_split) == 0:
                break
        BUILD_STATS["trees_built"] += 1
        trees.append(tree)
    return trees, varimp
