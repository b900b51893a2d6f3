"""Histogram accumulation — successor of ``hex.tree.ScoreBuildHistogram2`` /
``DHistogram`` [UNVERIFIED upstream paths, SURVEY.md §2.2 §3.3], and the
replacement for the bundled XGBoost ``gpu_hist`` CUDA builder (§2.4).

The hot loop of tree building: for every row, look up its current leaf
``nid`` and scatter its per-stat values into (node, col, bin) cells; reduce
across row shards. Mapping:

- H2O's per-chunk fork-join map + pairwise reduce → per-device scatter-add
  + ``psum`` over the rows mesh axis (via ``shard_map``).
- The stat lanes are CALLER-DEFINED (``stats`` is a tuple of (n,) arrays):
  the GBM/DRF path passes {w, wy, wh} — 3 lanes, because the wy² term of
  H2O's DHistogram squared-error gain cancels exactly across
  parent−left−right and carrying it would be 33% more MXU/HBM work for a
  constant offset (see shared_tree._split_scan) — while uplift trees pass
  their 4 treatment/control lanes. Histogram cost is ∝ lanes, so every
  consumer pays exactly for what it reads.

Two device implementations, auto-selected by backend:
- scatter path (CPU mesh): one `.at[].add` scatter per column (vmapped) —
  fast on CPU, pathological on TPU (XLA serializes scatters; measured ~1.3s
  per 1M×20-col pass at 256 nodes vs ~0.1s for the matmul path).
- **matmul path (TPU)**: the histogram is recast as MXU work. Per row chunk,
  build ``A_s = onehot(nid) * stat_s`` (chunk, N) and the 0/1 col-bin
  indicator ``E`` (chunk, C·B); then ``hist_s = A_sᵀ @ E`` — a dense matmul
  the systolic array eats, no scatter at all. Rows are processed in
  ``lax.scan`` chunks so the (chunk, C·B) indicator transient stays ~100MB.
  Inactive rows (nid<0) match no one-hot column and vanish automatically.
  Inputs stay float32 (bf16 would quantize the gradient stats the split
  gains are computed from); XLA runs f32 dots as multi-pass bf16 on the MXU.
  This is the ScoreBuildHistogram→TPU redesign the north star asks for; the
  Pallas kernel (hist_pallas.py) fuses the indicator build into the dot.

``histogram_in_jit`` is the primary entry: a pure traced function usable
inside a larger jitted program (the tree level step), so histogram + split
scan + partition fuse into one compiled launch with zero host round-trips.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from h2o3_tpu.parallel.mesh import (
    col_block_spec,
    get_mesh,
    n_col_shards,
    pad_cols_to_shards,
    row_axes,
    row_pspec,
    shard_map,
)

# The trace-time collective byte tally moved to ops/collectives.py (which
# also owns the quantized/hierarchical reduce lane the reductions below run
# through); re-exported here because this module is where the tally was
# born and half the stack imports it from here.
from h2o3_tpu.ops.collectives import (  # noqa: F401  (re-exports)
    collective_tally,
    record_collective,
    record_hbm,
    tally_group,
)

# Rows per scatter chunk: XLA materializes the vmapped scatter's updates as
# a (C, chunk, S) f32 broadcast (~1.2 KB/row at C=28, S=4 — measured 13.4 GB
# temp for the whole 10M-row tree program before chunking). 256k rows bounds
# the transient at ~115 MB; shards at or under the chunk take the
# single-chunk path, bit-identical to the unchunked original.
_SCATTER_ROW_CHUNK = 262_144


def _hist_scatter_local(bins_u8, nid, stats, n_nodes: int, n_bins: int):
    """Device-local scatter histogram: (C, n_nodes*n_bins, S).

    Rows with nid < 0 (finalized leaves / padding) MUST arrive with zeroed
    stats (``histogram_in_jit`` masks them): the scatter clamps their nid
    to 0 and a nonzero stat would pollute node 0.
    """
    S = stats.shape[1]
    nid_safe = jnp.maximum(nid, 0)

    def scatter_chunk(bins_c, nid_c, stats_c):
        def one_col(col):
            idx = nid_c * n_bins + col.astype(jnp.int32)
            out = jnp.zeros((n_nodes * n_bins, S), jnp.float32)
            return out.at[idx].add(stats_c)

        return jax.vmap(one_col, in_axes=1)(bins_c)  # (C, n_nodes*n_bins, S)

    n, C = bins_u8.shape
    if n <= _SCATTER_ROW_CHUNK:
        return scatter_chunk(bins_u8, nid_safe, stats)

    chunk = _SCATTER_ROW_CHUNK
    nchunks = -(-n // chunk)
    pad = nchunks * chunk - n
    if pad:  # padding rows carry zero stats — they land in bin 0 harmlessly
        bins_u8 = jnp.pad(bins_u8, ((0, pad), (0, 0)))
        nid_safe = jnp.pad(nid_safe, (0, pad))
        stats = jnp.pad(stats, ((0, pad), (0, 0)))

    def body(acc, args):
        return acc + scatter_chunk(*args), None

    acc0 = jnp.zeros((C, n_nodes * n_bins, S), jnp.float32)
    acc, _ = jax.lax.scan(
        body,
        acc0,
        (
            bins_u8.reshape(nchunks, chunk, C),
            nid_safe.reshape(nchunks, chunk),
            stats.reshape(nchunks, chunk, S),
        ),
    )
    return acc


def _select_local():
    """Backend-appropriate shard-local histogram implementation.

    Auto: scatter-add on CPU (fast there, pathological on TPU), the Pallas
    kernel (hist_pallas.py) on TPU. ``H2O3_TPU_HIST=matmul`` forces the
    plain-XLA MXU path, ``=scatter`` forces the scatter path, and
    ``=pallas`` forces the Pallas kernel (in the interpreter on CPU — the
    CI lane) on ANY backend, so A/B sweeps can reach all three local impls
    everywhere.
    """
    from h2o3_tpu import config

    override = config.get("H2O3_TPU_HIST")
    if override == "scatter":
        return _hist_scatter_local
    if override == "matmul":
        return _hist_matmul_local
    if override != "pallas" and jax.default_backend() == "cpu":
        return _hist_scatter_local

    def pallas_local(bins_u8, nid, stats, n_nodes, n_bins):
        from h2o3_tpu.ops.hist_pallas import hist_pallas_local, tiles_for

        return hist_pallas_local(
            bins_u8, nid, stats, n_nodes, n_bins,
            interpret=jax.default_backend() == "cpu",
            tiles=tiles_for(
                bins_u8.shape[1], n_nodes, n_bins, stats.shape[1]),
        )

    return pallas_local


def _local_is_pallas(local) -> bool:
    return local not in (_hist_scatter_local, _hist_matmul_local)


def _wants_row_order(
    n_rows: int, n_cols: int, n_nodes: int, n_bins: int, n_lanes: int,
) -> bool:
    """Whether a level that builds ``n_nodes`` nodes over shards of
    ``n_rows`` rows runs grouped — read off shapes alone: the local impl is
    the Pallas kernel and the level is wider than one node tile, where the
    dense grid passes over every row once a tile (ISSUE 33). One tile, or
    the CPU's scatter: nothing to group. Nor under the tile autotuner, whose
    tiles may change with a level's width: one order could not serve them."""
    from h2o3_tpu import config

    if (not _local_is_pallas(_select_local())
            or config.get("H2O3_TPU_PALLAS_TILES").strip() == "auto"):
        return False
    from h2o3_tpu.ops.hist_pallas import grouped_fits, plan_layout, tiles_for

    tiles = tiles_for(n_cols, n_nodes, n_bins, n_lanes)
    lay = plan_layout(n_cols, n_nodes, n_bins, n_lanes, tiles=tiles)
    return lay.n_nt > 1 and grouped_fits(n_rows, lay, tiles[0])


def row_order_in_jit(bins_u8, nid, stats, n_nodes: int, n_bins: int,
                     carry: tuple = (), mesh=None):
    """The rows of every shard in node order, for ``histogram_in_jit``'s
    ``order`` at this and every later level of the tree (the codes and the
    stat lanes must not change meanwhile): a
    :class:`~h2o3_tpu.ops.hist_pallas.RowOrder` whose arrays are sharded
    over the rows as the frame's are — the sort is shard-local, no row
    crosses a device — and the row-sharded per-row arrays of ``carry``
    ((n,) lanes, (n, C) uint8 codes) in that order (a shard's order is padded
    to whole row tiles: those places hold zeros). None where a level of
    ``n_nodes`` nodes would not run grouped (:func:`_wants_row_order`)."""
    n, C = bins_u8.shape
    mesh = mesh or get_mesh()
    if not _wants_row_order(
            n // int(mesh.devices.size), C, n_nodes, n_bins, len(stats)):
        return None
    from h2o3_tpu.ops.hist_pallas import sort_rows, tiles_for

    tiles = tiles_for(C, n_nodes, n_bins, len(stats))
    rspec = row_pspec(mesh)
    cspec = tuple(row_pspec(mesh, x.ndim) for x in carry)
    with jax.named_scope("ph_hist"):
        return shard_map(
            lambda b, n, s, c: sort_rows(
                b, n, s, n_nodes, n_bins, tiles=tiles, carry=c),
            mesh=mesh,
            in_specs=(rspec, rspec, rspec, cspec),
            out_specs=(_order_spec(mesh), cspec),
            check_vma=False,
        )(bins_u8, nid, jnp.stack(list(stats), axis=1), tuple(carry))


def order_codes_in_jit(order, n_cols: int, mesh=None):
    """The codes ``order`` was made from, ``(rows, n_cols)`` uint8, in it."""
    from h2o3_tpu.ops.hist_pallas import order_codes

    mesh = mesh or get_mesh()
    return shard_map(
        lambda o: order_codes(o, n_cols),
        mesh=mesh,
        in_specs=(_order_spec(mesh),),
        out_specs=row_pspec(mesh, 2),
        check_vma=False,
    )(order)


def restore_rows_in_jit(order, lanes_s: tuple, n: int, mesh=None) -> tuple:
    """Per-row lanes that lie in ``order`` back in the frame's row order,
    ``(n,)`` each, row-sharded."""
    from h2o3_tpu.ops.hist_pallas import restore_rows

    mesh = mesh or get_mesh()
    n_local = n // int(mesh.devices.size)
    rspec = tuple(row_pspec(mesh) for _ in lanes_s)
    return shard_map(
        lambda o, a: restore_rows(o, a, n_local),
        mesh=mesh,
        in_specs=(_order_spec(mesh), rspec),
        out_specs=rspec,
        check_vma=False,
    )(order, tuple(lanes_s))


def _order_spec(mesh):
    """A ``RowOrder``'s arrays carry the rows on their LAST axis (``n_live``:
    one count a shard)."""
    from h2o3_tpu.ops.hist_pallas import RowOrder

    return RowOrder(
        perm=row_pspec(mesh), bins3=row_pspec(mesh, 3, 2),
        stats_t=row_pspec(mesh, 2, 1), n_live=row_pspec(mesh))


# ---------------------------------------------------------------------------
# int16 histogram accumulation lanes (ISSUE 16, H2O3_TPU_HIST_I16 —
# arXiv:1806.11248's quantized gradient/hessian accumulation). Each stat
# lane is rescaled per node so row values fit an int8-range code
# (scale = absmax/127; scale 1 — EXACT — when the node's lane is already
# small integers, the w/count lanes and the parity suites), accumulated
# through the unchanged local impl inside a ±32767 int16 cell budget, and
# rescaled back after. A node whose accumulated cells would exceed the
# budget trips the overflow latch: the whole shard-local pass recomputes in
# f32 on-device (lax.cond) and tree_hist_i16_overflows_total tallies. The
# rescale happens BEFORE the cross-device reduce, so per-shard scales need
# not agree and the collective lane (quantized or not) is untouched.

from h2o3_tpu.utils import metrics as _mx

_I16_OVERFLOWS = _mx.counter(
    "tree_hist_i16_overflows_total",
    "shard-local int16 histogram accumulations that tripped the overflow "
    "latch and recomputed in f32 (H2O3_TPU_HIST_I16)", always=True)


def _i16_enabled() -> bool:
    from h2o3_tpu import config

    return config.get_bool("H2O3_TPU_HIST_I16")


def _i16_overflow_cb(flag) -> None:
    if bool(flag):
        _I16_OVERFLOWS.inc()


def _i16_local(local, bins_u8, nid, stats, n_nodes: int, n_bins: int):
    """Quantized shard-local accumulation with the f32 overflow fallback."""
    S = stats.shape[1]
    nid_safe = jnp.maximum(nid, 0)
    amag = jnp.abs(stats)
    absmax = jnp.zeros((n_nodes, S), jnp.float32).at[nid_safe].max(
        amag, mode="drop")
    nonint = jnp.zeros((n_nodes, S), jnp.float32).at[nid_safe].max(
        (stats != jnp.round(stats)).astype(jnp.float32), mode="drop")
    exact = (absmax <= 127.0) & (nonint == 0.0)
    scale = jnp.where(exact, 1.0, jnp.maximum(absmax, 1e-30) / 127.0)
    q = jnp.round(stats / scale[nid_safe])
    hq = local(bins_u8, nid, q, n_nodes, n_bins)  # (C, n_nodes*n_bins, S)
    C = hq.shape[0]
    hq4 = hq.reshape(C, n_nodes, n_bins, S)
    overflow = (jnp.abs(hq4) > 32767.0).any()
    hist = jax.lax.cond(
        overflow,
        lambda _: local(bins_u8, nid, stats, n_nodes, n_bins),
        lambda _: (hq4 * scale[None, :, None, :]).reshape(
            C, n_nodes * n_bins, S),
        None,
    )
    jax.debug.callback(_i16_overflow_cb, overflow)
    return hist


def _maybe_i16(local):
    """Wrap a dense local impl in the i16 lane when the knob is on.

    The Pallas kernel accumulates in its own VMEM tiles and is left alone
    (documented in MIGRATION.md); read at trace time, so every program
    cache keyed on shared_tree._kernel_key retraces on a knob flip."""
    if not _i16_enabled() or _local_is_pallas(local):
        return local
    return partial(_i16_local, local)


_ROW_CHUNK = 8192  # rows per matmul chunk: (chunk, C*B) transient ≤ ~120MB


def _hist_matmul_local(bins_u8, nid, stats, n_nodes: int, n_bins: int):
    """MXU histogram for one shard: returns (C, n_nodes*n_bins, S)."""
    n, C = bins_u8.shape
    S = stats.shape[1]
    chunk = min(_ROW_CHUNK, n)
    nchunks = -(-n // chunk)
    pad = nchunks * chunk - n
    if pad:
        bins_u8 = jnp.pad(bins_u8, ((0, pad), (0, 0)))
        nid = jnp.pad(nid, (0, pad), constant_values=-1)
        stats = jnp.pad(stats, ((0, pad), (0, 0)))
    bins_ch = bins_u8.reshape(nchunks, chunk, C)
    nid_ch = nid.reshape(nchunks, chunk)
    stats_ch = stats.reshape(nchunks, chunk, S)

    iota_nodes = jnp.arange(n_nodes, dtype=jnp.int32)

    def body(acc, args):
        b_c, nid_c, s_c = args
        oh_nid = (nid_c[:, None] == iota_nodes[None, :]).astype(jnp.float32)
        # 0/1 (col,bin) indicator: each row lights exactly one bin per column
        oh_cb = (
            b_c[:, :, None].astype(jnp.int32)
            == jnp.arange(n_bins, dtype=jnp.int32)[None, None, :]
        ).astype(jnp.float32).reshape(chunk, C * n_bins)
        # stat-scaled nid one-hot with the S lanes folded into A's columns:
        # ONE (chunk, N*S) @ (chunk, C*B) dot instead of S separate dots —
        # same contraction over the same rows per output cell, so the result
        # is bit-identical, but the fused program carries one HLO dot per
        # chunk instead of S
        A = (oh_nid[:, :, None] * s_c[:, None, :]).reshape(chunk, -1)
        out = jax.lax.dot_general(
            A,
            oh_cb,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(-1, S, C * n_bins)  # (N, S, C*B)
        return acc + jnp.transpose(out, (0, 2, 1)), None

    acc0 = jnp.zeros((n_nodes, C * n_bins, S), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (bins_ch, nid_ch, stats_ch))
    # (N, C*B, S) -> (C, N*B, S) to match the scatter path's layout
    h = acc.reshape(n_nodes, C, n_bins, S)
    return jnp.transpose(h, (1, 0, 2, 3)).reshape(C, n_nodes * n_bins, S)


def histogram_in_jit(
    bins_u8, nid, stats, n_nodes: int, n_bins: int, mesh=None,
    *, col_sharded: bool = False, order=None,
):
    """Cross-device histogram, traceable inside a jitted program.

    ``stats`` is a TUPLE of (n,) row-sharded arrays — the stat lanes.
    Returns (n_nodes, C, n_bins, S), replicated across the mesh.

    ``order`` (:func:`row_order_in_jit`, made earlier in the same tree from
    the same codes and lanes) runs the level grouped: each shard reads its
    rows in node order and a row chunk is contracted only against the node
    tiles whose rows it holds. ``nid`` then arrives IN the order
    (:func:`row_order_in_jit`'s ``carry``) and ``bins_u8`` and ``stats``
    give shapes only. The cells are those of the dense pass (real-valued
    statistics: summed in another order). The return is then
    ``(hist, steps)``, ``steps`` the int32 count of grid steps that
    contracted a row chunk, over all shards.

    ``col_sharded=True`` is the split-pipeline mode: the cross-device
    reduction ends in ``lax.psum_scatter`` over contiguous COLUMN blocks
    instead of a full ``psum`` — each device reduces (and keeps) only its
    C/P columns, moving 1/P of the all-reduce's replication volume — and the
    result comes back as (n_nodes, Cp, n_bins, S) with the column axis
    sharded over the mesh (Cp = C padded up to a multiple of the shard
    count; the padding columns hold all-zero histograms, are masked by the
    callers' column masks, and can never win a split). Each block's cells
    are bit-identical to the same slice of the replicated reduction, which
    is what lets the downstream per-block winner merge reproduce the
    replicated argmax exactly.
    """
    mesh = mesh or get_mesh()
    local = _select_local()
    S = len(stats)
    n_dev = int(mesh.devices.size)
    n_col = n_col_shards(mesh)
    C = bins_u8.shape[1]
    Cp = pad_cols_to_shards(C, mesh) if col_sharded else C

    from h2o3_tpu.ops import collectives

    local_acc = _maybe_i16(local)

    def reduce(h):
        # the cross-device reduction runs through the collective lane
        # (ops/collectives.py): stock psum/psum_scatter when the quant lane
        # is off — bit-for-bit the pre-lane program — or the block-
        # quantized / hierarchical variant when on; on a 2-D mesh the lane
        # itself stages an exact rows-axis psum first and scatters column
        # blocks over the cols axis only; the lane records the hist_reduce
        # byte tally (per lane) itself
        # lane_axis=-1: the S stat lanes {w, wy, wh} differ by orders of
        # magnitude and must not share quantization blocks
        if not col_sharded:
            return collectives.psum(
                h, n_dev=n_dev, phase="hist_reduce", lane_axis=-1, mesh=mesh)
        if Cp > C:
            # divisibility pad on the HISTOGRAM (cheap: hist-sized, not
            # bins-sized) so C < P and C % P != 0 stay correct with no
            # full-frame column padding anywhere
            h = jnp.pad(h, ((0, Cp - C), (0, 0), (0, 0)))
        return collectives.psum_scatter(
            h, n_dev=n_dev, phase="hist_reduce", lane_axis=-1, mesh=mesh)

    def body(b, n, s):
        # retired/padding rows (nid < 0) carry zero stats into every impl
        s = jnp.where((n >= 0)[:, None], s, 0.0)
        return reduce(local_acc(b, n, s, n_nodes, n_bins))

    def body_grouped(order, n):
        from h2o3_tpu.ops.hist_pallas import hist_pallas_grouped, tiles_for

        h, steps = hist_pallas_grouped(
            order, n, n_nodes, n_bins, C,
            interpret=jax.default_backend() == "cpu",
            tiles=tiles_for(C, n_nodes, n_bins, S))
        return reduce(h), jax.lax.psum(steps, row_axes(mesh))

    # node tiles asked for (tree_node_tiles_total): 64 node slots over every
    # row each, tallied and replayed per dispatch like the bytes below
    from h2o3_tpu.ops.hist_pallas import NODE_TILE

    record_collective("node_tiles", -(-n_nodes // NODE_TILE))

    # HBM model of hist + split (see record_hbm): the dense tensor
    # is written once and its (possibly column-sharded) slice re-read by the
    # split scan; the Pallas local impl additionally pays its two unscramble
    # passes over the padded kernel output. Terminal force-leaf levels skip
    # the scan read this counts — a deliberate (small) upper bound; the
    # saturated-region entries, by contrast, are scaled by the EXECUTED
    # iteration count at dispatch time (tally_group in collectives.py).
    dense_b = C * n_nodes * n_bins * S * 4
    scan_b = (Cp / n_col if col_sharded else C) * n_nodes * n_bins * S * 4
    if _local_is_pallas(local):
        from h2o3_tpu.ops.hist_pallas import plan_layout, tiles_for

        opad = plan_layout(
            C, n_nodes, n_bins, S, tiles=tiles_for(C, n_nodes, n_bins, S)
        ).nbytes
        record_hbm("pallas_unfused", 4 * opad + dense_b + scan_b)
    else:
        record_hbm("dense", dense_b + scan_b)

    # ph_hist: phase tag consumed by tools/profile_fused.py (HLO op_name
    # metadata carries the scope path into the profiler trace)
    rspec = row_pspec(mesh)
    hspec = col_block_spec(0, mesh) if col_sharded else P()
    with jax.named_scope("ph_hist"):
        if order is None:
            h = shard_map(
                body,
                mesh=mesh,
                in_specs=(rspec, rspec, rspec),
                out_specs=hspec,
                check_vma=False,
            )(bins_u8, nid, jnp.stack(list(stats), axis=1))
        else:
            record_collective("hist_grouped", 1)
            h, steps = shard_map(
                body_grouped,
                mesh=mesh,
                in_specs=(_order_spec(mesh), rspec),
                out_specs=(hspec, P()),
                check_vma=False,
            )(order, nid)
        h = jnp.transpose(
            h.reshape(h.shape[0], n_nodes, n_bins, S), (1, 0, 2, 3)
        )  # (n_nodes, C[p], n_bins, S)
    return h if order is None else (h, steps)


_BUILD_HIST_PROG: dict = {}


def build_histograms(bins_u8, nid, stats, n_nodes: int, n_bins: int):
    """Standalone jitted histogram (kept for tests / direct use).

    Cached per (shape statics, impl knobs): the local-impl selection and
    the i16 lane are trace-time decisions, so an env flip must reach a
    fresh program here just like in the tree builders."""
    from h2o3_tpu import config

    key = (n_nodes, n_bins, config.get("H2O3_TPU_HIST"), _i16_enabled(),
           jax.default_backend())
    prog = _BUILD_HIST_PROG.get(key)
    if prog is None:
        prog = jax.jit(
            partial(histogram_in_jit, n_nodes=n_nodes, n_bins=n_bins))
        _BUILD_HIST_PROG[key] = prog
    return prog(bins_u8, nid, stats)
