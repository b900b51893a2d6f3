"""Pallas TPU histogram kernel — the ``gpu_hist`` successor proper
(SURVEY.md §2.4: the bundled XGBoost CUDA histogram builder is the one native
component the rebuild must replace with a TPU kernel).

Why the plain-XLA matmul path (``histogram._hist_matmul_local``) is slow: it
materializes a (row_chunk, C·B) one-hot indicator — ~235 MB at C=28, B=256 —
which cannot live in VMEM, so every chunk round-trips the indicator through
HBM and the pass is bandwidth-crippled (~1-3% MFU measured on a v5e,
round 2).

This kernel never materializes that transient:

- two grids over one step body (``_hist_step``). The dense grid is
  (node_tiles, col_tiles, row_chunks), row-fastest, so the output block for
  one (node_tile, col_tile) stays resident in VMEM while every row chunk
  accumulates into it: every level of one node tile, and every caller whose
  rows arrive per dispatch. It passes over all the rows once a NODE TILE, and
  a row belongs to one node. So from the first level of a tree that is wider
  than one tile the whole-tree program keeps its rows in node order
  (``sort_rows``, once a tree) and the grouped grid (col_tiles, visits)
  contracts a row chunk only against the node tiles whose rows it holds: a
  scalar-prefetched visit list (chunk, tile), tiles in order, so an output
  block is still resident over its run of visits — about one pass over the
  live rows a LEVEL (ISSUE 33; the section above ``RowOrder``);
- the kernel's operands carry the ROWS ON THEIR LANES — codes (n_ct, CT, n)
  int32, node ids (1, n), statistics (S, n) — so a step's blocks are dense
  in HBM (an (n, 1) operand is tiled to 128 lanes a row: 48x the bytes to
  stream) and every broadcast a one-hot needs runs down the sublanes and
  costs no lane shuffle. The stat-scaled node one-hot is (S·NT, R); a
  column's bin indicator is a (Bpad, R) compare that the MXU takes as a
  transposed 0/1 mask — it never exists as data;
- the indicator goes through the MXU ONCE per step: the 2-term bf16 split of
  the statistics is stacked on the M dimension ([hi; lo], 2·S·NT rows), one
  contraction per column, and the two halves of the result are added;
- rows with nid outside the tile (or nid = -1: retired/padding) match no
  one-hot row and contribute zero, so node tiling and row padding need no
  masking anywhere.

What binds a step is its instruction schedule, not HBM or the MXU's FLOPs
(PERF.md §3 says how to read it from the compiler with no chip): until
ISSUE 31 both operands were lane-tiled with ``jnp.tile`` — lane rotations
that kept the XLU full and spilled ~1,300 vregs a step — and under that
schedule sat the 576 KB a step of 128-lane-padded operand blocks.

``S`` (the stat-lane count) is caller-defined: the GBM/DRF path runs S=3
{w, wy, wh} — the wy² lane of H2O's DHistogram cancels in the gain and
carrying it would be 33% more MXU work (see shared_tree._split_scan) —
while uplift trees run their 4 treatment/control lanes. Kernel cost is
∝ S, so each consumer pays exactly for what it reads.

The output is (C, n_nodes·n_bins, S) per shard — the layout the
scatter/matmul paths emit. The kernel itself writes, per node tile,
(stat·NT + node) rows by column-major (col_in_tile·Bpad + bin) lanes; one
reshape/transpose "unscramble" pass over the full tensor in HBM brings that
to the dense layout. :func:`plan_layout` is the single source of the tile
geometry.
"""

from __future__ import annotations

import functools
import itertools
import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 512  # rows per grid step
COL_TILE = 8  # feature columns per grid step
NODE_TILE = 64  # tree nodes per grid step (S·NT = 192-256 M-rows on the MXU)
# A grid step takes ALL the columns while its output block stays under this
# (28 columns x 256 bins: up to 32 nodes): a quarter of the steps, no column
# padded to the tile, the node one-hot built once a row tile. A full node
# tile's block (5.5 MB, twice for the pipeline, once more where XLA places
# the kernel's output in VMEM) overruns the kernel's 16 MB of scoped VMEM.
WIDE_BLOCK_BYTES = 3 << 20


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _tiles() -> tuple[int, int, int]:
    """(ROW_TILE, COL_TILE, NODE_TILE), overridable via the
    ``H2O3_TPU_PALLAS_TILES`` knob ("row,col,node" — the tile-sweep hook:
    ``tools/bench_kernel_sweep.py`` varies tiles through the environment
    instead of monkeypatching module globals).
    Callers pass the resolved tuple into :func:`hist_pallas_local` /
    :func:`plan_layout` as a static argument, so every tile choice gets its
    own jit cache entry — no stale-executable footgun. The column tile is
    the one a step takes where the output block of ALL the columns would
    pass ``WIDE_BLOCK_BYTES`` (:func:`plan_layout`).

    ``'auto'`` is the SHAPE-AWARE autotuner (ISSUE 15): this shapeless
    accessor then returns the built-in defaults; shape-aware call sites
    resolve through :func:`tiles_for`, which runs a first-build micro-sweep
    per (shape-bucket, mesh) and caches the winner persistently."""
    from h2o3_tpu import config

    spec = config.get("H2O3_TPU_PALLAS_TILES").strip()
    if not spec or spec == "auto":
        return (ROW_TILE, COL_TILE, NODE_TILE)
    parts = [int(x) for x in spec.split(",")]
    if len(parts) != 3 or any(p <= 0 for p in parts) or parts[0] % 128:
        raise ValueError(
            f"H2O3_TPU_PALLAS_TILES must be 'ROW,COL,NODE' positive ints "
            f"(ROW a multiple of 128: the rows are the lanes of the kernel's "
            f"blocks) or 'auto', got {spec!r}"
        )
    return tuple(parts)


# ---------------------------------------------------------------------------
# tile autotuner (H2O3_TPU_PALLAS_TILES=auto, ISSUE 15 / ROADMAP 4b): a
# first-build micro-sweep over a small tile grid, cached per
# (shape-bucket, mesh) beside the persistent compile cache so same-bucket
# rebuilds (and later processes) perform ZERO new sweeps. Explicit
# "ROW,COL,NODE" values bypass the sweep unchanged; '' keeps the built-in
# defaults.

from h2o3_tpu.utils import metrics as _mx

_TILE_SWEEPS = _mx.counter(
    "pallas_tile_sweeps_total",
    "tile-autotuner micro-sweeps executed (H2O3_TPU_PALLAS_TILES=auto; a "
    "same-bucket rebuild must add zero)", always=True)
_TUNED_TILES: dict = {}  # in-process cache: key -> (row, col, node)
_SWEEP_ROWS = 4096  # rows of synthetic data per sweep candidate


def _tile_cache_path() -> str:
    """The persistent winner store, beside the XLA compile cache so one
    warm volume carries both the executables and the tile choices."""
    import os

    from h2o3_tpu import config

    return os.path.join(config.compile_cache_dir(), "pallas_tiles.json")


def _tile_bucket(c: int, n_nodes: int, n_bins: int, ns: int) -> tuple:
    """Shape bucket for the tuner cache: columns to the PR-1 ladder
    granularity (multiple of 8), nodes/bins to powers of two — the same
    coarsening the program caches already ride, so one sweep serves every
    shape that compiles to the same kernel geometry family."""
    cb = -(-c // 8) * 8
    nb = 1 << max(int(n_nodes - 1).bit_length(), 1)
    bb = 1 << max(int(n_bins - 1).bit_length(), 3)
    return (cb, nb, bb, ns)


def _sweep_grid(c: int, n_nodes: int) -> list:
    """The candidate triples: a small cross of row/col/node tiles clamped
    to the problem (12 candidates max — a first-build cost, paid once per
    bucket per mesh and then cached persistently)."""
    rows = (256, 512, 1024)
    cols = tuple(sorted({min(4, c), min(8, c)}))
    nodes = tuple(sorted({min(32, n_nodes), min(64, n_nodes)}))
    return [(r, ct, nt) for r in rows for ct in cols for nt in nodes]


def _run_tile_sweep(c, n_nodes, n_bins, ns, interpret: bool) -> tuple:
    """Time each candidate on synthetic data of the real geometry; return
    the fastest triple. Runs eagerly (concrete arrays) — safe to call from
    inside an outer trace, where it executes at trace time exactly once.

    A candidate whose tiles do not fit VMEM (the compiler's
    RESOURCE_EXHAUSTED — on a v5e 4 of the 12 candidates at 256 bins, 64
    nodes) is a measured outcome of the sweep: it is logged with the
    compiler's words and cannot win. Any other refusal raises — it is a
    defect in the kernel, not a property of the candidate."""
    import time

    import numpy as np

    from h2o3_tpu.utils.log import Log
    from h2o3_tpu.utils.overload import is_oom

    rng = np.random.default_rng(0)
    n = _SWEEP_ROWS
    bins = jnp.asarray(rng.integers(0, n_bins, (n, c)).astype(np.uint8))
    nid = jnp.asarray(rng.integers(0, n_nodes, n).astype(np.int32))
    stats = jnp.asarray(rng.normal(size=(n, ns)).astype(np.float32))
    best, best_t = None, None
    for tiles in _sweep_grid(c, n_nodes):
        fn = lambda: hist_pallas_local(
            bins, nid, stats, n_nodes, n_bins, interpret=interpret,
            tiles=tiles,
        )
        try:
            jax.block_until_ready(fn())  # compile
        except jax.errors.JaxRuntimeError as e:
            if not is_oom(e):
                raise
            Log.warn(f"Pallas tile autotuner: tiles {tiles} do not fit: "
                     f"{str(e).splitlines()[0][:300]}")
            continue
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        dt = time.perf_counter() - t0
        if best_t is None or dt < best_t:
            best, best_t = tiles, dt
    if best is None:
        raise RuntimeError(
            f"Pallas tile autotuner: no candidate of {_sweep_grid(c, n_nodes)}"
            f" fits VMEM at c={c} n_nodes={n_nodes} n_bins={n_bins} ns={ns}")
    # the candidate executables are one-shot — drop them (the winner
    # recompiles once inside the real program; keeping 11 losers loaded
    # per bucket would only grow the process's executable footprint)
    hist_pallas_local.clear_cache()
    return best


def tiles_for(c: int, n_nodes: int, n_bins: int, ns: int) -> tuple:
    """The tile triple for a problem shape — THE shape-aware resolver.

    Explicit ``H2O3_TPU_PALLAS_TILES="ROW,COL,NODE"`` values (and the ''
    defaults) bypass the tuner unchanged; ``'auto'`` looks the shape bucket
    up in the in-process cache, then the persistent winner store, and only
    then runs the micro-sweep (``pallas_tile_sweeps_total`` counts actual
    sweeps — the same-bucket-rebuild-adds-zero pin)."""
    from h2o3_tpu import config

    spec = config.get("H2O3_TPU_PALLAS_TILES").strip()
    if spec != "auto":
        return _tiles()
    from h2o3_tpu.parallel.mesh import mesh_key

    bucket = _tile_bucket(c, n_nodes, n_bins, ns)
    key = (bucket, mesh_key(), jax.default_backend())
    hit = _TUNED_TILES.get(key)
    if hit is not None:
        return hit
    import json
    import os

    path = _tile_cache_path()
    skey = repr(key)
    try:
        with open(path) as f:
            stored = json.load(f)
    except (OSError, ValueError):
        stored = {}
    if skey in stored:
        tiles = tuple(int(x) for x in stored[skey])
        _TUNED_TILES[key] = tiles
        return tiles
    _TILE_SWEEPS.inc()
    tiles = _run_tile_sweep(
        # sweep at the BUCKET geometry so every shape in the bucket lands
        # on the same winner (and the cache key matches what was measured)
        bucket[0], bucket[1], min(bucket[2], 256), ns,
        interpret=jax.default_backend() == "cpu",
    )
    _TUNED_TILES[key] = tiles
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        stored[skey] = list(tiles)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(stored, f, indent=0, sort_keys=True)
        os.replace(tmp, path)  # atomic publish (the PR-2 persist idiom)
    except OSError:
        pass  # read-only cache volume: the in-process cache still holds
    from h2o3_tpu.utils.log import Log

    Log.info(
        f"Pallas tile autotuner: bucket {bucket} on "
        f"{jax.default_backend()} -> tiles {tiles}"
    )
    return tiles


@dataclass(frozen=True)
class HistLayout:
    """Static tile geometry of the kernel's padded output for a problem
    shape: ``(n_nt·nt·ns, n_ct·ct·bpad)`` float32, rows ``(node_tile, stat,
    node_in_tile)``, lanes ``(col_tile, col_in_tile, bin)``. ``n_nt·nt >=
    n_nodes``, ``n_ct·ct >= C`` and ``bpad >= n_bins`` are tile padding.
    Padded BIN and NODE cells are exactly zero (no row ever lands there);
    padded COLUMNS carry the u8 pad code 0 and are sliced off by the
    unscramble."""

    ns: int         # stat lanes
    ct: int         # columns per tile
    bpad: int       # padded bins per tile (ct*bpad % 128 == 0)
    nt: int         # nodes per tile
    n_ct: int       # column tiles
    n_nt: int       # node tiles

    @property
    def nbytes(self) -> int:
        return 4 * (self.n_nt * self.nt * self.ns) * (
            self.n_ct * self.ct * self.bpad)


def plan_layout(
    c: int, n_nodes: int, n_bins: int, ns: int,
    tiles: tuple[int, int, int] | None = None,
) -> HistLayout:
    """The kernel's tile geometry for a problem shape: the node tile clamped
    to the frontier, and all the columns a step while that output block is
    under ``WIDE_BLOCK_BYTES``, else the triple's column tile."""
    _, col_tile, node_tile = tuple(tiles or _tiles())
    nt = min(node_tile, n_nodes)

    def bins_padded(ct):  # the lane dimension CT·Bpad is a multiple of 128
        bpad = _cdiv(n_bins, 16) * 16
        while (ct * bpad) % 128:
            bpad += 16
        return bpad

    ct = min(col_tile, c)
    if 4 * nt * ns * c * bins_padded(c) <= WIDE_BLOCK_BYTES:
        ct = c
    bpad = bins_padded(ct)
    return HistLayout(
        ns=ns, ct=ct, bpad=bpad, nt=nt, n_ct=_cdiv(c, ct),
        n_nt=_cdiv(n_nodes, nt),
    )


def _hist_step(bins_ref, nid_ref, stats_ref, out_ref, i_nt, first, *,
               nt, ct, bpad, ns):
    """One grid step of either kernel: the row chunk in the input blocks
    contracted against node tile ``i_nt`` into the resident output block,
    which is zeroed where ``first`` says this is the block's first step."""
    r = bins_ref.shape[2]  # bins block is (1, CT, R)
    m = nt * ns
    # All three operands arrive with the ROWS ON THE LANES, so everything
    # below broadcasts down the sublanes: a lane broadcast of an (R, 1)
    # column, or a lane-tiling of a sub-128-lane pattern (jnp.tile), is one
    # XLU shuffle per vreg, and the XLU then bounds the step. The column
    # tile arrives via the BlockSpec from the (n_ct, CT, npad) layout.
    nid_t = nid_ref[:]  # (1, R)
    stats_t = stats_ref[:]  # (S, R)
    bins_t = bins_ref[0]  # (CT, R) int32

    # stat-scaled node one-hot, nodes of this tile only: (S·NT, R), row
    # s·NT + j ↦ (stat s, node j) — the one-hot is made once, scaled S times
    node_j = i_nt * nt + jax.lax.broadcasted_iota(jnp.int32, (nt, r), 0)
    nid_match = (nid_t == node_j).astype(jnp.float32)
    a = jnp.concatenate(
        [nid_match * stats_t[s:s + 1, :] for s in range(ns)], axis=0)

    # Manual 2-term bf16 split of the stats operand (~16 mantissa bits, ≈
    # Precision.HIGH, which Mosaic doesn't support): the indicator operand is
    # exact in bf16, so only `a` needs decomposing. The two terms are stacked
    # on M so the indicator is pushed into the MXU once, not once per term.
    # Single-pass bf16 measurably corrupts split gains (2e-3).
    a_hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lhs = jnp.concatenate([a_hi, a - a_hi], axis=0).astype(jnp.bfloat16)

    @pl.when(first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    # One contraction per 128-lane-aligned group of columns (a column at 256
    # bins), so a group's indicator lives in registers from its compare to
    # its push and the output is addressed in aligned lane slices.
    cg = 128 // math.gcd(bpad, 128)
    bin_i = jnp.concatenate(
        [jax.lax.broadcasted_iota(jnp.int32, (bpad, r), 0)] * cg, axis=0)
    for g in range(ct // cg):
        codes = jnp.concatenate(
            [jnp.broadcast_to(bins_t[c:c + 1, :], (bpad, r))
             for c in range(g * cg, (g + 1) * cg)], axis=0)
        e_t = (codes == bin_i).astype(jnp.bfloat16)  # (cg·Bpad, R), 0/1: exact
        both = jax.lax.dot_general(
            lhs, e_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (2·S·NT, cg·Bpad)
        lanes = slice(g * cg * bpad, (g + 1) * cg * bpad)
        out_ref[:, lanes] = out_ref[:, lanes] + (both[:m] + both[m:])


def _hist_kernel(bins_ref, nid_ref, stats_ref, out_ref, **lay):
    """The dense grid ``(node_tiles, col_tiles, row_chunks)``: every row
    chunk against every node tile."""
    _hist_step(bins_ref, nid_ref, stats_ref, out_ref, pl.program_id(0),
               pl.program_id(2) == 0, **lay)


def _hist_kernel_grouped(visit_ref, nvis_ref, bins_ref, nid_ref, stats_ref,
                         out_ref, **lay):
    """The grouped grid ``(col_tiles, visits)``: visit ``v`` contracts the
    row chunk of ``visit_ref[v]`` (the block specs' index maps) against its
    node tile. The visits are ordered by tile, so an output block stays
    resident over its run of visits and is zeroed at the run's first; the
    visits past ``nvis_ref[0]`` repeat the last one's blocks and do nothing."""
    v = pl.program_id(1)

    @pl.when(v < nvis_ref[0])
    def _():
        tile = _visit_tile(visit_ref[v])
        first = (v == 0) | (tile != _visit_tile(visit_ref[jnp.maximum(v - 1, 0)]))
        _hist_step(bins_ref, nid_ref, stats_ref, out_ref, tile, first, **lay)


def _lane_operands(bins_u8, nid, stats, lay: HistLayout, row_tile: int):
    """The kernels' operands, the rows on their LANES and padded to whole row
    tiles: (n, C) codes → (n_ct, CT, npad) int32 (a grid step's column tile
    is the full second-to-last dim of its block), node ids (1, npad),
    statistics (S, npad). An (npad, 1) or (npad, S) operand is tiled to 128
    lanes a row in HBM: a step would stream 576 KB for 12 KB of data, and
    that DMA, not the step's instructions, would bound the kernel (PERF.md
    §6, PR 31)."""
    n, c = bins_u8.shape
    cpad = lay.n_ct * lay.ct
    npad = max(_cdiv(n, row_tile), 1) * row_tile
    if npad != n:
        bins_u8 = jnp.pad(bins_u8, ((0, npad - n), (0, 0)))
        nid = jnp.pad(nid, (0, npad - n), constant_values=-1)
        stats = jnp.pad(stats, ((0, npad - n), (0, 0)))
    if cpad != c:
        bins_u8 = jnp.pad(bins_u8, ((0, 0), (0, cpad - c)))
    bins3 = jnp.transpose(bins_u8.astype(jnp.int32)).reshape(
        lay.n_ct, lay.ct, npad)
    return bins3, nid.reshape(1, npad), jnp.transpose(stats)


def _dense_call(bins3, nid2, stats_t, lay: HistLayout, row_tile: int,
                interpret: bool):
    """Every row chunk against every node tile: the kernel's padded output
    ``(n_nt·nt·ns, cpad·bpad)`` (:class:`HistLayout`)."""
    nt, ct, bpad, ns, n_nt, n_ct = (
        lay.nt, lay.ct, lay.bpad, lay.ns, lay.n_nt, lay.n_ct)
    npad = nid2.shape[1]
    n_r = npad // row_tile
    cpad = n_ct * ct
    kernel = functools.partial(_hist_kernel, nt=nt, ct=ct, bpad=bpad, ns=ns)
    cost = pl.CostEstimate(
        flops=int(2 * npad * (nt * ns) * cpad * bpad),
        # Inputs re-stream once per revisiting grid dimension (bins per node
        # tile, nid/stats per (node, col) tile); the OUTPUT block is written
        # at row chunk 0 and read+rewritten on each of the following n_r - 1
        # chunks — 2·n_r − 1 accesses, not 1 (the old estimate undercounted
        # the dominant term and skewed the scheduler).
        bytes_accessed=int(
            npad * cpad * 4 * n_nt
            + npad * (ns + 1) * 4 * n_nt * n_ct
            + lay.nbytes * (2 * n_r - 1)
        ),
        transcendentals=0,
    )
    return pl.pallas_call(
        kernel,
        grid=(n_nt, n_ct, n_r),
        in_specs=[
            pl.BlockSpec(
                (1, ct, row_tile),
                lambda nt_, ct_, r_: (ct_, 0, r_),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, row_tile), lambda nt_, ct_, r_: (0, r_),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (ns, row_tile), lambda nt_, ct_, r_: (0, r_),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (nt * ns, ct * bpad), lambda nt_, ct_, r_: (nt_, ct_),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((n_nt * nt * ns, cpad * bpad), jnp.float32),
        cost_estimate=cost,
        interpret=interpret,
        name="hist_pallas_dense",  # the prefix is the trace readers' key
    )(bins3, nid2, stats_t)


def _unscramble(out, lay: HistLayout, c: int, n_nodes: int, n_bins: int):
    """The kernel's padded output — rows (node_tile, stat, node), lanes
    (col_tile, col, bin) — to the dense (C, n_nodes·n_bins, S)."""
    h6 = out.reshape(lay.n_nt, lay.ns, lay.nt, lay.n_ct, lay.ct, lay.bpad)
    h6 = jnp.transpose(h6, (3, 4, 0, 2, 5, 1))  # (n_ct, ct, n_nt, nt, Bpad, S)
    h = h6.reshape(lay.n_ct * lay.ct, lay.n_nt * lay.nt, lay.bpad, lay.ns)
    return h[:c, :n_nodes, :n_bins, :].reshape(c, n_nodes * n_bins, lay.ns)


@functools.partial(
    jax.jit, static_argnames=("n_nodes", "n_bins", "interpret", "tiles"),
)
def hist_pallas_local(
    bins_u8, nid, stats, n_nodes: int, n_bins: int, interpret: bool = False,
    tiles: tuple | None = None,
):
    """Shard-local Pallas histogram: (C, n_nodes*n_bins, S) float32.

    ``stats`` is the (n, S) stat matrix (S static from its shape). Drop-in
    replacement for ``_hist_matmul_local`` / ``_hist_scatter_local``.
    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU CI).
    ``tiles`` is the static (row, col, node) tile triple (callers resolve
    the ``H2O3_TPU_PALLAS_TILES`` knob via :func:`_tiles` so each tile
    choice compiles its own executable).
    """
    c = bins_u8.shape[1]
    row_tile = (tiles or _tiles())[0]
    lay = plan_layout(c, n_nodes, n_bins, stats.shape[1], tiles=tiles)
    out = _dense_call(
        *_lane_operands(bins_u8, nid, stats, lay, row_tile), lay, row_tile,
        interpret)
    return _unscramble(out, lay, c, n_nodes, n_bins)


# ---------------------------------------------------------------------------
# The grouped pass (ISSUE 33). A row belongs to one node, so of the dense
# grid's n_nt passes over the rows all but one multiply a row by zero. Where
# the rows lie in node order a row chunk holds the rows of one node tile (two
# at a boundary), and the grouped kernel visits a chunk only for those tiles:
# n_r + n_nt − 1 chunk visits a level instead of n_nt · n_r. The tree program
# sorts the rows once a tree (:func:`sort_rows`) and lives in that order from
# then on; its child numbering keeps the rows in node-tile order at the next
# level and close to it below (the descendants of one sorted node interleave
# inside its segment of the rows, and their node range may straddle a tile).
# Nothing here relies on that: each tile's chunk range is read from the node
# ids, so rows in any other order cost visits, up to the dense pass, never a
# wrong cell.


class RowOrder(NamedTuple):
    """A shard's rows in node order, as the kernels take them: what
    :func:`sort_rows` makes once a tree and :func:`hist_pallas_grouped`
    reads at every level. ``perm[i]`` is the frame row at place ``i`` of the
    order (:func:`restore_rows`); the rows from ``n_live`` on can add nothing
    to any cell (retired at the sort, or all their statistics zero)."""

    perm: jax.Array     # (npad,) int32
    bins3: jax.Array    # (n_ct, CT, npad) int32 codes
    stats_t: jax.Array  # (S, npad) float32
    n_live: jax.Array   # (1,) int32


def _sorted_by(key, lanes, is_stable: bool):
    """Every int32 lane of ``lanes`` (k, npad) in the order of ``key``. A
    permutation is applied by SORTING on a v5e (PERF.md §6, PR 33: a
    two-operand sort of 6M rows takes 11–18 ms, a gather of 6M elements 52
    alone and 111 inside the tree program), and one sort in a loop over the
    lanes, because a sort compiles in seconds an operand (160 s at 13)."""
    return jax.lax.map(
        lambda lane: jax.lax.sort(
            (key, lane), num_keys=1, is_stable=is_stable)[1], lanes)


def _as_lanes(x, npad: int):
    """A per-row array — (n,) of 4 bytes an element, or (n, C) uint8 codes,
    four to a word — as int32 lanes (k, npad), zero past its rows."""
    x = jnp.pad(x, ((0, npad - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))
    if x.ndim == 1:
        return jax.lax.bitcast_convert_type(x, jnp.int32)[None]
    codes = jnp.transpose(x.astype(jnp.int32))
    codes = jnp.pad(codes, ((0, -x.shape[1] % 4), (0, 0))).reshape(-1, 4, npad)
    return sum(codes[:, k] << (8 * k) for k in range(4))


def _from_lanes(lanes, like):
    """:func:`_as_lanes`' inverse, in the dtype and width of ``like``."""
    if like.ndim == 1:
        return jax.lax.bitcast_convert_type(lanes[0], like.dtype)
    codes = jnp.stack(
        [(lanes >> (8 * k)) & 0xFF for k in range(4)], axis=1
    ).reshape(-1, lanes.shape[1])[:like.shape[1]]
    return jnp.transpose(codes).astype(like.dtype)


def sort_rows(bins_u8, nid, stats, n_nodes: int, n_bins: int,
              tiles: tuple | None = None, carry: tuple = ()):
    """The rows sorted by node id (stable) and the kernels' row-invariant
    operands in that order, in the layout of a level that builds ``n_nodes``
    nodes (every level wider than one node tile has the same). Last come the
    rows no histogram of this tree can see: retired ones (``nid < 0``) and
    those whose statistics are all zero (out of the bag, sampled away).
    Returns the :class:`RowOrder` and each per-row array of ``carry`` —
    (n,) lanes, (n, C) uint8 codes — in the order, (npad, ...)."""
    c = bins_u8.shape[1]
    row_tile = (tiles or _tiles())[0]
    lay = plan_layout(c, n_nodes, n_bins, stats.shape[1], tiles=tiles)
    npad = max(_cdiv(bins_u8.shape[0], row_tile), 1) * row_tile
    live = jnp.pad((nid >= 0) & (stats != 0).any(axis=1),
                   (0, npad - nid.shape[0]))
    key = jnp.where(live, jnp.pad(nid, (0, npad - nid.shape[0])),
                    jnp.iinfo(jnp.int32).max)
    rows = (jnp.arange(npad, dtype=jnp.int32), bins_u8,
            *(stats[:, k] for k in range(stats.shape[1])), *carry)
    lanes = [_as_lanes(x, npad) for x in rows]
    ordered = _sorted_by(key, jnp.concatenate(lanes), is_stable=True)
    ends = list(itertools.accumulate(a.shape[0] for a in lanes))
    perm, codes, *rest = map(
        _from_lanes, jnp.split(ordered, ends[:-1]), rows)
    cpad = lay.n_ct * lay.ct
    bins3 = jnp.pad(
        jnp.transpose(codes.astype(jnp.int32)), ((0, cpad - c), (0, 0)))
    ns = stats.shape[1]
    return RowOrder(
        perm=perm, bins3=bins3.reshape(lay.n_ct, lay.ct, npad),
        stats_t=jnp.stack(rest[:ns]),
        n_live=live.sum(dtype=jnp.int32).reshape(1)), tuple(rest[ns:])


def order_codes(order: RowOrder, n_cols: int):
    """The ``(npad, n_cols)`` uint8 codes the order was made from, in the
    order — read back from the kernels' layout."""
    npad = order.perm.shape[0]
    return jnp.transpose(
        order.bins3.reshape(-1, npad)[:n_cols]).astype(jnp.uint8)


def restore_rows(order: RowOrder, lanes_s: tuple, n: int) -> tuple:
    """(npad,) lanes that lie in the order, back in the frame's: (n,) each."""
    npad = order.perm.shape[0]
    back = _sorted_by(
        order.perm, jnp.concatenate([_as_lanes(x, npad) for x in lanes_s]),
        is_stable=False)
    return tuple(_from_lanes(lane[None], x)[:n]
                 for lane, x in zip(back, lanes_s))


# A visit is one int32, node tile above row chunk: the list lives in SMEM
# (1 MiB on a v5e, the compiler's refusal says), so a word a visit.
_CHUNK_BITS = 20
_VISIT_LIST_BYTES = 768 << 10


def _visit_tile(visit):
    return visit >> _CHUNK_BITS


def _visit_chunk(visit):
    return visit & ((1 << _CHUNK_BITS) - 1)


def _visit_list_len(n_r: int, n_nt: int) -> int:
    """Rows in node-tile order need ``n_r + n_nt − 1`` visits a level; twice
    the row chunks leaves room for the rows a tile boundary disorders (the
    descendants of one sorted node interleave, and their node range may
    straddle a tile). A visit past the level's last costs an empty grid
    step."""
    return 2 * n_r + n_nt - 1


def grouped_fits(n_rows: int, lay: HistLayout, row_tile: int) -> bool:
    """Whether a shard of ``n_rows`` rows can run grouped at all: the visit
    list within its share of SMEM, its fields within their bits."""
    n_r = max(_cdiv(n_rows, row_tile), 1)
    return (n_r <= 1 << _CHUNK_BITS and lay.n_nt < 1 << (31 - _CHUNK_BITS)
            and 4 * _visit_list_len(n_r, lay.n_nt) <= _VISIT_LIST_BYTES)


def _visit_list(nid_s, lay: HistLayout, row_tile: int):
    """``(visits, n_visits)`` of the grouped grid from a level's node ids in
    kernel order ``(npad,)``: every node tile visits the contiguous run of
    row chunks from the first to the last that holds a row of it (a tile
    that owns no row visits chunk 0, so that its block is zeroed), tiles in
    order. ``n_visits`` above the list's length says the list is cut short
    and the level needs the dense pass."""
    n_nt = lay.n_nt
    n_r = nid_s.shape[0] // row_tile
    nid_c = nid_s.reshape(n_r, row_tile)
    live = (nid_c >= 0) & (nid_c < n_nt * lay.nt)
    tile_c = nid_c // lay.nt
    lo = jnp.where(live, tile_c, n_nt).min(axis=1)  # (n_r,): the chunk's
    hi = jnp.where(live, tile_c, -1).max(axis=1)    # lowest and highest tile
    t = jnp.arange(n_nt, dtype=jnp.int32)[:, None]
    holds = (lo[None, :] <= t) & (t <= hi[None, :])  # (n_nt, n_r)
    r = jnp.arange(n_r, dtype=jnp.int32)[None, :]
    first = jnp.where(holds, r, n_r).min(axis=1)
    last = jnp.where(holds, r, -1).max(axis=1)
    first = jnp.where(last < 0, 0, first)
    count = jnp.maximum(last - first + 1, 1)
    end = jnp.cumsum(count)
    n_visits = end[-1]
    v = jnp.minimum(
        jnp.arange(_visit_list_len(n_r, n_nt), dtype=jnp.int32), n_visits - 1)
    tile = (v[:, None] >= end[None, :]).sum(axis=1, dtype=jnp.int32)
    tile = jnp.minimum(tile, n_nt - 1)  # only where the list is cut short
    chunk = jnp.clip(first[tile] + v - (end[tile] - count[tile]), 0, n_r - 1)
    return (tile << _CHUNK_BITS) | chunk, n_visits.astype(jnp.int32)


def _grouped_call(visits, n_visits, bins3, nid2, stats_t,
                  lay: HistLayout, row_tile: int, interpret: bool):
    """The listed chunks against their tiles: the same padded output as
    :func:`_dense_call`."""
    nt, ct, bpad, ns, n_nt, n_ct = (
        lay.nt, lay.ct, lay.bpad, lay.ns, lay.n_nt, lay.n_ct)
    n_list = visits.shape[0]
    cpad = n_ct * ct
    kernel = functools.partial(
        _hist_kernel_grouped, nt=nt, ct=ct, bpad=bpad, ns=ns)
    cost = pl.CostEstimate(
        flops=int(2 * n_list * row_tile * (nt * ns) * cpad * bpad),
        bytes_accessed=int(
            n_list * row_tile * (cpad + (ns + 1) * n_ct) * 4
            + 4 * nt * ns * ct * bpad * n_ct * (2 * n_list - n_nt)),
        transcendentals=0,
    )
    row_block = lambda ct_, v_, visit_, nvis_: (0, _visit_chunk(visit_[v_]))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_ct, n_list),
            in_specs=[
                pl.BlockSpec(
                    (1, ct, row_tile),
                    lambda ct_, v_, visit_, nvis_: (
                        ct_, 0, _visit_chunk(visit_[v_])),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec((1, row_tile), row_block,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((ns, row_tile), row_block,
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (nt * ns, ct * bpad),
                lambda ct_, v_, visit_, nvis_: (_visit_tile(visit_[v_]), ct_),
                memory_space=pltpu.VMEM,
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((n_nt * nt * ns, cpad * bpad), jnp.float32),
        cost_estimate=cost,
        interpret=interpret,
        name="hist_pallas_grouped",  # the prefix is the trace readers' key
    )(visits, n_visits.reshape(1), bins3, nid2, stats_t)


def hist_pallas_grouped(
    order: RowOrder, nid_s, n_nodes: int, n_bins: int, n_cols: int,
    interpret: bool = False, tiles: tuple | None = None,
):
    """Shard-local histogram of a level over rows in ``order``: the
    ``(C, n_nodes·n_bins, S)`` of :func:`hist_pallas_local` on the rows as
    they came, and the grid steps that contracted a row chunk (int32). The
    level's node ids ``nid_s`` arrive IN the order (:func:`sort_rows`'
    ``carry``). Right whatever the order is; a level whose visits pass the
    list's length takes the dense kernel."""
    row_tile = (tiles or _tiles())[0]
    ns, npad = order.stats_t.shape
    lay = plan_layout(n_cols, n_nodes, n_bins, ns, tiles=tiles)
    assert order.bins3.shape == (lay.n_ct, lay.ct, npad), (
        order.bins3.shape, lay)
    nid2 = jnp.where(
        jnp.arange(npad) < order.n_live, nid_s, -1).reshape(1, npad)
    visits, n_visits = _visit_list(nid2[0], lay, row_tile)
    fits = n_visits <= visits.shape[0]
    out = jax.lax.cond(
        fits,
        lambda: _grouped_call(visits, n_visits, order.bins3, nid2,
                              order.stats_t, lay, row_tile, interpret),
        lambda: _dense_call(order.bins3, nid2, order.stats_t, lay, row_tile,
                            interpret),
    )
    steps = lay.n_ct * jnp.where(fits, n_visits, lay.n_nt * (npad // row_tile))
    return _unscramble(out, lay, n_cols, n_nodes, n_bins), steps
