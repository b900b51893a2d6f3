"""Feature binning for histogram tree building — the quantile-bin successor
of ``hex.tree.DHistogram`` bin-edge derivation [UNVERIFIED upstream path,
SURVEY.md §2.2].

H2O re-derives per-(node,col) bin ranges from surviving rows at every level;
static quantile binning (the XGBoost-hist approach) computes edges ONCE from
global column quantiles and prebins every row to a uint8 code — trading
h2o's adaptive ranges for a single O(n) pass and a device-resident compressed
design matrix (the C1Chunk analog that actually pays on TPU: 1 byte/cell in
HBM, histograms indexed directly by code). SURVEY.md §7 flags AUC-parity as
the risk; with 255 quantile bins the split resolution exceeds h2o's default
nbins=20, and tests pin accuracy against sklearn GBMs.

Bin layout per column: code 0 = NA, codes 1..nbins = data bins.
Numeric: quantile buckets (edges stored for predict-time rebinning).
Categorical: code = category_id + 1; domains wider than 254 levels clamp the
tail into the last bin (h2o groups rare levels similarly at nbins_cats).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.parallel.mesh import row_sharding
from h2o3_tpu.utils import metrics as _mx

MAX_BINS = 255  # codes 1..255 fit uint8 with 0 reserved for NA


# ---------------------------------------------------------------------------
# shape-bucket ladder (H2O3_TPU_SHAPE_BUCKETS): AutoML/grid builds differ in
# data-dependent shapes (actual quantile-bin count, feature count after
# drops), and every distinct shape is a fresh multi-second XLA compile of the
# whole-tree program. Rounding bins/cols up to a coarse ladder collapses
# near-identical shapes onto one compiled program. The padding is inert by
# construction: padded bins are empty (every candidate split there fails
# min_rows and loses the argmax to a real bin), padded columns carry
# cols_enabled=0 and the NA code everywhere, and the column-sampling RNG is
# drawn at the REAL column count — so a bucketed build scores identically to
# an exact-shape build (pinned by tests).


def _buckets_enabled() -> bool:
    from h2o3_tpu import config

    return config.get_bool("H2O3_TPU_SHAPE_BUCKETS")


def bucket_nbins(n_bins: int) -> int:
    """Histogram bin-axis bucket: next power of two (min 8, cap 256)."""
    if not _buckets_enabled() or n_bins >= 256:
        return n_bins
    b = 8
    while b < n_bins:
        b <<= 1
    return b


def bucket_cols(n_cols: int) -> int:
    """Feature-axis bucket: next multiple of 4 (min 4).

    Histogram cost is ∝ columns, so every padded column is pure overhead on
    every build that hits the program — a multiple-of-8 ladder costs the
    28-col headline +14% histogram work forever to save compiles it never
    needs. Multiple-of-4 keeps the compile-collapse for the odd widths
    AutoML feature-drops produce at ≤3 padded columns."""
    if not _buckets_enabled():
        return n_cols
    return max(4, -(-n_cols // 4) * 4)


@dataclass
class BinSpec:
    """Fitted binning for one frame's feature set."""

    names: list[str]
    is_cat: np.ndarray  # (C,) bool
    nbins: np.ndarray  # (C,) int, actual bin count per column (excl. NA bin)
    edges: np.ndarray  # (C, MAX_BINS-1) float32 right-inclusive bin edges, +inf padded
    cards: np.ndarray  # (C,) categorical cardinality (0 for numeric)
    domains: list | None = None  # train-time cat domains (for test adaptation)

    @property
    def ncols(self) -> int:
        return len(self.names)

    @property
    def max_bins(self) -> int:
        return int(self.nbins.max()) + 1  # +1 for the NA bin 0


_EDGE_PROG: dict = {}


def _device_quantile_edges(frame: Frame, names: list[str], nbins: int, sample: int):
    """Per-column quantile edges computed ON DEVICE — fit_bins pulling every
    column to the host (4 MB each at 1M rows) dominated GBM build time; this
    pulls only (Cn, nbins-1) edges + counts (KBs)."""
    nrow = frame.nrow
    ns = min(nrow, sample)
    key = (nbins, ns, jax.default_backend())
    prog = _EDGE_PROG.get(key)
    if prog is None:

        @jax.named_scope("ph_edges")
        def run(X):  # (ns, Cn)
            xs = jnp.sort(X, axis=0)  # NaN sort to the end
            m = (~jnp.isnan(X)).sum(axis=0)  # (Cn,)
            q = jnp.linspace(0.0, 1.0, nbins + 1)[1:-1]  # (nbins-1,)
            pos = q[None, :] * jnp.maximum(m[:, None] - 1, 0)  # (Cn, nbins-1)
            lo = jnp.floor(pos).astype(jnp.int32)
            frac = (pos - lo).astype(jnp.float32)
            hi = jnp.minimum(lo + 1, jnp.maximum(m[:, None] - 1, 0))
            g = lambda idx: jnp.take_along_axis(xs.T, idx, axis=1)
            e = g(lo) * (1 - frac) + g(hi) * frac  # (Cn, nbins-1)
            return e.astype(jnp.float32), m

        prog = jax.jit(run)
        _EDGE_PROG[key] = prog

    idx = np.round(np.linspace(0, nrow - 1, ns)).astype(np.int32)
    idx_dev = jnp.asarray(idx)
    X = jnp.stack([frame.vec(n).data[idx_dev] for n in names], axis=1)
    e, m = prog(X)
    return np.asarray(e), np.asarray(m)


@_mx.span("tree.fit_bins")  # ends in the pull of the edges to the host
def fit_bins(frame: Frame, cols: list[str], nbins: int = MAX_BINS, sample: int = 200_000, seed: int = 7, nbins_cats: int | None = None) -> BinSpec:
    """Compute per-column quantile edges from (a sample of) the data.

    CPU: host numpy on pulled columns (the exact path tests pin). TPU: one
    fused device program + a KB-sized pull (see _device_quantile_edges).
    """
    nbins = min(nbins, MAX_BINS)
    C = len(cols)
    is_cat = np.zeros(C, bool)
    nb = np.zeros(C, np.int64)
    edges = np.full((C, MAX_BINS - 1), np.inf, np.float32)
    cards = np.zeros(C, np.int64)
    domains: list = [None] * C
    rng = np.random.default_rng(seed)

    numeric: list[int] = []
    for ci, name in enumerate(cols):
        v = frame.vec(name)
        if v.is_categorical():
            is_cat[ci] = True
            cards[ci] = v.cardinality
            # nbins_cats (upstream's categorical cap): levels past the cap
            # group into the last bin via the binning clip below. Like
            # upstream, it is INDEPENDENT of the numeric nbins — only the
            # uint8 code space bounds it
            cap = MAX_BINS if nbins_cats is None else min(nbins_cats, MAX_BINS)
            nb[ci] = min(v.cardinality, max(cap, 1))
            domains[ci] = v.domain
        else:
            numeric.append(ci)

    if numeric and jax.default_backend() != "cpu":
        e_dev, m = _device_quantile_edges(
            frame, [cols[ci] for ci in numeric], nbins, sample
        )
        for row, ci in enumerate(numeric):
            if m[row] == 0:
                nb[ci] = 1
                continue
            e = np.unique(e_dev[row].astype(np.float32))
            e = e[np.isfinite(e)]
            nb[ci] = len(e) + 1
            edges[ci, : len(e)] = e
    else:
        for ci in numeric:
            x = frame.vec(cols[ci]).to_numpy()
            x = x[~np.isnan(x)]
            if len(x) == 0:
                nb[ci] = 1
                continue
            if len(x) > sample:
                x = rng.choice(x, sample, replace=False)
            qs = np.quantile(x, np.linspace(0, 1, nbins + 1)[1:-1])
            e = np.unique(qs.astype(np.float32))
            nb[ci] = len(e) + 1
            edges[ci, : len(e)] = e
    return BinSpec(list(cols), is_cat, nb, edges, cards, domains)


def fit_bins_for(params, frame: Frame, cols: list[str]) -> BinSpec:
    """fit_bins driven by a SharedTreeParams-style object — the one place
    the tree builders derive binning from params (and the one place the
    nbins_top_level no-op is disclosed at runtime)."""
    from h2o3_tpu.utils.log import Log

    if getattr(params, "nbins_top_level", 1024) != 1024:
        Log.warn(
            "nbins_top_level has no effect: bins are static quantiles fit "
            "once (upstream re-bins per level); tune nbins / nbins_cats")
    return fit_bins(
        frame, cols, nbins=params.nbins,
        seed=abs(params.seed) or 7,
        nbins_cats=getattr(params, "nbins_cats", None),
    )


_BINFRAME_PROG: dict = {}


def _u8_cache_enabled() -> bool:
    from h2o3_tpu import config

    return config.get_bool("H2O3_TPU_TREE_U8CACHE")


def _spec_fingerprint(spec: BinSpec) -> tuple:
    """Content fingerprint of a BinSpec — the u8 bin-code cache key.

    Two specs with equal fingerprints bin a given frame to the identical
    code matrix, so a cache hit returns the same buffer a fresh bin_frame
    call would produce (the knob's bit-for-bit guarantee)."""
    doms = tuple(
        tuple(d) if d is not None else None
        for d in (spec.domains or [None] * spec.ncols)
    )
    return (
        tuple(spec.names), spec.is_cat.tobytes(), spec.nbins.tobytes(),
        spec.edges.tobytes(), doms, jax.default_backend(),
    )


def bin_frame(spec: BinSpec, frame: Frame):
    """Prebin all feature columns to a row-sharded (npad, C) uint8 matrix.

    All columns bin in ONE fused device program (one dispatch, not one per
    column).

    u8-code-native frames (ISSUE 16, ``H2O3_TPU_TREE_U8CACHE``): the code
    matrix is memoized on the frame keyed by the spec's content
    fingerprint, so repeated builds over one frame (AutoML, grids, CV,
    checkpoint restarts) stop re-reading every f32 column per build — the
    dominant frame HBM traffic of a multi-model session. The traffic an
    ACTUAL binning pass moves (one f32 read + one u8 write per cell) is
    tallied under ``tree_hist_hbm_bytes_total{path=rebin}``; cache hits
    move nothing and tally nothing, which is what the wave-2 A/B measures.

    Span ``tree.bin_frame{cache=hit|miss}``: a miss is the enqueue of the
    pass (it ends in no sync; the pass's device time is under ``ph_bin``).
    """
    from h2o3_tpu.parallel.mesh import mesh_epoch

    cache = None
    fp = None
    B = None
    if _u8_cache_enabled():
        fp = _spec_fingerprint(spec)
        cache = frame.__dict__.setdefault("_bin_cache", {})
        hit = cache.get(fp)
        if hit is not None:
            epoch, B = hit
            if epoch != mesh_epoch():
                # cached codes were padded/placed for a dead topology
                # (elastic reform, ISSUE 17): drop and rebin on the new mesh
                cache.pop(fp, None)
                B = None
    with _mx.span("tree.bin_frame", cache="miss" if B is None else "hit"):
        if B is None:
            B = _bin_pass(spec, frame)
            if cache is not None:
                cache[fp] = (mesh_epoch(), B)
    return B


def _bin_pass(spec: BinSpec, frame: Frame):
    """The binning pass itself (:func:`bin_frame` without the cache)."""
    from h2o3_tpu.models.datainfo import _adapt_codes

    datas = []
    for ci, name in enumerate(spec.names):
        v = frame.vec(name)
        if spec.is_cat[ci]:
            dom = spec.domains[ci] if spec.domains else v.domain
            datas.append(_adapt_codes(v, dom))
        else:
            datas.append(v.data)

    key = (tuple(bool(c) for c in spec.is_cat), tuple(int(n) for n in spec.nbins),
           jax.default_backend())
    prog = _BINFRAME_PROG.get(key)
    if prog is None:
        is_cat_t, nbins_t = key[0], key[1]

        @jax.named_scope("ph_bin")
        def run(datas, edges):
            cols = []
            for ci in range(len(is_cat_t)):
                d = datas[ci]
                if is_cat_t[ci]:
                    cols.append(jnp.clip(d + 1, 0, nbins_t[ci]).astype(jnp.uint8))
                else:
                    e = edges[ci, : max(nbins_t[ci] - 1, 0)]
                    b = jnp.searchsorted(e, d, side="left").astype(jnp.int32) + 1
                    b = jnp.where(jnp.isnan(d), 0, b)
                    cols.append(b.astype(jnp.uint8))
            return jnp.stack(cols, axis=1)

        prog = jax.jit(run)
        _BINFRAME_PROG[key] = prog

    B = prog(tuple(datas), jnp.asarray(spec.edges))
    B = jax.device_put(B, row_sharding())
    # rebin traffic model: one f32 read + one u8 write per (row, col) cell
    # (lazy import: shared_tree imports this module)
    from h2o3_tpu.models.tree.shared_tree import _HIST_HBM_BYTES

    _HIST_HBM_BYTES.inc(5.0 * B.shape[0] * B.shape[1], path="rebin")
    return B


# ---------------------------------------------------------------------------
# Exclusive feature bundling (ISSUE 16, H2O3_TPU_TREE_EFB — arXiv:1706.08359
# §4). Sparse/one-hot suites carry many columns that sit at one dominant bin
# code almost everywhere; two such columns whose non-default rows never
# overlap can share ONE u8 column (their non-default codes mapped to
# disjoint sub-ranges), shrinking the histogram C dimension before the
# kernel grid sees it. The pass is host-side and greedy at BinSpec build
# time, requires ZERO conflicts (no row non-default in two bundled columns
# at once — the lossless regime, unlike LightGBM's bounded-conflict mode),
# and the device histogram is expanded back to real columns right after
# accumulation (expand_hist), so split records, varimp, MOJO and scoring
# never see bundle ids. The default-bin cell is reconstructed as
# node_total − Σ(non-default cells): exact whenever the stat lanes are
# dyadic/in-range (the parity suites), within f32 associativity otherwise.


@dataclass
class EFBPlan:
    """Host-side exclusive-feature-bundling plan for one BinSpec."""

    n_cols: int          # real feature count C
    n_bins: int          # total code space per column (spec.max_bins)
    bundles: list        # list[list[int]] — real col ids per bundled column
    src_col: np.ndarray  # (C,) int32: bundled column carrying real col f
    offset: np.ndarray   # (C,) int32: code offset of col f inside its bundle
    default: np.ndarray  # (C,) int32: dominant code d_f; -1 = pass-through
    nbins: np.ndarray    # (C,) int32: non-default code count per column

    @property
    def n_cols_b(self) -> int:
        return len(self.bundles)

    @property
    def key(self) -> tuple:
        """Hashable content fingerprint for program caches."""
        return (self.n_cols, self.n_bins, self.src_col.tobytes(),
                self.offset.tobytes(), self.default.tobytes(),
                self.nbins.tobytes())


def fit_efb(spec: BinSpec, bins_u8, nrow: int | None = None):
    """Greedy zero-conflict bundling over the frame's host bin codes.

    Returns an :class:`EFBPlan` when bundling shrinks the column count,
    else ``None``. O(C · bundles · rows) host work on the pulled u8 matrix
    — a one-time cost per BinSpec, dwarfed by the per-tree device work it
    removes."""
    B_host = np.asarray(bins_u8)
    if nrow is not None:
        B_host = B_host[:nrow]
    n, C = B_host.shape
    if C != spec.ncols or n == 0:
        return None
    total_codes = spec.max_bins

    # dominant code + non-default mask per column (cols at >50% non-default
    # rows can hardly co-bundle and skip straight to pass-through)
    dominant = np.zeros(C, np.int32)
    nz_masks: list = [None] * C
    order: list[int] = []
    for f in range(C):
        codes, counts = np.unique(B_host[:, f], return_counts=True)
        d = int(codes[np.argmax(counts)])
        nnz = n - int(counts.max())
        if nnz > n // 2 or int(spec.nbins[f]) + 1 > total_codes:
            continue
        dominant[f] = d
        nz_masks[f] = B_host[:, f] != d
        order.append(f)
    order.sort(key=lambda f: int(nz_masks[f].sum()))

    src_col = np.zeros(C, np.int32)
    offset = np.zeros(C, np.int32)
    default = np.full(C, -1, np.int32)
    nbins_nd = np.asarray(spec.nbins, np.int32).copy()  # non-default codes

    bundles: list[list[int]] = []
    occ: list[np.ndarray] = []   # per-bundle occupied-rows mask
    used: list[int] = []         # per-bundle consumed code count
    multi: set[int] = set()      # bundles holding >1 column
    for f in order:
        need = int(nbins_nd[f])
        placed = False
        for bi in range(len(bundles)):
            if used[bi] + need > total_codes - 1:
                continue
            if np.any(occ[bi] & nz_masks[f]):
                continue
            src_col[f] = bi
            offset[f] = used[bi]
            default[f] = dominant[f]
            bundles[bi].append(f)
            occ[bi] |= nz_masks[f]
            used[bi] += need
            multi.add(bi)
            placed = True
            break
        if not placed:
            src_col[f] = len(bundles)
            offset[f] = 0
            default[f] = dominant[f]
            bundles.append([f])
            occ.append(nz_masks[f].copy())
            used.append(need)
    # cols skipped above (dense / wide) pass through unchanged
    for f in range(C):
        if nz_masks[f] is None:
            src_col[f] = len(bundles)
            bundles.append([f])
            occ.append(np.zeros(0, bool))
            used.append(0)
    # a column alone in its bundle needs no re-coding: pass it through so
    # its histogram column is bit-identical (no rank mapping at all)
    for bi, group in enumerate(bundles):
        if bi not in multi and len(group) == 1:
            default[group[0]] = -1
            offset[group[0]] = 0

    if len(bundles) >= C:
        return None
    return EFBPlan(C, total_codes, bundles, src_col, offset, default,
                   nbins_nd)


_BUNDLE_PROG: dict = {}


def bundle_bins(plan: EFBPlan, bins_u8):
    """Build the (npad, Cb) bundled u8 code matrix on device.

    Bundle code 0 = every member at its default; member f's code c != d_f
    maps to ``offset_f + rank_f(c)`` where rank skips d_f (rank 1..nbins_f)
    — a bijection, since zero conflicts mean at most one member is
    non-default per row. Pass-through columns copy verbatim."""
    key = (plan.key, jax.default_backend())
    prog = _BUNDLE_PROG.get(key)
    if prog is None:
        groups = [list(g) for g in plan.bundles]
        offs = plan.offset.copy()
        defs = plan.default.copy()

        def run(B):
            cols = []
            for group in groups:
                if len(group) == 1 and defs[group[0]] < 0:
                    cols.append(B[:, group[0]])
                    continue
                acc = jnp.zeros(B.shape[0], jnp.int32)
                for f in group:
                    c = B[:, f].astype(jnp.int32)
                    d = int(defs[f])
                    rank = jnp.where(c < d, c + 1, c)
                    acc = acc + jnp.where(c == d, 0, int(offs[f]) + rank)
                cols.append(acc.astype(jnp.uint8))
            return jnp.stack(cols, axis=1)

        prog = jax.jit(run)
        _BUNDLE_PROG[key] = prog
    return jax.device_put(prog(bins_u8), row_sharding())


def expand_arrays(plan: EFBPlan, n_cols_pad: int, n_bins_h: int):
    """Precompute the (Cp, Bh) gather tables expand_hist consumes.

    ``kind``: 0 = structurally-zero cell, 1 = gather from src_bin of the
    carrying bundled column, 2 = the default cell (node_total − Σ
    non-default). Padded columns (f >= C) reproduce the all-codes-NA
    padding histogram: all node mass in bin 0."""
    Cp, Bh = n_cols_pad, n_bins_h
    src_col = np.zeros(Cp, np.int32)
    src_bin = np.zeros((Cp, Bh), np.int32)
    kind = np.zeros((Cp, Bh), np.int8)
    for f in range(plan.n_cols):
        src_col[f] = plan.src_col[f]
        ncodes = int(plan.nbins[f]) + 1  # real codes 0..nbins_f
        d = int(plan.default[f])
        for b in range(min(ncodes, Bh)):
            if d < 0:  # pass-through: identity gather
                src_bin[f, b] = b
                kind[f, b] = 1
            elif b == d:
                kind[f, b] = 2
            else:
                rank = b + 1 if b < d else b
                src_bin[f, b] = int(plan.offset[f]) + rank
                kind[f, b] = 1
    for f in range(plan.n_cols, Cp):
        kind[f, 0] = 2  # padded col: everything at the NA code
    return src_col, src_bin, kind


def expand_hist(arrs, hist_b):
    """Expand a bundled histogram (N, Cb', Bh, S) to real columns
    (N, Cp, Bh, S) — pure traced function, usable inside the tree
    programs. ``node_total`` per (node, stat) comes from summing any one
    bundled column's bins (every row lands in exactly one code of every
    column)."""
    src_col, src_bin, kind = (jnp.asarray(a) for a in arrs)
    g = jnp.take(hist_b, src_col, axis=1)              # (N, Cp, Bh, S)
    idx = jnp.broadcast_to(src_bin[None, :, :, None], g.shape)
    G = jnp.take_along_axis(g, idx, axis=2)
    node_tot = hist_b[:, 0, :, :].sum(axis=1)          # (N, S)
    gather = (kind == 1)[None, :, :, None]
    dflt = node_tot[:, None, :] - jnp.where(gather, G, 0.0).sum(axis=2)
    return jnp.where(
        gather, G,
        jnp.where((kind == 2)[None, :, :, None], dflt[:, :, None, :], 0.0))
