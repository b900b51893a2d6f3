"""Quantized collective lane + hierarchical reduction placement — the
wire-level successor of the PR-5/PR-8 sharded reduces (ROADMAP item 3).

Every hot cross-device reduction in the stack (the tree histogram
``hist_reduce``, the GLM Gram ``gram_reduce``, the DL gradient
``dl_grad_reduce``) used to move full-precision float32. EQuARX
(arXiv:2506.17615) shows a block-quantized allreduce inside XLA recovers
most of that bandwidth at negligible accuracy cost, and arXiv:2110.10548
shows reduction *placement* on hierarchical interconnects (reduce within
the cheap level first, cross the expensive one with less) is a second,
independent multiplier. This module provides both as drop-in wrappers for
``lax.psum`` / ``lax.psum_scatter`` (scatter dimension 0, tiled), used
inside the existing ``shard_map`` bodies:

- **Block quantization** (``H2O3_TPU_COLLECTIVE_QUANT``): each device's
  local contribution is split into per-chunk payloads, blocked
  (``H2O3_TPU_COLLECTIVE_QUANT_BLOCK`` elements per block), and encoded as
  an int8 payload + one f32 scale per block. The reduce itself decomposes
  into ``all_to_all`` (the int8 payload + scales really are what crosses
  the wire — this is not an emulation) followed by a dequantize-sum in
  f32. Scales are POWERS OF TWO: scaling is then exact in f32, so any
  block whose values are integers with magnitude <= 127 round-trips
  BIT-EXACTLY — which is precisely the regime of the PR-5 adversarial tie
  suites (unit weights, integer targets), so split decisions there stay
  bit-identical to the exact lane. ``passes=2`` adds a residual-correction
  pass (quantize and ship ``x - dequant(quant(x))`` too, ~14 effective
  mantissa bits): the gain/solve-critical reduces (GLM Gram, DL gradient)
  run with it so IRLS coefficients stay inside the pinned parity
  envelopes; when pass 1 is already exact the residual is exactly zero.
- **Exact side lanes**: small gain-critical payloads that feed argmaxes or
  solves directly (the packed GLM b/deviance psum, node totals, winner
  gathers, the solve's G all_gather, the DL updated-param gather) stay
  f32 — only the bulk reduce payload quantizes.
- **Hierarchical two-stage reduction** (``H2O3_TPU_COLLECTIVE_HIER``, mesh
  levels resolved by ``parallel/mesh.hier_inner``): stage 1 reduces
  exactly within each contiguous inner sub-axis group (the ICI level),
  stage 2 moves only the (quantized) chunk payloads across groups (the DCN
  level) via grouped ``all_to_all``. The tiled chunk-d-to-device-d
  contract of ``psum_scatter`` is preserved by remapping each device's
  outer-strided chunk set before the cross-group exchange.

Consistency invariant (load-bearing for the PR-5 parity suites): the
wrapped ``psum`` is implemented as the wrapped reduce-scatter over the same
P-chunk grid followed by an EXACT all_gather, so a replicated reduction's
chunk ``d`` is bit-identical to what the sharded lane hands device ``d`` —
for ANY data, quantized or not. ``H2O3_TPU_COLLECTIVE_QUANT=0`` (with the
hierarchy knob unset) routes every call straight to the stock primitives:
bit-for-bit the pre-lane programs.

This module also owns the trace-time collective byte tally (moved here
from ``ops/histogram.py``; the old names are re-exported there). Entries
now carry a ``lane`` (``quant``/``exact``) so
``tree_collective_bytes_total`` can expose the wire-compression claim as a
counter dimension, and a ``group`` tag replacing the old trace-time weight
multiplier: entries recorded under ``tally_group("sat")`` are scaled at
DISPATCH time by the saturated-region iterations the program actually
executed (read from the build stats), not by the trace-time upper bound.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp

from h2o3_tpu.parallel.mesh import ROWS_AXIS

# ---------------------------------------------------------------------------
# collective byte tally — trace-time accounting of the cross-device payload
# the compiled programs move. Collectives live inside fused jitted programs,
# so per-execution host counting is impossible; instead every collective
# call site records, AT TRACE TIME, the bytes its one execution will move,
# and the dispatching caller (shared_tree._run_counted) captures the tally
# during the program's first trace and replays it per dispatch. The model is
# REPLICATION VOLUME — the reduced/gathered bytes the collective leaves on
# each device (psum: the full reduced tensor, psum_scatter: only the kept
# 1/P shard, all_gather: P x the local contribution) — except that the
# quant lane's reduce entries count the COMPRESSED payload (int8 + scales,
# the wire bytes a real quantized collective moves), which is the whole
# point of the lane. A 1-device mesh moves nothing and tallies 0.

_TALLY: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "h2o3_coll_tally", default=None
)
_TALLY_GROUP: contextvars.ContextVar[str] = contextvars.ContextVar(
    "h2o3_coll_group", default=""
)


@contextlib.contextmanager
def collective_tally(out: list):
    """Collect (phase, lane, group, bytes) entries recorded while tracing
    under this."""
    tok = _TALLY.set(out)
    try:
        yield out
    finally:
        _TALLY.reset(tok)


@contextlib.contextmanager
def tally_group(name: str):
    """Tag entries recorded inside with a dispatch-time weight group.

    The node_cap-saturated ``while_loop`` body traces ONCE but executes a
    data-dependent number of times; entries recorded under
    ``tally_group("sat")`` are multiplied at dispatch time by the EXECUTED
    iteration count the program returns (shared_tree._run_counted), so the
    counters report actual volume instead of the old n_sat upper bound."""
    tok = _TALLY_GROUP.set(name)
    try:
        yield
    finally:
        _TALLY_GROUP.reset(tok)


def record_collective(phase: str, nbytes: float, lane: str = "exact") -> None:
    lst = _TALLY.get()
    if lst is not None and nbytes > 0:
        lst.append((phase, lane, _TALLY_GROUP.get(), float(nbytes)))


def record_hbm(path: str, nbytes: float) -> None:
    """Trace-time tally of the MODELED per-device HBM traffic of the
    histogram+split phases (``tree_hist_hbm_bytes_total{path}``): one write
    per materialized intermediate plus one read per consumed one, recorded
    where the intermediates are created and replayed per dispatch by
    shared_tree._run_counted. Rides the same tally as the collective bytes under an ``hbm/`` phase prefix."""
    record_collective("hbm/" + path, nbytes)


# ---------------------------------------------------------------------------
# lane configuration


def quant_enabled() -> bool:
    """Whether the block-quantized lane is on. ``auto`` (default) engages
    only when the mesh spans >1 process — the ICI+DCN regime EQuARX targets,
    where wire bytes are the binding constraint; ``1`` forces it anywhere
    (the A/B + parity-test lane); ``0`` restores the stock collectives
    bit-for-bit."""
    from h2o3_tpu import config

    v = config.get("H2O3_TPU_COLLECTIVE_QUANT").strip().lower()
    if v in ("auto", ""):
        return jax.process_count() > 1
    return v not in ("0", "false")


def quant_block() -> int:
    from h2o3_tpu import config

    return max(8, config.get_int("H2O3_TPU_COLLECTIVE_QUANT_BLOCK"))


def quant_key() -> tuple:
    """Program-cache component: the lane changes the traced collectives, so
    a program compiled under one (quant, block, hierarchy) setting must
    never serve another. Folded into ``parallel/mesh.mesh_key`` so every
    tree/GLM/DL program cache picks it up through the one chokepoint."""
    from h2o3_tpu.parallel.mesh import hier_inner, n_col_shards

    return (quant_enabled(), quant_block(), hier_inner(n_col_shards()))


def lane_active(n_dev: int) -> bool:
    from h2o3_tpu.parallel.mesh import hier_inner

    return n_dev > 1 and (quant_enabled() or hier_inner(n_dev) > 0)


def payload_bytes(nelem: int, quant: bool, block: int, passes: int) -> float:
    """Wire bytes of one ``nelem``-element reduce payload: int8 + one f32
    scale per block, per pass, vs plain f32."""
    if not quant:
        return nelem * 4.0
    return float(nelem) * passes * (1.0 + 4.0 / block)


def modeled_reduce_bytes(
    nelem: int, n_dev: int, *, passes: int = 1
) -> dict[str, float]:
    """Per-lane replication-volume model of ONE wrapped ``psum_scatter``
    over ``nelem`` elements — what the GLM/DL host tallies (which cannot
    ride the trace-time tally) record per executed iteration/minibatch.
    Mirrors the wrapper's own recording exactly, including the 2-D mesh's
    stage-1 exact rows-axis psum (``n_dev`` stays the TOTAL device count;
    the lane geometry is read from the process mesh)."""
    from h2o3_tpu.parallel.mesh import hier_inner, n_col_shards, n_row_groups

    if n_dev <= 1:
        return {}
    quant = quant_enabled()
    rows = n_row_groups()
    ncol = n_col_shards()
    inner = hier_inner(ncol)
    out = {"exact": 0.0, "quant": 0.0}
    if rows > 1:
        out["exact"] += nelem * 4.0  # stage-1 exact rows-axis reduce
    if ncol > 1:
        if not quant and not inner:
            out["exact"] += nelem * 4.0 / ncol
        else:
            if inner:
                out["exact"] += nelem * 4.0  # intra-group exact reduce
            out["quant" if quant else "exact"] += payload_bytes(
                nelem // ncol, quant, quant_block(), passes
            )
    return {k: v for k, v in out.items() if v}


# ---------------------------------------------------------------------------
# block quantizer (int8 payload + power-of-two f32 scale per block)


def _encode8(xb):
    """``xb``: (..., nblk, B) f32 → (int8 same shape, f32 (..., nblk)).

    The per-block scale is the smallest POWER OF TWO ``s`` with
    ``max|x|/s <= 127``: scaling by a power of two is exact in f32, so
    integer-valued blocks with magnitude <= 127 (the adversarial tie
    suites' regime) quantize losslessly. An all-zero block gets s=1."""
    amax = jnp.max(jnp.abs(xb), axis=-1)
    e = jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-38) / 127.0))
    s = jnp.where(amax > 0, jnp.exp2(e), 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(xb / s[..., None]), -127.0, 127.0).astype(jnp.int8)
    return q, s


def _decode8(q, s):
    return q.astype(jnp.float32) * s[..., None]


# ---------------------------------------------------------------------------
# the lane core


def _exchange_sum(flat, axis_name, groups, n_peers: int, quant: bool,
                  block: int, passes: int):
    """The reduce step of a reduce-scatter among ``n_peers`` devices (the
    whole axis when ``groups`` is None, else each listed group): ``flat``
    is (n_peers, L) with row ``p`` destined for peer ``p``; returns the
    (L,) dequantized sum of the rows this device received. Payloads cross
    as int8 + f32 block scales when ``quant`` (plus an int8 residual pass
    when ``passes >= 2``); the dequantize-sum runs in f32 in ascending
    peer order — a fixed order shared by the replicated and sharded
    wrappers, which is what keeps their results bit-identical."""
    L = flat.shape[1]
    if not quant:
        ft = jax.lax.all_to_all(
            flat, axis_name, 0, 0, axis_index_groups=groups)
        return ft.sum(axis=0)
    Lp = -(-L // block) * block
    fp = jnp.pad(flat, ((0, 0), (0, Lp - L)))
    xb = fp.reshape(n_peers, Lp // block, block)
    parts = [_encode8(xb)]
    if passes >= 2:
        # residual-correction pass: exactly zero when pass 1 was lossless
        parts.append(_encode8(xb - _decode8(*parts[0])))
    acc = jnp.zeros_like(xb)
    for q, s in parts:
        qt = jax.lax.all_to_all(q, axis_name, 0, 0, axis_index_groups=groups)
        st = jax.lax.all_to_all(s, axis_name, 0, 0, axis_index_groups=groups)
        acc = acc + _decode8(qt, st)
    return acc.sum(axis=0).reshape(Lp)[:L]


def _scatter_lane(x, axis_name, n_dev: int, phase: str | None, passes: int,
                  lane_axis: int | None = None):
    """The wrapped tiled reduce-scatter over axis 0 (chunk d → device d),
    lane active. ``x`` axis 0 must be divisible by ``n_dev``.

    ``lane_axis`` names a STAT-LANE axis of ``x`` (e.g. the histogram's S
    axis, whose {w, wy, wh} lanes differ by orders of magnitude): it is
    moved next to the chunk axis before the per-chunk flattening so
    quantization blocks never straddle lanes — each lane gets scales
    matched to its own magnitude instead of the largest cohabitant's.
    Purely an internal re-blocking: the returned chunk is in ``x``'s
    layout, and the exact path ignores it entirely."""
    from h2o3_tpu.parallel.mesh import hier_groups, hier_inner

    if lane_axis is not None and quant_enabled():
        ax = lane_axis % x.ndim
        assert ax != 0, "lane_axis cannot be the scatter axis"
        moved = _scatter_lane(
            jnp.moveaxis(x, ax, 1), axis_name, n_dev, phase, passes)
        return jnp.moveaxis(moved, 1, ax)

    quant = quant_enabled()
    inner = hier_inner(n_dev)
    block = quant_block()
    nelem = int(x.size)
    M0 = x.shape[0]
    assert M0 % n_dev == 0, (M0, n_dev)
    chunk_shape = (M0 // n_dev,) + x.shape[1:]

    if inner:
        ig, xg = hier_groups(n_dev, inner)
        # stage 1: exact reduce within the (cheap, ICI-level) inner groups
        x1 = jax.lax.psum(x, axis_name, axis_index_groups=ig)
        if phase:
            record_collective(phase, nelem * 4.0, lane="exact")
        outer = n_dev // inner
        # stage 2: device d = (g, j) needs global chunk d = g*inner + j; the
        # chunks with index ≡ j (mod inner) live across the cross group
        # {(g', j)} — gather this device's outer-strided chunk set (ordered
        # by destination g') and exchange within the cross group
        xc = x1.reshape(n_dev, -1)
        j = jax.lax.axis_index(axis_name) % inner
        sel = j + inner * jnp.arange(outer)
        mine = jnp.take(xc, sel, axis=0)
        red = _exchange_sum(mine, axis_name, xg, outer, quant, block, passes)
    else:
        red = _exchange_sum(
            x.reshape(n_dev, -1), axis_name, None, n_dev, quant, block,
            passes)
    if phase:
        record_collective(
            phase, payload_bytes(nelem // n_dev, quant, block, passes),
            lane="quant" if quant else "exact")
    return red.reshape(chunk_shape)


# ---------------------------------------------------------------------------
# public wrappers (call inside shard_map bodies, like the lax primitives)


def _lane_geometry(mesh, axis_name: str | None, n_dev: int):
    """``(stage1_axis, lane_axis, lane_width)`` — the reduce decomposition
    for the current mesh. On a 2-D rows×cols mesh the wrappers first run an
    EXACT ``lax.psum`` over the ``rows`` axis (the contiguous-device /
    intra-host level — arXiv:2110.10548's placement expressed as mesh
    structure) and the lane proper (quantized, scattered) runs over
    ``cols`` alone; the legacy 1-D mesh keeps its single ``rows``-axis lane
    with the caller-passed ``n_dev`` width. An explicit ``axis_name`` pins
    a single-stage reduce over that axis (test/microbench lane)."""
    from h2o3_tpu.parallel.mesh import (
        COLS_AXIS, get_mesh, is_2d, n_row_groups,
    )

    if axis_name is not None:
        return None, axis_name, n_dev
    m = mesh or get_mesh()
    if is_2d(m):
        rows = n_row_groups(m)
        return (ROWS_AXIS if rows > 1 else None), COLS_AXIS, m.shape[COLS_AXIS]
    return None, ROWS_AXIS, n_dev


def psum_scatter(x, *, n_dev: int, phase: str | None = None,
                 passes: int = 1, lane_axis: int | None = None,
                 axis_name: str | None = None, mesh=None):
    """Drop-in for ``lax.psum_scatter(x, axis, scatter_dimension=0,
    tiled=True)`` routed through the quantized/hierarchical lane when
    active. ``phase`` (when given) records the byte tally — call sites
    whose dispatch loop tallies host-side (GLM/DL) pass None and use
    :func:`modeled_reduce_bytes`. ``passes=2`` adds the residual-correction
    pass (the solve-critical reduces); ``lane_axis`` keeps mixed-magnitude
    stat lanes in separate quantization blocks (see :func:`_scatter_lane`).

    ``n_dev`` is the TOTAL device count of the caller's mesh; on a 2-D
    rows×cols mesh the reduce decomposes as exact ``psum`` over ``rows`` +
    a ``cols``-wide scatter, so the result is sharded over the COLUMN-BLOCK
    axis (1/n_col_shards per device, replicated across rows groups)."""
    stage1, ax, ncol = _lane_geometry(mesh, axis_name, n_dev)
    if stage1 is not None:
        x = jax.lax.psum(x, stage1)
        if phase:
            record_collective(phase, x.size * 4.0, lane="exact")
    if ncol <= 1:
        return jax.lax.psum_scatter(
            x, ax, scatter_dimension=0, tiled=True)
    if not lane_active(ncol):
        if phase:
            record_collective(phase, x.size * 4.0 / ncol, lane="exact")
        return jax.lax.psum_scatter(
            x, ax, scatter_dimension=0, tiled=True)
    return _scatter_lane(x, ax, ncol, phase, passes, lane_axis)


def psum(x, *, n_dev: int, phase: str | None = None, passes: int = 1,
         lane_axis: int | None = None, axis_name: str | None = None,
         mesh=None):
    """Drop-in for ``lax.psum(x, axis)`` (leading-axis tensors). The lane
    form is reduce-scatter over the SAME chunk grid as
    :func:`psum_scatter` (axis 0 padded up to the lane width) + an EXACT
    all_gather — so a replicated reduction's chunk ``d`` stays
    bit-identical to the sharded lane's device-``d`` block, for any data.
    On a 2-D mesh both wrappers share the identical stage-1 rows-axis
    ``psum``, so the invariant carries over to the pod shape. The broadcast
    half stays f32 (exact lane) by design; the compression claim lives on
    the scatter pipeline, which is the default (``H2O3_TPU_SPLIT_SHARD=1``)."""
    stage1, ax, ncol = _lane_geometry(mesh, axis_name, n_dev)
    if stage1 is not None:
        x = jax.lax.psum(x, stage1)
        if phase:
            record_collective(phase, x.size * 4.0, lane="exact")
    if ncol <= 1:
        return jax.lax.psum(x, ax)
    if not lane_active(ncol):
        if phase:
            record_collective(phase, x.size * 4.0, lane="exact")
        return jax.lax.psum(x, ax)
    M0 = x.shape[0]
    M0p = -(-M0 // ncol) * ncol
    if M0p > M0:
        x = jnp.pad(x, ((0, M0p - M0),) + ((0, 0),) * (x.ndim - 1))
    red = _scatter_lane(x, ax, ncol, phase, passes, lane_axis)
    full = jax.lax.all_gather(red, ax, axis=0, tiled=True)
    if phase:  # the broadcast leaves the full reduced tensor on each device
        record_collective(phase, x.size * 4.0, lane="exact")
    return full[:M0]


def exact_psum(x, mesh=None):
    """Exact f32 ``psum`` over the FULL row-shard device set — the small
    gain/solve-critical side payloads (packed b/deviance, weight sums,
    losses). On a 2-D mesh it stages rows-then-cols so its float grouping
    matches the lane wrappers' stage-1 exactly; on the 1-D mesh it is the
    stock single-axis psum, bit-for-bit."""
    from h2o3_tpu.parallel.mesh import COLS_AXIS, get_mesh, is_2d

    m = mesh or get_mesh()
    if is_2d(m):
        return jax.lax.psum(jax.lax.psum(x, ROWS_AXIS), COLS_AXIS)
    return jax.lax.psum(x, ROWS_AXIS)


def exact_pmax(x, mesh=None, phase: str | None = None):
    """Exact ``pmax`` over the full row-shard device set — the min/max lanes
    of the sharded group-by segment reduce (extrema cannot ride the additive
    quant lane; they are exact by construction in any order). Staged
    rows-then-cols on a 2-D mesh like :func:`exact_psum`."""
    from h2o3_tpu.parallel.mesh import COLS_AXIS, get_mesh, is_2d

    m = mesh or get_mesh()
    if phase:
        record_collective(phase, x.size * 4.0, lane="exact")
    if is_2d(m):
        return jax.lax.pmax(jax.lax.pmax(x, ROWS_AXIS), COLS_AXIS)
    return jax.lax.pmax(x, ROWS_AXIS)


def exact_pmin(x, mesh=None, phase: str | None = None):
    """Exact ``pmin`` counterpart of :func:`exact_pmax`."""
    from h2o3_tpu.parallel.mesh import COLS_AXIS, get_mesh, is_2d

    m = mesh or get_mesh()
    if phase:
        record_collective(phase, x.size * 4.0, lane="exact")
    if is_2d(m):
        return jax.lax.pmin(jax.lax.pmin(x, ROWS_AXIS), COLS_AXIS)
    return jax.lax.pmin(x, ROWS_AXIS)


def all_to_all_exchange(x, *, axis_name: str = ROWS_AXIS,
                        phase: str | None = None):
    """Tiled ``all_to_all`` over leading axis 0 (bucket ``d`` of every
    device lands on device ``d``) with the trace-time byte tally — the
    radix-partition exchange step of the distributed hash join. Payloads
    stay exact (small int key codes + row indices; quantizing indices would
    corrupt the join), so the whole tensor counts as exact wire bytes."""
    if phase:
        record_collective(phase, x.size * x.dtype.itemsize, lane="exact")
    return jax.lax.all_to_all(x, axis_name, 0, 0, tiled=True)
