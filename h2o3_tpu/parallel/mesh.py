"""Device mesh management — the cluster-topology successor of H2O's cloud.

H2O forms a static cloud of JVM nodes (``water.H2O.CLOUD`` / ``water.Paxos``
[UNVERIFIED upstream paths, SURVEY.md §0]) and homes chunk *i* of every Vec on
a fixed node. Here the "cloud" is a ``jax.sharding.Mesh`` over all addressable
devices: every column of a Frame is sharded the same way along rows, which
reproduces H2O's aligned chunk layout (row-local compute) by construction.
Like the H2O cloud, the mesh is static once created.

Two mesh generations coexist (ISSUE 14):

- **1-D** ``("rows",)`` — the historical default: ONE device axis shards
  frame rows for the data-parallel phases AND re-shards histogram columns
  for the split phase (PR 5). Every pre-pod program ever compiled ran on
  this shape; it stays the single-process default bit-for-bit.
- **2-D** ``("rows", "cols")`` — the pod shape (``H2O3_TPU_MESH_ROWS``):
  frame rows shard over BOTH axes (cols-major, so row-shard *i* sits on
  ``jax.devices()[i]`` exactly like the 1-D mesh and per-process shard
  ranges stay contiguous — sharded ingest depends on it), histogram/Gram/
  gradient reductions run stage-1 EXACT over the ``rows`` axis (contiguous
  device runs — the ICI/intra-host level when rows = local device count)
  and stage-2 over ``cols`` (the DCN hop), and the split phase's column
  blocks shard over ``cols`` ONLY — row sharding and the PR-5/PR-6 column
  blocks finally compose instead of sharing one axis. This is
  hierarchy-aware reduction placement (arXiv:2110.10548) expressed as mesh
  structure; the PR-9 quantized lane then compresses exactly the cross-
  group stage (ops/collectives.py).

Multi-host (the H2O multi-node analog) rides the same mesh:
``jax.distributed`` initializes the coordination service
(cluster/multihost.py bootstraps it from env/args) and ``jax.devices()``
spans hosts; XLA collectives ride ICI within a slice and DCN across slices.
Nothing in the algorithm layer knows about hosts — exactly as H2O
algorithms never touch ``water.RPC`` directly.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROWS_AXIS = "rows"
COLS_AXIS = "cols"


def shard_map(f, mesh, in_specs, out_specs, check_vma=True):
    """``jax.shard_map`` with the mesh positional — the call shape every
    shard_map site in this codebase uses."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


_mesh: Mesh | None = None

# Monotonic topology generation (ISSUE 17, elastic recovery): bumped ONLY by
# :func:`reform_mesh` — the supervised-recovery reshape point. Vec device
# arrays and host mirrors record the epoch they were padded/placed under and
# lazily re-pad + re-shard when it moves (frame/frame.py); ChunkStores refuse
# to serve blocks planned under a dead topology (frame/chunkstore.py).
# ``set_mesh`` deliberately does NOT bump it: tests swap sub-meshes and
# manage their frames' placement themselves — that contract stays bit-exact.
_mesh_epoch: int = 0


def mesh_epoch() -> int:
    """The current topology generation (see ``_mesh_epoch``)."""
    return _mesh_epoch


def set_mesh(mesh: Mesh | None) -> None:
    global _mesh
    _mesh = mesh


def _mesh_rows_knob(n_dev: int) -> int:
    """Resolved ``H2O3_TPU_MESH_ROWS``: how many ROWS-axis groups the
    process mesh factors into. 0/1/'' = the legacy 1-D mesh; 'auto' = each
    process's local device count when the cloud spans >1 process (rows =
    the ICI/intra-host level, cols = hosts) and 1-D otherwise; an integer
    forces that rows size (the CPU-proxy A/B + test lane — e.g. '2' makes
    the 8-device proxy a 2x4 pod stand-in). A value that does not divide
    the device count falls back to 1-D with a warning rather than refusing
    to form a cloud."""
    from h2o3_tpu import config
    from h2o3_tpu.utils.log import Log

    v = config.get("H2O3_TPU_MESH_ROWS").strip().lower()
    if v in ("", "0", "1", "false"):
        return 1
    if v == "auto":
        try:
            if jax.process_count() <= 1:
                return 1
            r = jax.local_device_count()
        except RuntimeError:
            return 1
    else:
        r = int(v)
    if r <= 1:
        return 1
    if n_dev % r != 0:
        Log.warn(
            f"H2O3_TPU_MESH_ROWS={v} does not divide the {n_dev}-device "
            "cloud; using the 1-D rows mesh")
        return 1
    return r


def make_mesh_2d(rows: int, cols: int, devices=None) -> Mesh:
    """A rows×cols mesh over the first rows*cols ``devices``. The device
    grid is filled COLS-MAJOR (``mesh.devices[r, c] = devices[c*rows + r]``)
    so each ``rows``-axis group is a contiguous run of the device list —
    the intra-host/ICI level when rows = local device count — and so the
    cols-major row-shard order (:func:`row_pspec`) lands shard *i* on
    ``devices[i]``, identical to the 1-D mesh's layout."""
    devices = np.array(jax.devices() if devices is None else devices)
    grid = devices[: rows * cols].reshape(cols, rows).T
    return Mesh(grid, (ROWS_AXIS, COLS_AXIS))


def get_mesh() -> Mesh:
    """The process-wide mesh, created lazily over all devices: 1-D
    ``("rows",)`` by default, rows×cols under ``H2O3_TPU_MESH_ROWS``."""
    global _mesh
    if _mesh is None:
        devices = np.array(jax.devices())
        r = _mesh_rows_knob(devices.size)
        if r > 1:
            _mesh = make_mesh_2d(r, devices.size // r, devices)
        else:
            _mesh = Mesh(devices, (ROWS_AXIS,))
    return _mesh


def is_2d(mesh: Mesh | None = None) -> bool:
    """Whether the mesh is the rows×cols pod shape (vs the legacy 1-D)."""
    return COLS_AXIS in (mesh or get_mesh()).axis_names


def row_axes(mesh: Mesh | None = None) -> tuple:
    """Mesh axes sharding FRAME ROWS, in shard-major order. 2-D meshes
    shard rows over BOTH axes, cols-major: shard index c*R + r sits on
    mesh.devices[r, c] = jax.devices()[c*R + r] — the same shard→device map
    as the 1-D mesh, which keeps per-process shard ranges contiguous (the
    sharded-ingest and make_array_from_callback contracts)."""
    m = mesh or get_mesh()
    return (COLS_AXIS, ROWS_AXIS) if is_2d(m) else (ROWS_AXIS,)


def row_pspec(mesh: Mesh | None = None, ndim: int = 1, axis: int = 0) -> P:
    """PartitionSpec sharding dimension ``axis`` of an ``ndim``-dim array
    over the frame-row axes (replicated elsewhere)."""
    ax = row_axes(mesh)
    spec = [None] * ndim
    spec[axis] = ax if len(ax) > 1 else ax[0]
    return P(*spec)


def col_axis_name(mesh: Mesh | None = None) -> str:
    """The mesh axis COLUMN BLOCKS shard over: ``cols`` on a 2-D mesh,
    the one shared ``rows`` axis on the legacy 1-D mesh."""
    return COLS_AXIS if is_2d(mesh) else ROWS_AXIS


def n_col_shards(mesh: Mesh | None = None) -> int:
    """How many column blocks the split/Gram/DL scatter phase deals."""
    m = mesh or get_mesh()
    return m.shape[col_axis_name(m)]


def n_row_groups(mesh: Mesh | None = None) -> int:
    """Width of the stage-1 exact reduce (the ``rows`` axis of a 2-D mesh;
    1 on the legacy mesh — no separate stage exists there)."""
    m = mesh or get_mesh()
    return m.shape[ROWS_AXIS] if is_2d(m) else 1


def plan_mesh(n_devices: int, n_hosts: int = 1) -> tuple[int, int]:
    """Re-plan the rows×cols factorization for a (possibly changed)
    formation of ``n_devices`` devices over ``n_hosts`` hosts — the elastic
    half of :func:`_mesh_rows_knob`. ``H2O3_TPU_MESH_ROWS=auto`` resolves
    against the NEW formation (rows = devices per host when the formation
    spans >1 host), not the boot-time one; an explicit integer is honored
    when it divides the new device count and falls back to 1-D with a
    warning otherwise; ''/'0'/'1' stays 1-D. Returns ``(rows, cols)`` with
    ``rows == 1`` meaning the legacy 1-D ``("rows",)`` mesh."""
    from h2o3_tpu import config
    from h2o3_tpu.utils.log import Log

    n_devices = int(n_devices)
    v = config.get("H2O3_TPU_MESH_ROWS").strip().lower()
    if v in ("", "0", "1", "false"):
        return 1, n_devices
    if v == "auto":
        if n_hosts <= 1:
            return 1, n_devices
        r = max(n_devices // max(n_hosts, 1), 1)
    else:
        r = int(v)
    if r <= 1:
        return 1, n_devices
    if n_devices % r != 0:
        Log.warn(
            f"H2O3_TPU_MESH_ROWS={v} does not divide the re-planned "
            f"{n_devices}-device formation; using the 1-D rows mesh")
        return 1, n_devices
    return r, n_devices // r


def reform_mesh(shape: tuple[int, int] | None = None) -> Mesh:
    """Drop the cached mesh and rebuild over the devices that are live NOW —
    the supervised-recovery reform step (cluster/recovery.py). The new Mesh
    is a distinct object, so every program cache keyed through
    :func:`mesh_key` (which includes ``id(mesh)``) misses and retraces
    against the re-formed topology instead of replaying a program compiled
    for the dead one.

    Elastic recovery (ISSUE 17): ``shape=(rows, cols)`` re-forms onto an
    EXPLICIT topology over the first ``rows*cols`` live devices — ``rows ==
    1`` builds the legacy 1-D ``("rows",)`` mesh, ``rows > 1`` the 2-D pod
    mesh — which is how a job resumes on fewer (or more) devices than it
    started with. ``shape=None`` keeps the same-topology behavior: re-plan
    from the knob over every live device. Either way the topology epoch
    (:func:`mesh_epoch`) ticks, so Vec placements and host mirrors padded
    for the old shard counts re-derive lazily on next touch."""
    global _mesh, _mesh_epoch
    _mesh_epoch += 1
    if shape is None:
        _mesh = None
        return get_mesh()
    rows, cols = int(shape[0]), int(shape[1])
    if rows < 1 or cols < 1:
        raise ValueError(f"reform_mesh: bad shape {shape!r}")
    devices = np.array(jax.devices())
    if rows * cols > devices.size:
        raise ValueError(
            f"reform_mesh: shape {rows}x{cols} needs {rows * cols} devices "
            f"but only {devices.size} are live")
    if rows > 1:
        _mesh = make_mesh_2d(rows, cols, devices)
    else:
        _mesh = Mesh(devices[:cols], (ROWS_AXIS,))
    return _mesh


def n_shards() -> int:
    """Row-shard count: the TOTAL device count of the process mesh (frame
    rows always shard over every device, on either mesh generation)."""
    return int(get_mesh().devices.size)


def row_sharding(mesh: Mesh | None = None) -> NamedSharding:
    """Sharding for a row-partitioned column (1-D or leading-row N-D array)."""
    m = mesh or get_mesh()
    return NamedSharding(m, row_pspec(m))


# ---------------------------------------------------------------------------
# column-block layout (the sharded split pipeline, shared_tree/_split_scan):
# on the legacy 1-D mesh the SAME device axis that shards rows for the
# histogram pass re-shards the histogram's column axis for the split phase;
# on the 2-D pod mesh column blocks shard over the ``cols`` axis ONLY (the
# ``rows`` axis finished its exact stage-1 reduce first), so device (r, c)
# owns the contiguous block of columns [c*Cb, (c+1)*Cb). Contiguity is
# load-bearing either way: lowest-block-then-lowest-local-index IS
# lowest-global-index, which is what lets the per-block winner merge
# reproduce jnp.argmax tie-breaking exactly.


def pad_cols_to_shards(n_cols: int, mesh: Mesh | None = None) -> int:
    """Smallest multiple of the column-block count >= n_cols (and >= the
    block count, so C < blocks still gives every block real shape — the
    extra blocks hold only zero-histogram padding columns that can never
    win a split)."""
    m = n_col_shards(mesh)
    return max(m, -(-n_cols // m) * m)


def col_block_size(n_cols: int, mesh: Mesh | None = None) -> int:
    """Columns per device block under :func:`pad_cols_to_shards` padding."""
    return pad_cols_to_shards(n_cols, mesh) // n_col_shards(mesh)


def col_block_spec(axis: int = 0, mesh: Mesh | None = None) -> P:
    """PartitionSpec sharding dimension ``axis`` over the column blocks."""
    return P(*((None,) * axis + (col_axis_name(mesh),)))


def block_quantum(mesh: Mesh | None = None, multiple: int = 8) -> int:
    """Smallest row count a streamed chunk can carry: one f32 sublane tile
    (``multiple``) per shard. Every out-of-core row block is a multiple of
    this, so a block slices into equal per-device shards with the same
    tiling-friendly layout the resident ``pad_to_shards`` rows get — and a
    block-sized sub-frame's device arrays divide the mesh exactly with no
    extra padding rows (padding would perturb block-local reductions)."""
    return int((mesh or get_mesh()).devices.size) * multiple


def stream_block_rows(npad: int, budget_rows: int, mesh: Mesh | None = None) -> int:
    """Row count per out-of-core chunk: the largest multiple of
    :func:`block_quantum` that fits ``budget_rows`` (the HBM-window share one
    resident block may occupy), clamped to [quantum, npad]. A window too
    small for even one quantum block still streams — the device footprint is
    then one quantum block, the documented floor (frame/chunkstore.py)."""
    q = block_quantum(mesh)
    b = max(q, (max(budget_rows, 0) // q) * q)
    return min(b, max(npad, q))


def pad_flat_to_shards(n: int, mesh: Mesh | None = None) -> int:
    """Smallest multiple of the SCATTER-block count >= max(n, blocks) — the
    padded length of a FLATTENED parameter/gradient vector so the gradient
    ``psum_scatter`` (over the col-block axis: the whole 1-D mesh, or the
    ``cols`` axis of a 2-D one after its exact rows stage) deals every
    block an equal slice (the DL sharded-gradient lane; padded tail entries
    are zero and their zero gradients keep elementwise optimizer state zero
    forever)."""
    m = n_col_shards(mesh)
    return max(m, -(-n // m) * m)


def mesh_key() -> tuple:
    """Program-cache component for the process mesh: traced collectives and
    shard_map block layouts bake the mesh in at trace time, so a program
    compiled for one mesh must never serve another (tests swap 1/2/8-device
    sub-meshes within one process). Shared by the tree, GLM and DL program
    caches. Includes the collective-lane key (ops/collectives.quant_key):
    the quant/hierarchy knobs change the traced reduce structure, so every
    program cache picks them up through this one chokepoint."""
    from h2o3_tpu.ops.collectives import quant_key

    m = get_mesh()
    shape = tuple(m.shape.items()) if hasattr(m, "shape") else ()
    return (shape, id(m), quant_key())


# ---------------------------------------------------------------------------
# hierarchical reduction placement (ops/collectives.py two-stage lane): the
# 1-D rows axis factors into contiguous INNER groups (the cheap interconnect
# level — ICI within a slice/host) × an OUTER level (the expensive hop —
# DCN across hosts). This module owns the mesh-level resolution so a future
# 2D mesh (ROADMAP item 2) changes exactly one function.


def hier_inner(n_dev: int | None = None) -> int:
    """Inner-group size of the two-stage hierarchical reduction WITHIN the
    collective lane's one reduce axis, or 0 for single-stage.
    ``H2O3_TPU_COLLECTIVE_HIER``: 'auto' groups by the devices each process
    contributes (the ICI/DCN boundary) when the mesh spans >1 process and
    the factorization is clean; an integer forces that inner size (the A/B
    + test lane — e.g. '2' splits the 8-device CPU proxy into 4 fake-ICI
    pairs); '0'/'' disables. On a 2-D rows×cols mesh the MESH is the
    hierarchy — stage 1 is the exact ``rows``-axis psum the reduce wrappers
    already run (ops/collectives.py) — so 'auto' resolves to 0 there and
    only an explicit integer further subdivides the ``cols`` lane."""
    from h2o3_tpu import config

    if n_dev is None:
        n_dev = n_col_shards()
    v = config.get("H2O3_TPU_COLLECTIVE_HIER").strip().lower()
    if v in ("0", "", "false"):
        return 0
    if v == "auto":
        if is_2d():
            return 0  # the rows axis already reduces the ICI level exactly
        try:
            inner = jax.local_device_count()
        except RuntimeError:
            return 0
        if jax.process_count() <= 1:
            return 0
    else:
        inner = int(v)
    if 1 < inner < n_dev and n_dev % inner == 0:
        return inner
    return 0


def hier_groups(n_dev: int, inner: int) -> tuple[list, list]:
    """(inner_groups, cross_groups) for :func:`hier_inner`'s factorization:
    inner groups are contiguous runs of ``inner`` device indices (stage-1
    exact reduce); cross groups tie position ``j`` of every inner group
    together (stage-2 quantized exchange). Ascending order inside every
    group is load-bearing: grouped collectives exchange by listed position,
    and the lane's chunk remap assumes position == outer index."""
    outer = n_dev // inner
    inner_groups = [
        list(range(g * inner, (g + 1) * inner)) for g in range(outer)
    ]
    cross_groups = [
        [g * inner + j for g in range(outer)] for j in range(inner)
    ]
    return inner_groups, cross_groups


def replicated_sharding(mesh: Mesh | None = None) -> NamedSharding:
    return NamedSharding(mesh or get_mesh(), P())


_ROW_BUCKET_MIN = 1 << 16  # frames below this keep exact shard-aligned pads


def _bucket_rows(n: int) -> int:
    """Row-count bucket: round up to 5 significant bits (steps ≤ 3.125%).

    Part of the shape-bucket ladder (H2O3_TPU_SHAPE_BUCKETS): AutoML/grid
    runs over frames of near-identical row counts (CV folds, sampled
    frames, train/valid splits) then share one compiled program per
    algorithm instead of recompiling per exact row count. Every padded row
    is real device work on every build, so the ladder is deliberately
    fine — ≤3.1% pad buys the collapse of the ±few-percent row-count
    variation that actually occurs; a coarser ladder charged the 1M-row
    headline ~5% forever. Only frames above _ROW_BUCKET_MIN bucket —
    small-frame compiles are cheap and exact shapes keep tests/debug
    predictable."""
    from h2o3_tpu import config

    if n <= _ROW_BUCKET_MIN or not config.get_bool("H2O3_TPU_SHAPE_BUCKETS"):
        return n
    step = 1 << (n.bit_length() - 5)
    return -(-n // step) * step


def pad_to_shards(n: int, mesh: Mesh | None = None, multiple: int = 8) -> int:
    """Padded row count: a multiple of (shards * multiple) ≥ n, bucketed to
    the row ladder above _ROW_BUCKET_MIN (see :func:`_bucket_rows`).

    The per-shard row count is kept a multiple of 8 (f32 sublane tile) so
    device layouts stay tiling-friendly.
    """
    m = int((mesh or get_mesh()).devices.size)
    block = m * multiple
    return max(block, ((_bucket_rows(n) + block - 1) // block) * block)


def shard_rows(arr, mesh: Mesh | None = None):
    """Place a host array onto the mesh, sharded along the leading axis.

    On a multi-process cloud the mesh spans non-addressable devices; each
    process holds the same full host array (SPMD command replication,
    cluster/spmd.py) and contributes its addressable shards."""
    sh = row_sharding(mesh)
    if jax.process_count() > 1:
        a = np.asarray(arr)
        return jax.make_array_from_callback(a.shape, sh, lambda idx: a[idx])
    return jax.device_put(arr, sh)


def pull_to_host(x):
    """Full host value of a (possibly cross-process) device array.

    Fully-addressable arrays device_get directly. Cross-process sharded
    arrays allgather — a COLLECTIVE: on a multi-process cloud this must run
    inside replicated execution (every rank calls it at the same point),
    which the spmd command layer guarantees for build/parse/predict."""
    if getattr(x, "is_fully_addressable", True):
        return jax.device_get(x)
    from h2o3_tpu.cluster import spmd

    if not spmd.in_replicated():
        # an allgather entered by one rank alone deadlocks the cloud — fail
        # fast instead (coordinator-only REST paths must stay off sharded
        # data or go through spmd.run)
        raise RuntimeError(
            "host pull of a cross-process array outside replicated "
            "execution (multi-process cloud): route through spmd.run"
        )
    from jax.experimental import multihost_utils as mh

    return np.asarray(mh.process_allgather(x, tiled=True))
