"""The chip's entry conditions, checked on the CPU (ISSUE 21): does the
DEFAULT tree lane lower for a TPU, where does the compile cache go, and does
chip_smoke.py refuse to run without a chip?

``jax.export`` with ``platforms=["tpu"]`` runs the Pallas→Mosaic lowering on
the CPU and reports an unsupported primitive in seconds — the check that
would have caught PR 6's default selecting a split kernel that had only
ever run in the interpreter (deleted in ISSUE 30). The Pallas kernel the
chip default selects lowers at the headline geometry and at the widths of
the other tree builders, and so does the whole tree chunk program, with
that kernel and no other in it. A lowering verdict is not a run:
``chip_smoke.py`` is the proof that the program executes.

The cache-placement and smoke-rehearsal tests at the end start fresh
interpreters (jax reads JAX_COMPILATION_CACHE_DIR at import).
"""

import collections
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from h2o3_tpu.models.tree import shared_tree as st
from h2o3_tpu.ops import hist_pallas as hp
from h2o3_tpu.ops import histogram as hg

# the headline shape after shape bucketing: 28 -> 32 columns, 255 -> 256
# bins, the GBM/DRF stat lanes {w, wy, wh}, default tiles
COLS, BINS, LANES, ROWS = 32, 256, 3, 4096
TILES = (hp.ROW_TILE, hp.COL_TILE, hp.NODE_TILE)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hist_args(lanes=LANES):
    return (jax.ShapeDtypeStruct((ROWS, COLS), jnp.uint8),
            jax.ShapeDtypeStruct((ROWS,), jnp.int32),
            jax.ShapeDtypeStruct((ROWS, lanes), jnp.float32))


def _export_tpu(f, *args) -> str:
    return jax.export.export(jax.jit(f), platforms=["tpu"])(*args).mlir_module()


@pytest.mark.parametrize("n_nodes,lanes", [
    (1, LANES), (64, LANES), (2048, LANES), (64, 4), (1024, 4)])
def test_default_histogram_kernel_lowers_for_tpu(monkeypatch, n_nodes, lanes):
    """The local histogram impl the chip selects (``_select_local`` with the
    chip's branches taken): the Pallas kernel, compiled — not interpreted —
    at the frontier widths of a depth-6 GBM (1, 64), at DRF's ``node_cap``
    (2048: 32 node tiles) and with uplift's four statistic lanes, at a
    shallow frontier and at uplift's own ``node_cap`` (1024)."""
    monkeypatch.delenv("H2O3_TPU_HIST", raising=False)
    monkeypatch.delenv("H2O3_TPU_PALLAS_TILES", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    local = hg._select_local()
    assert hg._local_is_pallas(local)
    module = _export_tpu(
        lambda b, n, s: local(b, n, s, n_nodes, BINS), *_hist_args(lanes))
    assert "tpu_custom_call" in module


def test_histogram_kernel_keeps_its_name_in_the_lowered_text():
    """The profiler names a kernel's device events after its ``pallas_call``
    (``%hist_pallas_dense.66 = ... custom-call``), and the benchmark's
    ``hist_kernel_roofline_pct`` finds them by ``^%?hist_pallas``: the
    kernel carries a stable name with that prefix."""
    import re

    f = functools.partial(
        hp.hist_pallas_local, n_nodes=64, n_bins=BINS, interpret=False,
        tiles=TILES)
    names = re.findall(r'kernel_name = "([^"]+)"', _export_tpu(f, *_hist_args()))
    assert names == ["hist_pallas_dense"]
    assert re.match(r"^%?hist_pallas", names[0])


def _mosaic_body(module: str) -> str:
    """The Mosaic body of the one ``tpu_custom_call`` in an exported module,
    as text (it travels as base64 MLIR bytecode in the call's config)."""
    import base64
    import re

    from jax._src.lib.mlir import ir

    (body,) = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', module)
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        return str(ir.Module.parse(base64.b64decode(body)))


@pytest.mark.parametrize("n_nodes", [1, 16, 64])
def test_histogram_kernel_step_has_no_lane_tiling(n_nodes):
    """The regression ISSUE 31 removed, pinned without the compiler's
    schedule dump: ``jnp.tile`` of a sub-128-lane pattern (the (R, 8) codes
    256 times, the (R, S) statistics NT times) reaches Mosaic as
    ``tpu.repeat`` and becomes one lane rotation a vreg — the XLU was full
    in 60% of the old step's bundles. The step builds its one-hots with the
    rows on the lanes: no ``tpu.repeat``, no transposed-LHS contraction, and
    operand blocks (1, R) / (S, R) / (1, CT, R), not (R, 1) / (R, S)."""
    f = functools.partial(
        hp.hist_pallas_local, n_nodes=n_nodes, n_bins=BINS, interpret=False,
        tiles=TILES)
    body = _mosaic_body(_export_tpu(f, *_hist_args()))
    assert "tpu.repeat" not in body
    r = hp.ROW_TILE
    ct = hp.plan_layout(COLS, n_nodes, BINS, LANES, tiles=TILES).ct
    assert ct == (COLS if n_nodes < 64 else hp.COL_TILE)  # all columns a step
    for block in (f"memref<1x{r}xi32", f"memref<{LANES}x{r}xf32",
                  f"memref<1x{ct}x{r}xi32"):
        assert block in body, block
    assert f"memref<{r}x1xi32" not in body
    assert body.count("tpu.matmul") == ct  # one contraction a column


def _chunk_program(monkeypatch, max_depth, node_cap, rows=ROWS, sharding=None,
                   drf=False):
    """The jitted 5-tree ``build_trees_scanned`` chunk program at 28 columns
    and 255 bins with the chip's branches taken, and its operands as
    shapes: what a ``gbm_higgs`` call dispatches, captured where
    ``_run_counted`` would run it. ``drf``: a random forest's OWN program,
    as ``drf.py`` asks for it — the 0.632 bootstrap, 5 of 28 columns a node,
    ``min_rows`` 1, the response as the gradient."""
    import numpy as np

    from h2o3_tpu.models.tree.distributions import grad_hess

    monkeypatch.delenv("H2O3_TPU_HIST", raising=False)
    monkeypatch.delenv("H2O3_TPU_PALLAS_TILES", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    captured = {}

    def capture(fn, args, mult=1, counts_from=None):
        captured.update(fn=fn, args=args)
        raise LookupError("captured")

    monkeypatch.setattr(st, "_run_counted", capture)
    S = jax.ShapeDtypeStruct
    cols = 28
    with pytest.raises(LookupError, match="captured"):
        st.build_trees_scanned(
            S((rows, cols), jnp.uint8), S((rows,), jnp.float32),
            S((rows,), jnp.float32), S((rows,), jnp.float32),
            S((cols,), jnp.float32), jax.random.PRNGKey(42), 5,
            grad_fn=((lambda F, y, w: (y, w)) if drf else
                     (lambda F, y, w: grad_hess("bernoulli", F, y, w, 0.0))),
            grad_key=("drf",) if drf else ("lowering", max_depth),
            sample_rate=0.632 if drf else 1.0, n_bins=255,
            is_cat_cols=np.zeros(cols, bool), max_depth=max_depth,
            min_rows=1.0 if drf else 10.0, min_split_improvement=1e-5,
            learn_rates=np.full(5, 1.0 if drf else 0.1), max_abs_leaf=np.inf,
            col_sample_rate=5 / 28 if drf else 1.0, col_sample_rate_per_tree=1.0,
            node_cap=node_cap,
        )
    shapes = jax.tree_util.tree_map(
        lambda a: S(np.shape(a), a.dtype if isinstance(a, S)
                    else jnp.asarray(a).dtype, sharding=sharding),
        captured["args"])
    return captured["fn"], shapes


@pytest.mark.parametrize("max_depth,node_cap", [(6, 2048), (20, 2048)])
def test_tree_chunk_program_lowers_for_tpu(monkeypatch, max_depth, node_cap):
    """The whole ``build_trees_scanned`` chunk program (the dispatch behind
    every ``gbm_higgs`` tree, five trees a chunk) lowers for the TPU with
    the chip's branches taken, and the only Pallas kernel in its text is
    ``hist_pallas_dense`` up to depth 7. Depth 6 is the benchmark's shape;
    depth 20 with ``node_cap`` 2048 is a DRF's — the saturated
    ``lax.while_loop`` (ROADMAP B-R3: first trained on a chip in ISSUE 31):
    one loop for the levels, one for the scan over trees."""
    import re

    fn, shapes = _chunk_program(monkeypatch, max_depth, node_cap)
    module = _export_tpu(fn, *shapes)
    kernels = set(re.findall(r'kernel_name = "([^"]+)"', module))
    # one loop for the scan over trees, one for the saturated levels; a deep
    # tree's rows go into node order and back by sorting lane after lane
    # (``hist_pallas._sorted_by``): two loops more
    want_loops = 1 if st._sat_region(max_depth, node_cap)[1] == 0 else 4
    assert module.count("stablehlo.while") == want_loops
    monkeypatch.setattr(hg, "_wants_row_order", lambda *a: False)
    st._STEP_CACHE.clear()
    fn, shapes = _chunk_program(monkeypatch, max_depth, node_cap)
    unordered = _export_tpu(fn, *shapes)
    if max_depth == 6:
        # at most 16 built nodes a level, one node tile: the GBM cells'
        # program is ISSUE 33's bypass — operation for operation what it
        # lowers to with the row order withheld (the texts differ in their
        # source locations alone): no sort of the rows, no second kernel
        assert kernels == {"hist_pallas_dense"}, kernels
        ops = lambda text: collections.Counter(
            re.findall(r"stablehlo\.\w+|kernel_name = \S+", text))
        assert ops(module) == ops(unordered)
    else:
        # levels 8-19 build 128-1024 nodes: the rows sorted once a tree, the
        # grouped kernel, and the dense one behind each level's ``cond``
        assert kernels == {"hist_pallas_dense", "hist_pallas_grouped"}, kernels
        assert (module.count("stablehlo.sort")
                == unordered.count("stablehlo.sort") + 2)


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a DESCRIBED v5e host: the TPU's compiler is installed
    here and compiles for a chip that is not attached. Only this file's
    worker loads the TPU's library, and only once a test asks for it."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: nothing to pin
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip would be written to the persistent
    cache and could never be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.mark.parametrize("max_depth,drf", [
    (6, False), (7, False), (8, False), (20, False), (20, True)])
def test_tree_chunk_program_compiles_for_a_described_v5e(
        monkeypatch, v5e_chip, no_compile_cache, max_depth, drf):
    """What the chip's own compiler says of the chunk program, with no chip
    (a compile is not a run). Depth 6 — the benchmark's cells — and depth 7
    compile, and since ISSUE 31 so do depth 8 and a DRF's depth 20 at
    ``node_cap`` 2048. Until then the program was REFUSED from depth 8 on
    (ROADMAP B-R3): the first level that builds 64 nodes (one full node
    tile) has its kernel's ``f32[192, 8192]`` output placed in VMEM by XLA
    inside the tree program, and with the old grid step's 4 MB lane-tiled
    code block and its copies the kernel overran its 16 MB of scoped VMEM by
    676 KB (``RESOURCE_EXHAUSTED`` from ``lowered.compile()``). The last case
    is ``drf_higgs``'s own program (ISSUE 32): the bootstrap, the exact draw
    of 5 of 28 columns a node (a sort in every level, the saturated loop's
    body too) and ``min_rows`` 1, where the others compile GBM's gradients
    with no sampling."""
    from jax.sharding import Mesh, SingleDeviceSharding

    import numpy as np

    from h2o3_tpu.parallel import mesh as pm

    old = pm._mesh
    pm.set_mesh(Mesh(np.array([v5e_chip]), (pm.ROWS_AXIS,)))
    try:
        fn, shapes = _chunk_program(
            monkeypatch, max_depth, 2048, rows=65_536,
            sharding=SingleDeviceSharding(v5e_chip), drf=drf)
        lowered = fn.lower(*shapes)
    finally:
        pm.set_mesh(old)
    text = lowered.compile().as_text()
    # a kernel a level, up to the level that fills node_cap (2048 = 2**11:
    # the levels from there on are one while loop)
    assert text.count("tpu_custom_call") >= min(max_depth, 12)


@pytest.mark.parametrize("n_nodes,lanes", [
    (64, LANES), (2048, LANES), (64, 4), (1024, 4)])
def test_histogram_kernel_compiles_alone_for_a_described_v5e(
        v5e_chip, no_compile_cache, n_nodes, lanes):
    """The kernel by itself fits the chip's VMEM at a full node tile, at
    DRF's ``node_cap``, and with uplift's four lanes at a full node tile
    (the widest stacked operand: 2·4·64 = 512 M-rows) and at its
    ``node_cap``."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(v5e_chip)
    f = functools.partial(
        hp.hist_pallas_local, n_nodes=n_nodes, n_bins=BINS, interpret=False,
        tiles=TILES)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
            for a in _hist_args(lanes)]
    assert "tpu_custom_call" in jax.jit(f).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows", [4096, 6_029_312])
def test_grouped_kernel_compiles_alone_for_a_described_v5e(
        v5e_chip, no_compile_cache, rows):
    """ISSUE 33's grouped ``pallas_call`` at a saturated forest level — 1,024
    built nodes: 16 tiles of 64, 28 columns in four steps of 8, 256 bins —
    with its scalar-prefetch operands, the visit list among them: at
    ``drf_higgs``'s 6,029,312 rows that is 23,567 int32 of the chip's 1 MiB
    of SMEM. The level's ``cond`` carries the dense kernel beside it."""
    import re

    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(v5e_chip)
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    lay = hp.plan_layout(28, 1024, BINS, LANES, tiles=TILES)
    assert (lay.n_nt, lay.nt, lay.n_ct, lay.ct) == (16, 64, 4, 8)
    assert hp.grouped_fits(rows, lay, hp.ROW_TILE)
    order = hp.RowOrder(
        perm=S((rows,), jnp.int32), bins3=S((lay.n_ct, lay.ct, rows), jnp.int32),
        stats_t=S((LANES, rows), jnp.float32), n_live=S((1,), jnp.int32))
    f = functools.partial(
        hp.hist_pallas_grouped, n_nodes=1024, n_bins=BINS, n_cols=28,
        interpret=False, tiles=TILES)
    text = jax.jit(f).lower(order, S((rows,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert set(re.findall(r"hist_pallas_\w+", text)) >= {
        "hist_pallas_grouped", "hist_pallas_dense"}


def test_tile_sweep_skips_only_candidates_that_do_not_fit(monkeypatch):
    """On the chip 4 of the 12 sweep candidates exceed scoped VMEM at the
    headline geometry (PR 21): that RESOURCE_EXHAUSTED is a measured outcome
    — the candidate is logged and cannot win. Any other refusal is a kernel
    defect and must surface, and so must a sweep in which nothing fits."""
    grid = [(256, 4, 32), (512, 8, 64)]
    monkeypatch.setattr(hp, "_sweep_grid", lambda c, n: grid)

    def fake(refuse, exc):
        def hist(bins, nid, stats, n_nodes, n_bins, *, interpret, tiles):
            if tiles in refuse:
                raise exc
            return jnp.zeros(())

        hist.clear_cache = lambda: None
        return hist

    oom = jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem")
    monkeypatch.setattr(hp, "hist_pallas_local", fake({grid[0]}, oom))
    assert hp._run_tile_sweep(COLS, 64, BINS, LANES, interpret=False) == grid[1]
    monkeypatch.setattr(hp, "hist_pallas_local", fake(set(grid), oom))
    with pytest.raises(RuntimeError, match="no candidate"):
        hp._run_tile_sweep(COLS, 64, BINS, LANES, interpret=False)
    monkeypatch.setattr(hp, "hist_pallas_local", fake(
        {grid[0]}, NotImplementedError("Unimplemented primitive: cumsum")))
    with pytest.raises(NotImplementedError):
        hp._run_tile_sweep(COLS, 64, BINS, LANES, interpret=False)


@pytest.mark.parametrize("rows", [6_000_000, 6_029_312])
def test_binomial_statistics_program_lowers_for_tpu(rows):
    """The program behind every binomial metric on a chip (GLM's since
    ISSUE 26, the tree models' before): the weighted sums and the
    1024-bucket score histogram over ``glm_higgs``'s 6,000,000 rows and over
    their padded length."""
    from h2o3_tpu.models import metrics as MM

    lane = jax.ShapeDtypeStruct((rows,), jnp.float32)
    exported = jax.export.export(
        MM._binom_device_stats(), platforms=["tpu"])(lane, lane, lane)
    assert exported.out_avals[0].shape == (4 + 2 * MM._NBUCKETS,)


@pytest.mark.parametrize("standardize", [True, False])
def test_design_program_lowers_for_tpu(standardize):
    """``DataInfo.transform``'s one program at ``glm_higgs``'s shape: 28
    numeric columns and the intercept over the 6,029,312 padded rows."""
    from h2o3_tpu.models import datainfo
    from h2o3_tpu.parallel.mesh import mesh_key

    rows, cols = 6_029_312, 28
    plan = ((("num",),) * cols, standardize, False, False, True, rows,
            mesh_key())
    lane = jax.ShapeDtypeStruct((rows,), jnp.float32)
    exported = jax.export.export(
        jax.jit(functools.partial(datainfo._design.__wrapped__, plan)),
        platforms=["tpu"],
    )(jax.ShapeDtypeStruct((), jnp.int32),
      jax.ShapeDtypeStruct((cols, 4), jnp.float32), [(lane,)] * cols)
    X, valid = exported.out_avals
    assert X.shape == (rows, cols + 1) and valid.shape == (rows,)


@pytest.mark.parametrize("n_pad,any_cat", [
    (1, False), (64, False), (64, True), (2048, False), (2048, True)])
def test_partition_lowers_for_tpu_with_no_row_gather(n_pad, any_cat):
    """``_partition_update`` at the headline shape (ISSUE 29): a TPU runs a
    per-row gather element by element, and an ``(n, 1)`` row lane is tiled to
    128 lanes a row, so the partition lowers with neither — at the cells'
    widest frontier (64) and at ``node_cap`` (2048) alike: one formulation,
    no crossover."""
    import re

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    module = _export_tpu(
        functools.partial(st._partition_update, any_cat=any_cat),
        S((ROWS, COLS), jnp.uint8), S((ROWS,), jnp.int32),
        S((ROWS,), jnp.float32), S((n_pad,), jnp.int32),
        S((n_pad,), jnp.int32), S((n_pad,), jnp.bool_),
        S((n_pad, BINS), jnp.bool_), S((n_pad,), jnp.bool_),
        S((n_pad,), jnp.bool_), S((n_pad,), jnp.float32),
        S((n_pad,), jnp.int32))
    gathers = [ln for ln in module.splitlines() if "stablehlo.gather" in ln
               or "stablehlo.dynamic_gather" in ln]
    assert not [g for g in gathers if f"tensor<{ROWS}" in g.split("->")[-1]], gathers
    assert not re.search(rf"tensor<{ROWS}x1x", module)  # no (ROWS, 1) tensor
    assert "stablehlo.dot_general" in module  # the node tables: one contraction
    # the node ids leave behind a barrier (the histogram kernel's (n, 1)
    # operand must not carry its tiling back up the program)
    assert "stablehlo.optimization_barrier" in module


# ---------------------------------------------------------------------------
# compile-cache placement and the smoke's refusal, in fresh interpreters

_CACHE_PROBE = """
import json, os
import jax
import h2o3_tpu
from h2o3_tpu import config
from h2o3_tpu.cluster import cloud
from h2o3_tpu.ops import hist_pallas
h2o3_tpu.init(log_level="WARN")
declared_cpu = jax.config.jax_compilation_cache_dir
# the backend stays the CPU (JAX_PLATFORMS); only the cache policy is made
# to see an accelerator declaration
cloud._declared_platform = lambda: "tpu"
cloud._enable_compile_cache()
print(json.dumps({"declared_cpu": declared_cpu,
                  "declared_tpu": jax.config.jax_compilation_cache_dir,
                  "resolver": config.compile_cache_dir(),
                  "tiles": hist_pallas._tile_cache_path()}))
"""


def _cache_probe(**env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_placed_from_outside(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, init() leaves jax's cache dir
    equal to it whatever the platform (no code path sets another) and the
    tile store inside it."""
    d = str(tmp_path / "placed")
    assert _cache_probe(JAX_COMPILATION_CACHE_DIR=d) == {
        "declared_cpu": d, "declared_tpu": d, "resolver": d,
        "tiles": os.path.join(d, "pallas_tiles.json")}


def test_compile_cache_default_is_checkout_jax_cache():
    """Unset, everything resolves to <checkout>/.jax_cache — a fixed path
    (the path is part of the cache key): no pid, no time, no temp name. An
    accelerator declaration makes init() set it; a declared CPU keeps the
    cache off (the XLA:CPU AOT hazard) but resolves the same place."""
    want = os.path.join(ROOT, ".jax_cache")
    assert _cache_probe() == {
        "declared_cpu": None, "declared_tpu": want, "resolver": want,
        "tiles": os.path.join(want, "pallas_tiles.json")}


def test_chip_smoke_refuses_to_run_without_a_chip():
    """The rehearsal: on the CPU chip_smoke.py exits non-zero, names the
    missing chip, and prints no result line."""
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
