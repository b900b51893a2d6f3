"""The chip's entry conditions, checked on the CPU (ISSUE 21): does the
DEFAULT tree lane lower for a TPU, where does the compile cache go, and does
chip_smoke.py refuse to run without a chip?

``jax.export`` with ``platforms=["tpu"]`` runs the Pallas→Mosaic lowering on
the CPU and reports an unsupported primitive in seconds — the check that
would have caught ``H2O3_TPU_SPLIT_FUSE=auto`` selecting a kernel
(``ops/split_pallas.py``) that had only ever run in the interpreter. Two
halves: every Pallas kernel the chip default selects lowers at the headline
geometry, and the fuse gate stays off on every backend for which the split
kernel does not. A lowering verdict is not a run: ``chip_smoke.py`` is the
proof that the program executes.

The cache-placement and smoke-rehearsal tests at the end start fresh
interpreters (jax reads JAX_COMPILATION_CACHE_DIR at import).
"""

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
from h2o3_tpu.ops.split_pallas import split_candidates

# the headline shape after shape bucketing: 28 -> 32 columns, 255 -> 256
# bins, the GBM/DRF stat lanes {w, wy, wh}, default tiles
COLS, BINS, LANES, ROWS = 32, 256, 3, 4096
TILES = (hp.ROW_TILE, hp.COL_TILE, hp.NODE_TILE)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hist_args():
    return (jax.ShapeDtypeStruct((ROWS, COLS), jnp.uint8),
            jax.ShapeDtypeStruct((ROWS,), jnp.int32),
            jax.ShapeDtypeStruct((ROWS, LANES), jnp.float32))


def _export_tpu(f, *args) -> str:
    return jax.export.export(jax.jit(f), platforms=["tpu"])(*args).mlir_module()


@pytest.mark.parametrize("n_nodes", [1, 64])
def test_default_histogram_kernel_lowers_for_tpu(monkeypatch, n_nodes):
    """The local histogram impl the chip selects (``_select_local`` with the
    chip's branches taken): the Pallas kernel, compiled — not interpreted —
    in the dense output mode the default lane consumes."""
    monkeypatch.delenv("H2O3_TPU_HIST", raising=False)
    monkeypatch.delenv("H2O3_TPU_PALLAS_TILES", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    local = hg._select_local()
    assert hg._local_is_pallas(local)
    module = _export_tpu(
        lambda b, n, s: local(b, n, s, n_nodes, BINS), *_hist_args())
    assert "tpu_custom_call" in module


@pytest.mark.parametrize("n_nodes", [1, 64])
def test_blocked_histogram_kernel_lowers_for_tpu(n_nodes):
    """The blocked output mode (what the tile autotuner sweeps) lowers too:
    only the split kernel stands between the chip and the fused pipeline."""
    f = functools.partial(
        hp.hist_pallas_local, n_nodes=n_nodes, n_bins=BINS, interpret=False,
        blocked=True, tiles=TILES)
    assert "tpu_custom_call" in _export_tpu(f, *_hist_args())


@pytest.mark.parametrize("blocked,name", [
    (False, "hist_pallas_dense"), (True, "hist_pallas_blocked")])
def test_histogram_kernels_keep_their_names_in_the_lowered_text(blocked, name):
    """The profiler names a kernel's device events after its ``pallas_call``
    (``%hist_pallas_dense.66 = ... custom-call``), and the benchmark's
    ``hist_kernel_roofline_pct`` finds them by ``^%?hist_pallas``: both
    layouts carry a stable name with that prefix."""
    import re

    f = functools.partial(
        hp.hist_pallas_local, n_nodes=64, n_bins=BINS, interpret=False,
        blocked=blocked, tiles=TILES)
    names = re.findall(r'kernel_name = "([^"]+)"', _export_tpu(f, *_hist_args()))
    assert names == [name]
    assert re.match(r"^%?hist_pallas", names[0])


def _split_lowers(platform: str) -> bool:
    L = hp.plan_layout(COLS, 64, BINS, LANES, tiles=TILES)
    f = functools.partial(split_candidates, layout=L, interpret=False)
    try:
        jax.export.export(
            jax.jit(lambda b, t: f(b, t, 10.0)), platforms=[platform])(
            jax.ShapeDtypeStruct(L.shape, jnp.float32),
            jax.ShapeDtypeStruct((64, LANES), jnp.float32))
    except (NotImplementedError, ValueError):
        return False
    return True


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_fuse_gate_is_off_wherever_the_split_kernel_does_not_lower(
        monkeypatch, backend):
    """``_split_fuse_on()`` under 'auto' must not select a kernel the
    compiler refuses. If a rewrite makes ``split_candidates`` lower for a
    backend, this test stops constraining the gate there — flipping the
    default then needs a chip run and a cell, not this file."""
    if _split_lowers(backend):
        pytest.skip(f"split_candidates lowers for {backend}: gate is free")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    for auto in ("auto", ""):
        monkeypatch.setenv("H2O3_TPU_SPLIT_FUSE", auto)
        assert st._split_fuse_on() is False
        assert st._split_fuse_active((), split_shard=False) is False
    monkeypatch.setenv("H2O3_TPU_SPLIT_FUSE", "1")  # still means "force it"
    assert st._split_fuse_on() is True


def test_tile_sweep_skips_only_candidates_that_do_not_fit(monkeypatch):
    """On the chip 4 of the 12 sweep candidates exceed scoped VMEM at the
    headline geometry (PR 21): that RESOURCE_EXHAUSTED is a measured outcome
    — the candidate is logged and cannot win. Any other refusal is a kernel
    defect and must surface, and so must a sweep in which nothing fits."""
    grid = [(256, 4, 32), (512, 8, 64)]
    monkeypatch.setattr(hp, "_sweep_grid", lambda c, n: grid)

    def fake(refuse, exc):
        def hist(bins, nid, stats, n_nodes, n_bins, *, interpret, blocked,
                 tiles):
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
