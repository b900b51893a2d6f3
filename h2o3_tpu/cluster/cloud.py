"""Cloud lifecycle — successor of ``water.H2O`` main / ``water.Paxos`` cloud
formation / ``HeartBeatThread`` [UNVERIFIED upstream paths, SURVEY.md §0].

H2O boots a JVM per node, gossips membership, and locks the cloud at the
first job. The TPU-native cloud is the JAX runtime itself:

- single host: ``init()`` just builds the device mesh;
- multi-host: ``init(coordinator=...)`` calls ``jax.distributed.initialize``
  — the JAX coordination service replaces Paxos + heartbeats (it performs
  liveness detection and fail-stop, matching H2O's no-elastic-recovery
  semantics, SURVEY.md §5.3).

``cluster_info()`` is the ``GET /3/Cloud`` analog.
"""

from __future__ import annotations

import os
import time

import jax

from h2o3_tpu.parallel import mesh as _mesh
from h2o3_tpu.utils import metrics
from h2o3_tpu.utils.log import Log

_started_at: float | None = None


# cluster health as gauges: a scraper sees the degraded latch / probe
# failures without polling /3/Cloud JSON, and the transition counter
# preserves flap history a point-in-time gauge cannot show
_G_DEGRADED = metrics.gauge(
    "cloud_degraded", "1 while the fail-stop degraded latch is set")
_G_HEALTHY = metrics.gauge(
    "cloud_healthy", "1 while every probed local device passes health checks")
_G_GENERATION = metrics.gauge(
    "cloud_generation",
    "cloud formation epoch: starts at 0 and ticks on every supervised "
    "recover() reform (cluster/recovery.py). Replicated spmd commands are "
    "stamped with the generation they entered under and fail-stop if the "
    "cloud re-formed while they waited — a retried collective can never "
    "interleave with a wedged predecessor")
_C_TRANSITIONS = metrics.counter(
    "cloud_health_transitions_total", "health state changes, by target state")


def _declared_platform() -> str:
    """The platform the process DECLARED (env or jax config), without
    touching the backend — a backend touch here would break the later
    ``jax.distributed.initialize()``, which must run before any backend
    init. '' = auto-detect."""
    return (os.environ.get("JAX_PLATFORMS")
            or str(jax.config.jax_platforms or "")).strip().lower()


def _enable_compile_cache() -> None:
    """Persistent XLA compilation cache (SURVEY.md §7: compile-latency
    amortization across the many jit programs of AutoML/tree loops), at
    ``config.compile_cache_dir()``. When ``JAX_COMPILATION_CACHE_DIR`` is
    set jax has already placed the cache itself and no directory is set in
    code. Otherwise ACCELERATOR BACKENDS ONLY: XLA:CPU cache entries are
    AOT-compiled with the builder machine's exact CPU features; loading
    them on a host with a different feature set is a documented
    SIGILL/segfault hazard (the cpu_aot_loader "machine type mismatch"
    error), observed crashing the test suite inside cache
    (de)serialization. Only an explicit cpu declaration disables the cache
    (auto-detected accelerators keep it; test/driver cpu runs always
    declare)."""
    from h2o3_tpu import config

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if _declared_platform() == "cpu":
            Log.debug("compile cache skipped on XLA:CPU (AOT feature-"
                      "mismatch SIGILL hazard)")
            return
        jax.config.update(
            "jax_compilation_cache_dir", config.compile_cache_dir())
    # every program is cached: with a compile-time floor, a program whose
    # compile straddles it is stored by one process and not the next, and
    # "a second process adds no entries" stops being checkable
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def init(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    mesh=None,
    log_level: str | None = None,
) -> dict:
    """Bring up (or attach to) the cloud and build the row mesh.

    Mirrors ``h2o.init()``: idempotent, returns cluster status. For
    multi-host pods pass the coordinator address (maps to
    ``jax.distributed.initialize``, the Paxos/flatfile successor).
    ``log_level`` defaults from the H2O3_TPU_LOG_LEVEL knob (config.py).
    """
    global _started_at
    from h2o3_tpu import config

    Log.set_level(log_level or config.get("H2O3_TPU_LOG_LEVEL"))
    _enable_compile_cache()
    if coordinator is not None and not jax.distributed.is_initialized():
        # Must run before any backend use (jax.devices() etc.).
        # heartbeat_timeout bounds dead-member detection (SURVEY §5.3): the
        # coordination service's heartbeat IS the HeartBeatThread successor;
        # jax's default 100 s is tunable down for tests/latency-sensitive ops
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
            heartbeat_timeout_seconds=config.get_int(
                "H2O3_TPU_HEARTBEAT_TIMEOUT"),
        )
    from h2o3_tpu.utils import telemetry

    telemetry.install()
    from h2o3_tpu.cluster import spmd

    spmd.mark_multi_process(jax.process_count() > 1)  # hot-path flag (DKV keys)
    if mesh is not None:
        _mesh.set_mesh(mesh)
    m = _mesh.get_mesh()
    if _started_at is None:
        _started_at = time.time()
        Log.info(
            f"h2o3_tpu cloud up: {len(jax.devices())} device(s) "
            f"({jax.devices()[0].platform}), {jax.process_count()} process(es), "
            f"mesh axes {dict(m.shape)}"
        )
    return cluster_info()


_degraded: str | None = None
_generation = 0


def generation() -> int:
    """Current cloud formation epoch (see the ``cloud_generation`` gauge).
    Moves ONLY through :func:`recover` — ``clear_degraded`` (the manual
    escape hatch) leaves it alone, so a cloud that never reforms keeps
    generation 0 forever and the spmd generation fence stays inert."""
    return _generation


def adopt_generation(gen: int) -> None:
    """Fast-forward this rank's generation to a NEWER one observed on the
    replicated command stream (a follower learning the coordinator's
    reform). Never moves backwards — the fence against pre-reform commands
    stays intact."""
    global _generation
    if gen > _generation:
        Log.warn(f"cloud generation adopted from command stream: "
                 f"{_generation} -> {gen}")
        _generation = gen
        _G_GENERATION.set(_generation)


def mark_degraded(reason: str) -> None:
    """Latch the cloud unhealthy (fail-stop semantics, SURVEY §5.3): called
    when a replicated command dies with a coordination-service failure
    signature — a dead member makes the cloud unusable; restart is the
    recovery path, durability comes from checkpoints. `/3/Cloud` surfaces it.

    The latch instant is when the flight-recorder ring still holds the
    dying dispatch, so the incident bundle captures HERE — before any
    supervisor reform/retry (or operator restart) discards the evidence."""
    global _degraded
    if _degraded is None:
        _degraded = reason
        _G_DEGRADED.set(1)
        _C_TRANSITIONS.inc(to="degraded")
        Log.err(f"cloud degraded (fail-stop): {reason}")
        from h2o3_tpu.utils import flightrec

        flightrec.record("degraded", reason=str(reason)[:200],
                         generation=_generation)
        flightrec.capture_incident(reason, trigger="degraded")


def degraded_reason() -> str | None:
    return _degraded


def recover(reason: str = "") -> int:
    """The SINGLE supervised un-latch transition (degraded → recovering →
    healthy): tick the cloud generation and release the latch. Only the
    recovery supervisor (cluster/recovery.py) should call this — ticking
    the generation is what fences every command stamped under the old
    formation out of the re-formed cloud, which is the invariant that makes
    auto-restart safe. ``clear_degraded()`` remains the manual escape hatch
    (no generation tick: the operator is asserting the OLD cloud is fine).
    No-op (returns the current generation) when the latch is not set."""
    global _degraded, _generation
    if _degraded is None:
        return _generation
    _C_TRANSITIONS.inc(to="recovering")
    _generation += 1
    _G_GENERATION.set(_generation)
    Log.warn(
        f"cloud recovering (generation {_generation - 1} -> {_generation}; "
        f"was degraded: {_degraded})"
        + (f" — {reason}" if reason else "")
    )
    _degraded = None
    _G_DEGRADED.set(0)
    _C_TRANSITIONS.inc(to="healthy")
    from h2o3_tpu.utils import flightrec

    flightrec.record("generation", generation=_generation,
                     was=_generation - 1)
    return _generation


def clear_degraded() -> None:
    """Un-latch the degraded flag. The latch is one-way BY DESIGN in
    production (restart is the recovery path) — this exists for the chaos
    test suite and for an operator who has verified every rank restarted
    clean and wants the coordinator process reusable."""
    global _degraded
    if _degraded is not None:
        Log.warn(f"cloud degraded latch cleared (was: {_degraded})")
        _C_TRANSITIONS.inc(to="healthy")
    _degraded = None
    _G_DEGRADED.set(0)


def cluster_info() -> dict:
    from h2o3_tpu.utils import devmem

    m = _mesh.get_mesh()
    # per-device health (the /3/Cloud node-table analog), read through the
    # devmem ledger's rate-limited poller — the ONE memory_stats reader in
    # the process (the node table may be up to H2O3_TPU_DEVMEM_POLL_SECS
    # old; a device that errors on the probe reports unhealthy instead of
    # killing the route). Only addressable devices are probed: remote
    # hosts' devices reject memory_stats and must not mark a healthy
    # multi-host cloud unhealthy.
    nodes = []
    healthy = True
    for d in devmem.device_stats():
        node = {"id": d["id"], "platform": d["platform"],
                "process": d["process"], "healthy": d["error"] is None}
        if "in_use" in d:
            node["mem_in_use"] = d["in_use"]
        if "limit" in d:
            node["mem_limit"] = d["limit"]
        if not node["healthy"]:
            healthy = False
        nodes.append(node)
    out_degraded = degraded_reason()
    if out_degraded is not None:
        healthy = False
    _G_HEALTHY.set(1 if healthy else 0)
    return {
        "version": "h2o3_tpu",
        "cloud_healthy": healthy,
        **({"degraded": out_degraded} if out_degraded else {}),
        "generation": _generation,
        "cloud_size": len(jax.devices()),
        "processes": jax.process_count(),
        "platform": jax.devices()[0].platform,
        "mesh": dict(m.shape),
        "nodes": nodes,
        "uptime_ms": int((time.time() - _started_at) * 1e3) if _started_at else 0,
    }


def shutdown() -> None:
    """Drop all state (the process keeps running; devices are managed by JAX)."""
    from h2o3_tpu.cluster.registry import DKV
    from h2o3_tpu.cluster import spmd

    spmd.shutdown_followers()  # release any follower_loop ranks first
    DKV.remove_all()
