"""The measured window, one general generator for each traffic ``kind``.
A traffic mix is a data file (``traffic/<mix>.json``) of a kind's parameters;
a new mix of a kind that is here needs no code."""

from __future__ import annotations

import tempfile
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Programs compiled (or loaded from the persistent cache: jax times both
    under one event) since the listener was installed."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.count += 1


def block_on(obj, depth: int = 5) -> None:
    """``block_until_ready`` on every device array reachable from ``obj``."""
    import jax

    if isinstance(obj, jax.Array):
        obj.block_until_ready()
    elif depth and isinstance(obj, dict):
        for v in obj.values():
            block_on(v, depth - 1)
    elif depth and isinstance(obj, (list, tuple)):
        for v in obj:
            block_on(v, depth - 1)
    elif depth and hasattr(obj, "__dict__"):
        block_on(vars(obj), depth - 1)


def device_bytes() -> tuple[int, int]:
    """What the fullest chip holds now, as (live, reserved) bytes: live
    buffers, and what the runtime has reserved for compiled programs'
    temporaries (``memory_stats()`` counts the two apart, and a tree
    program's reservation is many times its live buffers)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(((int(s.get("bytes_in_use", 0)), int(s.get("bytes_reserved", 0)))
                for s in stats), key=sum, default=(0, 0))


def read_counters(names) -> dict:
    from h2o3_tpu.utils import metrics

    return {n: float(metrics.counter_value(n)) for n in names}


def one_call(mod, cfg, data, fresh_frame: bool = False) -> tuple:
    """One whole ``train`` call, timed to the point where everything it
    produced is ready. Returns (estimator, seconds, passes). With
    ``fresh_frame`` the client first wraps the resident columns in a new
    frame, inside the timed call: nothing the program cached on the old
    frame (its binned codes, its statistics) is there for this call."""
    t0 = time.perf_counter()
    if fresh_frame:
        data.rewrap()
    est = mod.build_estimator(cfg)
    mod.train(est, data)
    block_on(est.model.output)
    n_passes = mod.passes(cfg, est)
    mod.release(est)
    return est, time.perf_counter() - t0, n_passes


def train_loop(mod, cfg, data, params: dict, seconds: float, trace: bool,
               counter_names=(), compiles: CompileCounter | None = None) -> dict:
    """Closed loop of one client: whole ``train`` calls back to back. With
    ``trace`` the profiler is open around one call (``traced_call``, counted
    from 1), which then always runs, and the counters are read as
    differences over the same call."""
    import jax

    traced_at = int(params.get("traced_call", 2)) - 1 if trace else None
    fresh = bool(params.get("fresh_frame", False))
    calls, failed, models, traced, held = [], 0, [], None, (0, 0)
    compiled0 = compiles.count if compiles else 0
    last = 0.0
    t_open = time.perf_counter()
    while True:
        i = len(calls) + failed
        elapsed = time.perf_counter() - t_open
        forced = i == 0 or (traced_at is not None and i <= traced_at)
        if not forced and elapsed + last > seconds:
            break
        tracing = i == traced_at
        if tracing:
            logdir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            before = read_counters(counter_names)
            jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            est, wall, n_passes = one_call(mod, cfg, data, fresh)
        except Exception:  # a failed call is counted, and fails the run
            import traceback

            traceback.print_exc()
            failed += 1
            if failed > 2:
                break
            continue
        finally:
            if tracing:
                jax.profiler.stop_trace()
        if tracing:
            after = read_counters(counter_names)
            traced = {"dir": logdir, "wall_s": wall, "passes": n_passes,
                      "index": i, "where": "inside train()",
                      "counters": {k: after[k] - before[k] for k in after}}
        held = max(held, device_bytes(), key=sum)
        calls.append({"wall_s": wall, "passes": n_passes})
        models.append(est)
        last = wall
    return {"window_s": time.perf_counter() - t_open, "calls": calls,
            "failed": failed, "models": models, "traced": traced,
            "device_bytes": held,
            "compiles_in_window": (compiles.count - compiled0) if compiles else None}


KINDS = {"train_loop": train_loop}
