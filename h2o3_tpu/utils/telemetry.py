"""Tracing / profiling — successor of ``water.TimeLine`` / ``/3/Timeline``
and the ``/3/Profiler`` stack sampler [UNVERIFIED upstream paths, SURVEY.md
§5.1].

On TPU, XLA compile time IS the dominant hidden cost (AutoML builds many
small programs), so the timeline's first-class events are compilations:
``install()`` hooks jax's compile monitoring events into a ring buffer.
``profiler`` wraps ``jax.profiler.trace`` (xplane dumps viewable in
TensorBoard/XProf) — the JProfile/stack-sampling analog for a compiled
runtime.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time

_EVENTS: collections.deque = collections.deque(maxlen=4096)
_LOCK = threading.Lock()
_INSTALLED = False


def record(kind: str, msg: str) -> None:
    with _LOCK:
        _EVENTS.append({"ts": time.time(), "kind": kind, "msg": msg})


def events(n: int = 200) -> list[dict]:
    with _LOCK:
        return list(_EVENTS)[-n:]


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        record("compile", f"XLA compilation of {kw.get('fun_name', '?')} "
                          f"in {duration:.3f} s")


def install() -> None:
    """Capture XLA compile events into the timeline (idempotent) — one per
    program compiled OR loaded from the persistent cache (jax times both
    under the one event; a load is just much quicker), through jax's
    monitoring hooks. No logger is touched: scraping jax's compile log
    meant enabling it, one stderr line of argument shapes per traced
    function."""
    global _INSTALLED
    if _INSTALLED:
        return
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _INSTALLED = True
    record("telemetry", "compile-event capture installed")


@contextlib.contextmanager
def profiler(logdir: str):
    """``jax.profiler.trace`` wrapper — xplane dumps for TensorBoard/XProf.
    Start/end also stamp the flight-recorder ring (utils/flightrec.py), so
    an xplane capture window cross-references with the dispatch events by
    timestamp — which programs the profiler saw is readable from the ring."""
    import jax

    from h2o3_tpu.utils import flightrec

    record("profiler", f"trace started → {logdir}")
    flightrec.record("profiler_start", logdir=logdir)
    with jax.profiler.trace(logdir):
        yield
    record("profiler", f"trace written → {logdir}")
    flightrec.record("profiler_end", logdir=logdir)


def timeline(n: int = 200) -> dict:
    """The GET /3/Timeline payload: compile/profiler events merged with the
    metrics layer's recent span events, by timestamp."""
    # ONE snapshot under the lock serves both the event tail and the compile
    # count — iterating the live deque unlocked raced concurrent record()
    # appends (RuntimeError: deque mutated during iteration)
    with _LOCK:
        snap = list(_EVENTS)
    compile_count = sum(1 for e in snap if e["kind"] == "compile")
    evs = snap[-n:]
    span_count = 0
    try:
        from h2o3_tpu.utils import metrics

        spans = metrics.recent_spans(n)
        span_count = len(spans)
        evs = evs + [
            {"ts": s["ts"], "kind": "span",
             "msg": s["name"], "dur_ms": round(s["dur_s"] * 1e3, 3),
             **({"job": s["trace"]} if s["trace"] else {})}
            for s in spans
        ]
        evs = sorted(evs, key=lambda e: e["ts"])[-n:]
    except Exception:  # metrics layer disabled/broken must not sink /3/Timeline
        pass
    return {
        "events": evs,
        "compile_count": compile_count,
        "span_count": span_count,
    }
