"""Incident flight recorder — an always-on bounded ring of structured
events + automatic incident bundles (the ISSUE-13 tentpole, pieces 2–3).

The PR-10 self-healing cloud retries past failures but used to discard
exactly the evidence a postmortem needs: what the dead generation was
dispatching when the latch tripped. This module keeps the last
``H2O3_TPU_FLIGHTREC_SIZE`` events in a preallocated ring whose append is
O(µs) and lock-free (one atomic counter bump + one list-slot store — safe
under the GIL; readers snapshot and sort by sequence number), so it runs in
EVERY process all the time, including ``H2O3_TPU_METRICS=0``:

- program dispatch start/end with program key + shape bucket + mesh key
  (the cached-program key carries all three) via :func:`dispatch`, which
  also feeds the ``dispatch_device_seconds{site}`` histogram — measured
  device-time attribution per hot site (tree chunk, IRLS chunk, DL chunk,
  serving batch, stream block). Each dispatch is a span from the metrics
  layer's one core (``metrics.OpenSpan``), so inside a ``jax.profiler``
  capture it is a ``dispatch:<site>`` annotation on the host plane, under
  the program span that issued it (``utils/telemetry.summarize`` reads
  them; the wrapper there also stamps ``profiler`` events into this ring);
- collective phase tallies (per-dispatch byte totals, models/tree);
- stream-block fetch/evict (frame/chunkstore.py), serving
  page-in/eviction (serving/residency.py);
- generation ticks, degraded latches, watchdog trips (cluster/*).

**Incident bundles**: :func:`capture_incident` freezes the evidence — ring
dump + metrics registry snapshot + devmem attribution state + the log tail
— into one JSON file written atomically through persist (temp-file +
``os.replace``; survives a crash mid-write) under
``H2O3_TPU_INCIDENT_DIR``. ``cloud.mark_degraded`` captures at the latch
(the watchdog/death-signature instant — the ring still holds the dying
dispatch), ``recovery.reform`` captures before any reform/retry, and the
supervised-restart loop surfaces the bundle path in the job's recovery
block. Captures dedup per degraded episode (same cloud generation within
:data:`_DEDUP_SECS`) so a failure storm writes one bundle, not hundreds.
"""

from __future__ import annotations

import itertools
import json
import os
import tempfile
import threading
import time

from h2o3_tpu import config as _config
from h2o3_tpu.utils import faults as _faults
from h2o3_tpu.utils import jobacct as _jobacct
from h2o3_tpu.utils import metrics as _mx

_DISPATCH_SECONDS = _mx.histogram(
    "dispatch_device_seconds",
    "wall seconds inside hot device-dispatch sites, by site (tree = fused "
    "tree/level programs, irls_chunk = fused GLM chunk, dl_chunk = DL "
    "epoch-chunk program, serving_batch = batched scorer dispatch, "
    "stream_block = out-of-core per-block compute). Host wall INSIDE the "
    "dispatch call: device time only where the site blocks on its result "
    "— on an asynchronous backend a site that does not block records "
    "enqueue time, and the residue attributes to the site that syncs")
_INCIDENTS = _mx.counter(
    "incident_bundles_total",
    "incident bundles written (ring dump + metrics + devmem + log tail), "
    "by trigger", always=True)

# ring size is read ONCE at import (like H2O3_TPU_METRICS): the append is
# the hot path and must not re-read the environment. 0 disables the ring.
try:
    _SIZE = max(int(_config.get("H2O3_TPU_FLIGHTREC_SIZE")), 0)
except (TypeError, ValueError):
    _SIZE = 4096

_RING: list = [None] * _SIZE
_SEQ = itertools.count()
_last_seq = -1  # advisory high-water for status(); exact value via events()


def record(kind: str, **fields) -> None:
    """Append one structured event. O(µs), no locks: one atomic counter
    bump + one slot store (field values should be JSON-safe scalars)."""
    global _last_seq
    if not _SIZE:
        return
    i = next(_SEQ)
    _RING[i % _SIZE] = (i, time.time(), kind, fields)
    _last_seq = i


def events(n: int | None = None, kind: str | None = None) -> list[dict]:
    """Snapshot of the ring, oldest→newest (sorted by sequence number;
    torn slots from concurrent appends simply reflect whichever event won
    the slot). ``kind`` filters; ``n`` keeps the newest n."""
    snap = [e for e in list(_RING) if e is not None]
    snap.sort(key=lambda e: e[0])
    out = [
        {"seq": s, "ts": ts, "kind": k, **f}
        for s, ts, k, f in snap
        if kind is None or k == kind
    ]
    return out[-n:] if n else out


def ring_status() -> dict:
    nxt = _last_seq + 1
    return {
        "size": _SIZE,
        "next_seq": nxt,
        "dropped": max(nxt - _SIZE, 0),
    }


def trace_export(trace: str | None = None, n: int | None = None) -> dict:
    """Chrome/Perfetto trace JSON of the ring (``GET
    /3/FlightRecorder?format=trace``; tools/trace_report.py renders the
    same shape from an incident bundle). One lane per trace id:
    ``dispatch_end`` events — which carry the measured duration plus
    trace/span/parent ids — render as complete ("X") spans positioned at
    end-timestamp minus duration; every other ring kind (chunk_fetch,
    queue_wait, collectives, …) renders as an instant event on its trace's
    lane; ``profiler_start``/``profiler_end`` pairs render the window in
    which an xplane capture was open on a dedicated lane (the capture
    itself holds the spans: ``telemetry.summarize``). Registry spans of the exported
    traces (the "job" / "rest.request" parents) merge onto the same lanes,
    completing the span tree Perfetto shows."""
    return render_trace(events(n=n), trace=trace,
                        span_fetch=_mx.trace_events)


def render_trace(evs: list[dict], trace: str | None = None,
                 span_fetch=None) -> dict:
    """Render a list of ring-shaped events (live ring or an incident
    bundle's ``events``) as Chrome/Perfetto trace JSON. ``span_fetch``
    (trace_id -> registry span list) merges in-process registry spans —
    pass None when rendering a bundle, whose registry spans are gone."""
    if trace is not None:
        trace = str(trace)
        evs = [e for e in evs if e.get("trace") == trace
               or e["kind"] in ("profiler_start", "profiler_end")]
    out: list[dict] = [{"name": "process_name", "ph": "M", "pid": 1,
                        "tid": 0, "args": {"name": "h2o3_tpu flight recorder"}}]
    lanes: dict[str, int] = {}

    def lane(tr) -> int:
        key = tr if tr else "(untraced)"
        tid = lanes.get(key)
        if tid is None:
            tid = lanes[key] = len(lanes) + 1
        return tid

    if trace is not None:
        lane(trace)  # registry-only traces still get their lane
    prof_open: dict[str, float] = {}
    for e in evs:
        kind = e["kind"]
        args = {k: v for k, v in e.items()
                if k not in ("ts", "kind") and v is not None}
        if kind == "dispatch_start":
            continue  # the matching dispatch_end carries the measured span
        if kind == "dispatch_end" or "dur_ms" in e:
            # duration-carrying events (dispatch_end, the batcher's
            # queue_wait, …) render as complete spans anchored at their
            # end timestamp minus the measured duration
            dur_s = float(e.get("dur_ms") or 0.0) / 1e3
            name = (f"dispatch:{e.get('site', '?')}"
                    if kind == "dispatch_end" else kind)
            out.append({"name": name, "ph": "X",
                        "ts": (e["ts"] - dur_s) * 1e6,
                        "dur": max(dur_s * 1e6, 1.0),
                        "pid": 1, "tid": lane(e.get("trace")), "args": args})
        elif kind == "profiler_start":
            prof_open[str(e.get("logdir") or "")] = e["ts"]
        elif kind == "profiler_end":
            t0 = prof_open.pop(str(e.get("logdir") or ""), None)
            if t0 is not None:
                out.append({"name": "xplane_capture", "ph": "X",
                            "ts": t0 * 1e6,
                            "dur": max((e["ts"] - t0) * 1e6, 1.0),
                            "pid": 1, "tid": 0, "args": args})
        else:
            out.append({"name": kind, "ph": "i", "s": "t",
                        "ts": e["ts"] * 1e6,
                        "pid": 1, "tid": lane(e.get("trace")), "args": args})
    if span_fetch is not None:
        for tr, tid in list(lanes.items()):
            for s in span_fetch(tr):
                out.append({"name": s["name"], "ph": "X",
                            "ts": s["ts"] * 1e6,
                            "dur": max(s["dur_s"] * 1e6, 1.0), "pid": 1,
                            "tid": tid,
                            "args": {"span_id": s["id"],
                                     "parent_id": s["parent"],
                                     **s["labels"]}})
    out.append({"name": "thread_name", "ph": "M", "pid": 1, "tid": 0,
                "args": {"name": "profiler"}})
    for tr, tid in lanes.items():
        out.append({"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
                    "args": {"name": f"trace {tr}"}})
    return {"traceEvents": out, "displayTimeUnit": "ms",
            "otherData": {"traces": sorted(lanes),
                          **({"trace": trace} if trace else {})}}


def reset() -> None:
    """Drop every recorded event (tests). Sequence numbers keep counting
    so ordering stays monotonic across a reset."""
    for i in range(_SIZE):
        _RING[i] = None


# -- per-dispatch device-time attribution ------------------------------------

#: span ids the overload hang watchdog declared wedged (overload.py adds
#: via :func:`mark_span_hung`): a dispatch that UNWEDGES after its trip
#: fail-stops at its own exit — its result belongs to a formation the
#: supervisor already gave up on, and raising there is what hands the job
#: to recovery.run_supervised. Module-level set: the clean-exit check is
#: one truthiness test when nothing is hung.
_HUNG_SPANS: set = set()


def mark_span_hung(span) -> None:
    """Flag an open dispatch span as watchdog-tripped (overload.py)."""
    if span is not None:
        _HUNG_SPANS.add(span)


class _Dispatch:
    """Context manager stamping dispatch start/end events into the ring and
    feeding ``dispatch_device_seconds{site}``. A class, not a
    @contextmanager: the hot sites enter/exit this once per device program
    and the generator machinery is measurably slower.

    Every dispatch is also a **span** in the active trace tree (ISSUE-18),
    opened and closed by the metrics layer's one core
    (``metrics.OpenSpan``: id, parent, trace, clock, profiler annotation
    ``dispatch:<site>``): start/end events carry ``trace`` (the enclosing
    job/request trace id, None when untraced), the span's id from the
    shared sequence, and the ``parent`` span active at entry. The span is
    the active one for the dispatch body, so nested dispatches (a
    stream_block wrapping a tree chunk) and registry spans parent
    correctly — all of it gate-free, like the ring itself. On exit the
    measured wall feeds the per-job ledger (utils/jobacct.py) under the
    same trace id."""

    __slots__ = ("site", "meta", "_s")

    def __init__(self, site: str, meta: dict):
        self.site = site
        self.meta = meta

    def __enter__(self):
        s = self._s = _mx.OpenSpan(f"dispatch:{self.site}")
        record("dispatch_start", site=self.site, trace=s.trace,
               span=s.id, parent=s.parent, **self.meta)
        if _faults.armed():
            # chaos hooks INSIDE the open span: hang_check sleeps while the
            # ring shows an open dispatch_start (what the hang watchdog
            # walks for); oom_check raises a synthetic RESOURCE_EXHAUSTED.
            # A raise here must still stamp dispatch_end + classify, so
            # route it through our own __exit__ before propagating.
            try:
                _faults.hang_check(self.site)
                _faults.oom_check(self.site)
            except BaseException:
                import sys

                self.__exit__(*sys.exc_info())
                raise
        return self

    def __exit__(self, exc_type, exc, tb):
        s = self._s
        dur = s.close()
        record("dispatch_end", site=self.site,
               dur_ms=round(dur * 1e3, 3),
               trace=s.trace, span=s.id, parent=s.parent,
               **({"error": exc_type.__name__} if exc_type else {}))
        _DISPATCH_SECONDS.observe(dur, site=self.site)
        _jobacct.on_dispatch(s.trace, self.site, dur)
        from h2o3_tpu.utils import devmem

        devmem.on_dispatch()  # high-water marks sample at dispatch boundaries
        if exc is not None:
            from h2o3_tpu.utils import overload as _ov

            _ov.note_dispatch_error(self.site, exc)
        elif _HUNG_SPANS and s.id in _HUNG_SPANS:
            # the hang watchdog tripped on this span and already latched the
            # cloud degraded: a late result from a wedged dispatch must not
            # be trusted — fail-stop so the supervisor's reform+resume owns
            # the job from here.
            _HUNG_SPANS.discard(s.id)
            raise RuntimeError(
                f"cloud is degraded (fail-stop): dispatch site "
                f"{self.site!r} span {s.id} was declared wedged by "
                "the hang watchdog and its late result is discarded; "
                "supervised jobs resume from their latest snapshot")
        return False


def dispatch(site: str, **meta) -> _Dispatch:
    """Wrap one hot device dispatch: ``with flightrec.dispatch("tree",
    program=key): out = fn(*args)``. Meta lands in the ring only (free-form
    — program keys, block indices), never as metric labels."""
    return _Dispatch(site, meta)


# -- incident bundles --------------------------------------------------------

_DEDUP_SECS = 30.0
_CAP_LOCK = threading.Lock()
_last_bundle: tuple[float, int, str] | None = None  # (monotonic, gen, path)


def incident_dir() -> str:
    """H2O3_TPU_INCIDENT_DIR ('' = <tmp>/h2o3_incidents)."""
    d = _config.get("H2O3_TPU_INCIDENT_DIR").strip()
    return d or os.path.join(tempfile.gettempdir(), "h2o3_incidents")


def last_incident() -> str | None:
    """Path of the most recently written bundle (None before the first)."""
    return _last_bundle[2] if _last_bundle else None


def _rank() -> int:
    """This process's pod rank (0 single-process / before jax init)."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:  # noqa: BLE001 — capture must work before jax init
        return 0


def _sibling_bundles(path: str, gen: int) -> list[str]:
    """Other ranks' bundles for the same degraded episode. Every rank's
    latch fires `capture_incident` locally (collectives are dead on the
    failure path, so no gather — each rank freezes its OWN ring), and the
    incident dir is a shared volume on pods: bundles of the same cloud
    generation ARE the pod-wide capture. This cross-references them so one
    bundle leads a postmortem to the rest."""
    d = os.path.dirname(path)
    if not d or "://" in path:
        return []
    try:
        tag = f"_gen{gen}_"
        return sorted(
            os.path.join(d, f) for f in os.listdir(d)
            if tag in f and f.endswith(".json")
            and os.path.join(d, f) != path
        )
    except OSError:
        return []


def capture_incident(reason: str, trigger: str = "degraded",
                     extra: dict | None = None) -> str | None:
    """Freeze the evidence for a postmortem: ring dump + metrics registry
    snapshot + devmem attribution + log tail, written atomically through
    persist BEFORE any reform/retry discards the dying state. Returns the
    bundle path (the cached one when this degraded episode — same cloud
    generation within the dedup window — already captured), or None when
    capture itself fails (never raises: this runs on failure paths)."""
    global _last_bundle
    try:
        from h2o3_tpu.cluster import cloud

        gen = cloud.generation()
    except Exception:  # noqa: BLE001 — capture must work before cloud init
        gen = -1
    with _CAP_LOCK:
        if (_last_bundle is not None and _last_bundle[1] == gen
                and time.monotonic() - _last_bundle[0] < _DEDUP_SECS):
            return _last_bundle[2]
        try:
            from h2o3_tpu import persist
            from h2o3_tpu.utils import devmem
            from h2o3_tpu.utils.log import Log

            rank = _rank()
            bundle = {
                "schema": "h2o3_incident/2",
                "ts": time.time(),
                "reason": str(reason)[:2000],
                "trigger": trigger,
                "generation": gen,
                "rank": rank,
                "ring": ring_status(),
                "events": events(),
                "devmem": devmem.status(),
                "metrics": _mx.REGISTRY.compact_snapshot(),
                "jobs": _jobacct.all_jobs(),
                "log_tail": Log.tail(200),
                **(extra or {}),
            }
            stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
            path = os.path.join(
                incident_dir(),
                f"incident_{stamp}_gen{gen}_r{rank}_{os.getpid()}.json")
            d = os.path.dirname(path)
            if d and "://" not in path:
                os.makedirs(d, exist_ok=True)
            # each rank captures its OWN ring at its own latch; siblings of
            # this generation already on the (shared) volume get linked so
            # the bundle set is discoverable from any one of them.
            bundle["pod_bundles"] = _sibling_bundles(path, gen)
            persist.write_bytes(
                json.dumps(bundle, default=str).encode(), path)
            _last_bundle = (time.monotonic(), gen, path)
            _INCIDENTS.inc(trigger=trigger)
            record("incident", path=path, trigger=trigger,
                   reason=str(reason)[:200])
            Log.warn(f"incident bundle written: {path} ({trigger}: "
                     f"{str(reason)[:120]})")
            return path
        except Exception as e:  # noqa: BLE001 — never raise on a failure path
            try:
                from h2o3_tpu.utils.log import Log

                Log.warn(f"incident bundle capture failed: {e!r}")
            except Exception:  # noqa: BLE001 — interpreter teardown
                pass
            return None


def _reset_incidents_for_tests() -> None:
    global _last_bundle
    with _CAP_LOCK:
        _last_bundle = None
