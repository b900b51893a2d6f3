"""Tracing / profiling — successor of ``water.TimeLine`` / ``/3/Timeline``
and the ``/3/Profiler`` stack sampler [UNVERIFIED upstream paths, SURVEY.md
§5.1].

On TPU, XLA compile time IS the dominant hidden cost (AutoML builds many
small programs), so the timeline's first-class events are compilations:
``install()`` hooks jax's compile pipeline (trace, lower, compile or cache
load) into the registry, by stage and by the root program span open on the
calling thread, and into a ring buffer, by the innermost span (where a
first ``train()`` or a first scoring request spends its set-up).
``profiler`` wraps ``jax.profiler.trace`` (xplane dumps viewable in
TensorBoard/XProf) — the JProfile/stack-sampling analog for a compiled
runtime — and ``summarize`` reduces such a capture to three tables: device
seconds by ``ph_*`` scope, the program's span tree with self times, and the
device's idle time by the program span that was open on the host
(``python -m h2o3_tpu.utils.telemetry <logdir>`` prints them).
"""

from __future__ import annotations

import collections
import contextlib
import glob
import json
import os
import re
import threading
import time

from h2o3_tpu.utils import metrics as _metrics

_EVENTS: collections.deque = collections.deque(maxlen=4096)
_LOCK = threading.Lock()
_INSTALLED = False


def record(kind: str, msg: str) -> None:
    with _LOCK:
        _EVENTS.append({"ts": time.time(), "kind": kind, "msg": msg})


def events(n: int = 200) -> list[dict]:
    with _LOCK:
        return list(_EVENTS)[-n:]


# the three stages of jax's compile pipeline, each one ``log_elapsed_time``
# event (jax/_src/dispatch.py); "compile" times an XLA compile and a load
# from the persistent cache alike
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

_C_SECONDS = _metrics.counter(
    "compile_seconds_total",
    "seconds of jax's compile pipeline, by stage (trace | lower | compile, "
    "a compile being an XLA compile or a persistent-cache load) and by the "
    "root program span open on the calling thread (root=- outside any); "
    "a stage nested in another is counted as that one's")
_C_EVENTS = _metrics.counter(
    "compile_events_total",
    "programs through each stage of jax's compile pipeline, by stage and "
    "root span (stage=lower counts the programs lowered)")
_C_CACHE_HITS = _metrics.counter(
    "compile_cache_hits_total",
    "persistent XLA compilation-cache hits (jax monitoring event "
    "'/jax/compilation_cache/cache_hits') — a warm scoring replica or a "
    "same-shape-bucket rebuild should count only hits here and compile "
    "zero new programs")

_TLS = threading.local()


def _on_enter(event: str, value: float, **kw) -> None:
    """jax marks the start of each stage with a scalar event: this thread's
    open stages, innermost last, each as the count of stages nested in it."""
    if event in _STAGES:
        _TLS.__dict__.setdefault("open", []).append(0)


def _on_stage(event: str, start: float, end: float, **kw) -> None:
    """A stage has ended. Only an outermost one counts its seconds: what jax
    does inside it is its own (jnp's jitted helpers trace inside the outer
    function's trace; threefry's lowering rule traces its bit operations,
    over a thousand times for a tree program; an eager call while a function
    is traced lowers and compiles inside that trace), so a thread's seconds
    are the union of its stages, each counted once, and the ring holds one
    event for each outermost stage with the count of those nested in it."""
    stage = _STAGES.get(event)
    if stage is None:
        return
    opened = _TLS.__dict__.get("open")
    nested = opened.pop() if opened else 0
    span, root = _metrics.open_span_names() or ("-", "-")
    cache = _TLS.__dict__.pop("cache", "-") if stage == "compile" else "-"
    _C_EVENTS.inc(stage=stage, root=root)
    if opened:  # inside another stage: that one's seconds
        opened[-1] += nested + 1
        return
    secs, fun = end - start, kw.get("fun_name", "?")
    _C_SECONDS.inc(secs, stage=stage, root=root)
    with _LOCK:
        _EVENTS.append({
            "ts": end, "kind": "compile", "stage": stage, "span": span,
            "root": root, "fun": fun, "seconds": secs, "nested": nested,
            "cache": cache, "msg": f"{stage} {fun} in {secs:.3f} s under {span}"})


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT:
        _C_CACHE_HITS.inc()
        _TLS.cache = "hit"
    elif event == _CACHE_MISS:
        _TLS.cache = "miss"


def install() -> None:
    """The process's one set of ``jax.monitoring`` listeners (idempotent):
    each stage of the compile pipeline into ``compile_seconds_total`` and
    ``compile_events_total`` by ``stage`` and ``root`` span, and into the
    ring as a ``compile`` event with the stage, the innermost open span,
    jax's ``fun_name``, the seconds and, for a compile, whether the
    persistent cache had it (``cache=hit|miss|-``); a stage nested in
    another is that one's (``nested`` counts them: :func:`_on_stage`). Cache
    hits go into ``compile_cache_hits_total``. The listeners run only when
    jax traces, lowers or compiles: a warm call hears nothing. No logger is
    touched: scraping jax's compile log meant enabling it, one stderr line
    of argument shapes per traced function."""
    global _INSTALLED
    if _INSTALLED:
        return
    import jax

    jax.monitoring.register_scalar_listener(_on_enter)
    jax.monitoring.register_event_time_span_listener(_on_stage)
    jax.monitoring.register_event_listener(_on_event)
    _INSTALLED = True
    record("telemetry", "compile-event capture installed")


@contextlib.contextmanager
def profiler(logdir: str):
    """``jax.profiler.trace`` wrapper — xplane dumps for TensorBoard/XProf.
    Every program span open during the capture (``metrics.span``, the flight
    recorder's ``dispatch:<site>``) is in it, as an annotation on the host
    plane with ``span_id``/``parent``/``trace`` stats, on the device
    operations' own clock: :func:`summarize` reads them. Start/end also
    stamp the flight-recorder ring (utils/flightrec.py), which says when a
    capture was open; the ring's clock is the epoch and the capture's is its
    own start, so nothing more is read from that pair."""
    import jax

    from h2o3_tpu.utils import flightrec

    record("profiler", f"trace started → {logdir}")
    flightrec.record("profiler_start", logdir=logdir)
    with jax.profiler.trace(logdir):
        yield
    record("profiler", f"trace written → {logdir}")
    flightrec.record("profiler_end", logdir=logdir)


# ---------------------------------------------------------------------------
# reduction of a capture

_DEVICE_PLANE = re.compile(r"^/device:(?:TPU|GPU):(\d+)$")
_HOST_PLANE = "/host:CPU"
_OP_LINE = "XLA Ops"  # the line of a device plane whose events are operations
#: the stat that carries an operation's ``jax.named_scope`` path
#: (``jit(run)/ph_bin/concatenate:``) on a TPU v5e: a stat of the event's
#: METADATA on the ``XLA Ops`` line (the event's own stats are its offsets)
SCOPE_STAT = "tf_op"
# control-flow operations span the operations of their bodies: they count
# towards busy time (a union) and never as an operation of their own
_CONTAINER = re.compile(r"^%?(while|conditional|call)([.\d]*)( |$)")
_PHASE = re.compile(r"(?:^|/)(ph_\w+)")
_GAP_NS = 1e6  # idle gaps under 1 ms are summed, not attributed


# -- the .xplane.pb itself --------------------------------------------------
# ``jax.profiler.ProfileData`` gives an event's name, times and OWN stats; the
# scope path is a stat of the event's *metadata* (``XEventMetadata.stats``),
# which it does not expose. So the capture is read from its protobuf wire
# format, with the field numbers of ``xplane.proto`` (tsl/profiler/protobuf):
# a hundred lines of Python instead of a dependency on a protobuf package.

def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            val, i = buf[i:i + ln], i + ln
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield key >> 3, val


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stats(raw_stats, stat_names: dict) -> dict:
    """``{stat name: value}`` of XStat messages (metadata_id=1; double=2,
    uint64=3, int64=4, str=5, bytes=6, ref=7 → a stat-metadata name)."""
    import struct

    out = {}
    for raw in raw_stats:
        name, val = None, None
        for f, v in _fields(raw):
            if f == 1:
                name = stat_names.get(v)
            elif f == 2:
                val = struct.unpack("<d", v)[0]
            elif f == 3:
                val = v
            elif f == 4:
                val = v - (1 << 64) if v >> 63 else v
            elif f == 5:
                val = _text(v)
            elif f == 6:
                val = bytes(v)
            elif f == 7:
                val = stat_names.get(v, "")
        if name is not None:
            out[name] = val
    return out


def _plane_lines(raw) -> list:
    """The lines of one XPlane (lines=3, event_metadata=4, stat_metadata=5):
    ``[(line name, [(event name, start_ns, dur_ns, the event's and its
    metadata's stats)])]``."""
    lines, ev_meta, stat_names = [], {}, {}
    for f, v in _fields(raw):
        if f == 3:
            lines.append(v)
        elif f in (4, 5):  # map entry: key=1, value=2
            entry = dict(_fields(v))
            (ev_meta if f == 4 else stat_names)[entry.get(1, 0)] = entry[2]
    stat_names = {k: next((_text(v) for f, v in _fields(m) if f == 2), "")
                  for k, m in stat_names.items()}
    meta: dict = {}  # XEventMetadata: name=2, stats=5

    def metadata(mid):
        got = meta.get(mid)
        if got is None:
            fs = list(_fields(ev_meta.get(mid, b"")))
            got = meta[mid] = (
                next((_text(v) for f, v in fs if f == 2), ""),
                _stats([v for f, v in fs if f == 5], stat_names))
        return got

    out = []
    for raw_line in lines:  # XLine: name=2, timestamp_ns=3, events=4
        lname, t0, events = "", 0, []
        for f, v in _fields(raw_line):
            if f == 2:
                lname = _text(v)
            elif f == 3:
                t0 = v
            elif f == 4:
                events.append(v)
        evs = []
        for raw_ev in events:  # XEvent: metadata_id=1, offset_ps=2, duration_ps=3, stats=4
            mid = off = dur = 0
            own = []
            for f, v in _fields(raw_ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
                elif f == 4:
                    own.append(v)
            ename, mstats = metadata(mid)
            evs.append((ename, t0 + off / 1e3, dur / 1e3,
                        {**mstats, **_stats(own, stat_names)} if own else mstats))
        out.append((lname, evs))
    return out


def load_capture(path: str) -> dict:
    """A capture in the form the reduction works on: ``{"device": {chip:
    [[name, start_ns, dur_ns, scope_path], ...]}, "host": [[name, start_ns,
    dur_ns, span_id, parent_id], ...]}`` — each chip's operations and the
    program's spans, on one clock. ``path`` is a profiler log directory (its
    newest ``.xplane.pb``), an ``.xplane.pb``, or a ``.json`` file already
    of that form (a small recorded trace)."""
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    if os.path.isdir(path):
        found = sorted(glob.glob(
            os.path.join(path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            return {"device": {}, "host": []}
        path = found[-1]
    with open(path, "rb") as f:
        space = memoryview(f.read())
    device: dict = {}
    host: list = []
    for f_no, raw in _fields(space):  # XSpace: planes=1; XPlane: name=2
        if f_no != 1:
            continue
        pname = next((_text(v) for f, v in _fields(raw) if f == 2), "")
        m = _DEVICE_PLANE.match(pname)
        if m:
            ops = device.setdefault(m.group(1), [])
            for lname, evs in _plane_lines(raw):
                if lname == _OP_LINE:
                    ops.extend([n, s, d, str(st.get(SCOPE_STAT, ""))]
                               for n, s, d, st in evs)
        elif pname == _HOST_PLANE:
            for _lname, evs in _plane_lines(raw):
                # a span that says which branch it took (its ``path`` label)
                # is summed under that name: model.score_metrics{path=device}
                host.extend([n + (f"{{path={st['path']}}}" if "path" in st else ""),
                             s, d, int(st["span_id"]), int(st.get("parent", 0))]
                            for n, s, d, st in evs if "span_id" in st)
    return {"device": device, "host": host}


def _union(intervals) -> list:
    """Sorted, merged ``[start, end]`` pairs of ``(start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _reduce(cap: dict) -> dict:
    chips = sorted(cap.get("device", {}).items())
    spans = [{"name": n, "start": s, "end": s + d, "id": i, "parent": p}
             for n, s, d, i, p in cap.get("host", ())]
    by_id = {sp["id"]: sp for sp in spans}

    # device seconds by phase scope: the union within a scope, averaged
    # over the chips; containers are left out (their bodies are counted)
    scope_iv: dict = {}
    for _chip, ops in chips:
        for name, s, d, scope in ops:
            if _CONTAINER.match(name):
                continue
            ph = _PHASE.findall(scope)
            scope_iv.setdefault(ph[-1] if ph else "(no scope)", []).append(
                (s, s + d))
    n_chips = max(len(chips), 1)
    by_scope = {k: sum(e - s for s, e in _union(iv)) / n_chips / 1e9
                for k, iv in scope_iv.items()}
    busy = [_union((s, s + d) for _n, s, d, _sc in ops) for _c, ops in chips]

    # the span tree: total and self seconds by name
    child_ns: dict = {}
    for sp in spans:
        if sp["parent"] in by_id:
            child_ns[sp["parent"]] = (child_ns.get(sp["parent"], 0.0)
                                      + sp["end"] - sp["start"])
    table: dict = {}
    for sp in sorted(spans, key=lambda sp: sp["start"]):
        row = table.setdefault(
            sp["name"], {"name": sp["name"], "count": 0, "total_s": 0.0,
                         "self_s": 0.0})
        dur = sp["end"] - sp["start"]
        row["count"] += 1
        row["total_s"] += dur / 1e9
        row["self_s"] += max(dur - child_ns.get(sp["id"], 0.0), 0.0) / 1e9

    def depth(sp) -> int:
        d = 0
        while sp["parent"] in by_id and d < 64:
            sp, d = by_id[sp["parent"]], d + 1
        return d

    for sp in spans:
        sp["depth"] = depth(sp)

    # the window: the root spans' extent (a train() call), else the device's
    roots = [sp for sp in spans if sp["parent"] not in by_id]
    first = busy[0] if busy else []
    if roots:
        w0 = min(sp["start"] for sp in roots)
        w1 = max(sp["end"] for sp in roots)
    elif first:
        w0, w1 = first[0][0], first[-1][1]
    else:
        w0 = w1 = 0.0

    # the first chip's idle gaps inside the window, each put down to the
    # deepest span open on the host while it lasted
    gaps, at = [], w0
    for s, e in first:
        if s > at:
            gaps.append((at, min(s, w1)))
        at = max(at, e)
    if w1 > at:
        gaps.append((at, w1))
    gaps = [(a, b) for a, b in gaps if b > a]
    idle_by: dict = {}
    longest = []
    short_ns = 0.0
    for a, b in gaps:
        if b - a < _GAP_NS:
            short_ns += b - a
            continue
        inside = [sp for sp in spans if sp["start"] < b and sp["end"] > a]
        cuts = sorted({a, b, *(t for sp in inside
                              for t in (sp["start"], sp["end"]) if a < t < b)})
        parts: dict = {}
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            open_ = [sp for sp in inside if sp["start"] <= mid < sp["end"]]
            who = (max(open_, key=lambda sp: sp["depth"])["name"]
                   if open_ else "(no span)")
            parts[who] = parts.get(who, 0.0) + hi - lo
        for who, ns in parts.items():
            idle_by[who] = idle_by.get(who, 0.0) + ns / 1e9
        longest.append({"span": max(parts, key=parts.get),
                        "dur_s": (b - a) / 1e9, "at_s": (a - w0) / 1e9})
    longest.sort(key=lambda g: -g["dur_s"])
    busy_s = (sum(e - s for iv in busy for s, e in iv) / n_chips / 1e9)
    return {
        "window_s": (w1 - w0) / 1e9,
        "device": {"busy_s": busy_s,
                   "by_scope": dict(sorted(by_scope.items(),
                                           key=lambda kv: -kv[1]))},
        "spans": list(table.values()),
        "idle": {"total_s": sum(b - a for a, b in gaps) / 1e9,
                 "under_1ms_s": short_ns / 1e9,
                 "by_span": dict(sorted(idle_by.items(),
                                        key=lambda kv: -kv[1])),
                 "longest": longest[:10]},
    }


def summarize(logdir: str) -> dict:
    """Reduce one profiler capture (:func:`profiler`, ``jax.profiler.trace``
    or the benchmark's ``start_trace``) to what an operator asks of it:

    - ``device``: seconds an operation ran (``busy_s``, a union, averaged
      over the chips) and the same by ``ph_*`` scope (the deepest phase scope
      of an operation's path; the union within a scope, so an operation
      inside a control-flow container is counted once);
    - ``spans``: the program's span tree by name — count, total seconds and
      self seconds (a span's duration less its children's);
    - ``idle``: the first chip's idle time inside the window (the root spans'
      extent), each gap of 1 ms or more put down to the deepest program span
      open on the host while it lasted (``by_span``), and the ten longest.
    """
    return _reduce(load_capture(logdir))


def _print_summary(rep: dict) -> None:
    dev, idle = rep["device"], rep["idle"]
    print(f"window {rep['window_s']:.3f} s, device busy {dev['busy_s']:.3f} s, "
          f"idle {idle['total_s']:.3f} s ({idle['under_1ms_s']:.3f} s in gaps "
          "under 1 ms)")
    print("\ndevice seconds by scope")
    for k, v in dev["by_scope"].items():
        print(f"  {k:<28}{v:>12.4f}")
    print("\nspans                         count     total_s      self_s")
    for r in rep["spans"]:
        print(f"  {r['name']:<28}{r['count']:>5}{r['total_s']:>12.4f}"
              f"{r['self_s']:>12.4f}")
    print("\nidle seconds by the deepest span open on the host")
    for k, v in idle["by_span"].items():
        print(f"  {k:<28}{v:>12.4f}")
    print("\nlongest idle gaps")
    for g in idle["longest"]:
        print(f"  {g['dur_s']:>10.4f} s at {g['at_s']:>9.3f} s  {g['span']}")


def timeline(n: int = 200) -> dict:
    """The GET /3/Timeline payload: compile/profiler events merged with the
    metrics layer's recent span events, by timestamp."""
    # ONE snapshot under the lock serves both the event tail and the compile
    # count — iterating the live deque unlocked raced concurrent record()
    # appends (RuntimeError: deque mutated during iteration)
    with _LOCK:
        snap = list(_EVENTS)
    compile_count = sum(1 for e in snap if e["kind"] == "compile"
                        and e.get("stage", "compile") == "compile")
    spans = _metrics.recent_spans(n)
    evs = snap[-n:] + [
        {"ts": s["ts"], "kind": "span",
         "msg": s["name"], "dur_ms": round(s["dur_s"] * 1e3, 3),
         **({"job": s["trace"]} if s["trace"] else {})}
        for s in spans
    ]
    return {
        "events": sorted(evs, key=lambda e: e["ts"])[-n:],
        "compile_count": compile_count,
        "span_count": len(spans),
    }


if __name__ == "__main__":
    import sys

    if len(sys.argv) != 2:
        raise SystemExit("usage: python -m h2o3_tpu.utils.telemetry <logdir>")
    _print_summary(summarize(sys.argv[1]))
