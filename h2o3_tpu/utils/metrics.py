"""Cluster-wide metrics registry + per-job span tracing — the first-class
observability layer (successor of ``water.util.Log`` counters + ``/3/Timeline``
phase timing, done as one subsystem; docs/OBSERVABILITY.md is the runbook).

Three pieces, one module:

- **Registry** (:data:`REGISTRY`): thread-safe labeled counters, gauges and
  bucketed histograms. Served as Prometheus text exposition over
  ``GET /3/Metrics`` (JSON with ``?format=json``) and snapshotted into bench
  artifacts, so the live endpoint and the bench numbers can never disagree.
- **Spans** (:func:`span`): a hierarchical timing context manager.
  ``span("gbm.build_tree", trees=8)`` nests under the enclosing span and
  under the active Job's trace (:func:`trace`, entered by
  ``ModelBuilder.train`` and ``Job.start``); every completed span lands in
  the per-trace event list (served as Chrome-trace JSON over
  ``GET /3/Jobs/{key}/trace``), in the recent-span ring merged into
  ``/3/Timeline``, in the ``span_seconds`` latency histogram and, while a
  profiler session is open, in the capture itself (:class:`OpenSpan`).
- **Gate**: ``H2O3_TPU_METRICS=0`` turns the layer into near-free no-ops
  (read once at import — the hot paths must not re-read the environment).
  Counters created with ``always=True`` keep counting even when gated:
  the tree-build counters behind the ``BUILD_STATS`` back-compat alias are
  a test/bench CONTRACT (dispatch/compile accounting), not optional
  telemetry.

Hot-path budget: one ``perf_counter`` pair, one profiler annotation (a flag
test while no capture is open) + one locked dict update per span/observe —
the bench fused-tree acceptance bound is <= 2% overhead registry-on vs
``H2O3_TPU_METRICS=0``.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import contextvars
import itertools
import threading
import time

# read ONCE at import: the gate is checked on every counter bump and span
# enter — config.get (env lookup) per call would itself be the overhead the
# gate exists to remove. set_enabled() is the test/bench override.
from h2o3_tpu import config as _config

_ENABLED: bool = _config.get_bool("H2O3_TPU_METRICS")


def enabled() -> bool:
    return _ENABLED


def set_enabled(flag: bool) -> None:
    """Test/ops override of the import-time H2O3_TPU_METRICS gate."""
    global _ENABLED
    _ENABLED = bool(flag)


# ---------------------------------------------------------------------------
# metric families

# Prometheus default buckets extended down (sub-ms device dispatches) and up
# (multi-minute AutoML steps).
DEFAULT_BUCKETS: tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0, 600.0,
)


def _label_key(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


def _escape_label(v) -> str:
    return (
        str(v).replace("\\", "\\\\").replace("\n", "\\n").replace('"', '\\"')
    )


def _escape_help(v: str) -> str:
    return v.replace("\\", "\\\\").replace("\n", "\\n")


def _fmt(v: float) -> str:
    """Prometheus sample value: integral floats render without the .0 tail."""
    f = float(v)
    return str(int(f)) if f.is_integer() else repr(f)


class _Family:
    kind = "untyped"

    def __init__(self, name: str, help: str, always: bool = False):
        self.name = name
        self.help = help
        self.always = always  # True: bypass the H2O3_TPU_METRICS gate
        self._lock = threading.Lock()
        self._children: dict[tuple, object] = {}

    def _on(self) -> bool:
        return _ENABLED or self.always

    def remove(self, **labels) -> None:
        """Drop one labeled child. Bounded-cardinality families (the per-job
        ledger's ``job_*`` series) evict LRU jobs through this so the
        registry can't grow one child per job forever."""
        with self._lock:
            self._children.pop(_label_key(labels), None)


class Counter(_Family):
    kind = "counter"

    def __init__(self, name: str, help: str, always: bool = False):
        super().__init__(name, help, always)
        self._children[()] = 0.0  # unlabeled child renders from creation

    def inc(self, amount: float = 1.0, **labels) -> None:
        if not self._on():
            return
        k = _label_key(labels)
        with self._lock:
            self._children[k] = self._children.get(k, 0.0) + amount

    def value(self, **labels) -> float:
        with self._lock:
            return float(self._children.get(_label_key(labels), 0.0))

    def set_(self, v: float, **labels) -> None:
        """Non-monotonic write — ONLY for the BUILD_STATS back-compat alias
        (``BUILD_STATS[k] = v``) and counter resets; not part of the
        Prometheus counter contract."""
        with self._lock:
            self._children[_label_key(labels)] = float(v)

    def samples(self):
        with self._lock:
            return [(dict(k), v) for k, v in sorted(self._children.items())]


class Gauge(_Family):
    kind = "gauge"

    def __init__(self, name: str, help: str, always: bool = False):
        super().__init__(name, help, always)
        self._children[()] = 0.0

    def set(self, v: float, **labels) -> None:
        if not self._on():
            return
        with self._lock:
            self._children[_label_key(labels)] = float(v)

    def inc(self, amount: float = 1.0, **labels) -> None:
        if not self._on():
            return
        k = _label_key(labels)
        with self._lock:
            self._children[k] = self._children.get(k, 0.0) + amount

    def dec(self, amount: float = 1.0, **labels) -> None:
        self.inc(-amount, **labels)

    def value(self, **labels) -> float:
        with self._lock:
            return float(self._children.get(_label_key(labels), 0.0))

    samples = Counter.samples


class _HistChild:
    __slots__ = ("counts", "sum", "count")

    def __init__(self, n_buckets: int):
        self.counts = [0] * (n_buckets + 1)  # last slot = +Inf overflow
        self.sum = 0.0
        self.count = 0


class Histogram(_Family):
    kind = "histogram"

    def __init__(self, name: str, help: str, buckets=None, always: bool = False):
        super().__init__(name, help, always)
        self.buckets = tuple(sorted(buckets or DEFAULT_BUCKETS))

    def observe(self, v: float, **labels) -> None:
        if not self._on():
            return
        k = _label_key(labels)
        i = bisect.bisect_left(self.buckets, v)
        with self._lock:
            child = self._children.get(k)
            if child is None:
                child = self._children[k] = _HistChild(len(self.buckets))
            child.counts[i] += 1
            child.sum += v
            child.count += 1

    def samples(self):
        """[(labels, cumulative_bucket_counts, sum, count)] — cumulative per
        the Prometheus histogram contract (``le`` buckets are inclusive
        prefixes)."""
        out = []
        with self._lock:
            for k, c in sorted(self._children.items(), key=lambda kv: kv[0]):
                cum, tot = [], 0
                for n in c.counts:
                    tot += n
                    cum.append(tot)
                out.append((dict(k), cum, c.sum, c.count))
        return out


class MetricsRegistry:
    """Process-wide family registry (one per coordinator process; followers
    keep their own — REST serves the coordinator's, like H2O's per-node
    logs)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._families: dict[str, _Family] = {}

    def _get(self, name: str, cls, *args, **kw):
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = self._families[name] = cls(name, *args, **kw)
            elif not isinstance(fam, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {fam.kind}"
                )
            return fam

    def counter(self, name: str, help: str = "", always: bool = False) -> Counter:
        return self._get(name, Counter, help, always)

    def gauge(self, name: str, help: str = "", always: bool = False) -> Gauge:
        return self._get(name, Gauge, help, always)

    def histogram(self, name: str, help: str = "", buckets=None,
                  always: bool = False) -> Histogram:
        return self._get(name, Histogram, help, buckets, always)

    def families(self) -> list[_Family]:
        with self._lock:
            return [self._families[n] for n in sorted(self._families)]

    def to_prometheus(self) -> str:
        """Prometheus text exposition (format 0.0.4) of every family."""
        lines: list[str] = []
        for fam in self.families():
            if fam.help:
                lines.append(f"# HELP {fam.name} {_escape_help(fam.help)}")
            lines.append(f"# TYPE {fam.name} {fam.kind}")
            if isinstance(fam, Histogram):
                for labels, cum, s, n in fam.samples():
                    base = [f'{k}="{_escape_label(v)}"'
                            for k, v in sorted(labels.items())]
                    for le, c in zip(
                        [*(_fmt(b) for b in fam.buckets), "+Inf"], cum
                    ):
                        lab = ",".join(base + [f'le="{le}"'])
                        lines.append(f"{fam.name}_bucket{{{lab}}} {c}")
                    suffix = "{" + ",".join(base) + "}" if base else ""
                    lines.append(f"{fam.name}_sum{suffix} {_fmt(s)}")
                    lines.append(f"{fam.name}_count{suffix} {n}")
            else:
                for labels, v in fam.samples():
                    if labels:
                        lab = ",".join(
                            f'{k}="{_escape_label(val)}"'
                            for k, val in sorted(labels.items())
                        )
                        lines.append(f"{fam.name}{{{lab}}} {_fmt(v)}")
                    else:
                        lines.append(f"{fam.name} {_fmt(v)}")
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict:
        """Full JSON-shape dump (the ``?format=json`` payload)."""
        out = {}
        for fam in self.families():
            if isinstance(fam, Histogram):
                vals = [
                    {"labels": labels,
                     "buckets": {(_fmt(b) if i < len(fam.buckets) else "+Inf"): c
                                 for i, (b, c) in enumerate(
                                     zip([*fam.buckets, float("inf")], cum))},
                     "sum": s, "count": n}
                    for labels, cum, s, n in fam.samples()
                ]
            else:
                vals = [{"labels": labels, "value": v}
                        for labels, v in fam.samples()]
            out[fam.name] = {"type": fam.kind, "help": fam.help, "values": vals}
        return out

    def compact_snapshot(self) -> dict:
        """One-line-JSON-friendly registry block for bench artifacts:
        counters/gauges keep per-child values (labels inlined as
        ``name{k=v}``), histograms compact to ``{count, sum}``."""
        out: dict = {}
        for fam in self.families():
            if isinstance(fam, Histogram):
                for labels, _cum, s, n in fam.samples():
                    out[_flat_name(fam.name, labels)] = {
                        "count": n, "sum": round(s, 6)
                    }
            else:
                for labels, v in fam.samples():
                    out[_flat_name(fam.name, labels)] = (
                        int(v) if float(v).is_integer() else round(v, 6)
                    )
        return out

    def reset(self) -> None:
        """Drop every family (tests/bench phase isolation)."""
        with self._lock:
            self._families.clear()


def _flat_name(name: str, labels: dict) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    return f"{name}{{{inner}}}"


def render_snapshot(snap: dict) -> str:
    """Prometheus text exposition of a :meth:`MetricsRegistry.snapshot`-shaped
    dict. The pod-federation path (cluster/federation.py) merges per-rank
    snapshots into one dict that lives in no registry — this renders it with
    the exact same escaping/formatting rules as :meth:`to_prometheus`."""
    lines: list[str] = []
    for name in sorted(snap):
        fam = snap[name]
        if fam.get("help"):
            lines.append(f"# HELP {name} {_escape_help(fam['help'])}")
        lines.append(f"# TYPE {name} {fam.get('type', 'untyped')}")
        for val in fam.get("values", ()):
            base = [f'{k}="{_escape_label(v)}"'
                    for k, v in sorted(val.get("labels", {}).items())]
            if "buckets" in val:
                for le, c in val["buckets"].items():
                    lab = ",".join(base + [f'le="{le}"'])
                    lines.append(f"{name}_bucket{{{lab}}} {_fmt(c)}")
                suffix = "{" + ",".join(base) + "}" if base else ""
                lines.append(f"{name}_sum{suffix} {_fmt(val['sum'])}")
                lines.append(f"{name}_count{suffix} {_fmt(val['count'])}")
            elif base:
                lines.append(f"{name}{{{','.join(base)}}} {_fmt(val['value'])}")
            else:
                lines.append(f"{name} {_fmt(val['value'])}")
    return "\n".join(lines) + "\n"


REGISTRY = MetricsRegistry()


def counter(name: str, help: str = "", always: bool = False) -> Counter:
    return REGISTRY.counter(name, help, always)


def gauge(name: str, help: str = "", always: bool = False) -> Gauge:
    return REGISTRY.gauge(name, help, always)


def histogram(name: str, help: str = "", buckets=None,
              always: bool = False) -> Histogram:
    return REGISTRY.histogram(name, help, buckets, always)


def counter_value(name: str, /, **labels) -> float:
    """Registry read without create-on-miss (0.0 for unknown families or
    children). ``name`` may also be one sample's flat name, the form
    :meth:`MetricsRegistry.compact_snapshot` prints:
    ``tree_hist_hbm_bytes_total{path=rebin}``; a histogram child's running
    sum and count are ``span_seconds_sum{name=glm.fit}`` and
    ``span_seconds_count{name=glm.fit}``. A reader that can pass one string
    only (the benchmark's ``COUNTERS``) reaches every sample this way."""
    base, brace, _ = name.partition("{")
    fam = REGISTRY._families.get(base)
    if isinstance(fam, (Counter, Gauge)):
        if not brace:
            return fam.value(**labels)
        return next((float(v) for lab, v in fam.samples()
                     if _flat_name(base, lab) == name), 0.0)
    stem, _, part = base.rpartition("_")  # span_seconds + sum | count
    hist = REGISTRY._families.get(stem)
    if part in ("sum", "count") and isinstance(hist, Histogram):
        want = stem + name[len(base):] if brace else _flat_name(stem, labels)
        return next((float(s if part == "sum" else n)
                     for lab, _cum, s, n in hist.samples()
                     if _flat_name(stem, lab) == want), 0.0)
    return 0.0


# ---------------------------------------------------------------------------
# spans

# trace id (the owning Job's key) and active span id flow through
# contextvars: Job.start copies the creator's context into the worker
# thread, so spans opened anywhere inside the job body nest under it, while
# unrelated REST threads stay untraced.
_TRACE_VAR: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "h2o3_trace", default=None
)
_SPAN_VAR: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "h2o3_span", default=None
)

_TRACE_KIND_VAR: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "h2o3_trace_kind", default=None
)
# (innermost open span's name, its root span's name): what a reader that
# knows no span id is told of where it runs (telemetry's compile listener)
_OPEN_VAR: contextvars.ContextVar[tuple[str, str] | None] = contextvars.ContextVar(
    "h2o3_open_span", default=None
)

_IDS = itertools.count(1)

_MAX_TRACES = 128
_MAX_SPANS_PER_TRACE = 4096
_TRACE_LOCK = threading.Lock()
_TRACES: "collections.OrderedDict[str, list[dict]]" = collections.OrderedDict()
_RECENT: collections.deque = collections.deque(maxlen=1024)

_SPAN_SECONDS = histogram(
    "span_seconds", "wall time of named spans (the trace tree's histogram view)"
)


@contextlib.contextmanager
def trace(trace_id: str, kind: str = "job"):
    """Enter a trace scope (Job.start does this with the job key; the REST
    server with a per-request id and ``kind="request"``). Joins an
    already-active JOB trace instead of replacing it: a Job nested inside a
    replicated command (spmd _exec_build's inner Job) contributes its spans
    to the OUTER job's trace — the one the client is polling. A job entered
    under a REQUEST trace is the opposite case: the job outlives the
    request and is polled by its own key, so a ``kind="job"`` trace SHADOWS
    an active request trace (the POST that launched a 10-minute build must
    not be charged the build's device-seconds).

    NOT gated by H2O3_TPU_METRICS: the trace id is the attribution key the
    flight-recorder ring and the per-job ledger (utils/jobacct.py) stamp on
    every dispatch, and those run in every process all the time. The gate
    only controls whether :func:`span` RECORDS into the registry."""
    if _TRACE_VAR.get() is not None and not (
        kind == "job" and _TRACE_KIND_VAR.get() == "request"
    ):
        yield
        return
    token = _TRACE_VAR.set(str(trace_id))
    ktoken = _TRACE_KIND_VAR.set(kind)
    # a NEW trace roots its own span tree: clear any span inherited from
    # the shadowed scope (a job thread copies the launching request's
    # contextvars — without this the job's root span would parent under
    # the request's rest.request span, a node in a DIFFERENT trace)
    stoken = _SPAN_VAR.set(None)
    otoken = _OPEN_VAR.set(None)
    try:
        yield
    finally:
        _OPEN_VAR.reset(otoken)
        _SPAN_VAR.reset(stoken)
        _TRACE_VAR.reset(token)
        _TRACE_KIND_VAR.reset(ktoken)


def current_trace() -> str | None:
    return _TRACE_VAR.get()


def current_span() -> int | None:
    """Active span id (None outside any span) — the parent the flight
    recorder links its dispatch events under."""
    return _SPAN_VAR.get()


def open_span_names() -> tuple[str, str] | None:
    """``(innermost, root)``: the names of the innermost span open in this
    context and of the span with no parent it nests under (None outside any
    span). A span the ``H2O3_TPU_METRICS`` gate leaves unopened has no name."""
    return _OPEN_VAR.get()


def next_span_id() -> int:
    """Allocate a span id from the shared sequence (the serving batcher's
    batch and queue-wait ids): one counter, so a trace tree mixing ring and
    registry spans never collides."""
    return next(_IDS)


def push_span(sid: int):
    """Make ``sid`` the active span (returns the reset token): the serving
    batcher parents its shared dispatch under the batch id this way."""
    return _SPAN_VAR.set(sid)


def pop_span(token) -> None:
    _SPAN_VAR.reset(token)


def _record_span(ev: dict) -> None:
    _RECENT.append(ev)
    tid = ev["trace"]
    if tid is None:
        return
    with _TRACE_LOCK:
        spans = _TRACES.get(tid)
        if spans is None:
            while len(_TRACES) >= _MAX_TRACES:
                _TRACES.popitem(last=False)
            spans = _TRACES[tid] = []
        if len(spans) < _MAX_SPANS_PER_TRACE:
            spans.append(ev)


_ANNOTATION = None  # jax.profiler.TraceAnnotation, imported at the first span


def _annotation(name: str, **stats):
    """The profiler's own annotation for ``name``: while a profiler session
    is open (``jax.profiler.trace`` / ``start_trace``) it lands on the
    capture's host plane with ``stats`` intact, on the device trace's clock;
    with no session open it is a flag test."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION(name, **stats)


class OpenSpan:
    """The one span enter/exit core, shared by :func:`span` and the flight
    recorder's dispatch spans: constructing it OPENS the span (an id from
    the shared sequence, parent and trace from the contextvars, the active
    span and its name pushed (:func:`open_span_names`), a profiler
    annotation entered that carries the ids and the caller's ``labels`` as
    its stats, the clocks stamped) and
    :meth:`close` ends it and returns its seconds. What the caller then
    records (trace tree and histogram, or ring event and job ledger) is the
    caller's; how a span is timed, nested and put into a profiler capture
    is here and nowhere else. Not gated: :func:`span` checks the gate before
    it opens one, the dispatch spans run in every process."""

    __slots__ = ("id", "parent", "trace", "ts", "_t0", "_tok", "_otok", "_ann")

    def __init__(self, name: str, labels: dict | None = None):
        self.id = next(_IDS)
        self.parent = _SPAN_VAR.get()
        self.trace = _TRACE_VAR.get()
        self._tok = _SPAN_VAR.set(self.id)
        outer = _OPEN_VAR.get()
        self._otok = _OPEN_VAR.set(
            (name, name if self.parent is None or outer is None else outer[1]))
        stats = {"span_id": self.id, "parent": self.parent or 0,
                 "trace": self.trace or ""}
        # the span's labels ride behind its own stats, so a capture says
        # which branch a span took (model.score_metrics: path=device|host)
        self._ann = _annotation(name, **stats, **{
            k: v for k, v in (labels or {}).items() if k not in stats})
        self._ann.__enter__()
        self.ts = time.time()
        self._t0 = time.perf_counter()

    def close(self) -> float:
        dur = time.perf_counter() - self._t0
        self._ann.__exit__(None, None, None)
        _OPEN_VAR.reset(self._otok)
        _SPAN_VAR.reset(self._tok)
        return dur


@contextlib.contextmanager
def span(name: str, **labels):
    """Time a named region. Nests under the active span/trace; on exit the
    completed span is recorded into the trace tree, the recent ring (merged
    into /3/Timeline) and the ``span_seconds`` histogram. For its life it is
    also an annotation inside any open profiler capture (:class:`OpenSpan`),
    so an xplane holds the program's span tree beside the device operations."""
    if not _ENABLED:
        yield None
        return
    s = OpenSpan(name, labels)
    try:
        yield s.id
    finally:
        dur = s.close()
        _record_span({
            "name": name,
            "trace": s.trace,
            "id": s.id,
            "parent": s.parent,
            "ts": s.ts,
            "dur_s": dur,
            "thread": threading.get_ident(),
            "labels": {k: str(v) for k, v in labels.items()},
        })
        _SPAN_SECONDS.observe(dur, name=name)


def trace_events(trace_id: str) -> list[dict]:
    with _TRACE_LOCK:
        return list(_TRACES.get(str(trace_id), ()))


def trace_summary(trace_id: str) -> dict:
    """Per-span-name {count, total_ms} rollup — the Job dict's phase
    summary (stable once the job has finished: no new spans arrive)."""
    out: dict[str, dict] = {}
    for ev in trace_events(trace_id):
        agg = out.setdefault(ev["name"], {"count": 0, "total_ms": 0.0})
        agg["count"] += 1
        agg["total_ms"] += ev["dur_s"] * 1e3
    for agg in out.values():
        agg["total_ms"] = round(agg["total_ms"], 3)
    return out


def chrome_trace(trace_id: str) -> dict:
    """Chrome-trace/Perfetto JSON for one trace (``GET /3/Jobs/{key}/trace``).
    Complete events ("ph": "X") carry span/parent ids in args so the tree
    reconstructs exactly even when sibling spans share a thread lane."""
    evs = [{"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
            "args": {"name": "h2o3_tpu coordinator"}}]
    for s in trace_events(trace_id):
        evs.append({
            "name": s["name"],
            "ph": "X",
            "ts": s["ts"] * 1e6,          # Chrome trace wants microseconds
            "dur": max(s["dur_s"] * 1e6, 1.0),
            "pid": 1,
            "tid": s["thread"] % 1_000_000,
            "args": {"span_id": s["id"], "parent_id": s["parent"],
                     **s["labels"]},
        })
    return {"traceEvents": evs, "displayTimeUnit": "ms",
            "otherData": {"trace": str(trace_id)}}


def recent_spans(n: int = 200) -> list[dict]:
    """Most recent completed spans across ALL traces (the /3/Timeline merge
    source)."""
    return list(_RECENT)[-n:]


def reset_spans() -> None:
    """Drop all recorded spans/traces (tests)."""
    with _TRACE_LOCK:
        _TRACES.clear()
    _RECENT.clear()
