"""The compile pipeline counted inside the program: ``telemetry.install``'s one
``jax.monitoring`` listener by stage (trace, lower, compile) and by the root
program span open on the calling thread, the ring event by the innermost span,
trace seconds as a union of nested traces, and a second identical ``train()``
that compiles nothing."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from test_span_tracing import _frame

import h2o3_tpu
from h2o3_tpu.utils import metrics as mx
from h2o3_tpu.utils import telemetry

STAGES = ("trace", "lower", "compile")
PKG = os.path.dirname(os.path.abspath(h2o3_tpu.__file__))


@pytest.fixture(autouse=True)
def _installed():
    h2o3_tpu.init()


def _by_root(root):
    return {s: (mx.counter_value(f"compile_seconds_total{{root={root},stage={s}}}"),
                mx.counter_value(f"compile_events_total{{root={root},stage={s}}}"))
            for s in STAGES}


def _diff(after, before):
    return {s: (after[s][0] - before[s][0], after[s][1] - before[s][1]) for s in STAGES}


def _compiles(n=4096):
    """The ring's compile-pipeline events (other tests record plain ones)."""
    return [e for e in telemetry.events(n) if e["kind"] == "compile" and "stage" in e]


def _fresh(n):
    """A program no other test compiles: its shape is its own."""
    return jax.jit(lambda x: jnp.sum(x * 3.0) + jnp.max(x)), jnp.ones(n, jnp.float32)


def test_a_jit_under_train_adds_every_stage_once():
    f, x = _fresh(1931)
    before = _by_root("train")
    with mx.span("train"):
        f(x).block_until_ready()
    mid = _by_root("train")
    first = _diff(mid, before)
    assert all(secs > 0 and n >= 1 for secs, n in first.values()), first
    assert first["lower"][1] == first["compile"][1] >= 1
    with mx.span("train"):
        f(x).block_until_ready()
    assert _diff(_by_root("train"), mid) == {s: (0.0, 0.0) for s in STAGES}


def test_the_ring_names_the_innermost_span_and_the_program():
    f, x = _fresh(1933)
    with mx.span("train"), mx.span("gbm.build_tree"):
        f(x).block_until_ready()
    evs = [e for e in _compiles() if e["span"] == "gbm.build_tree" and e["root"] == "train"]
    stages = [e["stage"] for e in evs[-3:]]
    assert stages == ["trace", "lower", "compile"], evs[-3:]
    trace, lower, comp = evs[-3:]
    assert trace["fun"] == "<lambda>" and trace["nested"] >= 1  # jnp's helpers inside it
    assert lower["fun"] == comp["fun"] == "jit(<lambda>)"
    assert all(e["seconds"] > 0 for e in evs[-3:]) and comp["cache"] in ("hit", "miss", "-")
    # /3/Timeline counts backend compiles, not every stage
    assert telemetry.timeline(4096)["compile_count"] >= 1


def test_nested_traces_count_once():
    """jnp's jitted helpers trace inside the outer function's trace: the trace
    seconds counted are what the outermost trace lasted, though the durations
    jax reports sum to more."""
    heard = []

    def listen(event, start, end, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            heard.append((kw.get("fun_name"), start, end))

    def f(x):
        for _ in range(6):
            x = jnp.tanh(jnp.sum(x * 2.0, keepdims=True) + jnp.cumsum(x))
        return x

    x = jnp.ones(1951)
    jax.monitoring.register_event_time_span_listener(listen)
    try:
        before = _by_root("train")
        with mx.span("train"):
            jax.jit(f)(x).block_until_ready()
        got = _diff(_by_root("train"), before)["trace"]
    finally:
        jax.monitoring.unregister_event_time_span_listener(listen)
    (_, s0, e0), = [h for h in heard if h[0] == "f"]
    nested = [h for h in heard if h[0] != "f" and s0 <= h[1] and h[2] <= e0]
    assert nested and len(nested) == len(heard) - 1, heard
    assert sum(e - s for _, s, e in heard) > e0 - s0
    assert got[0] == pytest.approx(e0 - s0, abs=1e-6)
    assert got[1] == len(heard)


def test_nested_stages_are_the_outermost_ones():
    """Planted events in jax's order (a scalar when a stage opens, a time span
    when it closes): two traces nested in an outer trace, then an eager call
    inside a second trace that lowers and compiles there. Only the outermost
    count their seconds, and each is one ring event with its nested count."""
    T, L, C = (f"/jax/core/compile/{x}" for x in (
        "jaxpr_trace_duration", "jaxpr_to_mlir_module_duration", "backend_compile_duration"))
    mon = jax.monitoring

    def stage(ev, s, e, fun, inner=()):
        mon.record_scalar(ev, s, fun_name=fun)
        for args in inner:
            stage(*args)
        mon.record_event_time_span(ev, s, e, fun_name=fun)

    before = _by_root("train")
    with mx.span("train"), mx.span("probe.nest"):
        stage(T, 10.0, 20.0, "outer", [(T, 11.0, 12.0, "a"), (T, 13.0, 15.0, "b")])
        stage(T, 30.0, 40.0, "eager", [(L, 31.0, 32.0, "jit(h)"), (C, 32.0, 35.0, "jit(h)")])
    got = _diff(_by_root("train"), before)
    assert got == {"trace": (20.0, 4.0), "lower": (0.0, 1.0), "compile": (0.0, 1.0)}
    evs = [e for e in _compiles() if e["span"] == "probe.nest"]
    assert [(e["fun"], e["seconds"], e["nested"]) for e in evs] == [
        ("outer", 10.0, 2), ("eager", 10.0, 2)]


def test_a_lowering_that_traces_is_one_lowering():
    """threefry's lowering rule traces its bit operations, over a thousand
    times for a deep forest's program: those traces are the lowering's
    seconds, and the ring holds the program's trace and its lowering."""
    T = "/jax/core/compile/jaxpr_trace_duration"
    L = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    mon = jax.monitoring
    before = _by_root("train")
    with mx.span("train"), mx.span("dispatch:tree"):
        mon.record_scalar(T, 100.0, fun_name="deep")
        mon.record_event_time_span(T, 100.0, 101.0, fun_name="deep")
        mon.record_scalar(L, 101.0, fun_name="jit(deep)")
        for i in range(2000):
            t = 101.0 + i * 1e-4
            mon.record_scalar(T, t, fun_name="add")
            mon.record_event_time_span(T, t, t + 5e-5, fun_name="add")
        mon.record_event_time_span(L, 101.0, 102.0, fun_name="jit(deep)")
    got = _diff(_by_root("train"), before)
    assert got["trace"] == (1.0, 2001.0) and got["lower"] == (1.0, 1.0)
    evs = [e for e in _compiles() if e["span"] == "dispatch:tree"][-2:]
    assert [(e["stage"], e["fun"], e["seconds"], e["nested"]) for e in evs] == [
        ("trace", "deep", 1.0, 0), ("lower", "jit(deep)", 1.0, 2000)]


@pytest.mark.parametrize("where", ["no span", "gate off"])
def test_outside_any_open_span_the_root_is_a_dash(where):
    f, x = _fresh(1937 if where == "no span" else 1939)
    before = _by_root("-")
    mx.set_enabled(where != "gate off")
    try:
        if where == "gate off":
            with mx.span("train"):  # gated: never opened, so it leaves no name
                assert mx.open_span_names() is None
                f(x).block_until_ready()
        else:
            assert mx.open_span_names() is None
            f(x).block_until_ready()
    finally:
        mx.set_enabled(True)
    last = [e for e in _compiles() if e["stage"] == "compile"
            and e["fun"] == "jit(<lambda>)"][-1]
    assert (last["span"], last["root"]) == ("-", "-")
    grew = _diff(_by_root("-"), before)["lower"][1]
    # the registry is behind the gate, the ring is not
    assert grew == (1.0 if where == "no span" else 0.0)


def test_threads_compiling_at_once_keep_their_own_spans():
    """Eight threads each compile their own programs under their own span, with
    a short switch interval: every lowering is counted once, and each ring
    event names its own thread's span."""
    import sys
    import threading

    n, per = 8, 3
    progs = [[_fresh(2003 + 16 * t + k) for k in range(per)] for t in range(n)]
    before = _by_root("train")
    errors = []

    def work(t):
        try:
            with mx.span("train"), mx.span(f"probe.t{t}"):
                for f, x in progs[t]:
                    f(x).block_until_ready()
        except Exception as e:  # reported below, with the thread that raised
            errors.append((t, e))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in range(n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(th.is_alive() for th in threads), errors
    assert _diff(_by_root("train"), before)["lower"][1] == n * per
    lowered = [e for e in _compiles() if e["stage"] == "lower"
               and e["span"].startswith("probe.t")]
    assert len(lowered) == n * per
    assert {e["span"] for e in lowered} == {f"probe.t{t}" for t in range(n)}


def test_open_span_names_follow_the_span_tree():
    assert mx.open_span_names() is None
    with mx.span("train"):
        assert mx.open_span_names() == ("train", "train")
        with mx.span("job"), mx.span("gbm.build"):
            assert mx.open_span_names() == ("gbm.build", "train")
            with mx.trace("job-x"):  # a new trace roots its own tree
                assert mx.open_span_names() is None
                with mx.span("job"):
                    assert mx.open_span_names() == ("job", "job")
            assert mx.open_span_names() == ("gbm.build", "train")
    assert mx.open_span_names() is None


def test_compile_cache_hits_still_count():
    h0 = mx.counter_value("compile_cache_hits_total")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert mx.counter_value("compile_cache_hits_total") == h0 + 1
    # the compile that the hit belongs to says so in the ring
    jax.monitoring.record_event_time_span(
        "/jax/core/compile/backend_compile_duration", 1.0, 1.25, fun_name="jit(probe)")
    last = telemetry.events(1)[-1]
    assert (last["fun"], last["cache"], last["seconds"]) == ("jit(probe)", "hit", 0.25)


def test_jax_monitoring_is_registered_with_in_telemetry_install_only():
    import inspect

    calls = []
    for dirpath, _dirs, files in os.walk(PKG):
        for fn in files:
            if fn.endswith(".py"):
                path = os.path.join(dirpath, fn)
                with open(path) as f:
                    calls += [(os.path.relpath(path, PKG), m)
                              for m in re.findall(r"monitoring\.register_\w+", f.read())]
    assert {p for p, _ in calls} == {os.path.join("utils", "telemetry.py")}, calls
    body = inspect.getsource(telemetry.install)
    assert sorted(m for _, m in calls) == sorted(re.findall(r"monitoring\.register_\w+", body))
    assert len(calls) == 3  # one callback for each kind of listener


@pytest.mark.parametrize("algo", ["glm", "gbm", "drf"])
def test_a_second_identical_train_compiles_nothing(algo):
    """What the benchmark's ``compile_s_per_call`` reads: a first ``train()``
    lowers its programs under ``root=train``, an identical second one adds 0
    compile seconds and 0 events at every stage."""
    from h2o3_tpu import estimators as E

    make = {"glm": lambda: E.H2OGeneralizedLinearEstimator(family="binomial", lambda_=1e-4),
            "gbm": lambda: E.H2OGradientBoostingEstimator(
                ntrees=4, max_depth=3, score_tree_interval=2, seed=1),
            "drf": lambda: E.H2ORandomForestEstimator(
                ntrees=4, max_depth=5, score_tree_interval=2, seed=1)}[algo]
    fr = _frame()
    jax.clear_caches()  # programs other tests compiled are traced again
    before = _by_root("train")
    make().train(y="y", training_frame=fr)
    mid = _by_root("train")
    first = _diff(mid, before)
    assert first["lower"][1] >= 1 and first["lower"][0] > 0, first
    assert first["trace"][0] > 0 and first["compile"][1] == first["lower"][1]
    make().train(y="y", training_frame=fr)
    assert _diff(_by_root("train"), mid) == {s: (0.0, 0.0) for s in STAGES}
