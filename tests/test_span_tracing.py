"""One span primitive through ``train()`` (ISSUE 25): the trace tree a GLM and
a GBM call leave, the flat-name form of ``counter_value``, spans inside a
profiler capture, the reduction of a capture (``telemetry.summarize``) on a
small recorded trace, the gate, and the phase scopes in the lowered programs."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu.utils import flightrec, telemetry
from h2o3_tpu.utils import metrics as mx

HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# (a) the tree a train() call leaves

def _frame(n=600, seed=3):
    from h2o3_tpu.frame.frame import Frame

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + 0.3 * rng.normal(size=n) > 0).astype(int)
    import pandas as pd

    df = pd.DataFrame(X, columns=[f"x{i}" for i in range(4)])
    df["y"] = np.where(y == 1, "s", "b")
    return Frame.from_pandas(df)


# span -> the parent it must have ("?" = any one of the set)
GLM_TREE = {
    "train": None, "job": "train", "glm.build": "job",
    "glm.datainfo": "glm.build", "glm.fit": "glm.build",
    "glm.coef_output": "glm.fit", "model.score_metrics": "glm.build",
    "model.predict_raw": "model.score_metrics",
    "metrics.binomial": "model.score_metrics",
}
GBM_TREE = {
    "train": None, "job": "train", "gbm.build": "job",
    "tree.fit_bins": "gbm.build", "tree.bin_frame": "gbm.build",
    "gbm.response_lanes": "gbm.build", "gbm.build_tree": "gbm.build", "gbm.pull_records": "gbm.build",
    "gbm.train_metric": "gbm.build", "model.score_metrics": "gbm.build",
    "metrics.binomial": {"gbm.train_metric", "model.score_metrics"},
}


# a forest's builder has GBM's spans under its own name (ISSUE 32)
DRF_TREE = {
    (k.replace("gbm.", "drf.")): (
        {x.replace("gbm.", "drf.") for x in v} if isinstance(v, set)
        else v and v.replace("gbm.", "drf."))
    for k, v in GBM_TREE.items()}


@pytest.mark.parametrize("algo,want", [
    ("glm", GLM_TREE), ("gbm", GBM_TREE), ("drf", DRF_TREE)])
def test_train_leaves_one_tree_rooted_at_train(algo, want):
    from h2o3_tpu import estimators as E

    est = {"glm": lambda: E.H2OGeneralizedLinearEstimator(
               family="binomial", lambda_=1e-4),
           "gbm": lambda: E.H2OGradientBoostingEstimator(
               ntrees=4, max_depth=3, score_tree_interval=2, seed=1),
           "drf": lambda: E.H2ORandomForestEstimator(
               ntrees=4, max_depth=5, score_tree_interval=2, seed=1)}[algo]()
    flightrec.reset()
    before = set(mx._TRACES)
    est.train(y="y", training_frame=_frame())
    (tid,) = set(mx._TRACES) - before  # ONE new trace: the job's key
    evs = mx.trace_events(tid)
    by_id = {e["id"]: e for e in evs}
    roots = [e for e in evs if e["parent"] not in by_id]
    assert [r["name"] for r in roots] == ["train"]
    assert roots[0]["labels"] == {"algo": algo} and roots[0]["parent"] is None
    names = {e["name"] for e in evs}
    assert set(want) <= names, set(want) - names
    for e in evs:
        if e["parent"] is None:
            continue
        par = by_id[e["parent"]]
        ok = want.get(e["name"])
        if ok is not None:
            assert par["name"] in (ok if isinstance(ok, set) else {ok}), (
                e["name"], par["name"])
        # a child lies inside its parent's interval (wall clock: 5 ms slack)
        assert e["ts"] >= par["ts"] - 5e-3
        assert e["ts"] + e["dur_s"] <= par["ts"] + par["dur_s"] + 5e-3
    # children tile their parent: the builder's children sum to no more than it
    build = next(e for e in evs if e["name"] == f"{algo}.build")
    kids = sum(e["dur_s"] for e in evs if e["parent"] == build["id"])
    assert 0 < kids <= build["dur_s"] + 5e-3
    # the device dispatch is a span of the same tree, in the ring: its
    # parent is the program span that issued it
    site = "irls_chunk" if algo == "glm" else "tree"
    disp = [e for e in flightrec.events(kind="dispatch_end")
            if e["site"] == site and e["trace"] == tid]
    assert disp and all(
        by_id[d["parent"]]["name"] == ("glm.fit" if algo == "glm"
                                       else f"{algo}.build_tree") for d in disp)


@pytest.mark.parametrize("with_validation", [False, True])
def test_glm_train_issues_the_design_program_once_for_each_frame(with_validation):
    """``DataInfo.transform`` is one dispatch (``dispatch:design``): a GLM
    ``train()`` opens it once, under ``glm.datainfo``, and the training
    metrics come from that design matrix (no ``dispatch:design`` under
    ``model.score_metrics``); a validation frame is transformed once, there."""
    from h2o3_tpu import estimators as E

    est = E.H2OGeneralizedLinearEstimator(family="binomial", lambda_=1e-4)
    flightrec.reset()
    before = set(mx._TRACES)
    est.train(y="y", training_frame=_frame(),
              validation_frame=_frame(n=300, seed=4) if with_validation else None)
    (tid,) = set(mx._TRACES) - before
    by_id = {e["id"]: e for e in mx.trace_events(tid)}

    def ancestors(span_id):
        while span_id in by_id:
            yield by_id[span_id]["name"]
            span_id = by_id[span_id]["parent"]

    designs = [list(ancestors(e["parent"]))
               for e in flightrec.events(kind="dispatch_end")
               if e["site"] == "design" and e["trace"] == tid]
    assert designs[0][0] == "glm.datainfo"
    under_metrics = [a for a in designs if "model.score_metrics" in a]
    assert len(designs) == 1 + len(under_metrics)
    assert len(under_metrics) == (1 if with_validation else 0)
    assert all(a[0] == "model.predict_raw" for a in under_metrics)


def test_bin_frame_span_says_hit_or_miss():
    from h2o3_tpu.models.tree.binning import bin_frame, fit_bins

    fr = _frame(seed=5)
    spec = fit_bins(fr, [f"x{i}" for i in range(4)])
    with mx.trace("binspan-1"):
        bin_frame(spec, fr)
        bin_frame(spec, fr)
    labels = [e["labels"]["cache"] for e in mx.trace_events("binspan-1")
              if e["name"] == "tree.bin_frame"]
    assert labels == ["miss", "hit"]


# ---------------------------------------------------------------------------
# (b) counter_value on flat names

@pytest.fixture
def families():
    c = mx.counter("t25_bytes_total", "test family")
    c.inc(7.0, path="rebin")
    c.inc(2.0, path="dense", lane="x")
    c.inc(1.0)
    h = mx.histogram("t25_seconds", "test family")
    h.observe(0.25, name="a.b")
    h.observe(0.5, name="a.b")
    h.observe(3.0)
    yield
    for fam in ("t25_bytes_total", "t25_seconds"):
        mx.REGISTRY._families.pop(fam, None)


@pytest.mark.parametrize("flat,want", [
    ("t25_bytes_total", 1.0),                        # the unlabelled child, as before
    ("t25_bytes_total{path=rebin}", 7.0),            # a labelled counter
    ("t25_bytes_total{lane=x,path=dense}", 2.0),     # labels in sorted order, as printed
    ("t25_seconds_sum{name=a.b}", 0.75),             # a histogram child's sum
    ("t25_seconds_count{name=a.b}", 2.0),            # ... and count
    ("t25_seconds_sum", 3.0),                        # the unlabelled histogram child
    ("t25_seconds_count", 1.0),
    ("t25_seconds", 0.0),                            # a histogram has no one value
    ("t25_bytes_total{path=nope}", 0.0),             # unknown child
    ("t25_seconds_sum{name=nope}", 0.0),
    ("t25_nope_total{path=rebin}", 0.0),             # unknown family
    ("t25_nope_sum{name=a.b}", 0.0),
])
def test_counter_value_resolves_flat_names(families, flat, want):
    assert mx.counter_value(flat) == want


def test_flat_names_are_the_ones_compact_snapshot_prints(families):
    snap = mx.REGISTRY.compact_snapshot()
    assert snap["t25_bytes_total{path=rebin}"] == 7
    assert mx.counter_value("t25_bytes_total{path=rebin}") == 7.0
    assert snap["t25_seconds{name=a.b}"] == {"count": 2, "sum": 0.75}
    # keyword labels still work, for counters and now for histogram parts
    assert mx.counter_value("t25_bytes_total", path="rebin") == 7.0
    assert mx.counter_value("t25_seconds_sum", name="a.b") == 0.75


# ---------------------------------------------------------------------------
# (c) spans inside a profiler capture, and the reduction of a capture

def test_span_is_an_annotation_inside_a_profiler_capture(tmp_path):
    """The capture's host plane holds the program's span tree, ids and all:
    read back with the capture's own reader (no clock to line up)."""
    logdir = str(tmp_path / "cap")
    x = jnp.arange(1024.0)
    with telemetry.profiler(logdir):
        with mx.trace("cap-1"), mx.span("outer.t25", k="v") as outer:
            with mx.span("inner.t25", path="device", parent="x") as inner:
                (x * 2).block_until_ready()
            with flightrec.dispatch("t25site"):
                pass
    assert glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    cap = telemetry.load_capture(logdir)
    host = {name: (sid, parent, s, d) for name, s, d, sid, parent in cap["host"]}
    # a span's `path` label names the branch it took, in the summary too
    host["inner.t25"] = host.pop("inner.t25{path=device}")
    assert host["outer.t25"][:2] == (outer, 0)
    assert host["inner.t25"][:2] == (inner, outer)
    assert host["dispatch:t25site"][1] == outer  # the ring's span, same core
    o, i = host["outer.t25"], host["inner.t25"]
    assert o[2] <= i[2] and i[2] + i[3] <= o[2] + o[3]  # one clock, nested
    # jax's own reader sees the same event with its stats intact
    from jax.profiler import ProfileData

    pb = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))[-1]
    plane = ProfileData.from_file(pb).find_plane_with_name("/host:CPU")
    evs = [ev for line in plane.lines for ev in line.events
           if ev.name == "inner.t25"]
    assert len(evs) == 1
    st = dict(evs[0].stats)
    assert int(st["span_id"]) == inner and int(st["parent"]) == outer
    assert st["trace"] == "cap-1"
    # the labels ride behind the span's own stats, which a label cannot take
    assert st["path"] == "device" and len(st) == 4
    rep = telemetry.summarize(logdir)
    rows = {r["name"]: r for r in rep["spans"]}
    assert rows["outer.t25"]["self_s"] <= rows["outer.t25"]["total_s"]
    assert rows["outer.t25"]["self_s"] == pytest.approx(
        rows["outer.t25"]["total_s"] - rows["inner.t25{path=device}"]["total_s"]
        - rows["dispatch:t25site"]["total_s"], abs=1e-6)


@pytest.fixture(scope="module")
def small():
    return telemetry.summarize(os.path.join(HERE, "small_capture.json"))


def test_summarize_device_seconds_by_scope(small):
    dev = small["device"]
    # the while container (1..5 s) is not an operation of its own; its body's
    # two ph_hist operations overlap by 0.5 s (union 3.5 s, not the sum 4 s)
    assert dev["by_scope"] == pytest.approx(
        {"ph_hist": 3.5, "ph_part": 1.0, "ph_bin": 1.0, "(no scope)": 0.5,
         "ph_pred": 1.0})  # ph_part/ph_pred: the deepest phase scope names it
    assert list(dev["by_scope"])[0] == "ph_hist"  # most time first
    assert dev["busy_s"] == pytest.approx(6.5)    # 0.5..1, 1..5 (container), 7..9


def test_summarize_span_tree_has_self_times(small):
    rows = {r["name"]: r for r in small["spans"]}
    assert rows["train"]["total_s"] == pytest.approx(10.0)
    assert rows["train"]["self_s"] == pytest.approx(0.2)
    assert rows["gbm.build"]["self_s"] == pytest.approx(9.8 - 0.5 - 4.2 - 2.0 - 2.9)
    assert rows["gbm.build_tree"] == {
        "name": "gbm.build_tree", "count": 2,
        "total_s": pytest.approx(4.2), "self_s": pytest.approx(4.2 - 0.3)}
    assert small["window_s"] == pytest.approx(10.0)


def test_summarize_puts_a_planted_gap_down_to_the_deepest_open_span(small):
    idle = small["idle"]
    # the planted gap: the device is idle from 5 s to 7 s while the host sits
    # in gbm.pull_records (5.0..7.0 s), the deepest span open then
    assert idle["longest"][0] == {
        "span": "gbm.pull_records", "dur_s": pytest.approx(2.0),
        "at_s": pytest.approx(5.0)}
    assert idle["by_span"]["gbm.pull_records"] == pytest.approx(2.0)
    # the leading 0.5 s: 0.1 s of train's own, 0.1 s of gbm.build, 0.3 s of tree.fit_bins
    assert idle["by_span"]["tree.fit_bins"] == pytest.approx(0.3)
    assert idle["by_span"]["train"] == pytest.approx(0.1 + 0.1)
    # the trailing 1 s (9..10) lies in model.score_metrics (7.0..9.9) and train
    assert idle["by_span"]["model.score_metrics"] == pytest.approx(0.9)
    assert idle["total_s"] == pytest.approx(3.5)
    assert idle["under_1ms_s"] == 0.0
    assert sum(idle["by_span"].values()) == pytest.approx(3.5)


def test_summarize_of_an_empty_directory_is_empty(tmp_path):
    rep = telemetry.summarize(str(tmp_path))
    assert rep["spans"] == [] and rep["device"]["by_scope"] == {}
    assert rep["window_s"] == 0.0 and rep["idle"]["total_s"] == 0.0


# ---------------------------------------------------------------------------
# (d) the gate

def test_span_gated_off_opens_no_annotation_and_records_nothing(monkeypatch):
    opened = []
    real = mx._annotation
    monkeypatch.setattr(
        mx, "_annotation", lambda name, **kw: opened.append(name) or real(name, **kw))
    n0 = mx.counter_value("span_seconds_count{name=gated.t25}")
    mx.set_enabled(False)
    try:
        with mx.trace("gated-25"), mx.span("gated.t25") as sid:
            assert sid is None
            assert mx.current_span() is None  # nothing pushed
    finally:
        mx.set_enabled(True)
    assert opened == []
    assert mx.trace_events("gated-25") == []
    assert mx.counter_value("span_seconds_count{name=gated.t25}") == n0
    with mx.trace("gated-25"), mx.span("gated.t25") as sid:  # and on again
        assert sid is not None and mx.current_span() == sid
    assert opened == ["gated.t25"]
    assert mx.counter_value("span_seconds_count{name=gated.t25}") == n0 + 1


def test_span_and_dispatch_share_one_enter_exit_core(monkeypatch):
    """Exactly one implementation of a span: both go through OpenSpan."""
    made = []
    real = mx.OpenSpan

    class Spy(real):
        def __init__(self, name, *labels):
            made.append(name)
            super().__init__(name, *labels)

    monkeypatch.setattr(mx, "OpenSpan", Spy)
    with mx.span("core.t25"):
        with flightrec.dispatch("coresite"):
            pass
    assert made == ["core.t25", "dispatch:coresite"]


# ---------------------------------------------------------------------------
# the phase scopes are in the lowered programs, and change nothing else

def _glm_args(n=256, p=8):
    f32 = jnp.float32
    return (jax.ShapeDtypeStruct((n, p), f32), jax.ShapeDtypeStruct((n,), f32),
            jax.ShapeDtypeStruct((n,), f32), jax.ShapeDtypeStruct((n,), f32),
            jax.ShapeDtypeStruct((p,), f32))


def _lowered(which):
    from h2o3_tpu.models import glm
    from h2o3_tpu.models import metrics as MM
    from h2o3_tpu.models.tree import binning, shared_tree as st
    from h2o3_tpu.ops import gram

    f32 = jnp.float32
    X, y, w, off, beta = _glm_args()
    if which == "irls_pass":
        return glm._irls_pass.lower(X, y, w, off, beta, "binomial",
                                    ("family_default", 1.5, 1.0, 1e-5))
    if which == "deviance_pass":
        return glm._deviance_pass.lower(X, y, w, off, beta, "binomial",
                                        ("family_default", 1.5, 1.0, 1e-5))
    if which == "weighted_gram":
        return gram.weighted_gram.lower(X, w, y)
    if which == "softmax_probs":
        return glm._softmax_probs.lower(X, jax.ShapeDtypeStruct((8, 3), f32))
    if which == "binom_stats":
        return MM._binom_device_stats().lower(y, y, w)
    if which == "linear_mu":
        return glm._linear_mu.lower(X, beta, off, "binomial",
                                    ("family_default", 1.5, 1.0, 1e-5))
    if which == "response_lanes":
        return glm._response_lanes.lower(
            jax.ShapeDtypeStruct(y.shape, jnp.int8), w, None, None)
    if which == "gbm_response_lanes":
        from h2o3_tpu.models.tree import gbm

        return gbm._response_lanes.lower(
            jax.ShapeDtypeStruct(y.shape, jnp.int8), None, 200, spw=3.0, n_classes=0)
    if which == "design":
        from h2o3_tpu.models import datainfo
        from h2o3_tpu.parallel.mesh import mesh_key

        plan = ((("num",), ("cat", 3, 3)), True, False, True, True,
                y.shape[0], mesh_key())
        return datainfo._design.lower(
            plan, jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((2, 4), f32),
            [(y,), (jax.ShapeDtypeStruct(y.shape, jnp.int8),)])
    if which == "finish_level":
        n, C, npad = 256, 4, 2
        fn = jax.jit(lambda bins, nid, preds, vi, ok, gain, nw, col, cm: st._finish_level(
            bins, nid, preds, vi, ok, gain, nw, nw, nw, col, col,
            jnp.zeros(npad, bool), cm, jnp.zeros(npad, bool), 0.1, 10.0, npad)[:2])
        S = jax.ShapeDtypeStruct
        return fn.lower(S((n, C), jnp.uint8), S((n,), jnp.int32), S((n,), f32),
                        S((C,), f32), S((npad,), bool), S((npad,), f32),
                        S((npad,), f32), S((npad,), jnp.int32), S((npad, 8), bool))
    raise AssertionError(which)


@pytest.mark.parametrize("which,scopes", [
    ("irls_pass", {"ph_gram", "ph_dev"}),
    ("deviance_pass", {"ph_dev"}),
    ("weighted_gram", {"ph_gram"}),
    ("softmax_probs", {"ph_score"}),
    ("binom_stats", {"ph_metric"}),
    ("linear_mu", {"ph_score"}),
    ("response_lanes", {"ph_std"}),
    ("gbm_response_lanes", {"ph_std"}),
    ("design", {"ph_std"}),
    ("finish_level", {"ph_leaf", "ph_part", "ph_pred"}),
])
def test_phase_scopes_are_metadata_of_the_lowered_program(which, scopes):
    low = _lowered(which)
    with_names = low.as_text(debug_info=True)
    found = set(re.findall(r"\bph_[a-z]+", with_names))
    assert scopes <= found, (scopes, found)
    # metadata only: without the debug info no scope is left in the program
    assert not re.findall(r"\bph_[a-z]+", low.as_text())


def test_scopes_do_not_change_the_compiled_program(monkeypatch):
    """The same function with ``named_scope`` made a no-op compiles to the
    same operations at the same cost: a scope names, and does nothing else."""
    import contextlib

    from h2o3_tpu.ops import gram

    X, _y, w, _off, _beta = _glm_args()
    fn = gram.weighted_gram.__wrapped__

    def compiled(f):
        c = jax.jit(f).lower(X, w, w).compile()
        text = re.sub(r", metadata=\{[^}]*\}", "", c.as_text())
        return text, c.cost_analysis()

    scoped_text, scoped_cost = compiled(lambda a, b, c: fn(a, b, c))
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain_text, plain_cost = compiled(lambda a, b, c: fn(a, b, c))
    assert "ph_gram" not in plain_text
    assert scoped_text == plain_text
    assert scoped_cost == plain_cost
