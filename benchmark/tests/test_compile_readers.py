"""The readers of the compile layer, each on a planted ``ctx`` and a planted
registry: the three ``setup_*`` readers take the process totals under the root
span ``train`` from the registry when they run, ``compile_s_per_call`` the
differences over the traced call; a program without the families, or one
whose metrics are gated off, gives nothing, not zero."""

import importlib

import pytest

from h2o3_tpu.utils import metrics

NAMES = ("setup_lower_s", "setup_compile_s", "setup_programs_lowered", "compile_s_per_call")
READERS = {n: importlib.import_module(f"benchmark.layer_metrics.{n}") for n in NAMES}

# a GBM process after its warm-up call and the window: the warm-up traced
# 9.5 s, lowered 14 programs in 2.0 s and loaded them in 3.0 s; the harness
# compiled 1 program outside any train()
TOTALS = {("train", "trace"): (9.5, 800), ("train", "lower"): (2.0, 14),
          ("train", "compile"): (3.0, 14), ("-", "lower"): (0.4, 1),
          ("-", "compile"): (0.7, 1)}


def plant(monkeypatch, totals):
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", reg)
    if totals is not None:
        secs, evs = reg.counter("compile_seconds_total"), reg.counter("compile_events_total")
        for (root, stage), (s, n) in totals.items():
            secs.set_(s, root=root, stage=stage)
            evs.set_(n, root=root, stage=stage)


def ctx(counters):
    return {"call": {"counters": counters, "passes": 10, "wall_s": 1.7}}


CALL = {"compile_seconds_total{root=train,stage=trace}": 0.25,
        "compile_seconds_total{root=train,stage=lower}": 0.5,
        "compile_seconds_total{root=train,stage=compile}": 0.75}


@pytest.mark.parametrize("name,want", [
    ("setup_lower_s", 11.5), ("setup_compile_s", 3.0), ("setup_programs_lowered", 14.0),
    ("compile_s_per_call", 1.5)])
def test_reader_on_a_planted_registry(monkeypatch, name, want):
    plant(monkeypatch, TOTALS)
    assert READERS[name].read(ctx(CALL)) == pytest.approx(want)


def test_a_call_that_compiled_nothing_reads_zero(monkeypatch):
    plant(monkeypatch, TOTALS)
    assert READERS["compile_s_per_call"].read(ctx({k: 0.0 for k in CALL})) == 0.0


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("program", ["without the families", "metrics gated off"])
def test_nothing_without_the_families(monkeypatch, name, program):
    # the parent commit has no such families; a gated program has them at 0
    plant(monkeypatch, None if program == "without the families"
          else {k: (0.0, 0) for k in TOTALS})
    r = READERS[name]
    assert r.read(ctx({c: 0.0 for c in getattr(r, "COUNTERS", ())})) is None
    assert all(isinstance(c, str) for c in getattr(r, "COUNTERS", ()))


def test_readers_through_a_train_call():
    """A 5,000-row CPU train() of GLM, read as the harness reads it: the
    warm-up lowers programs under ``train``, and a second call adds nothing."""
    import json
    import os

    import h2o3_tpu
    from benchmark.harness import window

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "glm_higgs.json")) as f:
        cfg = json.load(f)
    cfg["rows"] = 5000
    mod = importlib.import_module("benchmark.configs.glm_higgs")
    h2o3_tpu.init()
    data = mod.make_frame(cfg, 2**31 + 7)
    window.one_call(mod, cfg, data)
    names = READERS["compile_s_per_call"].COUNTERS
    before = window.read_counters(names)
    window.one_call(mod, cfg, data)
    after = window.read_counters(names)
    data.drop()
    c = ctx({k: after[k] - before[k] for k in after})
    got = {n: READERS[n].read(c) for n in NAMES}
    assert got["setup_programs_lowered"] >= 1 and got["setup_lower_s"] > 0, got
    assert got["setup_compile_s"] > 0 and got["compile_s_per_call"] == 0.0, got
