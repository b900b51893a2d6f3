"""The network cell off the chip: ``correct`` comes out true for a sound
rehearsal of ``dl_higgs.train`` and false for the control (the reference one
precision below the configuration's) and for each fault of ``faults_dl.py``;
the cell's three readers on planted counters and on the counters a small
training leaves. Run by hand: pytest benchmark/tests

Each fault is rehearsed at rows where it shows: a fault of the schedule
moves the weights only as far as the schedule moves in the rows trained, so
the dropped annealing (the rate 2.1% lower after 10,500,000 rows) needs
600,000 rows to stand clear of float32's rounding."""

import importlib
import json
import time

import pytest

from benchmark.configs import dl_higgs
from benchmark.harness.main import load_json, main
from benchmark.harness.peaks import peaks_for
from benchmark.tests.faults_dl import PLANTED

SEED = 2**31 + 77


@pytest.mark.parametrize("fault,rows", [
    (None, 40000), ("control", 40000), ("momentum_held", 40000),
    ("half_batch", 40000), ("weight_1pct", 40000), ("annealing_dropped", 600000),
    ("count_stuck", 40000), ("rows_repeated", 40000)])
def test_correct_decides(fault, rows, monkeypatch, capsys):
    sound = dl_higgs.compare
    if fault == "control":  # the control's numbers stand where the program's do
        monkeypatch.setattr(dl_higgs, "compare", lambda cfg, X, y, model: {
            "program": sound(cfg, X, y, model, control=True)["control"]})
    elif fault:
        plant = PLANTED["dl_higgs"][fault]
        monkeypatch.setattr(dl_higgs, "compare", lambda cfg, X, y, model: sound(
            cfg, X, y, plant(model, X, y, cfg)))
    rc = main(["--workload", "dl_higgs.train", "--seed", str(SEED), "--seconds", "1",
               "--rehearse", "--rows", str(rows)], time.perf_counter())
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is (fault is None), line["checks"]


READERS = {n: importlib.import_module(f"benchmark.layer_metrics.{n}") for n in (
    "dl_step_us", "dl_step_roofline_pct", "dl_score_metrics_s_per_call")}
CALL = {"dispatch_device_seconds_sum{site=dl_chunk}": 12.0,
        "dl_minibatch_steps_total": 105000.0,
        "span_seconds_sum{name=model.score_metrics}": 0.3,
        "span_seconds_count{name=train}": 1.0}
S = 1e9  # ns a second
# one chip: the epoch's row gather, then the epoch loop holding a segment
# loop (itself holding two step loops of 1.5 s and 3 s) and a step loop of
# 0.5 s, then the metrics' loop over blocks of 65,536 rows
TRACE = {"device": {"0": [
    ["%fusion.1 = f32[11010048,28]{0,1} fusion(f32[11010048,28]{0,1} %X.1, ...)", 0, 0.6 * S],
    ["%while.3 = (s32[], f32[28,300]{1,0}) while(...), condition=...", 0.6 * S, 6.0 * S],
    ["%while.4 = (s32[], f32[28,300]{1,0}) while(...), condition=...", 0.7 * S, 4.8 * S],
    ["%while.5 = (s32[], f32[28,300]{1,0}) while(...), condition=...", 0.8 * S, 1.5 * S],
    ["%fusion.184 = f32[100,300]{0,1} fusion(f32[100,28]{0,1} %x, ...)", 0.9 * S, 0.1 * S],
    ["%abs_reduce_fusion.42 = (f32[], f32[300,300]{1,0}) fusion(...)", 1.0 * S, 0.1 * S],
    ["%while.5 = (s32[], f32[28,300]{1,0}) while(...), condition=...", 2.5 * S, 3.0 * S],
    ["%fusion.184 = f32[100,300]{0,1} fusion(f32[100,28]{0,1} %x, ...)", 2.6 * S, 0.1 * S],
    ["%while.6 = (s32[], f32[28,300]{1,0}) while(...), condition=...", 6.0 * S, 0.5 * S],
    ["%fusion.186 = f32[100,2]{0,1} fusion(f32[100,300]{0,1} %h, ...)", 6.1 * S, 0.1 * S],
    ["%while.9 = (s32[], f32[2,65536]{1,0}) while(...), condition=...", 7.0 * S, 0.4 * S],
    ["%fusion.200 = f32[65536,300]{0,1} fusion(f32[65536,28]{0,1} %b, ...)", 7.1 * S, 0.1 * S],
    ["%fusion.201 = f32[1000,3]{0,1} fusion(...)", 7.2 * S, 0.1 * S]]}}


def ctx(counters, peaks=True, trace=None):
    cfg = load_json(dl_higgs.__file__.replace(".py", ".json"))
    return {"call": {"counters": counters, "passes": 1, "wall_s": 13.0},
            "cfg": cfg, "config": dl_higgs, "log": lambda *a: None,
            "trace": {"device": {}} if trace is None else trace,
            "peaks": peaks_for("TPU v5 lite") if peaks else None}


@pytest.mark.parametrize("name,want", [
    ("dl_step_us", 12.0 / 105000 * 1e6),
    # 6 x 100 x 369,000 FLOPs a step at 197 TFLOP/s, 105,000 steps, over
    # the innermost loops that hold a minibatch: 1.5 + 3 + 0.5 s
    ("dl_step_roofline_pct", 100 * 6 * 100 * 369000 / 197e12 * 105000 / 5.0),
    ("dl_score_metrics_s_per_call", 0.3)])
def test_reader_on_planted_counters(name, want):
    assert READERS[name].read(ctx(CALL, trace=TRACE)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_nothing_where_the_program_counts_nothing(name):
    """A program without the counters (the parent of this cell) reads 0.0
    for every flat name and leaves no step loop in the trace; a rehearsal
    has no peaks."""
    assert READERS[name].read(ctx({k: 0.0 for k in CALL})) is None
    if name == "dl_step_roofline_pct":
        assert READERS[name].read(ctx(CALL, peaks=False, trace=TRACE)) is None
        only_pred = {"device": {"0": TRACE["device"]["0"][-3:]}}
        assert READERS[name].read(ctx(CALL, trace=only_pred)) is None


def test_readers_counters_are_the_ones_a_training_leaves():
    """Every flat name the readers ask for moves in a small training."""
    import numpy as np

    from benchmark.configs.higgs_data import make_frame
    from h2o3_tpu.estimators import H2ODeepLearningEstimator
    from h2o3_tpu.utils import metrics

    names = sorted({c for r in READERS.values() for c in getattr(r, "COUNTERS", ())})
    before = {n: metrics.counter_value(n) for n in names}
    data = make_frame(2000, 28, 3)
    H2ODeepLearningEstimator(hidden=[8], epochs=2, mini_batch_size=100, seed=1).train(
        y="label", training_frame=data.frame)
    moved = {n: metrics.counter_value(n) - before[n] for n in names}
    assert all(v > 0 for v in moved.values()), moved
    assert moved["dl_minibatch_steps_total"] == 2 * 20
    assert np.isfinite(list(moved.values())).all()
