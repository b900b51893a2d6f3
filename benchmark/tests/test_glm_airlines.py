"""The airline GLM cell off the chip: ``correct`` comes out true for a sound
rehearsal of ``glm_airlines.train`` and false for the control (the
reference one precision below the configuration's) and for each fault of
``faults_glm_airlines.py``; the reference's fit does not depend on where it
starts; the cell's three readers on a planted trace and counters, and None
(not 0) where the program has no such kernel or counter. Run by hand:
pytest benchmark/tests"""

import importlib
import json
import time

import numpy as np
import pytest

from benchmark.configs import glm_airlines, glm_airlines_ref
from benchmark.harness.main import load_json, main
from benchmark.harness.peaks import peaks_for
from benchmark.tests.faults_glm_airlines import PLANTED

SEED = 2**31 + 91
ROWS = 60000


@pytest.mark.parametrize("fault", [None, "control", *PLANTED["glm_airlines"]])
def test_correct_decides(fault, monkeypatch, capsys):
    sound = glm_airlines.compare
    if fault == "control":  # the control's numbers stand where the program's do
        monkeypatch.setattr(glm_airlines, "compare", lambda cfg, X, y, model: {
            "program": sound(cfg, X, y, model, control=True)["control"]})
    elif fault:
        plant = PLANTED["glm_airlines"][fault]

        def planted(cfg, X, y, model):
            memo: dict = {}
            sound(cfg, X, y, model, memo=memo)
            return sound(cfg, X, y, plant(model, X, y, cfg, memo), memo=memo)

        monkeypatch.setattr(glm_airlines, "compare", planted)
    rc = main(["--workload", "glm_airlines.train", "--seed", str(SEED), "--seconds",
               "1", "--rehearse", "--rows", str(ROWS)], time.perf_counter())
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is (fault is None), line["checks"]


def test_reference_fit_does_not_depend_on_its_start():
    """A full fit from zero and the fit from a perturbed optimum (as the
    timed size starts from the program's coefficients) land on the same
    coefficients: the comparison's optimum is the problem's, not the
    path's."""
    import h2o3_tpu

    h2o3_tpu.init()
    cfg = load_json(glm_airlines.__file__.replace(".py", ".json"))
    cfg["rows"] = 30000
    data = glm_airlines.make_frame(cfg, 3)
    X, y = data.host()
    data.drop()
    zero = glm_airlines_ref.fit(X, y, cfg)
    nudged = zero["coef"] * (1 + 0.01 * np.random.default_rng(0).normal(size=len(zero["coef"])))
    again = glm_airlines_ref.fit(X, y, cfg, start=nudged)
    assert zero["iterations"] >= 3 and again["step"] < 1e-10
    np.testing.assert_allclose(again["coef"], zero["coef"], rtol=0, atol=1e-9)


def test_reference_gram_is_the_one_hot_designs():
    """The reference's Gram from codes equals the materialised one-hot
    design's X'WX and X'Wz (float64, to its rounding)."""
    cfg = load_json(glm_airlines.__file__.replace(".py", ".json"))
    rng = np.random.default_rng(5)
    n = 4000
    levels = [int(cfg["data"]["columns"][c]["levels"]) for c in glm_airlines_ref.CATS]
    X = {"codes": np.stack([rng.integers(0, L, n) for L in levels], axis=1).astype(np.int16),
         "num": rng.normal(size=(n, 2)) * [300.0, 500.0] + [1300.0, 700.0]}
    D = glm_airlines_ref.Design(X, cfg)
    cols = [(X["codes"][:, j][:, None] == np.arange(1, L)[None, :]).astype(float)
            for j, L in enumerate(levels)]
    M = np.concatenate(cols + [D.xs, np.ones((n, 1))], axis=1)
    w, z = rng.random(n), rng.normal(size=n)
    G, b = D.gram(w, z)
    np.testing.assert_allclose(G, (M * w[:, None]).T @ M, rtol=1e-12, atol=1e-9)
    np.testing.assert_allclose(b, (M * w[:, None]).T @ z, rtol=1e-12, atol=1e-9)
    beta = rng.normal(size=D.p)
    np.testing.assert_allclose(D.eta(beta), M @ beta, rtol=1e-12, atol=1e-9)


READERS = {n: importlib.import_module(f"benchmark.layer_metrics.{n}") for n in (
    "glm_cat_gram_roofline_pct", "glm_cat_gram_ms_per_iter", "glm_design_bytes_per_row")}
S = 1e9  # ns a second
TRACE = {"device": {"0": [
    ["%fusion.3 = f32[50331648]{0} fusion(...)", 0, 0.2 * S],
    ["%cat_gram_pallas.1 = (f32[896,896]{1,0}, f32[896,24]{1,0}, f32[8,8]{1,0}) custom-call(...)",
     0.3 * S, 1.5 * S],
    ["%while.2 = (s32[]) while(...), condition=...", 2.0 * S, 8.0 * S],
    ["%cat_gram_pallas.1 = (f32[896,896]{1,0}, f32[896,24]{1,0}, f32[8,8]{1,0}) custom-call(...)",
     2.1 * S, 1.5 * S]]}}
CALL = {"glm_irls_iterations_total": 4.0, "glm_design_bytes_total": 36.0 * 50331648}


def ctx(counters, trace=TRACE, peaks=True):
    cfg = load_json(glm_airlines.__file__.replace(".py", ".json"))
    return {"call": {"counters": counters, "passes": 1, "wall_s": 12.0},
            "cfg": cfg, "config": glm_airlines, "log": lambda *a: None,
            "trace": trace, "peaks": peaks_for("TPU v5 lite") if peaks else None}


def test_readers_on_a_planted_trace():
    cfg = ctx(CALL)["cfg"]
    work = glm_airlines.needed_work(cfg, 1)["cat_gram"]
    assert work["flops"] == cfg["rows"] * 5 * 2 * (45 + 9)
    assert work["bytes"] == cfg["rows"] * 5 * (24 + 8 + 8)
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert READERS["glm_cat_gram_roofline_pct"].read(ctx(CALL)) == pytest.approx(
        100 * least / 3.0)
    assert READERS["glm_cat_gram_ms_per_iter"].read(ctx(CALL)) == pytest.approx(3000 / 4)
    assert READERS["glm_design_bytes_per_row"].read(ctx(CALL)) == pytest.approx(
        36.0 * 50331648 / cfg["rows"])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_none_without_the_kernel_or_counter(name):
    """A program without the kernel leaves no ``cat_gram_pallas`` event; a
    program without the counters reads 0.0 for every flat name."""
    no_kernel = {"device": {"0": TRACE["device"]["0"][:1]}}
    assert READERS[name].read(ctx({k: 0.0 for k in CALL}, trace=no_kernel)) is None
    if name != "glm_design_bytes_per_row":
        assert READERS[name].read(ctx(CALL, trace=no_kernel)) is None
    if name == "glm_cat_gram_roofline_pct":
        assert READERS[name].read(ctx(CALL, peaks=False)) is None
