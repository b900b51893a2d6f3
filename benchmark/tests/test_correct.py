"""`correct` has to come out false for the control (the plain reference one
precision below the configuration's, its numbers put in the program's place)
and for each fault planted under the timed path, and true for a sound run. At 20,000
rows on the CPU; the readings at the cells' own size are in PERF.md."""

import importlib
import json
import time

import pytest

from benchmark.configs.higgs_data import make_frame
from benchmark.harness.main import main
from benchmark.tests.faults import PLANTED

ROWS, SEED = 20000, 77
CONFIGS = sorted(PLANTED)


@pytest.mark.parametrize("fault", [None, "control", "unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("config", CONFIGS)
def test_fault_under_the_timed_path(config, fault, monkeypatch, capsys):
    """The rest of a run (no look for a chip), with the timed path sound, with
    the control in the program's place, and with each fault planted under
    it: ``main()``'s own comparison decides, and only the sound run is correct."""
    mod = importlib.import_module(f"benchmark.configs.{config}")
    if fault == "control":  # the control's numbers stand where the program's do
        sound = mod.compare
        monkeypatch.setattr(mod, "compare", lambda cfg, X, y, model: {
            "program": sound(cfg, X, y, model, control=True)["control"]})
    elif fault == "half_batch":  # half of the rows left out, the rest trained on
        def train(est, data):
            part = make_frame(ROWS, data.frame.ncol - 1, SEED, first_rows=ROWS // 2)
            return est.train(y="label", training_frame=part.frame)

        monkeypatch.setattr(mod, "train", train)
    elif fault:
        sound = mod.outputs
        monkeypatch.setattr(
            mod, "outputs", lambda est: PLANTED[config][fault](sound(est)))
    rc = main(["--workload", f"{config}.train", "--seed", str(SEED), "--seconds", "1",
               "--rehearse", "--rows", str(ROWS)], time.perf_counter())
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is (fault is None), line["checks"]
