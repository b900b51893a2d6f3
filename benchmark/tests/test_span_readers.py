"""The readers of the program's spans and labelled counters, each on a
planted ``ctx``: what they compute from the differences over the traced call,
and that a program without the spans (every flat name reads 0) gives nothing,
not zero. The last test reads real differences through the harness's own
``read_counters`` around a tiny CPU ``train()`` of each configuration."""

import importlib

import pytest

NAMES = ("job_layer_self_ms_per_call", "glm_datainfo_s_per_fit", "glm_fit_s_per_fit",
         "glm_score_metrics_s_per_fit", "tree_binning_host_s_per_call",
         "tree_rebin_bytes_per_call")
READERS = {n: importlib.import_module(f"benchmark.layer_metrics.{n}") for n in NAMES}

# one GLM fit: train 63.0 s = job layer 0.2 + datainfo 2.5 + fit 3.0 + metrics 57.0 + 0.3 of glm.build's own
GLM = {"span_seconds_sum{name=train}": 63.0, "span_seconds_count{name=train}": 1.0,
       "span_seconds_sum{name=glm.build}": 62.8, "span_seconds_sum{name=gbm.build}": 0.0,
       "span_seconds_count{name=glm.build}": 1.0,
       "span_seconds_sum{name=glm.datainfo}": 2.5, "span_seconds_sum{name=glm.fit}": 3.0,
       "span_seconds_sum{name=model.score_metrics}": 57.0}
# two GBM calls in one difference (a reader divides by the calls it sees)
GBM = {"span_seconds_sum{name=train}": 40.0, "span_seconds_count{name=train}": 2.0,
       "span_seconds_sum{name=gbm.build}": 39.9, "span_seconds_sum{name=glm.build}": 0.0,
       "span_seconds_sum{name=tree.fit_bins}": 0.30, "span_seconds_sum{name=tree.bin_frame}": 0.02,
       "span_seconds_count{name=tree.fit_bins}": 2.0,
       "tree_hist_hbm_bytes_total{path=rebin}": 2 * 5.0 * 4_063_232 * 28}


def ctx(counters):
    return {"call": {"counters": counters, "passes": 1, "wall_s": 63.2}}


@pytest.mark.parametrize("name,counters,want", [
    ("job_layer_self_ms_per_call", GLM, 200.0),
    ("job_layer_self_ms_per_call", GBM, 50.0),
    ("glm_datainfo_s_per_fit", GLM, 2.5),
    ("glm_fit_s_per_fit", GLM, 3.0),
    ("glm_score_metrics_s_per_fit", GLM, 57.0),
    ("tree_binning_host_s_per_call", GBM, 0.16),
    ("tree_rebin_bytes_per_call", GBM, 568_852_480.0),
])
def test_reader_on_planted_differences(name, counters, want):
    assert READERS[name].read(ctx(counters)) == pytest.approx(want)


def test_a_call_that_binned_nothing_reads_zero_bytes():
    hit = dict(GBM, **{"tree_hist_hbm_bytes_total{path=rebin}": 0.0})
    assert READERS["tree_rebin_bytes_per_call"].read(ctx(hit)) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_spans_gives_nothing(name):
    r = READERS[name]
    # the parent commit's counter_value answers 0.0 for every flat name, and so
    # does a program whose metrics are gated off: left out of the line, no error
    assert r.read(ctx({c: 0.0 for c in r.COUNTERS})) is None
    assert all(isinstance(c, str) for c in r.COUNTERS)


@pytest.mark.parametrize("config,want", [
    ("glm_higgs", ("job_layer_self_ms_per_call", "glm_datainfo_s_per_fit", "glm_fit_s_per_fit",
                   "glm_score_metrics_s_per_fit")),
    ("gbm_higgs", ("job_layer_self_ms_per_call", "tree_binning_host_s_per_call",
                   "tree_rebin_bytes_per_call")),
])
def test_readers_through_the_harness_counters(config, want):
    """A 5,000-row CPU train() of the configuration, its counters read as the
    window reads them: every reader of the cell finds something, the GLM three
    and the job layer tile the call, a fresh frame is binned once."""
    import json
    import os
    import time

    import h2o3_tpu
    from benchmark.harness import window

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", config + ".json")) as f:
        cfg = json.load(f)
    cfg["rows"] = 5000
    mod = importlib.import_module(f"benchmark.configs.{config}")
    h2o3_tpu.init()
    data = mod.make_frame(cfg, 2**31 + 5)
    names = sorted({c for n in want for c in READERS[n].COUNTERS})
    before = window.read_counters(names)
    est = mod.build_estimator(cfg)
    t0 = time.perf_counter()
    mod.train(est, data)
    wall = time.perf_counter() - t0
    after = window.read_counters(names)
    mod.release(est)
    c = {"call": {"counters": {k: after[k] - before[k] for k in after}, "passes": 1,
                  "wall_s": wall}}
    got = {n: READERS[n].read(c) for n in want}
    data.drop()
    assert all(v is not None and v >= 0 for v in got.values()), got
    if config == "glm_higgs":
        parts = sum(v for n, v in got.items() if n != "job_layer_self_ms_per_call")
        assert parts + got["job_layer_self_ms_per_call"] / 1e3 == pytest.approx(wall, rel=0.05)
    else:
        from h2o3_tpu.parallel.mesh import pad_to_shards

        assert got["tree_rebin_bytes_per_call"] == 5.0 * pad_to_shards(cfg["rows"]) * cfg["cols"]
