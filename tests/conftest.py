"""Test harness — replicates H2O's "real stack, local topology" strategy
(SURVEY.md §4): H2O tests boot a real in-process (or N-local-JVM) cloud; here
we boot a real 8-device sharded mesh on CPU so multi-chip semantics run in CI
without TPUs. No mocks anywhere below this line.
"""

import os

# Must be set before the jax backend initializes.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Persistent compilation cache shared across test processes/runs: most test
# wall time is XLA:CPU compilation of the same programs in every xdist
# worker, and the per-process compile COUNT is what intermittently aborts
# jaxlib (see pytest.ini). Cache hits fix both. JAX_COMPILATION_CACHE_DIR,
# when set, has already placed the cache — no directory is set in code then.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(__file__), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Tier control (SURVEY §4 test-size tiers; VERDICT r3 item 7): the default
# tier must stay under ~5 minutes so driver/CI timeouts never hit it. The
# heavyweight scenario/quality tests below run in the slow (nightly-style)
# tier: `pytest -m "" tests/`. Centralized here, measured from
# `--durations` on the build box — every family keeps at least one smoke in
# the default tier (gbm auc, mojo parity, client estimator, DL xor,
# multihost REST e2e, NA handling all stay).
_SLOW_BY_NAME = {
    "test_drf_multinomial",
    "test_automl_runs_xgboost_steps_first",
    "test_calibrate_model_platt_and_isotonic",
    "test_rulefit_binomial_and_linear_only",
    "test_rulefit_recovers_rules",
    "test_full_flow_over_client",
    "test_hist_subtraction_matches_direct",
    "test_stacked_ensemble_beats_or_matches_base_models",
    "test_stacked_ensemble_regression",
    "test_wave3_algos_build_over_rest",
    "test_native_scorer_bit_identical_to_numpy",
    "test_sklearn_proba_aligns_with_classes_for_numeric_labels",
    "test_gbm_multinomial",
    "test_calibration_survives_mojo_export",
    "test_pojo_standalone_scoring",
    "test_grid_parallel_respects_max_models",
    "test_grid_parallelism_matches_sequential",
    "test_scanned_chunk_builder_matches_loop_quality",
    "test_gbm_early_stopping",
    "test_dl_regression",
    "test_dl_reproducible",
    "test_bin_code_equality_device_vs_mojo",
    "test_gbm_sampling_reproducible",
    "test_gbm_poisson",
    "test_varimp_and_heatmap",
    "test_drf_mojo_parity",
    "test_gbm_varimp_ranks_informative_feature",
    "test_cartesian_grid_covers_product_and_ranks",
    "test_drf_checkpoint_adds_trees",
    "test_gbm_regression_beats_baseline_and_tracks_sklearn",
    # re-measured 2026-08-06 (--durations=60, tier-1 at ~18.5 min against
    # the 870 s window): the heaviest compile-bound cases move to the slow
    # tier. Families keep a tier-1 smoke — e.g. the binomial mojo parity,
    # the gbm worker-death resume, and one param variant of each swept
    # parity case stay (bracketed entries below mark ONE variant, not all).
    "test_profiler_writes_trace",
    "test_glm_fused_multinomial_parity_and_dispatches",
    "test_automl_budget_caps_each_model",
    "test_gbm_elastic_resume_8_to_4",
    "test_compile_cache_cross_process",
    "test_automl_poison_step_skipped_after_retry_budget",
    "test_pdp_recovers_shape",
    "test_gbm_regression_mojo_parity",
    "test_automl_worker_death_auto_resumes",
    "test_streamed_mono_matches_resident",
    "test_oversized_streamed_train_bounds_ledger_claims",
    "test_plot_surface_renders",
    "test_streamed_gbm_parity_on_2d_mesh",
    "test_adversarial_tie_suites_bit_exact_under_quant",
    "test_fused_parity_coarsened_saturated_levels",
    "test_get_leaderboard_extra_columns",
    "test_infogram_core_ranks_signal_over_noise",
    "test_oversized_frame_trains_through_eviction_cycles",
    "test_fused_mono_tie_break[1]",
    "test_fused_mono_constrained_signal[8]",
    "test_gbm_streaming_matches_resident[2]",
    "test_fused_cat_sharded_tie_break[2]",
    "test_fused_tie_break_duplicated_columns_nonzero_gains[8]",
    "test_upliftdrf_recovers_heterogeneous_effect[KL]",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (item.name in _SLOW_BY_NAME
                or item.name.split("[")[0] in _SLOW_BY_NAME):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(scope="session", autouse=True)
def cloud():
    import h2o3_tpu

    info = h2o3_tpu.init()
    assert info["cloud_size"] == 8
    yield info


@pytest.fixture()
def rng():
    return np.random.default_rng(42)
