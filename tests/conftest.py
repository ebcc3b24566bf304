"""Test configuration: force an 8-device virtual CPU mesh so sharding tests
run without TPU hardware (SURVEY.md §4's loopback-collective gap).

The env vars cover subprocesses the tests spawn; the config update covers
this process when something imported jax before conftest (safe: no backend
is initialized yet at conftest import time).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The package places no persistent compilation cache on the CPU backend by
# default (utils/compile_cache.py), but a JAX_COMPILATION_CACHE_DIR in the
# developer's environment would.  Keep the cache off for the suite with
# JAX's own switch: an XLA:CPU executable reloaded from the cache is not
# guaranteed bit-identical to a freshly compiled one under jax 0.9.0 (see
# test_arena.py's AOT test), which the bit-identity differentials cannot
# tolerate.  The cache tests in test_api_extras.py switch it back on in
# their own subprocesses.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)


# ---------------------------------------------------------------------
# Quick/slow tiers (round-5): the full suite is ~29 min on a 1-CPU
# container (jit-compile bound); `pytest -m quick` runs the <6.5s tests
# (~4-5 min), `pytest -m slow` the compile-heavy rest, plain `pytest`
# everything.  The list is data (measured durations), not decorators —
# re-measure with `pytest --durations=80` and update when it drifts.
_SLOW = {
    "test_rank.py::test_lambdarank_example_parity",
    "test_cli.py::test_reference_example_confs_run_unchanged[multiclass_classification-multi_logloss]",
    "test_train.py::test_reference_parity_binary",
    "test_bundling.py::test_training_metrics_unchanged_vs_no_bundle",
    "test_continued.py::test_continue_training_from_reference_model",
    "test_cli.py::test_reference_example_confs_run_unchanged[lambdarank-ndcg@3]",
    "test_model_io.py::test_reference_cli_loads_our_model",
    "test_sparse.py::test_wide_sparse_constructs_and_trains",
    "test_distributed.py::test_two_process_data_parallel_bitmatch",
    "test_predict_device.py::test_device_predict_matches_host_multiclass_categorical",
    "test_cli.py::test_reference_example_confs_run_unchanged[regression-l2]",
    "test_bundling.py::test_bundled_dataset_voting_parallel_full_vote_matches_data",
    "test_cegb.py::test_reference_cli_cegb_parity",
    "test_parallel.py::test_goss_and_bagging_under_data_parallel",
    "test_parallel.py::test_tree_learner_data_trains_end_to_end",
    "test_cli.py::test_init_score_sidecar_and_param",
    "test_sklearn.py::test_sklearn_clone_and_grid_search",
    "test_rank.py::test_lambdarank_mslr_shaped_no_recompile",
    "test_bundling.py::test_wave_grower_bundled_matches_serial",
    "test_sklearn.py::test_classifier_multiclass",
    "test_bundling.py::test_bundled_dataset_with_parallel_learner",
    "test_bundling.py::test_bundled_predict_device_matches_host",
    "test_cli.py::test_cli_snapshots_and_continue",
    "test_continued.py::test_init_model_multiclass",
    "test_cli.py::test_multi_error_top_k",
    "test_bundling.py::test_bundled_voting_tight_gate_no_phantom_splits",
    "test_wave.py::test_mixed_width_wave_matches_serial",
    "test_forced_splits.py::test_reference_cli_forced_splits_parity",
    "test_train.py::test_dart_and_goss_compose_with_bundling_and_categoricals",
    "test_train.py::test_multiclass",
    "test_parallel.py::test_tree_learner_feature_trains_end_to_end",
    "test_cegb.py::test_coupled_penalty_narrows_feature_set",
    "test_categorical.py::test_wave_categorical_matches_serial",
    "test_api_extras.py::test_pandas_categorical_roundtrip",
    "test_cegb.py::test_tradeoff_split_scaling_equality",
    "test_dump_model.py::test_if_else_code_compiles_and_matches[3]",
    "test_continued.py::test_init_model_with_now_trivial_feature",
    "test_wave.py::test_wave_gated_boosting_matches_serial_loss",
    "test_cli.py::test_cli_task_refit",
    "test_cli.py::test_cli_predict_from_model_file_only",
    "test_categorical.py::test_high_cardinality_categorical_uint16_path",
    "test_continued.py::test_refit_moves_leaf_values_toward_new_data",
    "test_bundling.py::test_reference_cli_efb_auc_parity",
    "test_cegb.py::test_split_penalty_prunes_splits",
    "test_cli.py::test_cli_train_predict_matches_python_api",
    "test_categorical.py::test_categorical_train_roundtrip_and_predict",
    "test_continued.py::test_init_model_file_roundtrip",
    "test_categorical.py::test_categorical_device_replay_matches_host_predict",
    "test_sampling.py::test_feature_fraction_bynode_deterministic",
    "test_continued.py::test_init_model_booster_equals_uninterrupted",
    "test_predict_device.py::test_prediction_early_stop_converges_to_same_argmax",
    "test_predict_device.py::test_pred_early_stop_device_matches_host_multiclass",
    "test_predict_device.py::test_pred_early_stop_multiclass_differential",
    "test_predict_device.py::test_loaded_model_device_predict_matches_host",
    "test_dump_model.py::test_dump_model_walk_matches_predict",
    "test_parallel.py::test_data_parallel_matches_single_device",
    "test_train.py::test_jit_cache_reuses_compiled_growers",
    "test_parallel.py::test_feature_parallel_matches_single_device",
    "test_parallel.py::test_wave_data_parallel_matches_single_device",
    "test_api_extras.py::test_pandas_int_categories_json_roundtrip",
    "test_sampling.py::test_balanced_bagging_mask_respects_class_fractions",
    "test_wave.py::test_wave_capacity1_matches_serial",
    "test_cli.py::test_cli_overrides_beat_config_file",
    "test_predict_device.py::test_device_predict_matches_host_binary",
    "test_categorical.py::test_categorical_search_matches_reference_oracle[False-0]",
    "test_sklearn.py::test_early_stopping_eval_set",
    "test_wave.py::test_wave_pass_count_regression_guard",
    "test_obs.py::test_off_path_overhead_guard",
    "test_tools.py::test_tpu_window_dry_run_end_to_end",
    "test_tools.py::test_run_suite_reports_failure",
    "test_wave_apply.py::test_batched_apply_differential[categorical_bitset-7]",
    "test_wave_apply.py::test_batched_apply_differential[categorical_bitset-23]",
    "test_wave_apply.py::test_batched_apply_differential[tie_gain-7]",
    "test_wave_apply.py::test_batched_apply_differential[tie_gain-23]",
    "test_wave_apply.py::test_batched_apply_differential[bagging-7]",
    "test_wave_apply.py::test_batched_apply_differential[bagging-23]",
    "test_wave_apply.py::test_batched_apply_mesh_parallel",
    "test_hist_fused.py::test_fused_packed_differential[nan_default_left-7]",
    "test_hist_fused.py::test_fused_packed_differential[categorical_bitset-7]",
    "test_hist_fused.py::test_fused_packed_differential[categorical_bitset-23]",
    "test_hist_fused.py::test_mesh_data_parallel_packed_matches_single",
    "test_hist_fused.py::test_packed_capacity_cuts_waves",
    "test_hist_quant.py::test_quant_training_auc_budget",
    "test_hist_quant.py::test_quant_grid_differential[nan_default_left-7-int16]",
    "test_hist_quant.py::test_quant_grid_differential[categorical_bitset-7-int16]",
    "test_hist_quant.py::test_quant_grid_differential[nan_default_left-7-int8]",
    "test_hist_quant.py::test_quant_grid_differential[categorical_bitset-23-int8]",
    "test_hist_quant.py::test_resume_bit_identical_int16",
    "test_hist_quant.py::test_fused_grad_bit_identical_wave_path",
    "test_hist_quant.py::test_fused_grad_bit_identical_bagging",
    "test_hist_quant.py::test_quant_mesh_parity",
    "test_hist_quant.py::test_fused_grad_ineligible_paths",
    "test_explain.py::test_oracle_matches_brute_force_categorical_nan",
    "test_robust.py::test_resume_bit_identical_dart",
    "test_robust.py::test_resume_bit_identical_two_device_mesh",
    "test_robust.py::test_sigterm_checkpoints_and_resumes",
    "test_online.py::test_device_refit_matches_host_multiclass",
    "test_online.py::test_device_refit_matches_host_mesh_2dev",
    "test_online.py::test_device_refit_matches_host_binary[0.0]",
    "test_rank_device.py::test_rank_data_parallel_end_to_end",
    "test_rank_device.py::test_trainer_routes_device_score_to_ndcg",
    "test_rank_device.py::test_fused_rank_gradients_bit_identical",
    "test_rank_device.py::test_fused_rank_gradients_bit_identical_wave_interpret",
    "test_rank_device.py::test_sharded_rank_grads_match_single_device_oracle[2]",
    "test_rank_device.py::test_sharded_rank_grads_match_single_device_oracle[3]",
    "test_serve.py::test_session_rank_topk_concurrent_mixed_sizes",
    "test_explain.py::test_session_explain_rank_model_parity",
}


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: compile-heavy test (>6.5s)")
    config.addinivalue_line("markers", "quick: fast tier (everything else)")


import pytest as _pytest_mod


@_pytest_mod.fixture(autouse=True)
def _flight_dumps_to_tmp(tmp_path, monkeypatch):
    """The flight recorder (obs/spans.py) dumps FLIGHT_rN.json on
    degradations and health aborts — several tests trigger those on
    purpose.  Default the dump dir to the test's tmp dir so no test can
    litter the repo root (a test that asserts on the dump location sets
    LGBM_TPU_FLIGHT_DIR itself and wins, monkeypatch being per-test)."""
    monkeypatch.setenv("LGBM_TPU_FLIGHT_DIR", str(tmp_path))


@_pytest_mod.fixture
def replace_plan(monkeypatch):
    """A reference path is a field of the growth plan, not a parameter:
    ``replace_plan(fused_grad=False)`` makes every trainer built from then
    on in the test run ``dataclasses.replace(select_path(...), **fields)``;
    called again it replaces those fields, with none it restores the plan."""
    def set_fields(**fields):
        import dataclasses

        from lightgbm_tpu.boosting import gbdt
        from lightgbm_tpu.core import plan
        monkeypatch.setattr(
            gbdt, "select_path", lambda config, facts: dataclasses.replace(
                plan.select_path(config, facts), **fields))
    return set_fields


def pytest_collection_modifyitems(config, items):
    import pytest as _pytest
    tests_root = config.rootpath / "tests"
    for item in items:
        # file path relative to tests/ + test name (params included) —
        # resolved from item.path, not nodeid string surgery, so nested
        # dirs or odd invocation roots can't silently mis-tier into quick
        try:
            rel = item.path.relative_to(tests_root).as_posix()
        except ValueError:  # collected from outside tests/ (plugins)
            rel = item.path.name
        nid = f"{rel}::{item.name}"
        if nid in _SLOW:
            item.add_marker(_pytest.mark.slow)
        else:
            item.add_marker(_pytest.mark.quick)
