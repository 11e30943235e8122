import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))
# NOTE: no XLA_FLAGS here — tests and benches must see the single real
# device; only launch/dryrun.py forces 512 placeholder host devices.

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import pytest

# Tests that took >5 s on the reference box (pytest --durations), tagged
# `slow` at param granularity so the fast lane (`-m "not slow"`, the CI
# test job) keeps sub-5s params of the same functions. The `slow` CI job
# runs them on push; `python -m pytest` with no -m filter runs everything.
_SLOW_NODE_IDS = {
    "test_api_session.py::test_train_emits_bus_events",
    "test_calibration.py::test_live_straggler_drift_refit_restores_prediction",
    "test_chaos.py::test_live_ps_crash_walks_the_compression_ladder",
    "test_checkpoint.py::test_restore_resumes_training_state",
    "test_docs.py::test_readme_snippets_execute",
    "test_kernel_properties.py::test_flash_attention_matches_ref_random",
    "test_kernel_properties.py::test_ssd_chunk_size_invariance",
    "test_kernel_properties.py::test_ssd_state_continuity",
    "test_kernels.py::test_flash_attention_fwd"
    "[1-256-256-2-1-64-True-float32-2e-05]",
    "test_kernels.py::test_flash_attention_grads[1-128-4-2-32]",
    "test_kernels.py::test_flash_attention_grads[2-128-2-2-64]",
    "test_kernels.py::test_ssd_matches_decode_recurrence",
    "test_kernels.py::test_ssd_scan[1-128-2-32-1-16-32-float32-0.0005]",
    "test_kernels.py::test_ssd_scan[1-256-2-64-1-32-128-float32-0.0005]",
    "test_kernels.py::test_ssd_scan[2-128-4-32-2-16-64-float32-0.0005]",
    "test_kv_quant.py::test_int8_kv_decode_tracks_fp_forward"
    "[qwen3-1.7b]",
    "test_kv_quant.py::test_int8_kv_decode_tracks_fp_forward"
    "[stablelm-1.6b]",
    "test_kv_quant.py::test_quant_roundtrip_error_bounded",
    "test_mitigation.py::test_compressed_step_reports_payload_bytes",
    "test_mitigation.py::test_error_feedback_convergence_parity",
    "test_mitigation.py::test_legacy_checkpoint_restores_with_zero_residual",
    "test_mitigation.py::test_residual_survives_checkpoint_restore",
    "test_mitigation.py::test_restores_counter_reported",
    "test_mitigation.py::"
    "test_session_async_ps_mode_emits_staleness_histogram",
    "test_mitigation.py::test_trainer_applies_mitigation_mid_run",
    "test_models_smoke.py::test_decode_matches_forward[mamba2-1.3b]",
    "test_models_smoke.py::test_decode_matches_forward[qwen3-1.7b]",
    "test_models_smoke.py::test_decode_matches_forward[zamba2-1.2b]",
    "test_models_smoke.py::test_forward_shapes_no_nans"
    "[deepseek-v2-lite-16b]",
    "test_models_smoke.py::test_forward_shapes_no_nans[hubert-xlarge]",
    "test_models_smoke.py::test_forward_shapes_no_nans[starcoder2-15b]",
    "test_models_smoke.py::test_forward_shapes_no_nans[zamba2-1.2b]",
    "test_models_smoke.py::test_train_step_decreases_loss"
    "[deepseek-v2-lite-16b]",
    "test_models_smoke.py::test_train_step_decreases_loss[hubert-xlarge]",
    "test_models_smoke.py::test_train_step_decreases_loss[mamba2-1.3b]",
    "test_models_smoke.py::test_train_step_decreases_loss[qwen2-vl-2b]",
    "test_models_smoke.py::test_train_step_decreases_loss[zamba2-1.2b]",
    "test_optim_variants.py::test_master_weights_training_converges",
    "test_optim_variants.py::test_moe_forward_same_under_rules",
    "test_perf_models.py::test_table2_svr_rbf_wins_for_k80",
    "test_system.py::test_training_survives_revocation_and_join",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid.rsplit("/", 1)[-1] in _SLOW_NODE_IDS:
            item.add_marker(pytest.mark.slow)
