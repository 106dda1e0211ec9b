"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the chip is reached only
through ``chip_smoke.py``) — the environment must be set before jax is
first imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# the persistent compile cache lives at ONE fixed path inside the
# checkout (utils/compile_cache.py); tests and the processes they spawn
# must neither fill it nor depend on what an earlier run left there
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pathlib
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test (deselect with -m 'not slow' for the "
        "fast core signal)",
    )


#: tests measured >~3s on the 1-core CI host (pytest --durations, r3).
#: `pytest -m "not slow"` gives the ~2-minute core signal; the full
#: suite stays the merge bar. Names are matched without parametrization.
SLOW_TESTS = {
    "test_small_resnet_trains",
    "test_trains_synthetic_to_high_accuracy",
    "test_lenet_conv_conf_trains_digits",
    "test_two_process_training_matches_single_process",
    "test_moe_transformer_lm_trains",
    "test_pipeline_gradients_match_sequential",
    "test_ring_conf_matches_dense_single_device",
    "test_gradients_match_dense",
    "test_mlp_conf_parses_and_builds",
    "test_sweep_two_points",
    "test_ring_lm_learns",
    "test_checkpoint_resume_reproduces_uninterrupted_run",
    "test_replica_batchnorm_trains_per_replica_buffers",
    "test_moe_conf_expert_parallel_matches_dense",
    "test_dense_moe_capacity_drops_tokens",
    "test_dense_lm_learns",
    "test_flash_mode_matches_dense",
    "test_chunked_run_matches_per_step_run",
    "test_lm_learns_markov_sequences",
    "test_pp_conf_matches_unstaged_single_device",
    "test_stacked_cd_reduces_reconstruction_error",
    "test_dense_moe_shapes_and_aux",
    "test_elastic_trains_and_contracts",
    "test_moe_conf_dense_trains_and_adds_aux",
    "test_random_sync_trains",
    "test_ring_conf_without_seq_axis_degrades",
    "test_ring_lm_matches_dense_loss",
    "test_pipeline_matches_sequential",
    "test_conv_net_shape_inference",
    "test_pp_conf_trains_on_data_pipe_mesh",
    "test_lm_bf16_trains",
    "test_sample_ratio_adapts_to_bandwidth",
    "test_sharded_resume_reproduces_uninterrupted_run",
    "test_moe_conf_full_dp_ep_mesh_trains",
    "test_pallas_backward_matches_dense",
    "test_chunk_equals_stepwise",
    "test_unrolled_autoencoder_finetunes",
    "test_replica_trainer_resumes_sharded_checkpoint",
    "test_bf16_conv_net_trains",
    "test_mnist_layer_distortion_end_to_end",
    "test_bn_chunk_equals_stepwise",
    "test_bn_eval_uses_running_stats",
    "test_distort_jits",
    "test_trains_digits_to_reference_accuracy",
    "test_fused_streams_identical_under_speculation",
    "test_fused_verify_zero_draft_width_matches_reference",
    "test_fused_under_tensor_parallel_matches_single_device",
    "test_fused_streams_identical_interleaved",
    "test_fused_streams_identical_prefix_warm",
    "test_serve_bench_kernels_fused_smoke",
    "test_fused_jit_cache_pinned_one_program_per_shape",
    "test_kernel_select_event_and_trace_attend_impl",
}


def pytest_collection_modifyitems(config, items):
    import pytest

    seen = set()
    for item in items:
        base = item.name.split("[")[0]
        if base in SLOW_TESTS:
            seen.add(base)
            item.add_marker(pytest.mark.slow)
    # staleness guard: a renamed/removed slow test must fail loudly, not
    # silently drift back into the fast core signal. Enforced whenever
    # collection was not narrowed by the operator (-k/-m/path args) —
    # a suite-size threshold would silently lapse if the suite shrank.
    opt = config.option
    narrowed = bool(
        opt.keyword
        or opt.markexpr
        or getattr(opt, "ignore", None)
        or getattr(opt, "ignore_glob", None)
        or getattr(opt, "deselect", None)
        or getattr(opt, "lf", False)  # --lf prunes to last-failed files
        or any(
            not os.path.isdir(str(a))
            for a in (config.args or [])
        )
    )
    missing = SLOW_TESTS - seen
    if missing and not narrowed:
        raise pytest.UsageError(
            f"conftest.SLOW_TESTS names not found in collection "
            f"(renamed/removed?): {sorted(missing)}"
        )


collect_ignore = ["mp_worker.py"]
