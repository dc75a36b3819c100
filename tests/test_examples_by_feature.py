"""Run every ``examples/by_feature`` script to completion, as
``test_examples.py`` runs the others (a file of its own so that the two
halves run on two workers)."""

import pytest

from test_examples import run_example


@pytest.mark.parametrize(
    "script,args",
    [
        ("by_feature/gradient_accumulation.py", []),
        ("by_feature/checkpointing.py", []),
        ("by_feature/tracking.py", []),
        ("by_feature/profiler.py", []),
        ("by_feature/cross_validation.py", ["--num_epochs", 2, "--num_folds", 2]),
        ("by_feature/memory.py", []),
        ("by_feature/early_stopping.py", []),
        ("by_feature/multi_process_metrics.py", []),
        ("by_feature/local_sgd.py", []),
        ("by_feature/automatic_gradient_accumulation.py", []),
        ("by_feature/schedule_free.py", ["--num_epochs", 8]),
        ("by_feature/gradient_accumulation_for_autoregressive_models.py", ["--num_windows", 4]),
        ("by_feature/megatron_style_gpt_pretraining.py", ["--tp", 2, "--pp", 2, "--num_steps", 6]),
        ("by_feature/fsdp_with_peak_mem_tracking.py", ["--num_epochs", 4]),
        ("by_feature/pipeline_training.py", ["--pp", 2, "--microbatches", 4, "--num_steps", 4]),
        ("by_feature/pipeline_training.py", ["--pp", 2, "--microbatches", 4, "--num_steps", 4,
                                             "--schedule", "1f1b"]),
        ("by_feature/multi_slice_dcn.py", ["--slices", 2, "--tp", 2, "--num_steps", 4]),
        # default --prefetch covers the toy epoch: the compute-free demo model
        # gives the producer no device time to hide uploads in, so a shallower
        # depth re-arms the example's h2d_blocking==0 assert as a load flake.
        ("by_feature/dispatch_amortized_training.py", ["--window", 4]),
        ("by_feature/elastic_training.py", []),
        ("by_feature/paged_serving.py", ["--requests", 6]),
    ],
)
def test_by_feature_examples(script, args, tmp_path):
    extra = []
    if "checkpointing" in script:
        extra = ["--output_dir", str(tmp_path / "ckpt")]
    elif "elastic" in script:
        extra = ["--project_dir", str(tmp_path / "elastic")]
    elif "tracking" in script:
        extra = ["--project_dir", str(tmp_path / "proj")]
    elif "profiler" in script:
        extra = ["--trace_dir", str(tmp_path / "trace")]
    run_example(script, *args, *extra)
