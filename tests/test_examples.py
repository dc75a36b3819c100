"""Run every example script to completion (reference ``tests/test_examples.py:305``
runs each example under subprocess with synthetic settings).

Each example runs in its own subprocess on the virtual 8-device CPU mesh —
pinned via ``jax.config`` inside the child (the env var alone is overridden by
the TPU plugin at import time, see ``conftest.py``). Checkpoint-resume is
exercised through ``complete_nlp_example`` and ``accelerate-tpu launch``
through the flagship example. The ``by_feature`` scripts run from
``test_examples_by_feature.py``: the suite hands a file to one worker, and
the two files take about the same time.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")

_RUNNER = """
import jax
jax.config.update("jax_platforms", "cpu")
import runpy, sys
sys.argv = [sys.argv[1]] + sys.argv[2:]
runpy.run_path(sys.argv[0], run_name="__main__")
"""


def run_example(script, *args, timeout=900, extra_env=None):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, "-c", _RUNNER, os.path.join(EXAMPLES, script), *map(str, args)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, (
        f"{script} failed (rc={proc.returncode})\nstdout:\n{proc.stdout[-3000:]}\n"
        f"stderr:\n{proc.stderr[-3000:]}"
    )
    return proc


def test_nlp_example(tmp_path):
    proc = run_example("nlp_example.py", "--num_epochs", 5)
    assert "accuracy" in proc.stdout


def test_cv_example(tmp_path):
    proc = run_example("cv_example.py", "--num_epochs", 3)
    assert "accuracy" in proc.stdout


def test_complete_nlp_example_with_resume(tmp_path):
    out = str(tmp_path / "out")
    os.makedirs(out)
    run_example(
        "complete_nlp_example.py", "--num_epochs", 2, "--checkpointing_steps", "epoch",
        "--with_tracking", "--output_dir", out,
    )
    assert os.path.isdir(os.path.join(out, "epoch_1"))
    assert os.path.isdir(os.path.join(out, "logs"))
    # Resume from the epoch_1 checkpoint and finish epochs 2-3.
    proc = run_example(
        "complete_nlp_example.py", "--num_epochs", 4, "--resume_from_checkpoint",
        "--output_dir", out,
    )
    assert "Resumed from checkpoint" in proc.stdout
    assert "epoch 2" in proc.stdout and "epoch 3" in proc.stdout
    assert "epoch 1:" not in proc.stdout  # epochs before the resume point are skipped


def test_complete_cv_example_step_checkpointing(tmp_path):
    out = str(tmp_path / "out")
    os.makedirs(out)
    proc = run_example(
        "complete_cv_example.py", "--num_epochs", 1, "--checkpointing_steps", 16,
        "--output_dir", out,
    )
    assert any(d.startswith("step_") for d in os.listdir(out)), os.listdir(out)


@pytest.mark.parametrize(
    "script",
    [
        "inference/pippy/llama.py",
        "inference/pippy/bert.py",
        "inference/pippy/gpt2.py",
        "inference/pippy/t5.py",
        "inference/distributed/distributed_inference.py",
        "inference/continuous_batching.py",
    ],
)
def test_inference_examples(script):
    run_example(script)


def test_launch_cli_runs_flagship(tmp_path):
    """`accelerate-tpu launch --cpu` end-to-end on the flagship example
    (reference runs its examples through the launcher in test_examples.py)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable, "-m", "accelerate_tpu.commands.launch", "--cpu",
            "--num_processes", "1",
            os.path.join(EXAMPLES, "by_feature", "gradient_accumulation.py"),
            "--num_epochs", "12",
        ],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]


def test_multihost_remote_launcher_dry_run():
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "multihost_remote_launcher.py"),
         "--tpu_name", "pod", "--tpu_zone", "us-central2-b", "--num_hosts", "2", "--debug"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "PYTHONPATH": REPO},
    )
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "--worker all" in proc.stdout
    assert "--num_machines 2" in proc.stdout
    assert "--main_process_ip pod-worker-0" in proc.stdout  # debug placeholder


def test_multihost_remote_launcher_requires_coordinator_for_real_runs():
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "multihost_remote_launcher.py"),
         "--tpu_name", "pod", "--tpu_zone", "z", "--num_hosts", "2"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "PYTHONPATH": REPO},
    )
    assert proc.returncode != 0
    assert "main_process_ip" in proc.stderr
