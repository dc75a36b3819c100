"""chip_smoke.py off the chip: ``--rehearse`` drives both phases at a tiny width
on the CPU and reports the device it really ran on; without the option a machine
with no TPU is refused before any phase runs. A child process each, as the driver
runs the script."""

import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(tmp_path, *options):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "ACCELERATE_COMPILE_CACHE_DIR")}
    # One CPU device, and JAX's own cache variable: the script must honour it
    # and leave <checkout>/.jax_cache alone.
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"), *options],
        capture_output=True, text=True, cwd=str(tmp_path), timeout=600, env=env,
    )


def test_rehearsal_runs_both_phases_on_the_cpu(tmp_path):
    result = _run(tmp_path, "--rehearse")
    assert result.returncode == 0, result.stdout[-2000:] + result.stderr[-2000:]
    lines = [json.loads(line) for line in result.stdout.strip().splitlines()]
    assert lines[-1] == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    phases = {line["phase"]: line for line in lines[:-1]}
    assert list(phases) == ["setup", "train", "serve", "teardown"]
    assert phases["setup"]["rehearsal"] is True
    assert all(line["note"] == "smoke run, not a measurement" for line in lines[:-1])
    for name in ("train", "serve"):
        assert phases[name]["ok"] and all(c["ok"] for c in phases[name]["checks"].values())
    # The checks that need a TPU are skipped, the others are all there.
    assert "attention_is_flash" not in phases["train"]["checks"]
    assert {"losses_finite", "loss_falls"} <= set(phases["train"]["checks"])
    assert "reference_agrees_within_margin" in phases["serve"]["checks"]
    assert phases["teardown"]["compile_cache_dir"] == str(tmp_path / "cache")
    assert phases["teardown"]["compile_cache_entries_after"] > 0


def test_no_tpu_is_refused_before_any_phase(tmp_path):
    result = _run(tmp_path)
    assert result.returncode != 0
    assert result.stdout.strip() == ""  # no phase ran, no result line
    assert "needs a TPU" in result.stderr
