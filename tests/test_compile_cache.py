"""Persistent XLA compilation cache: the second trace of a program must be
served from the cache directory instead of re-paying the XLA compile, and the
directory is resolved in one place (JAX_COMPILATION_CACHE_DIR, else the
library's argument or ACCELERATE_COMPILE_CACHE_DIR, else none). Runs in
subprocesses because the cache config must land before the process's first
compile to represent a cold start faithfully."""

import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, os, sys, time
import numpy as np
import optax
from accelerate_tpu import Accelerator
from accelerate_tpu.models import Llama, LlamaConfig
import jax

acc = Accelerator()
assert jax.config.jax_compilation_cache_dir == os.environ["ACCELERATE_COMPILE_CACHE_DIR"]
model = Llama(LlamaConfig.tiny())
model.init_params(jax.random.key(0))
pmodel, popt = acc.prepare(model, optax.sgd(0.05))
step = acc.build_train_step(pmodel, popt)
ids = np.random.default_rng(0).integers(0, 256, (4, 16)).astype(np.int32)
t0 = time.perf_counter()
loss = float(step({"input_ids": ids, "labels": ids}))
print(json.dumps({"first_step_s": time.perf_counter() - t0, "loss": loss}))
"""


def _run_probe(cache_dir, tmp_path):
    script = tmp_path / "probe.py"
    script.write_text(_PROBE)
    env = {
        **os.environ,
        "PYTHONPATH": REPO_ROOT,
        "JAX_PLATFORMS": "cpu",
        "ACCELERATE_COMPILE_CACHE_DIR": str(cache_dir),
    }
    result = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        cwd=REPO_ROOT, timeout=600, env=env,
    )
    assert result.returncode == 0, result.stdout[-1500:] + result.stderr[-1500:]
    return json.loads(result.stdout.strip().splitlines()[-1])


def test_second_trace_hits_cache_dir(tmp_path):
    cache_dir = tmp_path / "xla_cache"
    cold = _run_probe(cache_dir, tmp_path)
    entries = {f for f in os.listdir(cache_dir) if f.endswith("-cache")}
    assert entries, "cold run wrote no cache entries"
    # The bench model's fused train step must be among the cached programs.
    assert any("_step" in f or "jit" in f for f in entries)

    warm = _run_probe(cache_dir, tmp_path)
    after = {f for f in os.listdir(cache_dir) if f.endswith("-cache")}
    assert after == entries, (
        "warm run recompiled (new cache entries appeared): "
        f"{sorted(after - entries)[:5]}"
    )
    assert abs(cold["loss"] - warm["loss"]) < 1e-6


def test_cache_helper_is_noop_without_env(monkeypatch, tmp_path):
    from accelerate_tpu.utils.environment import maybe_enable_compilation_cache

    monkeypatch.delenv("ACCELERATE_COMPILE_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert maybe_enable_compilation_cache() is None
    resolved = maybe_enable_compilation_cache(str(tmp_path / "c"))
    assert resolved == str(tmp_path / "c") and os.path.isdir(resolved)
    import jax

    assert jax.config.jax_compilation_cache_dir == resolved


_RESOLUTION_PROBE = """
import json, os, sys
from accelerate_tpu.utils.environment import maybe_enable_compilation_cache
import jax

resolved = maybe_enable_compilation_cache(sys.argv[1] or None)
print(json.dumps({
    "resolved": resolved,
    "config_dir": jax.config.jax_compilation_cache_dir,
    "min_compile_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
    "min_entry_bytes": jax.config.jax_persistent_cache_min_entry_size_bytes,
    "argument_dir_made": bool(sys.argv[1]) and os.path.isdir(sys.argv[1]),
}))
"""


def test_cache_directory_resolution_order(tmp_path):
    """JAX's own variable wins and no other directory is set or made; then the
    argument / the library's variable; with none of them the cache stays off.
    With a directory, the gates are opened so every program is kept."""
    script = tmp_path / "probe.py"
    script.write_text(_RESOLUTION_PROBE)
    jax_dir, repo_dir, arg_dir = (str(tmp_path / n) for n in ("jax", "repo", "arg"))

    def probe(argument="", **env_vars):
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_COMPILATION_CACHE_DIR", "ACCELERATE_COMPILE_CACHE_DIR")}
        env.update(PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu", **env_vars)
        result = subprocess.run(
            [sys.executable, str(script), argument], capture_output=True, text=True,
            cwd=REPO_ROOT, timeout=300, env=env,
        )
        assert result.returncode == 0, result.stdout[-1500:] + result.stderr[-1500:]
        return json.loads(result.stdout.strip().splitlines()[-1]), result.stderr

    both, stderr = probe(arg_dir, JAX_COMPILATION_CACHE_DIR=jax_dir,
                         ACCELERATE_COMPILE_CACHE_DIR=repo_dir)
    assert both["resolved"] == both["config_dir"] == jax_dir
    assert not both["argument_dir_made"] and not os.path.isdir(repo_dir)
    assert (both["min_compile_secs"], both["min_entry_bytes"]) == (0.0, -1)
    assert "JAX_COMPILATION_CACHE_DIR" in stderr and arg_dir in stderr  # the one log line

    library, _ = probe(ACCELERATE_COMPILE_CACHE_DIR=repo_dir)
    assert library["resolved"] == library["config_dir"] == repo_dir
    assert os.path.isdir(repo_dir) and library["min_entry_bytes"] == -1

    argument, _ = probe(arg_dir)
    assert argument["resolved"] == argument["config_dir"] == arg_dir

    neither, _ = probe()
    assert neither["resolved"] is None and not neither["config_dir"]
    assert neither["min_entry_bytes"] != -1  # JAX's defaults untouched
