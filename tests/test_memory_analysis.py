"""Static HBM & sharding-layout auditor gate (analysis/memory.py + layout.py).

Runs in tier-1 (marker ``analysis``) next to the program-auditor gate:

- **golden byte counts** — the tiny dp8 MemoryReport's param / opt-state /
  accum classes must match byte counts computed independently from the leaf
  shapes (adamw opt-state exactly 2x params + the count scalar), with
  opt-state flagged replicated-on-dp — the finding the ZeRO PR (ROADMAP
  item 2) will be judged against;
- **donation honesty** — with donation active, the predicted peak counts
  donation-aliased output bytes ONCE (the compiled alias table, not hope);
- **window scaling** — a K-step fused window's batch-class bytes scale ~K;
- **layout detection** — a ``with_sharding_constraint(..., P())`` on
  dp-sharded data surfaces as a ``gather`` reshard site;
- **cross-validation** — the ``estimate-memory`` abstract-init param bytes
  and the MemoryReport param class agree exactly for the same config, so the
  two surfaces can't drift;
- **CLI contract** — ``accelerate-tpu memcheck`` exits 0 on the shipped tiny
  config and 1 under a starved ``--budget-gib`` / ``--replicated-opt-gib``;
- **lint gate** — the two new rules (``raw-device-baseline``,
  ``replicated-constraint``) hold the shipped tree at zero unbaselined
  findings.
"""

import json
import os
import subprocess
import sys

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import Accelerator
from accelerate_tpu.analysis import (
    find_implicit_reshards,
    lint_paths,
    load_baseline,
    memory_report_from_lowered,
)
from accelerate_tpu.models import Llama, LlamaConfig
from accelerate_tpu.state import AcceleratorState, GradientState

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "accelerate_tpu")


def _build(tx=None, **kwargs):
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(**kwargs)
    cfg = LlamaConfig.tiny(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_attention_heads=2, num_key_value_heads=2, num_hidden_layers=2,
    )
    model = Llama(cfg)
    model.init_params(jax.random.key(0))
    pmodel, popt = acc.prepare(model, tx if tx is not None else optax.adamw(3e-4))
    return acc, pmodel, popt


def _batch(batch=8, seq=16, vocab=128):
    ids = np.random.default_rng(0).integers(0, vocab, (batch, seq)).astype(np.int32)
    return {"input_ids": ids, "labels": ids}


def _leaf_bytes(tree) -> int:
    """Independent byte accounting straight off the leaf shapes."""
    return sum(
        int(np.prod(np.shape(l), dtype=np.int64))
        * np.dtype(getattr(l, "dtype", np.float32)).itemsize
        for l in jax.tree_util.tree_leaves(tree)
    )


# ============================================================== golden report
def test_memory_report_tiny_dp8_golden():
    """The acceptance property: the tiny dp8 adamw build's MemoryReport
    carries exact class byte counts, flags opt-state replicated-on-dp, and
    predicts no OOM under the generation table."""
    acc, pm, po = _build()
    step = acc.build_train_step(pm, po)
    report = acc.audit(step, _batch())
    mem = report.memory
    assert mem is not None
    assert mem.builder == "build_train_step"
    assert mem.mesh_axes.get("dp") == 8
    assert mem.window == 1

    params_bytes = _leaf_bytes(pm.handle.params)
    opt_bytes = _leaf_bytes(po.opt_state)
    assert mem.classes["params"].global_bytes == params_bytes
    assert mem.classes["opt_state"].global_bytes == opt_bytes
    assert mem.classes["accum"].global_bytes == params_bytes
    # adamw: mu + nu (param-shaped fp32 moments) + the i32 step count.
    assert opt_bytes == 2 * params_bytes + 4

    # Pure data parallel: every class is dp-replicated — per-device == global,
    # and the opt-state finding (the ZeRO target) is first-class.
    assert mem.classes["opt_state"].per_device_bytes == opt_bytes
    assert mem.replicated_bytes("opt_state", "dp") == opt_bytes
    assert mem.classes["opt_state"].sharded_bytes("dp") == 0
    finding = next(
        f for f in mem.replication_findings
        if f.cls == "opt_state" and f.axis == "dp"
    )
    assert finding.axis_size == 8
    assert finding.per_device_bytes == opt_bytes
    assert finding.savings_bytes == int(opt_bytes * (1 - 1 / 8))
    assert "opt_state replicated on dp" in finding.format()

    # OOM verdict under the generation table's 90% headroom contract.
    from accelerate_tpu.utils.modeling import HBM_HEADROOM, device_hbm_bytes

    assert mem.memory_analysis_available
    assert mem.budget_bytes == int(device_hbm_bytes() * HBM_HEADROOM)
    assert mem.fits
    assert mem.predicted_peak_bytes >= params_bytes + opt_bytes
    assert not mem.reshards

    summary = mem.summary_dict()
    assert summary["fits"] is True
    assert summary["opt_state_replicated_dp_bytes"] == opt_bytes
    assert set(summary["per_device_bytes"]) == {
        "params", "opt_state", "accum", "batch",
        "activation_workspace", "temp_output",
    }
    # The full dict round-trips to JSON (the CLI path).
    json.dumps(mem.to_dict())


def test_memory_report_fsdp_shards_param_and_opt_state():
    """Under fsdp the params (and the opt-state moments that mirror them)
    are sharded, not replicated — the split the report attributes per axis."""
    from accelerate_tpu import ParallelismConfig

    acc, pm, po = _build(parallelism_config=ParallelismConfig(fsdp_size=8))
    step = acc.build_train_step(pm, po)
    mem = acc.audit(step, _batch()).memory
    params = mem.classes["params"]
    assert params.per_device_bytes < params.global_bytes
    assert params.sharded_bytes("fsdp") > 0
    opt = mem.classes["opt_state"]
    assert opt.sharded_bytes("fsdp") > 0
    assert opt.per_device_bytes < opt.global_bytes
    by_axis = params.by_axis(mem.mesh_axes)
    assert by_axis["fsdp"]["sharded"] == params.sharded_bytes("fsdp")
    # No dp axis of size > 1 on this mesh: nothing can be "replicated on dp"
    # — the summary must not report a phantom dp footprint (nor would the
    # memcheck --replicated-opt-gib gate trip on one).
    assert mem.replicated_bytes("opt_state", "dp") == 0
    assert mem.summary_dict()["opt_state_replicated_dp_bytes"] == 0
    assert not any(f.axis == "dp" for f in mem.replication_findings)


def test_layout_normalize_last_tile_dim_replicate():
    """The `{devices=[1,1,8]<=[8] last_tile_dim_replicate}` spelling IS fully
    replicated (the last dim is the replication group, not a tensor dim) —
    re-pinning it to plain `{replicated}` must not read as a reshard, and a
    sharded value pinned to it must classify as a gather."""
    from accelerate_tpu.analysis.layout import _is_replicated, _normalize

    assert _normalize("{devices=[1,1,8]<=[8] last_tile_dim_replicate}") == "{replicated}"
    assert _is_replicated("{devices=[1,1,8]<=[8] last_tile_dim_replicate}")
    # A REAL tile dim > 1 stays sharded even in the last_tile_dim spelling.
    assert not _is_replicated("{devices=[8,1,1]<=[8] last_tile_dim_replicate}")
    text = """
  func.func public @main(%arg0: tensor<16x8xf32> {mhlo.sharding = "{devices=[1,1,8]<=[8] last_tile_dim_replicate}"}) -> (tensor<16x8xf32>) {
    %0 = stablehlo.custom_call @Sharding(%arg0) {backend_config = "", mhlo.sharding = "{replicated}"} : (tensor<16x8xf32>) -> tensor<16x8xf32>
    return %0 : tensor<16x8xf32>
  }
"""
    assert find_implicit_reshards(text) == []


def test_audit_memory_opt_out_and_foreign_artifacts():
    acc, pm, po = _build()
    step = acc.build_train_step(pm, po)
    assert acc.audit(step, _batch(), memory=False).memory is None
    # A raw jitted fn has no builder meta — audit still works, memory stays None.
    from accelerate_tpu.analysis import audit_built

    report = audit_built(jax.jit(lambda x: x * 2), jnp.ones((4,)))
    assert report.memory is None


# ============================================================ window scaling
def test_window_batch_bytes_scale_with_k():
    """window=K stacks K batches into the program's arguments: the batch
    class scales ~K while the donated classes stay fixed."""
    acc1, pm1, po1 = _build()
    step = acc1.build_train_step(pm1, po1)
    mem1 = acc1.audit(step, _batch()).memory

    acc4, pm4, po4 = _build()
    win = acc4.build_train_window(pm4, po4, window=4)
    wb = {k: np.stack([v] * 4) for k, v in _batch().items()}
    mem4 = acc4.audit(win, wb).memory

    assert mem4.window == 4 and mem4.builder == "build_train_window"
    assert mem4.classes["params"].global_bytes == mem1.classes["params"].global_bytes
    assert mem1.batch_bytes > 0
    ratio = mem4.batch_bytes / mem1.batch_bytes
    # K=4 stacked batch args, modulo the fixed rng/count/clip overhead riding
    # in the same residual bucket.
    assert 3.0 <= ratio <= 4.6, (mem1.batch_bytes, mem4.batch_bytes)


# ========================================================== donation aliasing
def test_donation_aliasing_excluded_from_double_counting():
    """With donation ACTIVE (no CPU+compile-cache policy drop), outputs alias
    the donated inputs and the predicted peak counts those bytes once."""
    acc, pm, po = _build(tx=optax.sgd(0.1))
    cache_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        step = acc.build_train_step(pm, po)  # donate gate consults the config
        mem = acc.audit(step, _batch()).memory
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    assert not mem.donation_dropped_by_policy
    assert all(c.donated for c in mem.classes.values())
    params_bytes = mem.classes["params"].per_device_bytes
    # params + opt + accum all alias in place.
    assert mem.aliased_bytes >= params_bytes
    assert mem.predicted_peak_bytes == (
        mem.argument_bytes + mem.temp_bytes + mem.output_bytes - mem.aliased_bytes
    )
    assert mem.predicted_peak_bytes < (
        mem.argument_bytes + mem.temp_bytes + mem.output_bytes
    )


# =========================================================== layout detection
def test_layout_detects_gather_reshard():
    """A with_sharding_constraint(..., P()) on dp-sharded data is an implicit
    sharded→replicated copy — the layout auditor names it, with global bytes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    acc, _, _ = _build()
    mesh = acc.mesh

    @jax.jit
    def widen(x):
        return jax.lax.with_sharding_constraint(x * 2, NamedSharding(mesh, P()))

    x = jax.device_put(jnp.ones((16, 8)), NamedSharding(mesh, P("dp")))
    lowered = widen.lower(x)
    sites = find_implicit_reshards(lowered.as_text())
    assert len(sites) == 1, sites
    site = sites[0]
    assert site.kind == "gather"
    assert site.to_sharding == "{replicated}"
    assert site.nbytes == 16 * 8 * 4
    # The same lowering through the memory report surface (no builder meta:
    # executable totals + reshards only).
    mem = memory_report_from_lowered(lowered, mesh=mesh)
    assert len(mem.gather_reshards) == 1
    assert mem.summary_dict()["gather_reshards"] == 1


def test_layout_quiet_on_matching_constraint():
    from jax.sharding import NamedSharding, PartitionSpec as P

    acc, _, _ = _build()
    mesh = acc.mesh

    @jax.jit
    def same(x):
        return jax.lax.with_sharding_constraint(x * 2, NamedSharding(mesh, P("dp")))

    x = jax.device_put(jnp.ones((16, 8)), NamedSharding(mesh, P("dp")))
    assert find_implicit_reshards(same.lower(x).as_text()) == []


def test_shipped_builders_have_no_reshards():
    """The fused train step ships with zero implicit resharding copies — a
    future constraint regression shows up here, not on-chip."""
    acc, pm, po = _build()
    step = acc.build_train_step(pm, po)
    mem = acc.audit(step, _batch()).memory
    assert mem.reshards == []


# ========================================================== estimate parity
def test_estimate_memory_cross_validates_against_memory_report():
    """The abstract-init estimate (`accelerate-tpu estimate-memory tiny`) and
    the static analyzer's param class are the SAME bytes — pinned so the two
    surfaces can't drift."""
    from accelerate_tpu.commands.estimate import abstract_param_bytes

    expected = abstract_param_bytes("tiny")
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator()
    model = Llama(LlamaConfig.tiny())
    model.init_params(jax.random.key(0))
    pm, po = acc.prepare(model, optax.adamw(3e-4))
    step = acc.build_train_step(pm, po)
    mem = acc.audit(step, _batch(vocab=256)).memory
    got = mem.classes["params"].global_bytes
    assert abs(got - expected) <= 0.01 * expected, (got, expected)


# ================================================= timeline predicted peak
def test_timeline_carries_predicted_peak_cross_check():
    acc, pm, po = _build()
    step = acc.build_train_step(pm, po)
    mem = acc.audit(step, _batch()).memory
    summary = acc.telemetry.timeline.summary()
    assert summary["memory"]["predicted_peak_bytes"] == mem.predicted_peak_bytes
    # CPU devices report no memory_stats: the prediction stands alone (the
    # ratio key appears only when an observed peak exists).
    observed = summary["memory"].get("peak_bytes_in_use", 0)
    if observed:
        assert summary["memory"]["predicted_vs_observed"] > 0
    acc.telemetry.timeline.reset()
    assert "predicted_peak_bytes" not in acc.telemetry.timeline.summary()["memory"]


def test_predicted_peak_sanity_after_real_steps():
    """Predicted-vs-observed sanity on the CPU rig: run real steps after the
    audit — the prediction must stay a plausible per-device number (at least
    the resident donated classes, within the generation budget)."""
    acc, pm, po = _build()
    step = acc.build_train_step(pm, po)
    mem = acc.audit(step, _batch()).memory
    for _ in range(3):
        loss = step(_batch())
    assert np.isfinite(float(jax.device_get(loss)))
    resident = (
        mem.classes["params"].per_device_bytes
        + mem.classes["opt_state"].per_device_bytes
    )
    assert mem.predicted_peak_bytes >= resident
    assert mem.predicted_peak_bytes <= mem.budget_bytes


# ===================================================================== CLI
def test_memcheck_cli_exit_codes(tmp_path):
    """`accelerate-tpu memcheck` exits 0 on the shipped tiny config (no OOM
    predicted) and 1 under a starved budget / replication threshold — the
    contract the verify recipe and the ZeRO acceptance gate rely on."""
    env = {**os.environ, "PYTHONPATH": REPO}
    base = [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
            "memcheck", "--summary", "--batch", "8", "--seq", "8"]
    ok = subprocess.run(base, capture_output=True, text=True, env=env)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    payload = json.loads(ok.stdout)
    assert payload["fits"] is True
    assert payload["opt_state_replicated_dp_bytes"] > 0
    assert set(payload["per_device_bytes"]) >= {
        "params", "opt_state", "accum", "batch", "activation_workspace",
    }
    starved = subprocess.run(
        base + ["--budget-gib", "0.0005", "--replicated-opt-gib", "0.000001"],
        capture_output=True, text=True, env=env,
    )
    assert starved.returncode == 1, starved.stdout + starved.stderr
    assert "predicted OOM" in starved.stderr
    assert "opt_state replicated on dp" in starved.stderr


def test_memcheck_cli_serving_mode(tmp_path):
    """`accelerate-tpu memcheck --serving` prices the paged decode window —
    KV pool as a first-class class, gather-view workspace from the compiled
    program — and gates it against the HBM budget: exit 0 on the shipped
    tiny rig, exit 1 under a starved budget naming the pool bytes (the
    OOM-before-launch discipline for the serving path, ROADMAP item 2)."""
    env = {**os.environ, "PYTHONPATH": REPO}
    base = [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
            "memcheck", "--serving", "--summary"]
    ok = subprocess.run(base, capture_output=True, text=True, env=env)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    payload = json.loads(ok.stdout)
    assert payload["fits"] is True
    assert payload["kv_pool_bytes_per_device"] > 0
    assert payload["per_device_bytes"]["kv_pool"] == payload["kv_pool_bytes_per_device"]
    assert payload["pool"]["num_blocks"] == 64
    starved = subprocess.run(
        base + ["--budget-gib", "0.0005"], capture_output=True, text=True, env=env,
    )
    assert starved.returncode == 1, starved.stdout + starved.stderr
    assert "predicted serving OOM" in starved.stderr
    assert "KV pool" in starved.stderr


# ================================================================ lint gate
def test_new_rules_hold_shipped_tree_at_zero_unbaselined():
    """The tier-1 gate for the two new rules: every raw-device-baseline
    finding in the shipped tree is a baselined legitimate reader (or inline-
    suppressed), and replicated-constraint has NO findings at all."""
    baseline = load_baseline(os.path.join(REPO, ".accelerate-lint-baseline.json"))
    findings = lint_paths([PACKAGE], baseline=baseline)
    live = [
        f for f in findings
        if f.rule in ("raw-device-baseline", "replicated-constraint")
        and not f.suppressed and not f.baselined
    ]
    assert live == [], "\n".join(f.format() for f in live)
    constraint = [f for f in findings if f.rule == "replicated-constraint"]
    assert constraint == [], "\n".join(f.format() for f in constraint)


def test_mesh_owners_not_baselined_for_device_rule():
    """parallel/mesh.py and state.py are rule-EXEMPT (they own the device
    list); the baseline must not accumulate entries for them."""
    baseline = load_baseline(os.path.join(REPO, ".accelerate-lint-baseline.json"))
    offenders = {
        p for (p, rule, _) in baseline
        if rule == "raw-device-baseline" and p in ("parallel/mesh.py", "state.py")
    }
    assert offenders == set()
