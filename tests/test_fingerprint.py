"""Program-contract fingerprint gate (analysis/fingerprint.py + the
`accelerate-tpu fingerprint` CLI).

Four layers, all tier-1 (marker ``fingerprint``):

- **dtype-flow pass**: accumulation-precision census + low-precision flags
  on synthetic StableHLO text;
- **drift classification**: each seeded regression class (dp all-gather,
  dropped donation, grown replicated bytes, vanished ZeRO traffic, new
  low-precision accumulation) classifies as a violation, the reverse
  directions as improvements, undirected census movement as benign-shape;
- **real drift drills**: the tiny builder re-lowered with seeded
  regressions — a ``P()``-replicating loss (dp all-gather), an un-donated
  step-body variant (donation misses), a bf16-accumulating loss (dtype-flow
  flag) — each produces a classified violation against the committed golden,
  and the CLI check path exits 1 on it;
- **golden stability**: the in-process extraction is byte-identical to the
  committed golden (written by a different process, under the opposite
  donation-policy regime — the policy-independence contract).
"""

import copy
import json
import os
import subprocess
import sys
from argparse import Namespace

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from accelerate_tpu import Accelerator
from accelerate_tpu.analysis.fingerprint import (
    BENIGN,
    IMPROVEMENT,
    VIOLATION,
    canonical_json,
    classify_drift,
    drift_verdict,
    dtype_flow,
    fingerprint_from_audit,
    fingerprint_hash,
    load_golden,
    write_golden,
)
from accelerate_tpu.analysis.audit import audit_lowered
from accelerate_tpu.commands.fingerprint import (
    CONFIG_NAMES,
    extract_config,
    fingerprint_command,
    run_fingerprints,
)
from accelerate_tpu.models import Llama, LlamaConfig
from accelerate_tpu.state import AcceleratorState, GradientState

pytestmark = pytest.mark.fingerprint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDENS = os.path.join(REPO, "tests", "goldens")


def _build(**kwargs):
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(**kwargs)
    cfg = LlamaConfig.tiny(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_attention_heads=2, num_key_value_heads=2, num_hidden_layers=2,
    )
    model = Llama(cfg)
    model.init_params(jax.random.key(0))
    pmodel, popt = acc.prepare(model, optax.sgd(0.1))
    return acc, pmodel, popt


def _batch(batch=8, seq=16):
    ids = np.random.default_rng(0).integers(0, 128, (batch, seq)).astype(np.int32)
    return {"input_ids": ids, "labels": ids}


def _golden(config="step") -> dict:
    doc = load_golden(GOLDENS, config)
    assert doc is not None, f"committed golden missing for {config!r}"
    return doc


# ================================================================= dtype flow
def test_dtype_flow_census_and_scalar_flag():
    text = (
        "%2 = stablehlo.dot_general %0, %1, contracting_dims = [1] x [0] : "
        "(tensor<8x16xbf16>, tensor<16x4xbf16>) -> tensor<8x4xf32>\n"
        "%5 = stablehlo.reduce(%4 init: %cst) applies stablehlo.add across "
        "dimensions = [0, 1] : (tensor<8x4xbf16>, tensor<bf16>) -> tensor<bf16>\n"
        "%6 = stablehlo.reduce(%4 init: %cst) applies stablehlo.maximum across "
        "dimensions = [0, 1] : (tensor<8x4xbf16>, tensor<bf16>) -> tensor<bf16>\n"
        "%7 = stablehlo.reduce(%3 init: %cst) applies stablehlo.add across "
        "dimensions = [0, 1] : (tensor<8x4xf32>, tensor<f32>) -> tensor<f32>\n"
    )
    flow = dtype_flow(text, compute_dtype="bfloat16")
    assert flow["dots"] == {"bf16xbf16->f32": 1}
    assert flow["reduces"]["add:bf16->bf16"] == 1
    assert flow["reduces"]["add:f32->f32"] == 1
    # The scalar bf16 add-reduce (loss/grad-norm shape) flags even under
    # bf16 compute; the bf16 max never flags (order statistics are safe).
    assert len(flow["flags"]) == 1
    assert "scalar reduce-add in bf16" in flow["flags"][0]


def test_dtype_flow_flags_downgrade_under_higher_compute():
    text = (
        "%5 = stablehlo.reduce(%4 init: %cst) applies stablehlo.add across "
        "dimensions = [0] : (tensor<8x4xbf16>, tensor<bf16>) -> tensor<4xbf16>\n"
    )
    # Non-scalar bf16 accumulation: flagged only under a HIGHER compute dtype.
    assert dtype_flow(text, compute_dtype="float32")["flags"]
    assert dtype_flow(text, compute_dtype="bfloat16")["flags"] == []
    assert dtype_flow(text, compute_dtype=None)["flags"] == []


def test_dtype_flow_parses_real_lowering():
    low = jax.jit(
        lambda x: lax.reduce(
            x.astype(jnp.bfloat16), jnp.bfloat16(0), lax.add, (0,)
        ).astype(jnp.float32)
    ).lower(jnp.ones((8,)))
    flow = dtype_flow(low.as_text(), compute_dtype="float32")
    assert flow["reduces"].get("add:bf16->bf16") == 1
    assert flow["flags"], flow


# ===================================================== classification (units)
def test_classify_seeded_dp_allgather_is_violation():
    golden = _golden()
    current = copy.deepcopy(golden)
    current["collectives"].append(
        {"op": "all-gather", "axes": ["dp"], "shape": "f32[128,64]",
         "zero": False, "count": 1}
    )
    entries = classify_drift(golden, current)
    hits = [e for e in entries if e.field == "collectives.dp_allgathers"]
    assert hits and hits[0].kind == VIOLATION
    assert drift_verdict(entries) == VIOLATION
    # The reverse direction is an improvement (golden stale, check passes).
    back = classify_drift(current, golden)
    assert drift_verdict(back) == IMPROVEMENT


def test_classify_dropped_donation_is_violation():
    golden = _golden()
    current = copy.deepcopy(golden)
    current["donation"]["misses"]["never-marked"] = 4
    entries = classify_drift(golden, current)
    assert any(
        e.field == "donation.misses.never-marked" and e.kind == VIOLATION
        for e in entries
    )
    narrowed = copy.deepcopy(golden)
    narrowed["donation"]["expected_argnums"] = [0]
    entries2 = classify_drift(golden, narrowed)
    assert any(
        e.field == "donation.expected_argnums" and e.kind == VIOLATION
        for e in entries2
    )


def test_classify_new_low_precision_accumulation_is_violation():
    golden = _golden()
    current = copy.deepcopy(golden)
    flag = "low-precision accumulation: scalar reduce-add in bf16 (loss/grad-norm shape)"
    current["dtype_flow"]["flags"] = [flag]
    entries = classify_drift(golden, current)
    assert any(e.field == "dtype_flow.flags" and e.kind == VIOLATION for e in entries)
    assert drift_verdict(classify_drift(current, golden)) == IMPROVEMENT


def test_classify_replicated_growth_is_violation():
    """The ZeRO-undo gate: opt-state bytes replicated on dp growing past the
    golden is a violation even though no collective changed."""
    golden = _golden("step_zero")
    current = copy.deepcopy(golden)
    current["memory"]["opt_state"]["by_axis"]["dp"]["replicated"] += 98304
    entries = classify_drift(golden, current)
    assert any(
        e.field == "memory.opt_state.replicated.dp" and e.kind == VIOLATION
        for e in entries
    )


def test_classify_shape_swap_at_equal_count_is_not_a_match():
    """A dp all-gather swapping shape at unchanged total count is a DIFFERENT
    program: it must surface (benign-shape — no gated direction) rather than
    classify as exact agreement against a now-stale golden."""
    golden = _golden()
    current = copy.deepcopy(golden)
    current["collectives"] = copy.deepcopy(golden["collectives"])
    site = current["collectives"][0]
    site["shape"] = site["shape"].replace("[", "[7,", 1)
    entries = classify_drift(golden, current)
    assert entries and drift_verdict(entries) == BENIGN
    assert any(e.field == "collectives" for e in entries)


def test_classify_vanished_memory_class_is_violation():
    """Attribution LOSS must not read as the savings it numerically mimics:
    a broken memory_classes thunk dropping opt_state would otherwise book
    'replicated bytes shrank to 0' as an improvement and disarm the gate."""
    golden = _golden()
    current = copy.deepcopy(golden)
    del current["memory"]["opt_state"]
    entries = classify_drift(golden, current)
    assert any(e.field == "memory.opt_state" and e.kind == VIOLATION for e in entries)
    assert drift_verdict(entries) == VIOLATION


def test_fingerprint_hash_excludes_config_label():
    """The hash is PROGRAM identity: a golden named 'step' and a bench row
    stamped 'bench_tiny' over the byte-identical program must join."""
    doc = _golden()
    relabeled = copy.deepcopy(doc)
    relabeled["config"] = "bench_whatever"
    assert fingerprint_hash(doc) == fingerprint_hash(relabeled)
    # But canonical_json (the golden serialization) keeps the label.
    assert canonical_json(doc) != canonical_json(relabeled)


def test_classify_vanished_zero_traffic_is_violation():
    golden = _golden("step_zero")
    assert golden["zero"]["declared"] and golden["zero"]["collectives"]
    current = copy.deepcopy(golden)
    current["zero"]["collectives"] = {}
    entries = classify_drift(golden, current)
    assert any(e.field == "zero.collectives" and e.kind == VIOLATION for e in entries)


def test_classify_benign_shape_changes_pass():
    golden = _golden()
    current = copy.deepcopy(golden)
    current["dtype_flow"]["reduces"] = dict(current["dtype_flow"]["reduces"])
    current["dtype_flow"]["reduces"]["add:f32->f32"] += 5
    current["donation"]["expected_leaves"] += 2
    entries = classify_drift(golden, current)
    assert entries and all(e.kind == BENIGN for e in entries)
    assert drift_verdict(entries) == BENIGN
    assert drift_verdict([]) == "match"


def test_classify_identity_mismatch_short_circuits():
    golden = _golden()
    current = copy.deepcopy(golden)
    current["builder"] = "something_else"
    entries = classify_drift(golden, current)
    assert len(entries) == 1 and entries[0].kind == VIOLATION
    assert entries[0].field == "builder"


def test_canonical_json_stability_and_hash():
    doc = _golden()
    scrambled = json.loads(json.dumps(doc))  # fresh dicts, parser key order
    assert canonical_json(doc) == canonical_json(scrambled)
    digest = fingerprint_hash(doc)
    assert len(digest) == 12 and int(digest, 16) >= 0
    # Any contract change moves the hash.
    changed = copy.deepcopy(doc)
    changed["donation"]["misses"]["unaliased"] = 1
    assert fingerprint_hash(changed) != digest


# ============================================================== real drills
def test_committed_golden_matches_inprocess_extraction_bytes():
    """The byte-stability + policy-independence acceptance property: the
    committed golden was written by a separate process with the compile
    cache scrubbed (donation live); this in-process extraction runs under
    the session cache (donation policy-waived on CPU). The canonical bytes
    must agree exactly."""
    fp = extract_config("step")
    assert canonical_json(fp) == open(
        os.path.join(GOLDENS, "fingerprint_step.json")
    ).read()
    assert classify_drift(_golden(), fp.to_dict()) == []


def test_drill_seeded_dp_allgather_classifies_violation():
    """A loss that pins a dp-sharded intermediate replicated re-lowers the
    SAME builder with a dp all-gather inside the step body — the fingerprint
    diff against the committed golden must carry the classified violation."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    acc, pm, po = _build()
    mesh = acc.mesh

    def gather_loss(outputs, batch):
        rep = jax.lax.with_sharding_constraint(
            outputs["logits"], NamedSharding(mesh, P())
        )
        return jnp.mean(rep)

    step = acc.build_train_step(pm, po, loss_fn=gather_loss)
    fp = acc.fingerprint(step, _batch(), config="step")
    entries = classify_drift(_golden(), fp.to_dict())
    assert drift_verdict(entries) == VIOLATION
    hits = [e for e in entries if e.field == "collectives.dp_allgathers"]
    assert hits and hits[0].kind == VIOLATION
    assert "dp" in hits[0].detail


def test_drill_dropped_donor_mark_classifies_violation():
    """The un-donated step-body variant (the donation regression) audited
    against the builder's contract fingerprints with never-marked misses —
    a classified violation against the committed golden."""
    acc, pm, po = _build()
    step = acc.build_train_step(pm, po)  # initializes opt state + accum
    meta = dict(step._audit_meta)
    step_body = acc._fused_step_body(pm, po, accum=1)
    handle = pm.handle
    args = (
        handle.params, po.opt_state, po._accum_grads, jnp.int32(0),
        acc._place_batch(_batch()), handle.rng, jnp.float32(0.0),
    )
    lowered = jax.jit(step_body).lower(*args)  # donation dropped
    report = audit_lowered(
        lowered, mesh=acc.mesh,
        expected_donations=meta["expected_donations"],
        expected_donated_leaves=meta["expected_donated_leaves"],
        compute_dtype=meta["compute_dtype"],
        builder="build_train_step",
    )
    fp = fingerprint_from_audit(report, lowered.as_text(), meta, config="step")
    entries = classify_drift(_golden(), fp.to_dict())
    assert drift_verdict(entries) == VIOLATION
    assert any(
        e.field == "donation.misses.never-marked" and e.kind == VIOLATION
        for e in entries
    )


def test_drill_bf16_loss_accumulation_classifies_violation():
    """A loss accumulating in bf16 under the f32 compute dtype re-lowers the
    builder with a flagged low-precision scalar reduction — the dtype-flow
    violation the numerics auditor exists for."""
    acc, pm, po = _build()

    def bf16_loss(outputs, batch):
        per_tok = jnp.sum(jax.nn.log_softmax(outputs["logits"]), axis=-1)
        lo = per_tok.astype(jnp.bfloat16)
        total = lax.reduce(lo, jnp.bfloat16(0), lax.add, tuple(range(lo.ndim)))
        return -total.astype(jnp.float32)

    step = acc.build_train_step(pm, po, loss_fn=bf16_loss)
    fp = acc.fingerprint(step, _batch(), config="step")
    assert fp.dtype_flow["flags"], fp.dtype_flow
    entries = classify_drift(_golden(), fp.to_dict())
    assert drift_verdict(entries) == VIOLATION
    assert any(
        e.field == "dtype_flow.flags" and e.kind == VIOLATION for e in entries
    )


# =============================================================== CLI contract
def _cli_args(**over):
    base = dict(
        check=True, update=False, configs="step", goldens_dir=GOLDENS,
        cpu_virtual_devices=8, keep_compile_cache=True, json=False,
        list_configs=False,
    )
    base.update(over)
    return Namespace(**base)


def test_cli_check_passes_on_shipped_tree(capsys):
    """`accelerate-tpu fingerprint --check` (subset) exits 0 against the
    committed goldens — the tier-1 wiring of the acceptance criterion."""
    fingerprint_command(_cli_args(json=True))
    doc = json.loads(capsys.readouterr().out)
    assert doc["verdict"] == "pass" and doc["failures"] == []
    assert doc["configs"]["step"]["verdict"] == "match"


def test_cli_check_exits_1_on_tampered_golden(tmp_path, capsys):
    """A golden pinning a BETTER past (smaller replicated opt-state, the
    banked ZeRO win) makes the clean tree read as replication growth — the
    check must exit 1 with the classified, evidence-carrying diff."""
    golden = _golden()
    tampered = copy.deepcopy(golden)
    tampered["memory"]["params"]["by_axis"]["dp"]["replicated"] = 0
    write_golden(str(tmp_path), tampered)
    with pytest.raises(SystemExit) as exc:
        fingerprint_command(_cli_args(goldens_dir=str(tmp_path), json=True))
    assert exc.value.code == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["verdict"] == "fail"
    res = doc["configs"]["step"]
    assert res["verdict"] == "violation"
    assert any(
        d["field"] == "memory.params.replicated.dp" and d["kind"] == "violation"
        for d in res["drift"]
    )


def test_cli_check_fails_on_missing_golden(tmp_path):
    results, failures = run_fingerprints(["decode_paged"], str(tmp_path), update=False)
    assert results["decode_paged"]["verdict"] == "missing-golden"
    assert failures and "--update" in failures[0]


def test_cli_update_roundtrips(tmp_path):
    results, failures = run_fingerprints(["decode_paged"], str(tmp_path), update=True)
    assert not failures and results["decode_paged"]["verdict"] == "updated"
    again, failures2 = run_fingerprints(["decode_paged"], str(tmp_path), update=False)
    assert not failures2 and again["decode_paged"]["verdict"] == "match"
    assert again["decode_paged"]["hash"] == results["decode_paged"]["hash"]


def test_goldens_committed_for_full_matrix():
    """Every matrix config ships a golden (the acceptance criterion's
    step/window × zero × plans × decode coverage), and each parses as
    canonical JSON (loading + re-serializing is byte-stable)."""
    for name in CONFIG_NAMES:
        path = os.path.join(GOLDENS, f"fingerprint_{name}.json")
        assert os.path.exists(path), f"golden missing for {name}"
        raw = open(path).read()
        assert canonical_json(json.loads(raw)) == raw, name
    # The matrix really spans the contract: a zero config, a window config,
    # a non-dp plan, and the serving decode program.
    assert _golden("step_zero")["zero"]["declared"] is True
    assert _golden("window4")["builder"] == "build_train_window"
    assert _golden("step_fsdp8")["mesh_axes"]["fsdp"] == 8
    # The decode window's golden pins the block-table gather program and the
    # pool+state donation contract.
    assert _golden("decode_paged")["builder"] == "serving_decode_paged"
    assert _golden("decode_paged")["donation"]["expected_argnums"] == [1, 6]
    # The int8-pool decode golden pins the dequant-in-DMA kernel inventory —
    # a silently vanished dequant kernel classifies as a violation, not a
    # quiet fallback to a full-precision gather.
    int8 = _golden("decode_paged_int8")
    assert int8["builder"] == "serving_decode_paged"
    assert int8["kernels"]["counts"]["paged_gather_dequant_kernel"] == 2
    # The spec-verify golden pins the draft scan + multi-token verify forward
    # and its pool/state donation contract (target pool, draft pool, state).
    spec = _golden("spec_verify")
    assert spec["builder"] == "serving_spec_verify"
    assert spec["donation"]["expected_argnums"] == [2, 3, 8]


@pytest.mark.slow
def test_full_matrix_check_and_cross_process_bytes(tmp_path):
    """The full acceptance command in a fresh process, twice: exit 0 against
    the committed goldens, and --update into a scratch dir from a second
    fresh process writes byte-identical goldens (cross-process determinism
    of the serialization, including the live-donation regime)."""
    env = {**os.environ, "PYTHONPATH": REPO}
    cmd = [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
           "fingerprint"]
    check = subprocess.run(cmd + ["--check"], capture_output=True, text=True,
                           env=env, timeout=900)
    assert check.returncode == 0, check.stdout + check.stderr
    update = subprocess.run(
        cmd + ["--update", "--goldens-dir", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert update.returncode == 0, update.stdout + update.stderr
    for name in CONFIG_NAMES:
        fresh = open(tmp_path / f"fingerprint_{name}.json").read()
        committed = open(os.path.join(GOLDENS, f"fingerprint_{name}.json")).read()
        assert fresh == committed, f"{name} bytes drifted across processes"
