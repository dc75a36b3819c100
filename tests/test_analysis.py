"""Static-analysis subsystem gate (analysis/): program auditor + invariant linter.

Two layers, both run in tier-1 (marker ``analysis``):

- the **program auditor** must (a) pass the shipped builders clean on the
  tiny config — zero dp-axis all-gathers, zero host callbacks, zero donation
  misses — and (b) FIRE on seeded violations of each detector, so a future
  PR that reintroduces a program-level regression is caught by construction,
  not by luck;
- the **invariant linter** must hold the shipped tree at zero unbaselined
  findings (with serving.py and utils/operations.py fully clean, not
  baselined), and each rule must fire on a minimal violating source.
"""

import json
import os
import subprocess
import sys
from functools import partial

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import Accelerator
from accelerate_tpu.analysis import (
    audit_built,
    audit_lowered,
    lint_paths,
    load_baseline,
    write_baseline,
)
from accelerate_tpu.analysis.lint import lint_source
from accelerate_tpu.models import Llama, LlamaConfig
from accelerate_tpu.state import AcceleratorState, GradientState

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "accelerate_tpu")


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


# ==================================================================== auditor
def test_train_step_audits_clean():
    acc, pm, po = _build()
    step = acc.build_train_step(pm, po)
    report = acc.audit(step, _batch())
    assert report.builder == "build_train_step"
    assert report.dp_allgathers == []
    assert report.host_callbacks == []
    assert report.donation_misses == []
    assert report.clean
    # Inventory sanity on the dp8 mesh: the gradient sync is there.
    assert report.collective_counts("dp")["all-reduce"] > 0
    assert report.mesh_axes.get("dp") == 8


def test_train_window_audits_clean():
    """The acceptance property: Accelerator.audit(build_train_window(...)) on
    the tiny config reports zero dp-axis all-gathers, zero host callbacks,
    and zero donation misses."""
    acc, pm, po = _build()
    win = acc.build_train_window(pm, po, window=2)
    wb = {k: np.stack([v, v]) for k, v in _batch().items()}
    report = acc.audit(win, wb)
    assert report.builder == "build_train_window"
    assert len(report.dp_allgathers) == 0
    assert len(report.host_callbacks) == 0
    assert len(report.donation_misses) == 0
    assert report.clean
    # summary_dict is the bench.py detail.audit schema.
    summary = report.summary_dict()
    assert summary["clean"] is True
    assert set(summary) >= {
        "clean", "dp_allgathers", "host_callbacks", "donation_misses",
        "donation_dropped_by_policy", "collectives_by_axis", "dtype_upcasts",
    }


def test_audit_detects_host_callback():
    @jax.jit
    def with_cb(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a) * 2, jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )
        return y + 1

    report = audit_built(with_cb, jnp.ones((4,)))
    assert report.host_callbacks, report.to_dict()
    assert not report.clean


def test_audit_detects_dp_allgather():
    """A program that re-materializes dp-sharded data replicated emits an
    all-gather whose replica groups vary along dp — the flagged violation."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    acc, pm, po = _build()
    mesh = acc.mesh

    @jax.jit
    def gathers(x):
        return jax.lax.with_sharding_constraint(x * 2, NamedSharding(mesh, P()))

    x = jax.device_put(jnp.ones((16, 8)), NamedSharding(mesh, P("dp")))
    report = audit_built(gathers, x, mesh=mesh)
    assert len(report.dp_allgathers) == 1, report.collective_counts()
    assert "dp" in report.dp_allgathers[0].axes
    assert not report.clean


def test_audit_detects_unaliased_donation():
    """A donated-but-unaliasable buffer (scalar output, partitioned regime)
    must surface as a sized 'unaliased' miss."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    acc, _, _ = _build()
    mesh = acc.mesh

    @partial(jax.jit, donate_argnums=(0,))
    def wasted(a, b):
        return jnp.sum(a) + jnp.sum(b)

    a = jax.device_put(jnp.ones((32, 32)), NamedSharding(mesh, P("dp")))
    report = audit_built(wasted, a, jnp.ones((4,)), mesh=mesh)
    assert len(report.donation_misses) == 1, report.to_dict()["donation"]
    miss = report.donation_misses[0]
    assert miss.reason == "unaliased"
    assert miss.nbytes == 32 * 32 * 4
    assert not report.clean


def test_undonated_train_step_variant_reports_misses():
    """The donation regression drill: the SAME step math jitted WITHOUT
    donation, audited against the builder's donation contract, must produce a
    non-empty donation_misses — while the shipped builder audits clean
    (test_train_step_audits_clean)."""
    acc, pm, po = _build()
    step = acc.build_train_step(pm, po)  # initializes opt state + accum buffer
    step_body = acc._fused_step_body(pm, po, accum=1)
    handle = pm.handle
    args = (
        handle.params, po.opt_state, po._accum_grads, jnp.int32(0),
        acc._place_batch(_batch()), handle.rng, jnp.float32(0.0),
    )
    lowered = jax.jit(step_body).lower(*args)  # deliberately un-donated
    report = audit_lowered(
        lowered, mesh=acc.mesh, expected_donations=(0, 1, 2, 3),
        builder="undonated_variant",
    )
    assert report.donation_misses, "un-donated variant must miss its contract"
    assert all(m.reason == "never-marked" for m in report.donation_misses)
    assert not report.clean


def test_partial_donation_regression_reports_under_marked():
    """A PARTIAL donation drop — params still donated, opt_state/accum/count
    dropped from donate_argnums — must NOT audit clean: donor marks exist, so
    the all-or-nothing 'never-marked' check stays quiet, and the builder's
    expected-donated-leaves count is what catches it."""
    acc, pm, po = _build()
    step = acc.build_train_step(pm, po)
    expected_leaves = step._audit_meta["expected_donated_leaves"]
    assert expected_leaves > 1
    step_body = acc._fused_step_body(pm, po, accum=1)
    handle = pm.handle
    args = (
        handle.params, po.opt_state, po._accum_grads, jnp.int32(0),
        acc._place_batch(_batch()), handle.rng, jnp.float32(0.0),
    )
    lowered = jax.jit(step_body, donate_argnums=(0,)).lower(*args)  # params only
    report = audit_lowered(
        lowered, mesh=acc.mesh,
        expected_donations=(0, 1, 2, 3),
        expected_donated_leaves=expected_leaves,
        builder="partially_donated_variant",
    )
    assert report.donation_misses, report.to_dict()["donation"]
    assert report.donation_misses[0].reason == "under-marked"
    assert not report.clean


def test_audit_detects_dtype_upcast():
    lowered = jax.jit(lambda a, b: jnp.dot(a, b)).lower(
        jnp.ones((8, 8)), jnp.ones((8, 8))
    )
    report = audit_lowered(lowered, compute_dtype="bfloat16")
    assert len(report.dtype_upcasts) == 1, report.dot_dtypes
    # The same program audited at fp32 compute dtype is not an upcast.
    report32 = audit_lowered(lowered, compute_dtype="float32")
    assert report32.dtype_upcasts == []


def test_audit_attributes_collectives_to_axes():
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    from accelerate_tpu import ParallelismConfig

    acc = Accelerator(parallelism_config=ParallelismConfig(fsdp_size=8))
    cfg = LlamaConfig.tiny(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_attention_heads=2, num_key_value_heads=2, num_hidden_layers=2,
    )
    model = Llama(cfg)
    model.init_params(jax.random.key(0))
    pm, po = acc.prepare(model, optax.sgd(0.1))
    report = acc.audit(acc.build_train_step(pm, po), _batch())
    counts = report.collective_counts()
    assert counts["all-gather"] > 0
    # Every gather varies along fsdp; none along dp (the flagged axis).
    assert report.collective_counts("fsdp")["all-gather"] == counts["all-gather"]
    assert report.dp_allgathers == []
    by_axis = report.collectives_by_axis()
    assert "fsdp" in by_axis and "dp" not in by_axis


def test_paged_serving_decode_audits_clean_with_pool_memory():
    """The PAGED decode window audits clean too (no host callbacks, no
    unclaimed dp collectives), its pool+state donation contract is visible,
    and its _audit_meta memory join attributes the persistent KV pool —
    the class `accelerate-tpu memcheck --serving` gates on."""
    from accelerate_tpu.serving import ContinuousBatcher

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    cfg = LlamaConfig.tiny(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_key_value_heads=2, num_hidden_layers=1,
    )
    model = Llama(cfg)
    model.init_params(jax.random.key(0))
    engine = ContinuousBatcher(
        model, batch_slots=2, max_new_tokens=4, max_cache_len=64,
        bucket_sizes=(8,), sync_every=2, block_size=4,
    )
    report = engine.audit_decode()
    assert report.builder == "serving_decode_paged"
    assert report.host_callbacks == []
    assert report.dp_allgathers == []
    assert report.memory is not None
    pool_bytes = report.memory.classes["kv_pool"].per_device_bytes
    assert pool_bytes == engine.kv_cache_bytes + engine._pool["mask"].nbytes


def test_bench_audit_failure_line_is_schemad(capsys):
    """bench.py fails a config's JSON line — schema'd, with the audit
    evidence attached — when the audited program has a dp-axis all-gather."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)
    exc = bench.BenchAuditFailure(
        "program audit: 2 all-gather(s) on the dp mesh axis",
        {"clean": False, "dp_allgathers": 2, "host_callbacks": 0,
         "donation_misses": 0},
    )
    bench._print_failure("tiny", exc)
    line = json.loads(capsys.readouterr().out.strip())
    assert line["schema_version"] == bench.BENCH_SCHEMA_VERSION
    assert line["value"] is None and line["failed"] is True
    assert line["detail"]["audit"]["dp_allgathers"] == 2
    assert "dp mesh axis" in line["detail"]["error"]


# ===================================================================== linter
def test_lint_shipped_tree_is_clean():
    """The tier-1 gate: zero findings on the shipped tree that are neither
    inline-suppressed nor baselined — reintroducing an uncounted host sync or
    an un-shimmed shard_map import fails CI here."""
    baseline = load_baseline(os.path.join(REPO, ".accelerate-lint-baseline.json"))
    findings = lint_paths([PACKAGE], baseline=baseline)
    live = [f for f in findings if not f.suppressed and not f.baselined]
    assert live == [], "\n".join(f.format() for f in live)


def test_lint_satellite_files_clean_without_baseline():
    """serving.py and utils/operations.py — the two oldest uncounted-transfer
    surfaces — are FIXED, not grandfathered: clean with no baseline at all."""
    for rel in ("serving.py", "utils/operations.py"):
        findings = lint_paths([os.path.join(PACKAGE, rel)])
        live = [f for f in findings if not f.suppressed]
        assert live == [], "\n".join(f.format() for f in live)


@pytest.mark.parametrize(
    "rule,relpath,source",
    [
        ("uncounted-device-get", "anywhere.py",
         "import jax\nx = jax.device_get(y)\n"),
        ("uncounted-item", "anywhere.py", "v = loss_array.item()\n"),
        ("uncounted-float-loss", "anywhere.py", "v = float(loss)\n"),
        ("uncounted-asarray", "serving.py",
         "import numpy as np\nv = np.asarray(device_thing)\n"),
        ("uncounted-asarray", "telemetry/foo.py",
         "import numpy as np\nv = np.array(device_thing)\n"),
        ("raw-shard-map", "anywhere.py",
         "from jax.experimental.shard_map import shard_map\n"),
        ("raw-shard-map", "anywhere.py",
         "import jax\nf = jax.shard_map(g, mesh=m, in_specs=i, out_specs=o)\n"),
        ("raw-donation", "anywhere.py",
         "import jax\nf = jax.jit(g, donate_argnums=(0, 1))\n"),
        ("traced-host-impurity", "anywhere.py",
         "import jax, time\n@jax.jit\ndef f(x):\n    return x + time.time()\n"),
        ("uncounted-block-until-ready", "anywhere.py",
         "x.block_until_ready()\n"),
        # jax.devices()/local_devices() as a baseline outside the mesh owners
        # — the elastic-runner bug class (PR 6 review).
        ("raw-device-baseline", "anywhere.py",
         "import jax\nworld = len(jax.devices())\n"),
        ("raw-device-baseline", "telemetry/foo.py",
         "import jax\ndev = jax.local_devices()[0]\n"),
        # Fully-unspecified constraint replicates the intermediate.
        ("replicated-constraint", "ops/foo.py",
         "import jax\ny = jax.lax.with_sharding_constraint(x, P())\n"),
        ("replicated-constraint", "accelerator.py",
         "y = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))\n"),
        ("replicated-constraint", "models/foo.py",
         "y = jax.lax.with_sharding_constraint(x, replicated(mesh))\n"),
        # Collective under a rank-dependent branch — the deadlock hazard.
        ("rank-divergent-collective", "anywhere.py",
         "if state.process_index == 0:\n    accelerator.wait_for_everyone()\n"),
        ("rank-divergent-collective", "anywhere.py",
         "import jax\nif jax.process_index() == 0:\n    out = gather(metrics)\n"),
        # The derived main-process properties are process_index-dependent too.
        ("rank-divergent-collective", "anywhere.py",
         "if accelerator.is_main_process:\n    blob = kv_all_gather(v, n, r, ns)\n"),
        # The ELSE arm runs on the complementary ranks — equally divergent.
        ("rank-divergent-collective", "anywhere.py",
         "if local_process_index != 0:\n    pass\nelse:\n    broadcast_one_to_all(x)\n"),
        # Guard-return spelling: the rest of the function runs on the
        # complementary ranks only — the classic deadlock shape.
        ("rank-divergent-collective", "anywhere.py",
         "def save(acc, metrics):\n"
         "    if not acc.is_main_process:\n        return\n"
         "    out = gather(metrics)\n"),
        ("rank-divergent-collective", "anywhere.py",
         "def f(state):\n"
         "    if state.process_index != 0:\n        raise RuntimeError\n"
         "    state.wait_for_everyone()\n"),
        # Guard-return nested under try/finally (the real save/export shape).
        ("rank-divergent-collective", "anywhere.py",
         "def f(acc, x):\n"
         "    try:\n"
         "        if not acc.is_main_process:\n            return\n"
         "        out = gather(x)\n"
         "    finally:\n        pass\n"),
        # Existing rules must keep firing inside default-argument expressions
        # (the _visit_block function-body rewrite must not skip node.args).
        ("raw-device-baseline", "anywhere.py",
         "import jax\ndef f(n=len(jax.devices())):\n    return n\n"),
    ],
)
def test_lint_rule_fires(rule, relpath, source):
    findings = [f for f in lint_source(source, relpath) if not f.suppressed]
    assert any(f.rule == rule for f in findings), findings


@pytest.mark.parametrize(
    "rule,relpath,source",
    [
        # dtype-carrying asarray is host canonicalization, not a readback.
        ("uncounted-asarray", "serving.py",
         "import numpy as np\nv = np.asarray(ids, np.int32)\n"),
        # Out-of-scope module: the asarray rule is hot-path scoped.
        ("uncounted-asarray", "utils/offload.py",
         "import numpy as np\nv = np.asarray(w)\n"),
        # The gated donation spelling — inline or via a named intermediate.
        ("raw-donation", "anywhere.py",
         "f = jax.jit(g, donate_argnums=safe_donate_argnums((0,)))\n"),
        ("raw-donation", "anywhere.py",
         "donate = safe_donate_argnums((0,))\nf = jax.jit(g, donate_argnums=donate)\n"),
        # time.time outside any traced body is fine.
        ("traced-host-impurity", "anywhere.py",
         "import time\ndef f():\n    return time.time()\n"),
        # The shim home is exempt.
        ("raw-shard-map", "utils/jax_compat.py",
         "from jax.experimental.shard_map import shard_map\n"),
        # The mesh owners legitimately enumerate devices.
        ("raw-device-baseline", "parallel/mesh.py",
         "import jax\ndevices = jax.devices()\n"),
        ("raw-device-baseline", "state.py",
         "import jax\nself.device = jax.local_devices()[0]\n"),
        # A named-axis constraint is the intended spelling.
        ("replicated-constraint", "ops/foo.py",
         "y = jax.lax.with_sharding_constraint(x, P('dp'))\n"),
        ("replicated-constraint", "accelerator.py",
         "y = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P('fsdp', None)))\n"),
        # Out of the hot-path scope; and the sharding-helper home is exempt.
        ("replicated-constraint", "utils/offload.py",
         "y = jax.lax.with_sharding_constraint(x, P())\n"),
        ("replicated-constraint", "parallel/sharding.py",
         "y = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))\n"),
        # Collective on EVERY rank, branch on the result — the safe spelling.
        ("rank-divergent-collective", "anywhere.py",
         "flags = kv_or_exchange(local, n, rank, ns)\n"
         "if state.process_index == 0:\n    log(flags)\n"),
        # Host-local work under a rank branch is fine (no collective).
        ("rank-divergent-collective", "anywhere.py",
         "if state.is_main_process:\n    buf[:] = payload\n"),
        # functools.reduce shares the terminal name, not the semantics.
        ("rank-divergent-collective", "anywhere.py",
         "import functools\nif process_index == 0:\n"
         "    total = functools.reduce(f, xs)\n"),
        # A branch on something else entirely stays out of scope.
        ("rank-divergent-collective", "anywhere.py",
         "if step % 10 == 0:\n    accelerator.wait_for_everyone()\n"),
        # A rank guard followed by host-local work only is fine.
        ("rank-divergent-collective", "anywhere.py",
         "def save(acc, blob, path):\n"
         "    if not acc.is_main_process:\n        return\n"
         "    write(path, blob)\n"),
        # A NON-exiting rank branch does not poison the rest of the block.
        ("rank-divergent-collective", "anywhere.py",
         "def f(acc):\n"
         "    if acc.is_main_process:\n        log('hi')\n"
         "    acc.wait_for_everyone()\n"),
    ],
)
def test_lint_rule_stays_quiet(rule, relpath, source):
    findings = [f for f in lint_source(source, relpath) if not f.suppressed]
    assert not any(f.rule == rule for f in findings), findings


def test_lint_traced_body_via_wrapper_reference():
    """A function handed to lax.scan is traced even without a @jit decorator."""
    src = (
        "import jax, time\n"
        "def body(carry, x):\n"
        "    return carry + time.time(), x\n"
        "out = jax.lax.scan(body, 0.0, xs)\n"
    )
    findings = lint_source(src, "anywhere.py")
    assert any(f.rule == "traced-host-impurity" for f in findings)


def test_lint_inline_suppression():
    src = "import jax\nx = jax.device_get(y)  # accelerate-lint: disable=uncounted-device-get\n"
    findings = lint_source(src, "anywhere.py")
    assert len(findings) == 1 and findings[0].suppressed
    # The wrong rule name does NOT suppress.
    src2 = "import jax\nx = jax.device_get(y)  # accelerate-lint: disable=uncounted-item\n"
    findings2 = lint_source(src2, "anywhere.py")
    assert len(findings2) == 1 and not findings2[0].suppressed


def test_lint_baseline_roundtrip(tmp_path):
    bad = tmp_path / "victim.py"
    bad.write_text("import jax\nx = jax.device_get(y)\n")
    findings = lint_paths([str(bad)])
    assert len([f for f in findings if not f.suppressed]) == 1
    baseline_file = tmp_path / "baseline.json"
    write_baseline(str(baseline_file), findings)
    baseline = load_baseline(str(baseline_file))
    again = lint_paths([str(bad)], baseline=baseline)
    assert all(f.baselined for f in again if not f.suppressed)
    # A NEW violation in the same file is not covered by the old baseline.
    bad.write_text("import jax\nx = jax.device_get(y)\nz = jax.device_get(w)\n")
    third = lint_paths([str(bad)], baseline=baseline)
    live = [f for f in third if not f.suppressed and not f.baselined]
    assert len(live) == 1 and "device_get(w)" in live[0].code


def test_lint_cli_gate(tmp_path):
    """`accelerate-tpu lint` exits 1 on a violation, 0 once baselined —
    the exact contract the verify recipe and CI hook rely on."""
    bad = tmp_path / "victim.py"
    bad.write_text("import jax\nx = jax.device_get(y)\n")
    env = {**os.environ, "PYTHONPATH": REPO}
    cmd = [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "lint",
           str(bad), "--baseline", str(tmp_path / "b.json")]
    first = subprocess.run(cmd, capture_output=True, text=True, env=env)
    assert first.returncode == 1, first.stdout + first.stderr
    assert "uncounted-device-get" in first.stdout
    wrote = subprocess.run(cmd + ["--write-baseline"], capture_output=True,
                           text=True, env=env)
    assert wrote.returncode == 0, wrote.stdout + wrote.stderr
    second = subprocess.run(cmd, capture_output=True, text=True, env=env)
    assert second.returncode == 0, second.stdout + second.stderr
    machine = subprocess.run(cmd + ["--json"], capture_output=True, text=True, env=env)
    payload = json.loads(machine.stdout)
    assert payload["findings"] == [] and payload["baselined"] == 1


def test_shipped_baseline_has_no_satellite_entries():
    """The checked-in baseline may grandfather host-side surfaces, but never
    the two satellite-cleaned files."""
    baseline = load_baseline(os.path.join(REPO, ".accelerate-lint-baseline.json"))
    offenders = {p for (p, _, _) in baseline}
    assert "serving.py" not in offenders
    assert "utils/operations.py" not in offenders

def test_parse_donors_survives_quoted_sharding_attrs():
    """Single-device lowerings spell donation as ``tf.aliasing_output`` AFTER
    an ``mhlo.sharding`` attr whose value is a QUOTED string containing
    braces. A naive ``{[^}]*}`` attr match stops at the quoted ``}`` and
    drops every aliasing mark behind it — the regression that made all the
    shipped builders read as 'under-marked' (1/N donated leaves, clean=False)
    on 1-device backends (the PR 9 known-issue, now fixed by a
    brace/quote-aware match)."""
    from accelerate_tpu.analysis.audit import _parse_donors

    text = (
        'func.func public @main('
        '%arg0: tensor<128x64xf32> {mhlo.sharding = "{replicated}", '
        'tf.aliasing_output = 0 : i32}, '
        '%arg1: tensor<64xf32> {mhlo.sharding = "{replicated}", '
        'tf.aliasing_output = 1 : i32}, '
        '%arg2: tensor<4xf32> {jax.buffer_donor = true, '
        'mhlo.sharding = "{replicated}"}, '
        '%arg3: tensor<8xf32>) -> (tensor<128x64xf32> {mhlo.sharding = "{replicated}"}) {'
    )
    donors, prealiased, sizes = _parse_donors(text)
    assert prealiased == {0, 1}
    assert donors == {2}
    assert sizes[0][1] == 128 * 64 * 4
