"""Pallas kernel layer tests (ISSUE 14 acceptance).

The three hot-op kernels (ops/pallas/) behind the registry (ops/registry.py):

- **paged decode / paged gather**: interpret-mode BIT-exact vs the committed
  reference seams (``paged_attention_reference`` / ``gather_block_view``) for
  every active slot — ragged chains, trash-block table tails, GQA, sliding
  windows + softcap, multi-token chunks — and padded slots are skipped
  (zeros), never computed.
- **fused optimizer update**: the closure-introspected plan recovers optax's
  exact hyperparameters for adam/adamw/sgd(+momentum) and falls back (None)
  on anything else; the kernel's one-pass chain is float-equivalent to the
  optax reference across modules (two different XLA programs — fusion/FMA
  contraction rounds elementwise chains differently, the documented PR 10
  zero-on/off precedent) and BIT-exact on the axis the contract lives on:
  ``build_train_window`` with ZeRO + the kernel engaged vs K sequential
  fused steps with the same kernel (params/opt-state/losses).
- **int8 matmul**: BIT-exact vs ``ops/int8.py``'s reference lowering
  (integer contraction is exact in any tiling; the rescale mirrors the
  reference's association), gradients untouched (straight-through).
- **registry**: env tri-state (unset → reference; ``pallas`` degrades to
  interpret off-TPU; explicit off → reference), per-op maps, unknown-token
  validation, builder-meta recording.
- **engine**: paged serving under ``ACCELERATE_KERNELS=pallas`` is
  token-identical to the contiguous engine, with pallas_call eqns visible in
  the decode program's audit inventory.
- **analysis**: audit kernel inventory, fingerprint drift (a vanished named
  kernel classifies as violation), traceview per-kernel time attribution.

All on the suite's virtual 8-device CPU mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import optax

from accelerate_tpu.ops.paged_attention import (
    gather_block_view,
    gather_view,
    paged_attention,
    paged_attention_reference,
)
from accelerate_tpu.ops.pallas.fused_update import (
    fused_update_apply,
    plan_fused_update,
    reference_update_apply,
)
from accelerate_tpu.ops.pallas.paged_decode import (
    gather_block_view_kernel,
    paged_attention_kernel,
)
from accelerate_tpu.ops.registry import (
    dispatch,
    parse_kernel_spec,
    resolve_backend,
    resolved_backends,
)
from accelerate_tpu.state import AcceleratorState, GradientState

pytestmark = pytest.mark.kernels


def _bit_equal(a, b):
    return bool((np.asarray(a) == np.asarray(b)).all())


def _tree_bit_equal(a, b):
    return all(jtu.tree_leaves(jtu.tree_map(_bit_equal, a, b)))


# =========================================================== paged decode op
def _pool_case(seed=0, N=9, bs=4, Hkv=2, D=8, B=3, M=3, S=1, H=4):
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.normal(size=(N, bs, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, bs, Hkv, D)), jnp.float32)
    # ragged validity incl. holes; trash block 0 stays mask-zero
    mask = jnp.asarray(rng.integers(0, 2, (N, bs)), jnp.int32).at[0].set(0)
    # ragged chains: trailing entries point at the trash block (0)
    tables = jnp.asarray([[1, 3, 0], [2, 4, 6], [5, 0, 0]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, M * bs, (B, S)), jnp.int32)
    return q, kp, vp, tables, pos, mask


@pytest.mark.parametrize("case", ["plain", "no_mask", "windowed", "chunk"])
def test_paged_decode_kernel_bit_exact_vs_reference(case):
    """The op seam: the chain-walk kernel matches the committed reference
    (gather + cached_attention) bit-for-bit — GQA, ragged chains with
    trash-block tails, sliding window + softcap, multi-token chunks."""
    kw = {}
    q, kp, vp, tables, pos, mask = _pool_case(S=4 if case == "chunk" else 1)
    if case == "windowed":
        kw = dict(window=5, softcap=10.0)
    pool_mask = None if case == "no_mask" else mask
    # Both sides jitted — how the seam runs in every shipped program (bare
    # eager dispatch rounds transcendental-bearing chains per-op, which is a
    # third numerics regime none of the deployed paths use).
    ref = jax.jit(lambda *a: paged_attention_reference(
        *a, q_positions=pos, pool_mask=pool_mask, **kw))(q, kp, vp, tables)
    out = jax.jit(lambda *a: paged_attention_kernel(
        *a, q_positions=pos, pool_mask=pool_mask, interpret=True, **kw
    ))(q, kp, vp, tables)
    assert _bit_equal(ref, out)


def test_paged_decode_kernel_skips_padded_slots():
    """Bucket-padded slots (active == 0) skip both the DMA chain walk and the
    compute: active rows stay bit-identical to the reference, skipped rows
    come back as zeros (the reference computes masked garbage there)."""
    q, kp, vp, tables, pos, mask = _pool_case()
    active = jnp.asarray([1, 0, 1], jnp.int32)
    ref = paged_attention_reference(q, kp, vp, tables, q_positions=pos,
                                    pool_mask=mask)
    out = paged_attention_kernel(q, kp, vp, tables, q_positions=pos,
                                 pool_mask=mask, active=active, interpret=True)
    assert _bit_equal(np.asarray(out)[[0, 2]], np.asarray(ref)[[0, 2]])
    assert (np.asarray(out)[1] == 0).all()


def test_paged_gather_kernel_bit_exact_and_skips():
    """The chain-walk view assembly (the serving engine's per-window swap):
    bit-identical to the XLA gather for L-stacked and single-layer pools;
    inactive slots assemble zeros instead of walking their chains."""
    _, kp, vp, tables, _, _ = _pool_case()
    stacked = jnp.stack([kp, vp])  # (L, N, bs, Hkv, D)
    assert _bit_equal(gather_block_view(stacked, tables),
                      gather_block_view_kernel(stacked, tables, interpret=True))
    assert _bit_equal(gather_block_view(kp, tables),
                      gather_block_view_kernel(kp, tables, interpret=True))
    active = jnp.asarray([0, 1, 1], jnp.int32)
    out = gather_block_view_kernel(stacked, tables, active=active, interpret=True)
    ref = gather_block_view(stacked, tables)
    assert _bit_equal(np.asarray(out)[:, 1:], np.asarray(ref)[:, 1:])
    assert (np.asarray(out)[:, 0] == 0).all()


# ============================================================== int8 matmul
@pytest.mark.parametrize("shape,dtype", [
    ((2, 17, 33), jnp.float32),   # 3D activations, odd dims
    ((8, 16), jnp.bfloat16),      # bf16 operands
    ((300, 64), jnp.float32),     # crosses the 256-row/col tile boundary
])
def test_int8_kernel_bit_exact_vs_reference(shape, dtype):
    from accelerate_tpu.ops.int8 import _int8_matmul_fwd_value
    from accelerate_tpu.ops.pallas.int8_mm import int8_matmul_kernel

    rng = np.random.default_rng(7)
    K = shape[-1]
    N = 300 if shape[0] == 300 else 29
    x = jnp.asarray(rng.normal(size=shape), dtype)
    w = jnp.asarray(rng.normal(size=(K, N)), dtype)
    # Both sides jitted (the deployed regime; see the paged-decode note).
    assert _bit_equal(jax.jit(_int8_matmul_fwd_value)(x, w),
                      jax.jit(lambda x, w: int8_matmul_kernel(
                          x, w, interpret=True))(x, w))


def test_int8_backward_is_straight_through_either_backend(monkeypatch):
    """The custom-VJP backward is the full-precision straight-through
    estimator regardless of which backend lowered the forward."""
    from accelerate_tpu.ops.int8 import int8_matmul

    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(6, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(12, 5)), jnp.float32)
    loss = lambda x: jnp.sum(int8_matmul(x, w))  # noqa: E731
    monkeypatch.delenv("ACCELERATE_KERNELS", raising=False)
    g_ref = jax.grad(loss)(x)
    monkeypatch.setenv("ACCELERATE_KERNELS", "interpret")
    g_ker = jax.grad(loss)(x)
    assert _bit_equal(g_ref, g_ker)


# ============================================================= fused update
def test_fused_update_plan_introspection():
    """The closure walk recovers optax's exact hyperparameters for the
    supported families and declines everything else (the per-optimizer
    clean-fallback contract)."""
    plan = plan_fused_update(optax.adamw(3e-4, weight_decay=0.01))
    assert plan.kind == "adam" and plan.describe() == "adamw"
    assert plan.b1 == 0.9 and plan.b2 == 0.999 and plan.eps == 1e-8
    assert plan.weight_decay == 0.01 and plan.step_size == -3e-4
    plan = plan_fused_update(optax.adam(0.1))
    assert plan.describe() == "adam" and plan.weight_decay is None
    plan = plan_fused_update(optax.sgd(0.1))
    assert plan.kind == "sgd" and plan.step_size == -0.1
    plan = plan_fused_update(optax.sgd(0.1, momentum=0.9))
    assert plan.kind == "sgd_momentum" and plan.momentum == 0.9
    # Unsupported constructions fall back to the reference chain:
    assert plan_fused_update(
        optax.adamw(optax.linear_schedule(1e-3, 1e-4, 100))  # schedule
    ) is None
    assert plan_fused_update(optax.sgd(0.1, momentum=0.9, nesterov=True)) is None
    assert plan_fused_update(optax.adafactor(1e-3)) is None


@pytest.mark.parametrize("opt", ["adamw", "adam", "sgd", "sgdm"])
def test_fused_update_kernel_matches_reference(opt):
    """Per-op parity: the one-pass kernel vs the optax reference chain.
    Params/moments are float-equivalent across the two XLA modules (ulp-scale
    FMA-contraction differences — docs/kernels.md); structure, count
    increment, and the zeroed accumulation buffer are exact."""
    tx = {
        "adamw": lambda: optax.adamw(3e-4, weight_decay=0.01),
        "adam": lambda: optax.adam(0.1),
        "sgd": lambda: optax.sgd(0.1),
        "sgdm": lambda: optax.sgd(0.1, momentum=0.9),
    }[opt]()
    plan = plan_fused_update(tx)
    rng = np.random.default_rng(3)
    params = {"a": jnp.asarray(rng.normal(size=(7, 13)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(3,)), jnp.float32),
              "c": jnp.float32(0.5)}
    grads = jtu.tree_map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32), params
    )
    state = tx.init(params)
    for _ in range(2):  # advance so count > 0 paths engage
        u, state = jax.jit(tx.update)(grads, state, params)
        params = optax.apply_updates(params, u)
    factor = jnp.float32(0.7)
    ref = jax.jit(lambda p, s, g: reference_update_apply(
        p, s, g, tx=tx, clip_factor=factor))(params, state, grads)
    out = jax.jit(lambda p, s, g: fused_update_apply(
        p, s, g, plan=plan, clip_factor=factor, interpret=True
    ))(params, state, grads)
    assert jtu.tree_structure(ref) == jtu.tree_structure(out)
    for a, b in zip(jtu.tree_leaves(ref[0]), jtu.tree_leaves(out[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    for a, b in zip(jtu.tree_leaves(ref[1]), jtu.tree_leaves(out[1])):
        if np.asarray(a).dtype.kind == "i":  # count: exact
            assert _bit_equal(a, b)
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
    # The fused zero-reset is exact zeros with the reference's structure.
    assert all((np.asarray(z) == 0).all() for z in jtu.tree_leaves(out[2]))
    # zero_buffer=False (the imperative path's mode) skips the buffer write
    # entirely — params/state identical, the zero slot is None.
    out2 = jax.jit(lambda p, s, g: fused_update_apply(
        p, s, g, plan=plan, clip_factor=factor, interpret=True,
        zero_buffer=False,
    ))(params, state, grads)
    assert out2[2] is None
    for a, b in zip(jtu.tree_leaves(out[0]), jtu.tree_leaves(out2[0])):
        assert _bit_equal(a, b)


def test_fused_update_handles_zero_size_leaf():
    """An empty leaf (0-row optional head) must not crash the kernel lever —
    the reference path handles it, so the fused path must too."""
    tx = optax.adam(0.1)
    plan = plan_fused_update(tx)
    params = {"w": jnp.ones((4, 4), jnp.float32),
              "empty": jnp.zeros((0,), jnp.float32)}
    grads = jtu.tree_map(jnp.ones_like, params)
    state = tx.init(params)
    ref = reference_update_apply(params, state, grads, tx=tx,
                                 clip_factor=jnp.float32(1.0))
    out = fused_update_apply(params, state, grads, plan=plan,
                             clip_factor=jnp.float32(1.0), interpret=True)
    assert out[0]["empty"].shape == (0,)
    np.testing.assert_allclose(np.asarray(ref[0]["w"]),
                               np.asarray(out[0]["w"]), rtol=1e-6)


# =================================================== train-step integration
CFG = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
           num_attention_heads=2, num_key_value_heads=2, num_hidden_layers=2)


def _build(zero, kernels, accum=1):
    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import Llama, LlamaConfig

    AcceleratorState._reset_state()
    GradientState._reset_state()
    acc = Accelerator(gradient_accumulation_steps=accum)
    acc.zero_sharding = zero
    acc.kernels = kernels
    model = Llama(LlamaConfig.tiny(**CFG))
    model.init_params(jax.random.key(0))
    pmodel, popt = acc.prepare(model, optax.adamw(3e-4))
    return acc, pmodel, popt


def _train_batches(n=4, batch=8, seq=16):
    ids = np.random.default_rng(0).integers(0, 128, (n, batch, seq)).astype(np.int32)
    return ids


def test_windowed_zero_parity_bit_exact_with_fused_kernel():
    """THE acceptance drill: build_train_window(4) with ZeRO sharding AND the
    fused-update kernel engaged is BIT-exact vs 4 sequential fused steps with
    the same kernel — params, opt-state, and every per-step loss (the PR 5 /
    PR 10 window-parity idiom holds on the kernel-backed path)."""
    ids = _train_batches()
    acc, pm, po = _build(True, "interpret")
    step = acc.build_train_step(pm, po)
    assert po.zero_active  # dp8 + adamw: the plan engaged (builder realized it)
    losses_seq = [float(step({"input_ids": b, "labels": b})) for b in ids]
    params_seq = jax.device_get(pm.handle.params)
    opt_seq = jax.device_get(po.opt_state)

    acc2, pm2, po2 = _build(True, "interpret")
    win = acc2.build_train_window(pm2, po2, window=4)
    wl = win({"input_ids": ids, "labels": ids})
    losses_win = [float(x) for x in np.asarray(jax.device_get(wl))]
    assert losses_seq == losses_win
    assert _tree_bit_equal(params_seq, jax.device_get(pm2.handle.params))
    assert _tree_bit_equal(opt_seq, jax.device_get(po2.opt_state))


def test_step_kernel_on_vs_off_float_equivalent():
    """Kernel-on vs kernels-off are different XLA modules: identical losses
    to float tolerance and params within ulp-scale bounds (the PR 10
    zero-on/off precedent — strict bitwise equality is NOT promised on this
    axis; the bit-exactness contract lives on window-vs-sequential above)."""
    ids = _train_batches()
    acc, pm, po = _build(True, "interpret")
    step = acc.build_train_step(pm, po)
    losses_k = [float(step({"input_ids": b, "labels": b})) for b in ids]
    params_k = jax.device_get(pm.handle.params)

    acc2, pm2, po2 = _build(True, "")
    step2 = acc2.build_train_step(pm2, po2)
    losses_r = [float(step2({"input_ids": b, "labels": b})) for b in ids]
    np.testing.assert_allclose(losses_k, losses_r, rtol=1e-5)
    for a, b in zip(jtu.tree_leaves(params_k),
                    jtu.tree_leaves(jax.device_get(pm2.handle.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_builder_meta_records_kernel_backends():
    acc, pm, po = _build(False, "interpret")
    step = acc.build_train_step(pm, po)
    meta = step._audit_meta["kernels"]
    assert meta["spec"] == "interpret"
    assert meta["backends"]["fused_update"] == "interpret"
    assert meta["fused_update_plan"] == "adamw"
    # Unsupported optimizer: the meta records the fallback.
    AcceleratorState._reset_state()
    GradientState._reset_state()
    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import Llama, LlamaConfig

    acc2 = Accelerator()
    acc2.kernels = "interpret"
    model = Llama(LlamaConfig.tiny(**CFG))
    model.init_params(jax.random.key(0))
    pm2, po2 = acc2.prepare(model, optax.adafactor(3e-4))
    step2 = acc2.build_train_step(pm2, po2)
    assert step2._audit_meta["kernels"]["fused_update_plan"] is None


def test_imperative_optimizer_step_engages_kernel():
    """The imperative path (backward() + optimizer.step()) resolves the same
    registry spec: params move float-equivalently to the reference path and
    the compiled update program carries the named kernel."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.test_utils import RegressionDataset, RegressionModel

    from accelerate_tpu.test_utils import regression_batches

    def run(kernels):
        AcceleratorState._reset_state()
        GradientState._reset_state()
        acc = Accelerator()
        acc.kernels = kernels
        model = RegressionModel()
        model.init_params(jax.random.key(0))
        dl = regression_batches(RegressionDataset(length=16, seed=5),
                                batch_size=8)
        pmodel, popt, pdl = acc.prepare(model, optax.adam(0.05), dl)
        for batch in pdl:
            out = pmodel(**batch)
            acc.backward(out.loss)
            popt.step()
        return popt, jax.device_get(pmodel.handle.params)

    popt_k, params_k = run("interpret")
    assert popt_k.kernels == "interpret"
    popt_r, params_r = run("")
    for a, b in zip(jtu.tree_leaves(params_k), jtu.tree_leaves(params_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


# ================================================================== registry
def test_registry_env_tristate_and_per_op(monkeypatch):
    monkeypatch.delenv("ACCELERATE_KERNELS", raising=False)
    assert resolve_backend("paged_decode") == "reference"  # unset = reference
    monkeypatch.setenv("ACCELERATE_KERNELS", "pallas")
    # off-TPU the pallas token degrades to the interpreter (clean fallback).
    assert resolve_backend("paged_decode") == "interpret"
    monkeypatch.setenv("ACCELERATE_KERNELS", "off")
    assert resolve_backend("paged_decode") == "reference"
    monkeypatch.setenv("ACCELERATE_KERNELS", "pallas,int8_matmul=off")
    assert resolve_backend("paged_decode") == "interpret"
    assert resolve_backend("int8_matmul") == "reference"
    # call-site override beats env
    assert resolve_backend("int8_matmul", "interpret") == "interpret"
    backends = resolved_backends("interpret")
    assert set(backends) >= {"paged_decode", "paged_gather", "fused_update",
                             "int8_matmul"}
    assert set(backends.values()) == {"interpret"}


def test_registry_rejects_unknown_tokens_and_ops():
    with pytest.raises(ValueError, match="unknown kernel backend"):
        parse_kernel_spec("warp_speed")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        parse_kernel_spec("paged_decode=fast")
    # A misspelled OP name must die too — it would otherwise silently run
    # reference everywhere while the operator believes kernels are engaged.
    with pytest.raises(ValueError, match="unknown kernel op"):
        parse_kernel_spec("paged_decod=pallas")
    from accelerate_tpu import Accelerator

    AcceleratorState._reset_state()
    acc = Accelerator()
    with pytest.raises(ValueError, match="unknown kernel backend"):
        acc.kernels = "warp_speed"
    with pytest.raises(ValueError, match="unknown kernel op"):
        acc.kernels = "fused_updat=pallas"


def test_registry_dispatch_runs_reference_and_kernel():
    q, kp, vp, tables, pos, mask = _pool_case()
    ref = dispatch("paged_decode", q, kp, vp, tables, q_positions=pos,
                   pool_mask=mask, backend="reference")
    ker = dispatch("paged_decode", q, kp, vp, tables, q_positions=pos,
                   pool_mask=mask, backend="interpret")
    assert _bit_equal(ref, ker)
    # the public op faces route the same way
    ref2 = paged_attention(q, kp, vp, tables, q_positions=pos, pool_mask=mask,
                           backend="reference")
    ker2 = paged_attention(q, kp, vp, tables, q_positions=pos, pool_mask=mask,
                           backend="pallas")  # degrades to interpret on CPU
    assert _bit_equal(ref2, ker2)
    assert _bit_equal(gather_view(kp, tables, backend="reference"),
                      gather_view(kp, tables, backend="interpret"))


# ==================================================================== engine
def _llama_for_serving():
    from accelerate_tpu.models import Llama, LlamaConfig

    cfg = LlamaConfig.tiny(vocab_size=256, hidden_size=32, intermediate_size=64,
                           num_attention_heads=2, num_key_value_heads=2,
                           num_hidden_layers=2)
    model = Llama(cfg)
    model.init_params(jax.random.key(0))
    return model


def test_paged_serving_token_identity_on_kernel_backend(monkeypatch):
    """ACCELERATE_KERNELS=pallas (interpret on this rig): a mixed-length wave
    through the engine stays token-identical to solo generate(), and the
    decode program's audit inventory names the gather kernel."""
    monkeypatch.setenv("ACCELERATE_KERNELS", "pallas")
    from accelerate_tpu.generation import generate
    from accelerate_tpu.serving import ContinuousBatcher

    model = _llama_for_serving()
    rng = np.random.default_rng(200)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32)
               for n in (5, 9, 3, 12, 7, 4)]
    paged = ContinuousBatcher(
        model, batch_slots=2, max_new_tokens=8, max_cache_len=512,
        cache_dtype=jnp.float32, bucket_sizes=(8, 16), sync_every=2,
        block_size=4,
    )
    rids = [paged.submit(p) for p in prompts]
    outs = paged.run()
    for rid, p in zip(rids, prompts):
        solo = generate(model, p[None], max_new_tokens=8, temperature=0.0,
                        cache_dtype=jnp.float32, include_prompt=False)
        np.testing.assert_array_equal(outs[rid], np.asarray(solo)[0])
    report = paged.audit_decode()
    counts = report.kernel_counts()
    assert counts.get("paged_gather_kernel", 0) >= 2  # k and v assemblies
    assert report.to_dict()["kernels"][0]["interpret"] is True


def test_paged_serving_explicit_off_stays_reference(monkeypatch):
    """An engine pinned kernels='off' lowers zero pallas_call eqns even under
    an inherited env spec — the explicit-off-beats-env contract."""
    monkeypatch.setenv("ACCELERATE_KERNELS", "pallas")
    from accelerate_tpu.serving import ContinuousBatcher

    model = _llama_for_serving()
    engine = ContinuousBatcher(
        model, batch_slots=2, max_new_tokens=4, max_cache_len=128,
        cache_dtype=jnp.float32, bucket_sizes=(8,), sync_every=2,
        block_size=4, kernels="off",
    )
    assert engine.audit_decode().kernel_counts() == {}


# ================================================================== analysis
def test_audit_kernel_inventory_on_train_step():
    acc, pm, po = _build(False, "interpret")
    step = acc.build_train_step(pm, po)
    ids = _train_batches(1)[0]
    report = acc.audit(step, {"input_ids": ids, "labels": ids})
    counts = report.kernel_counts()
    assert counts.get("fused_adamw_update_kernel", 0) > 0
    assert report.summary_dict()["kernels"] == counts
    # kernels-off program audits with an empty inventory
    acc2, pm2, po2 = _build(False, "")
    step2 = acc2.build_train_step(pm2, po2)
    assert acc2.audit(step2, {"input_ids": ids, "labels": ids}).kernel_counts() == {}


def test_fingerprint_vanished_kernel_is_violation():
    from accelerate_tpu.analysis.fingerprint import classify_drift, drift_verdict

    golden = {"kernels": {"counts": {"fused_adamw_update_kernel": 12},
                          "declared": {"fused_update": "interpret"}}}
    current = {"kernels": {"counts": {}, "declared": {}}}
    drifts = classify_drift(golden, current)
    assert drift_verdict(drifts) == "violation"
    assert any("vanished" in d.detail for d in drifts if d.kind == "violation")
    # the reverse direction (a kernel appearing) is benign, not gated
    assert drift_verdict(classify_drift(current, golden)) == "benign-shape"
    # count churn on a surviving kernel is benign
    moved = {"kernels": {"counts": {"fused_adamw_update_kernel": 10},
                         "declared": {"fused_update": "interpret"}}}
    assert drift_verdict(classify_drift(golden, moved)) == "benign-shape"


def test_fingerprint_extraction_scrubs_inherited_kernel_env(monkeypatch):
    """A fleet-wide ACCELERATE_KERNELS must not leak kernel-backed programs
    into the NON-kernel goldens: extract_config pins the env symmetrically
    (interpret for kernel configs, scrubbed otherwise), so `--update` under
    an inherited spec cannot corrupt the reference matrix."""
    from accelerate_tpu.commands.fingerprint import extract_config

    monkeypatch.setenv("ACCELERATE_KERNELS", "interpret")
    fp = extract_config("step")
    assert fp.kernels["counts"] == {}
    assert fp.kernels["declared"] == {} or set(
        fp.kernels["declared"].values()) == {"reference"}
    # and the env is restored for the caller
    import os

    assert os.environ["ACCELERATE_KERNELS"] == "interpret"


def test_kernel_goldens_pin_inventory():
    """The committed kernel-config goldens actually carry the named
    pallas_call inventory (the contract the drift gate rides on)."""
    import json
    import os

    from accelerate_tpu.analysis.fingerprint import default_goldens_dir

    d = default_goldens_dir()
    step = json.load(open(os.path.join(d, "fingerprint_step_zero_kernel.json")))
    assert step["kernels"]["counts"].get("fused_adamw_update_kernel", 0) > 0
    decode = json.load(
        open(os.path.join(d, "fingerprint_decode_paged_kernel.json"))
    )
    assert decode["kernels"]["counts"].get("paged_gather_kernel", 0) >= 2


def test_traceview_attributes_custom_call_time_to_named_kernels():
    """Synthetic Chrome-trace drill: op events carrying a kernel's name (or a
    bare custom-call spelling) attribute their clipped time to
    AttributionReport.kernels via the attached audit inventory."""
    from accelerate_tpu.telemetry.traceview import (
        attach_kernel_names,
        attribute_events,
    )

    events = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "ts": 0, "dur": 1_000_000, "name": "train_step"},
        {"ph": "X", "ts": 0, "dur": 300_000, "pid": 1, "tid": 1,
         "name": "fusion.1", "args": {"hlo_op": "fusion.1"}},
        {"ph": "X", "ts": 300_000, "dur": 500_000, "pid": 1, "tid": 1,
         "name": "tpu_custom_call fused_adamw_update_kernel",
         "args": {"hlo_op": "custom-call.7"}},
        {"ph": "X", "ts": 800_000, "dur": 100_000, "pid": 1, "tid": 1,
         "name": "tpu_custom_call mystery",
         "args": {"hlo_op": "custom-call.9"}},
    ]
    try:
        attach_kernel_names(["fused_adamw_update_kernel"])
        report = attribute_events(events)
    finally:
        attach_kernel_names(None)
    assert report.kernels["fused_adamw_update_kernel"] == pytest.approx(0.5)
    # kernel-shaped events outside the inventory are still visible
    assert report.kernels["unattributed-custom-call"] == pytest.approx(0.1)
    assert report.to_dict()["kernels"]


# ====================================================================== tune
def test_tune_space_sweeps_kernel_axis():
    from accelerate_tpu.tune.search import propose_moves
    from accelerate_tpu.tune.space import Candidate, CandidateSpace

    space = CandidateSpace()
    assert space.kernels == ("off", "pallas")
    base = Candidate()
    assert base.kernels == "off" and ".koff" in base.key()
    seeds = space.seeds()
    assert any(c.kernels == "pallas" for c in seeds)
    # kernels changes the lowered program: distinct lowering keys
    assert base.lowering_key() != base.replace(kernels="pallas").lowering_key()
    # compute-bound steps propose the kernel move
    moves = propose_moves(base, "compute", space)
    assert any(m.kernels == "pallas" for m in moves)
    # roundtrip through the report dict form
    assert Candidate.from_dict(base.replace(kernels="pallas").to_dict()).kernels == "pallas"
