"""Ulysses all-to-all sequence parallelism parity tests vs dense attention."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops.attention import dense_attention
from accelerate_tpu.parallel.mesh import ParallelismConfig
from accelerate_tpu.parallel.ulysses import ulysses_attention


def make_qkv(B=2, S=32, H=4, D=16, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    return q, k, v


def sp_mesh(sp=4, dp=2):
    return ParallelismConfig(sp_size=sp, dp_size=dp).build_mesh()


def test_ulysses_matches_dense_causal():
    mesh = sp_mesh()
    q, k, v = make_qkv()
    out = ulysses_attention(q, k, v, causal=True, mesh=mesh)
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_ulysses_matches_dense_with_padding_mask():
    mesh = sp_mesh()
    q, k, v = make_qkv(seed=1)
    mask = np.ones((2, 32), np.int32)
    mask[0, 20:] = 0
    mask[1, 7:] = 0
    mask = jnp.asarray(mask)
    out = ulysses_attention(q, k, v, causal=True, mask=mask, mesh=mesh)
    want = dense_attention(q, k, v, causal=True, mask=mask)
    np.testing.assert_allclose(
        np.asarray(out)[0, :20], np.asarray(want)[0, :20], atol=2e-5
    )
    np.testing.assert_allclose(np.asarray(out)[1, :7], np.asarray(want)[1, :7], atol=2e-5)


def test_ulysses_gradients_match_dense():
    mesh = sp_mesh()
    q, k, v = make_qkv(seed=2)

    def loss_u(q, k, v):
        return jnp.sum(ulysses_attention(q, k, v, causal=True, mesh=mesh) ** 2)

    def loss_d(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    gu = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gu, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_ulysses_sp1_degenerates_to_dense():
    mesh = ParallelismConfig().build_mesh()
    q, k, v = make_qkv()
    out = ulysses_attention(q, k, v, causal=True, mesh=mesh)
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


def test_ulysses_rejects_indivisible_heads():
    mesh = sp_mesh(sp=4, dp=2)
    q, k, v = make_qkv(H=2)  # 2 heads across sp=4
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention(q, k, v, mesh=mesh)


def test_ulysses_emits_all_to_all_in_training():
    """End-to-end: an sp mesh + SequenceParallelPlugin(ring_attention=False)
    routes the model's attention through Ulysses — visible as all-to-all in the
    compiled train step's HLO."""
    import re

    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import Llama, LlamaConfig
    from accelerate_tpu.utils.dataclasses import SequenceParallelPlugin

    acc = Accelerator(
        parallelism_config=ParallelismConfig(sp_size=4, dp_size=2),
        sp_plugin=SequenceParallelPlugin(sp_size=4, ring_attention=False),
    )
    cfg = LlamaConfig.tiny(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=2,
    )
    model = Llama(cfg)
    model.init_params(jax.random.key(0))
    pmodel, popt = acc.prepare(model, optax.sgd(0.1))
    assert pmodel.handle.module.config.attention_impl == "ulysses"
    # The config *object* is replaced, not mutated: anything else sharing the
    # original config instance keeps attention_impl="auto".
    assert cfg.attention_impl == "auto"
    step = acc.build_train_step(pmodel, popt)
    ids = np.random.default_rng(0).integers(0, 128, (8, 32)).astype(np.int32)
    loss = float(step({"input_ids": ids, "labels": ids}))
    assert np.isfinite(loss)
    hlo = step.lower({"input_ids": ids, "labels": ids}).compile().as_text()
    assert len(re.findall(r"\ball-to-all", hlo)) > 0, "no all-to-all in compiled step"


def test_sp_plugin_default_routes_to_ring():
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import Llama, LlamaConfig

    acc = Accelerator(parallelism_config=ParallelismConfig(sp_size=4, dp_size=2))
    model = Llama(LlamaConfig.tiny(num_attention_heads=4, num_key_value_heads=4))
    model.init_params(jax.random.key(0))
    pmodel, _ = acc.prepare(model, optax.sgd(0.1))
    assert pmodel.handle.module.config.attention_impl == "ring"


# ------------------------------------------- Mosaic kernels on a mesh of chips
@pytest.mark.parametrize("batch, masked", [(4, False), (4, True), (3, False)],
                         ids=["no_mask", "padding_mask", "batch_does_not_divide"])
def test_attention_kernel_runs_per_shard_on_a_mesh(batch, masked):
    """ops.attention._per_shard maps a kernel over batch (data axes) and heads
    (tp) by hand, because the compiler cannot partition a Mosaic kernel. With
    dense attention standing in for the kernel, the mapped call must equal the
    plain one: under jit on a dp2 x fsdp2 x tp2 mesh, gradients included."""
    from accelerate_tpu.ops.attention import _per_shard

    mesh = ParallelismConfig(dp_size=2, fsdp_size=2, tp_size=2).build_mesh()
    q, k, v = make_qkv(B=batch, seed=3)
    mask = None
    if masked:
        mask = np.ones((batch, 32), np.int32)
        mask[0, 20:] = 0
        mask = jnp.asarray(mask)

    def kernel(q, k, v, m):
        return dense_attention(q, k, v, causal=True, mask=m)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    mapped = jax.jit(jax.value_and_grad(loss(lambda q, k, v: _per_shard(kernel, q, k, v, mask, mesh)),
                                        argnums=(0, 1, 2)))
    plain = jax.value_and_grad(loss(lambda q, k, v: kernel(q, k, v, mask)), argnums=(0, 1, 2))
    (got, got_g), (want, want_g) = mapped(q, k, v), plain(q, k, v)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
