"""Multi-device inference — the production TPU serving configuration.

tp×fsdp-sharded params feed the cached ``generate()`` / ``ContinuousBatcher``
paths on the 8-device mesh, and every output is pinned token-identical to the
single-device decode. A 70B does not fit one chip, so sharded cached decode is
the deployment path (BASELINE.md north star #3); the reference's counterpart
evidence is its flagship multi-GPU dispatch-inference benchmark table
(``/root/reference/benchmarks/big_model_inference/README.md:26-38``).

What is pinned here, beyond token identity:
- the KV cache comes out of the prefill tp-sharded on the kv-heads axis
  (decode attends over tp-local heads; no per-step cache all-gather), and the
  LM-head logits stay vocab-sharded over tp;
- donation remains valid under sharding (the serving engine donates its cache
  + slot state every window; an explicit pin asserts the donated sharded
  buffers really die);
- beam search's per-step parent gather reorders a *sharded* cache;
- ``dispatch_model``'s multi-chip GSPMD placement feeds cached ``generate()``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import Accelerator, ParallelismConfig
from accelerate_tpu.generation import assisted_generate, generate
from accelerate_tpu.models import Llama, LlamaConfig
from accelerate_tpu.serving import ContinuousBatcher

CFG = dict(
    vocab_size=128,
    hidden_size=64,
    intermediate_size=128,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    max_position_embeddings=256,
)


@pytest.fixture()
def llama():
    # Function-scoped: each test builds its own Accelerator (mesh singleton is
    # reset between tests by conftest) and computes its baseline BEFORE the
    # params are sharded.
    model = Llama(LlamaConfig(**CFG))
    model.init_params(jax.random.key(0))
    return model


def _shard(model, **axes):
    acc = Accelerator(parallelism_config=ParallelismConfig(**axes))
    pmodel = acc.prepare(model)
    wq = pmodel.params["layers"]["attn"]["wq"]
    if axes.get("tp_size", 1) > 1:
        assert "tp" in tuple(wq.sharding.spec), wq.sharding
    if axes.get("fsdp_size", 1) > 1:
        assert "fsdp" in tuple(wq.sharding.spec), wq.sharding
    return pmodel


def _ragged(rng, rows, max_len):
    lens = rng.integers(max_len // 2, max_len + 1, rows)
    ids = rng.integers(1, CFG["vocab_size"], (rows, max_len)).astype(np.int32)
    mask = (np.arange(max_len)[None] < lens[:, None]).astype(np.int32)
    return np.where(mask, ids, 0).astype(np.int32), mask


def test_tp_fsdp_sharded_greedy_generate_matches_single_device(llama):
    rng = np.random.default_rng(90)
    ids, mask = _ragged(rng, 3, 10)
    base = np.asarray(generate(llama, ids, attention_mask=mask, max_new_tokens=8,
                               temperature=0.0, cache_dtype=jnp.float32))
    pmodel = _shard(llama, tp_size=2, fsdp_size=2)
    got = np.asarray(generate(pmodel, ids, attention_mask=mask, max_new_tokens=8,
                              temperature=0.0, cache_dtype=jnp.float32))
    np.testing.assert_array_equal(got, base)


def test_sharded_kv_cache_layout_and_vocab_sharded_logits(llama):
    """The prefill's output cache is tp-sharded on the kv-heads axis — decode
    attends over tp-local heads with NO cache all-gather — and the LM-head
    logits come out vocab-sharded (column-parallel head). This is the layout
    the cache (L, B, S, kv_heads, head_dim) was designed for."""
    pmodel = _shard(llama, tp_size=2, fsdp_size=2)
    ids = np.random.default_rng(91).integers(1, CFG["vocab_size"], (2, 8)).astype(np.int32)
    module = pmodel.handle.module
    cache = module.init_cache(2, 16, dtype=jnp.float32)
    out = jax.jit(lambda p, i, c: module.apply(p, input_ids=i, cache=c))(
        pmodel.params, ids, cache
    )
    k_spec = tuple(out["cache"]["k"].sharding.spec)  # (L, B, S, kv_heads, hd)
    assert len(k_spec) >= 4 and k_spec[3] == "tp", out["cache"]["k"].sharding
    logits_spec = tuple(out["logits"].sharding.spec)
    assert logits_spec and logits_spec[-1] == "tp", out["logits"].sharding


def test_donation_stays_valid_under_sharding(llama):
    """The serving engine donates its (sharded) cache + state every decode
    window; pin that a donated tp-sharded cache buffer really dies (no silent
    donation fallback doubling the live KV footprint)."""
    pmodel = _shard(llama, tp_size=2)
    module = pmodel.handle.module
    cache = module.init_cache(2, 16, dtype=jnp.float32)
    ids = jnp.asarray(np.random.default_rng(92).integers(1, CFG["vocab_size"], (2, 4)), jnp.int32)
    step = jax.jit(
        lambda p, i, c: module.apply(p, input_ids=i, cache=c)["cache"],
        donate_argnums=(2,),
    )
    out1 = step(pmodel.params, ids, cache)
    assert tuple(out1["k"].sharding.spec)[3] == "tp"
    k_before = out1["k"]
    out2 = step(pmodel.params, ids, out1)
    assert k_before.is_deleted()
    assert not out2["k"].is_deleted()


def test_beam_search_gathers_sharded_cache(llama):
    """Beam search's per-step parent gather reorders the beam dim of a
    tp-sharded cache; tokens must match the single-device beams exactly."""
    rng = np.random.default_rng(93)
    ids, mask = _ragged(rng, 2, 9)
    kw = dict(max_new_tokens=6, num_beams=3, attention_mask=mask,
              temperature=0.0, cache_dtype=jnp.float32)
    base = np.asarray(generate(llama, ids, **kw))
    pmodel = _shard(llama, tp_size=2, fsdp_size=2)
    got = np.asarray(generate(pmodel, ids, **kw))
    np.testing.assert_array_equal(got, base)


def test_beam_multiple_returns_sharded(llama):
    ids = np.random.default_rng(94).integers(1, CFG["vocab_size"], (2, 7)).astype(np.int32)
    kw = dict(max_new_tokens=5, num_beams=4, num_return_sequences=2,
              temperature=0.0, cache_dtype=jnp.float32)
    base = np.asarray(generate(llama, ids, **kw))
    pmodel = _shard(llama, tp_size=2)
    got = np.asarray(generate(pmodel, ids, **kw))
    assert got.shape[0] == 4  # B * num_return_sequences
    np.testing.assert_array_equal(got, base)


def test_batched_assisted_decoding_sharded_target_and_draft(llama):
    """Batched speculative decoding with BOTH models tp-sharded on the mesh:
    per-row accept/rollback over sharded caches, still exactly the target's
    greedy decode."""
    draft = Llama(LlamaConfig(**{**CFG, "num_hidden_layers": 1}))
    draft.init_params(jax.random.key(7))
    rng = np.random.default_rng(95)
    ids, mask = _ragged(rng, 2, 8)
    kw = dict(max_new_tokens=6, num_draft_tokens=3, attention_mask=mask,
              cache_dtype=jnp.float32)
    base = np.asarray(assisted_generate(llama, draft, ids, **kw))
    acc = Accelerator(parallelism_config=ParallelismConfig(tp_size=2, fsdp_size=2))
    pmodel = acc.prepare(llama)
    pdraft = acc.prepare(draft)
    got = np.asarray(assisted_generate(pmodel, pdraft, ids, **kw))
    np.testing.assert_array_equal(got, base)


def test_continuous_batcher_sharded_matches_solo(llama):
    """A full serving wave (slot refill, eviction, donation) with tp×fsdp
    sharded params: every request's output token-identical to its solo
    single-device greedy decode."""
    rng = np.random.default_rng(96)
    prompts = [rng.integers(1, CFG["vocab_size"], (n,)).astype(np.int32)
               for n in (5, 9, 3, 12, 7)]
    solos = [
        np.asarray(generate(llama, p[None], max_new_tokens=6, temperature=0.0,
                            cache_dtype=jnp.float32, include_prompt=False))[0]
        for p in prompts
    ]
    pmodel = _shard(llama, tp_size=2, fsdp_size=2)
    engine = ContinuousBatcher(pmodel, batch_slots=2, max_new_tokens=6,
                               max_cache_len=512, cache_dtype=jnp.float32,
                               bucket_sizes=(8, 16), sync_every=2, block_size=4)
    rids = [engine.submit(p) for p in prompts]
    outs = engine.run()
    for rid, ref in zip(rids, solos):
        np.testing.assert_array_equal(outs[rid], ref[: len(outs[rid])], err_msg=f"rid {rid}")
        assert all(x == 0 for x in ref[len(outs[rid]):])


def test_dispatch_model_multichip_feeds_cached_generate(llama):
    """A device_map spanning two chips executes as GSPMD sharding
    (big_modeling.py chip-placement policy); the dispatched model's cached
    generate() is token-identical to the pre-dispatch decode."""
    from accelerate_tpu.big_modeling import dispatch_model

    ids = np.random.default_rng(97).integers(1, CFG["vocab_size"], (2, 6)).astype(np.int32)
    base = np.asarray(generate(llama, ids, max_new_tokens=6, temperature=0.0,
                               cache_dtype=jnp.float32))
    dmap = {"embed": "tpu:0", "layers": "tpu:1", "final_norm": "tpu:0",
            "lm_head": "tpu:1"}
    dispatched = dispatch_model(llama, dmap)
    leaf = dispatched.params["layers"]["attn"]["wq"]
    assert len(leaf.sharding.device_set) == 2, leaf.sharding
    got = np.asarray(generate(dispatched, ids, max_new_tokens=6, temperature=0.0,
                              cache_dtype=jnp.float32))
    np.testing.assert_array_equal(got, base)
