"""Speculative decoding + int8 KV/weight quantization on the paged serving
engine (PR 20). Correctness contracts pinned here:

- greedy speculative decode is BIT-IDENTICAL to non-speculative serving (and
  therefore to solo ``generate()``) by construction — the verify window's
  per-position choices reuse the exact non-speculative sampling fold, and
  rejection is block-table truncation, never a numeric path;
- sampled streams stay functions of (engine rng, request id) under
  speculation — independent of traffic shape AND of whether a draft runs;
- the int8 KV pool round-trips within the documented ``amax/254`` per-row
  bound, prices >= 1.8x more tokens per HBM byte than the bf16 pool, and the
  speculative path composes with it bit-identically;
- rejection/truncation never leaks pool blocks (free-list accounting).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.generation import generate
from accelerate_tpu.models import Llama, LlamaConfig
from accelerate_tpu.ops.int8 import dequantize_kv, quantize_kv
from accelerate_tpu.serving import ContinuousBatcher


@pytest.fixture(scope="module")
def llama():
    model = Llama(LlamaConfig.tiny(num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2))
    model.init_params(jax.random.key(0))
    return model


@pytest.fixture(scope="module")
def draft(llama):
    """An INDEPENDENTLY-initialized copy of the target architecture: same
    tokenizer/vocab, different weights — a real draft that mispredicts, so
    the rejection/truncation path actually runs."""
    model = Llama(LlamaConfig.tiny(num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2))
    model.init_params(jax.random.key(7))
    return model


def _solo(model, prompt, max_new, **kw):
    return np.asarray(generate(
        model, prompt[None], max_new_tokens=max_new, temperature=0.0,
        cache_dtype=jnp.float32, include_prompt=False, **kw,
    ))[0]


def _paged(model, **overrides):
    kw = dict(batch_slots=2, max_new_tokens=8, max_cache_len=512,
              cache_dtype=jnp.float32, bucket_sizes=(8, 16), sync_every=2,
              block_size=4)
    kw.update(overrides)
    return ContinuousBatcher(model, **kw)


def _wave(model, prompts, **overrides):
    engine = _paged(model, **overrides)
    rids = [engine.submit(p) for p in prompts]
    outs = engine.run()
    return [np.asarray(outs[r]) for r in rids], engine


# ---------------------------------------------------- greedy bit-identity


@pytest.mark.parametrize("k", [1, 3])
def test_spec_greedy_bit_identity_perfect_draft(llama, k):
    """draft == target: every proposal the budget admits is accepted, and the
    outputs are bit-identical to the non-speculative engine at every k. The
    acceptance rate is < 1 even here — the final verify window truncates at
    the request's max_new budget while ``proposed`` counts k per live round —
    so the pin is a floor, never ``== 1.0``."""
    rng = np.random.default_rng(80)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (5, 9, 3, 12, 7, 4)]
    base, _ = _wave(llama, prompts)
    spec, engine = _wave(llama, prompts, speculative_k=k, draft_model=llama)
    for i, (a, b) in enumerate(zip(base, spec)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
    rep = engine.spec_report()
    assert rep["speculative_k"] == k
    assert rep["proposed_tokens"] > 0
    assert rep["acceptance_rate"] >= 0.5, rep  # tail-window truncation only
    # Speculation actually amortized windows: fewer target dispatches than
    # the token count it produced.
    verify_rounds = sum(1 for e in engine._dispatch_log if e.startswith("verify"))
    produced = sum(len(o) for o in spec)
    assert 0 < verify_rounds < produced


def test_spec_greedy_bit_identity_independent_draft(llama, draft):
    """A mispredicting draft exercises rejection (block-table truncation) on
    the real path — outputs must STILL be bit-identical to non-speculative
    serving, with a strictly lower acceptance rate than the perfect draft."""
    rng = np.random.default_rng(81)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (5, 9, 3, 12, 7, 4)]
    base, _ = _wave(llama, prompts)
    spec, engine = _wave(llama, prompts, speculative_k=3, draft_model=draft)
    for i, (a, b) in enumerate(zip(base, spec)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
    rep = engine.spec_report()
    assert rep["proposed_tokens"] > rep["accepted_tokens"]  # rejections ran
    assert 0.0 <= rep["acceptance_rate"] < 1.0
    # The tracer's per-request tallies sum to the engine ledger.
    records = engine.tracer.records()
    assert sum(r["spec_proposed"] for r in records) == rep["proposed_tokens"]
    assert sum(r["spec_accepted"] for r in records) == rep["accepted_tokens"]
    assert engine.tracer.summary()["spec"]["acceptance_rate"] == pytest.approx(
        rep["acceptance_rate"])


def test_spec_chunked_prefill_interplay(llama, draft):
    """Long prompts admitted chunk-by-chunk between VERIFY windows: the
    chunked-prefill machinery and the multi-token verify forward share the
    window programs, and outputs stay bit-identical to solo decode."""
    rng = np.random.default_rng(204)
    short = rng.integers(1, 256, (5,)).astype(np.int32)
    long_p = rng.integers(1, 256, (21,)).astype(np.int32)
    engine = _paged(llama, max_new_tokens=6, bucket_sizes=(8,), prefill_chunk=8,
                    max_tokens_per_request=64, speculative_k=2, draft_model=draft)
    r_short = engine.submit(short)
    r_long = engine.submit(long_p)
    outs = engine.run()
    np.testing.assert_array_equal(
        outs[r_short], _solo(llama, short, 6)[: len(outs[r_short])])
    np.testing.assert_array_equal(
        outs[r_long], _solo(llama, long_p, 6)[: len(outs[r_long])])
    log = engine._dispatch_log
    assert any(e.startswith("chunk") for e in log)
    assert any(e.startswith("verify") for e in log)


def test_spec_bit_identity_across_waves_and_refill(llama, draft):
    """Slot refill + wave boundaries: chains freed by wave 1 are reallocated
    to wave 2's requests (same block indices, new owners) and speculation
    stays bit-identical — truncation surgery never leaves stale rows behind."""
    rng = np.random.default_rng(82)
    w1 = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (5, 9, 3, 12)]
    w2 = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (7, 4, 11, 6)]
    engine = _paged(llama, speculative_k=3, draft_model=draft)
    r1 = [engine.submit(p) for p in w1]
    o1 = engine.run()
    r2 = [engine.submit(p) for p in w2]
    o2 = engine.run()
    for rid, p in zip(r1 + r2, w1 + w2):
        outs = o1 if rid in o1 else o2
        ref = _solo(llama, p, 8)
        np.testing.assert_array_equal(outs[rid], ref[: len(outs[rid])])


# ------------------------------------------------- sampled streams + spec


def test_spec_sampled_streams_traffic_and_draft_independent(llama, draft):
    """Sampled outputs are functions of (engine rng, request id) ONLY: the
    same streams fall out regardless of slot count, sync cadence, and —
    because the verify window reuses the non-speculative sampling fold
    per emitted position — regardless of whether a draft runs at all."""
    rng = np.random.default_rng(206)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (5, 6, 7)]

    def wave(slots, sync, **spec):
        engine = _paged(llama, batch_slots=slots, sync_every=sync,
                        bucket_sizes=(8,), rng=jax.random.key(7), **spec)
        rids = [engine.submit(p, temperature=0.9) for p in prompts]
        outs = engine.run()
        return [np.asarray(outs[r]) for r in rids]

    plain = wave(2, 2)
    spec_a = wave(2, 2, speculative_k=3, draft_model=draft)
    spec_b = wave(3, 1, speculative_k=2, draft_model=draft)  # traffic + k vary
    for i in range(len(prompts)):
        np.testing.assert_array_equal(plain[i], spec_a[i], err_msg=f"request {i}")
        np.testing.assert_array_equal(plain[i], spec_b[i], err_msg=f"request {i}")


# ---------------------------------------------------- rejection accounting


def test_spec_rejection_frees_all_blocks(llama, draft):
    """Free-list accounting through the truncation path: after waves full of
    rejections every chain is refcount-freed — no leaked blocks, no double
    frees (the free list is a permutation of the full block range)."""
    rng = np.random.default_rng(83)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (5, 9, 3, 12, 7, 4)]
    engine = _paged(llama, speculative_k=3, draft_model=draft)
    for _ in range(2):
        rids = [engine.submit(p) for p in prompts]
        outs = engine.run()
        assert all(r in outs for r in rids)
    stats = engine.pool_stats()
    assert stats["blocks_in_use"] == 0
    assert stats["blocks_free"] == engine.num_blocks
    assert sorted(engine._free_blocks) == list(range(1, engine.num_blocks + 1))


# ------------------------------------------------------------ int8 KV pool


def test_int8_kv_roundtrip_error_bound():
    """quantize_kv/dequantize_kv round-trip within the documented bound:
    per token row, ``|deq - x| <= amax/254`` (half a quantization step).
    All-zero rows are exact (scale clamps to 1.0, payload is 0)."""
    x = jax.random.normal(jax.random.key(11), (3, 6, 4, 16), jnp.float32) * 5.0
    x = x.at[0, 2].set(0.0)  # an all-zero token row
    q, scale = quantize_kv(x)
    assert q.dtype == jnp.int8 and scale.shape == (3, 6)
    deq = dequantize_kv(q, scale)
    amax = jnp.max(jnp.abs(x), axis=(-2, -1))
    err = jnp.max(jnp.abs(deq - x), axis=(-2, -1))
    assert bool(jnp.all(err <= amax / 254.0 + 1e-7))
    np.testing.assert_array_equal(np.asarray(deq[0, 2]), np.zeros((4, 16)))


def test_int8_pool_capacity_ratio():
    """The capacity headline: at the same block budget the int8 pool prices
    >= 1.8x more tokens per HBM byte than a bf16 pool (and >= 3.5x vs fp32)
    — int8 payload + one f32 scale per token row per side. Pinned at a
    realistic per-token row width (Hkv*D = 64); the scale overhead is fixed
    per row, so wider real-model rows only improve the ratio."""
    model = Llama(LlamaConfig.tiny(hidden_size=128, intermediate_size=256,
                                   num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2))
    model.init_params(jax.random.key(2))

    def bytes_for(dtype, quant):
        return _paged(model, cache_dtype=dtype, kv_quant=quant).kv_cache_bytes

    int8_bytes = bytes_for(jnp.float32, "int8")
    assert bytes_for(jnp.bfloat16, None) / int8_bytes >= 1.8
    assert bytes_for(jnp.float32, None) / int8_bytes >= 3.5


def test_int8_kv_decode_tolerance(llama):
    """Serving on the quantized pool: every request completes at full length
    and stays within the pinned decode tolerance — token divergence vs the
    full-precision pool bounded, not bit-exact (quantization is real)."""
    rng = np.random.default_rng(84)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (5, 9, 3, 12, 7, 4)]
    base, _ = _wave(llama, prompts)
    quant, engine = _wave(llama, prompts, kv_quant="int8")
    assert engine.pool_stats()["kv_quant"] == "int8"
    diverged, total = 0, 0
    for a, b in zip(base, quant):
        n = min(len(a), len(b))
        diverged += int((a[:n] != b[:n]).sum()) + abs(len(a) - len(b))
        total += max(len(a), len(b))
    assert diverged / total <= 0.3, f"{diverged}/{total} tokens diverged"
    # Pool accounting stays clean through the quantized scatter path.
    assert engine.pool_stats()["blocks_in_use"] == 0


def test_spec_composes_with_int8_kv(llama, draft):
    """Speculation on the quantized pool is bit-identical to NON-speculative
    serving on the same quantized pool: verify/truncation is layout surgery
    on int8 blocks + scales exactly as on full-precision blocks."""
    rng = np.random.default_rng(85)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (5, 9, 3, 12, 7, 4)]
    quant, _ = _wave(llama, prompts, kv_quant="int8")
    both, engine = _wave(llama, prompts, kv_quant="int8",
                         speculative_k=3, draft_model=draft)
    for i, (a, b) in enumerate(zip(quant, both)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
    # The draft's mirror pool stays full-precision and is priced separately.
    stats = engine.pool_stats()
    assert stats["draft_pool_bytes"] > 0
    assert engine.spec_report()["proposed_tokens"] > 0


# ------------------------------------------------- int8 weight-quant serving


def test_int8_weight_serving_matches_solo(llama):
    """matmul_precision="int8" through the serving engine is token-identical
    to solo ``generate(..., matmul_precision="int8")``: integer contraction
    is exact in any tiling, so the serving exactness contract carries over to
    the quantized-weight forward unchanged."""
    rng = np.random.default_rng(86)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (5, 9, 3)]
    outs, engine = _wave(llama, prompts, matmul_precision="int8")
    assert engine.matmul_precision == "int8"
    for out, p in zip(outs, prompts):
        ref = _solo(llama, p, 8, matmul_precision="int8")
        np.testing.assert_array_equal(out, ref[: len(out)])


# ------------------------------------------------------------- guard rails


def test_spec_and_quant_guards(llama, draft):
    """Construction guards: a draft without speculation, a negative k, and
    an unknown quant token all fail fast with actionable errors."""
    with pytest.raises(ValueError, match="draft_model"):
        _paged(llama, draft_model=draft)
    with pytest.raises(ValueError, match="speculative_k"):
        _paged(llama, speculative_k=-1)
    with pytest.raises(ValueError, match="kv_quant"):
        _paged(llama, kv_quant="int4")
