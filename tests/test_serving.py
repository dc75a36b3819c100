"""Continuous batching (``serving.ContinuousBatcher``): slot-refill serving
over the paged KV pool. Correctness contract: greedy outputs are EXACTLY the
solo ``generate()`` output for each prompt, however requests interleave — the
per-slot kv-mask holes and the rope/wpe position channel keep rows
independent — and sampled outputs depend only on (engine rng, request id),
not on traffic or slot assignment. Exceeds the reference, which serves whole
batches through ``model.generate`` with head-of-line blocking."""

import collections
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.generation import generate
from accelerate_tpu.models import GPT2, GPT2Config, Llama, LlamaConfig
from accelerate_tpu.models.gptx import GPTX, GPTXConfig
from accelerate_tpu.serving import ContinuousBatcher, SLOTargets
from accelerate_tpu.telemetry import get_span_ring, reset_spans


TINY = dict(num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2)


@pytest.fixture(scope="module")
def llama():
    model = Llama(LlamaConfig.tiny(**TINY))
    model.init_params(jax.random.key(0))
    return model


def _family(name):
    """A tiny initialised model of one cached decoder family: rotary
    (``llama``), rotary with a sliding window (``windowed``), learned absolute
    positions (``gpt2``), parallel-residual rotary (``gptx``)."""
    if name == "gpt2":
        model, key = GPT2(GPT2Config.tiny()), 3
    elif name == "gptx":
        model, key = GPTX(GPTXConfig.tiny()), 5
    elif name == "windowed":
        model, key = Llama(LlamaConfig.tiny(sliding_window=4, **TINY)), 11
    else:
        model, key = Llama(LlamaConfig.tiny(**TINY)), 0
    model.init_params(jax.random.key(key))
    return model


def _solo(model, prompt, max_new, eos=None):
    return np.asarray(generate(
        model, prompt[None], max_new_tokens=max_new, temperature=0.0,
        eos_token_id=eos, cache_dtype=jnp.float32, include_prompt=False,
    ))[0]


def _paged(model, **overrides):
    kw = dict(batch_slots=2, max_new_tokens=8, max_cache_len=512,
              cache_dtype=jnp.float32, bucket_sizes=(8, 16), sync_every=2,
              block_size=4)
    kw.update(overrides)
    return ContinuousBatcher(model, **kw)


def _free_list_is_full(engine):
    stats = engine.pool_stats()
    return stats["blocks_free"] == stats["num_blocks"] and engine.cache_columns_used == 0


def test_paged_false_is_refused_in_words(llama):
    """The constructor still takes ``paged`` (the benchmark's configuration
    files pass ``"paged": true``) and takes nothing but ``True``."""
    for value in (False, None, 0):
        with pytest.raises(ValueError, match="contiguous engine is gone"):
            ContinuousBatcher(llama, batch_slots=1, max_new_tokens=4, max_cache_len=64,
                              paged=value)


def test_paged_true_and_its_absence_build_the_same_engine(llama):
    """``paged=True`` selects nothing: geometry, pool and the decode
    program's lowering are those of an engine built without the keyword, and
    no ``paged`` attribute is left to ask about."""
    kw = dict(batch_slots=2, max_new_tokens=4, max_cache_len=64, bucket_sizes=(8,),
              cache_dtype=jnp.float32)
    with_kw, without = ContinuousBatcher(llama, paged=True, **kw), ContinuousBatcher(llama, **kw)
    assert with_kw.pool_stats() == without.pool_stats()
    assert not hasattr(with_kw, "paged") and "paged" not in with_kw.pool_stats()
    lowered = [e._decode().lower(*e._decode_args()).as_text() for e in (with_kw, without)]
    assert lowered[0] == lowered[1]


# One wave per family: prompt lengths, tokens to generate, engine geometry.
SOLO_WAVES = {
    "llama": ((5, 9, 3, 12, 7, 4), 8, dict(batch_slots=2, max_cache_len=512)),
    # one slot: every request is admitted behind another's chain positions,
    # and must still see learned positions 0..len-1
    "gpt2": ((6, 3, 5), 5, dict(batch_slots=1, max_cache_len=128, bucket_sizes=(8,))),
    "gptx": ((5, 9, 3, 12), 6, dict(batch_slots=2, max_cache_len=256)),
    "windowed": ((7, 4, 9, 5), 6, dict(batch_slots=2, max_cache_len=512)),
}


@pytest.mark.parametrize("family,sync_every,block_size", [
    ("llama", 1, 4), ("llama", 4, 4), ("llama", 1, 16), ("llama", 4, 16),
    ("gpt2", 2, 4), ("gpt2", 2, 16), ("gptx", 2, 4),
    ("windowed", 2, 4), ("windowed", 2, 16),
])
def test_continuous_batching_matches_solo_greedy(family, sync_every, block_size):
    """Ragged requests through fewer slots than requests: each output is
    token-identical to the solo greedy decode, with slot refill mid-flight —
    at every host-sync cadence (decode windows change only hole placement)
    and block size (block tables, gather views and scatter writes are pure
    layout, never numerics; blocks of 16 hold a whole 8-token bucket and its
    padding). GPT-2 pins the learned-position path (positions ride the
    token-position channel, never the chain-slot index); sliding windows
    measure valid-slot distance across the gathered view, so bucket-padding
    holes inside chains never stretch the window."""
    lengths, max_new, geometry = SOLO_WAVES[family]
    model = _family(family)
    rng = np.random.default_rng(80)
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in lengths]
    engine = _paged(model, max_new_tokens=max_new, sync_every=sync_every,
                    block_size=block_size, **geometry)
    rids = [engine.submit(p) for p in prompts]
    outs = engine.run()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(outs[rid], _solo(model, p, max_new), err_msg=f"rid {rid}")
    assert _free_list_is_full(engine)


def test_continuous_batching_eos_frees_slots_early(llama):
    """Requests stop at their own eos; the freed slot serves the next request
    while the neighbor keeps decoding (the point of continuous batching)."""
    rng = np.random.default_rng(81)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (6, 4, 5, 7)]
    # pick an eos that actually occurs for at least one prompt
    eos = int(_solo(llama, prompts[0], 8)[2])
    engine = ContinuousBatcher(llama, batch_slots=2, max_new_tokens=8,
                               max_cache_len=512, eos_token_id=eos,
                               cache_dtype=jnp.float32, bucket_sizes=(8,))
    rids = [engine.submit(p) for p in prompts]
    outs = engine.run()
    for rid, p in zip(rids, prompts):
        ref = _solo(llama, p, 8, eos=eos)
        trimmed = ref[: int(np.argmax(ref == eos)) + 1] if (ref == eos).any() else ref
        np.testing.assert_array_equal(outs[rid], trimmed, err_msg=f"rid {rid}")
    assert any((outs[r] == eos).any() for r in rids)  # early stop exercised


def test_continuous_batching_no_recompile_across_requests(llama):
    """Shapes never depend on traffic: one decode program, one chunk program
    per bucket, regardless of how many requests flow through."""
    engine = ContinuousBatcher(llama, batch_slots=2, max_new_tokens=4,
                               max_cache_len=512, cache_dtype=jnp.float32,
                               bucket_sizes=(8,))
    rng = np.random.default_rng(83)
    for _ in range(5):
        engine.submit(rng.integers(1, 256, (5,)).astype(np.int32))
    engine.run()
    assert list(engine._chunk_fns) == [8]  # the one bucket
    assert engine._chunk_fns[8]._cache_size() == 1
    assert engine._decode_fn._cache_size() == 1


def test_continuous_batching_sampled_streams_are_traffic_independent(llama):
    """Sampling mode: each request draws from fold_in(engine_rng, rid) — so
    its tokens depend only on (engine rng, request id), NOT on slot count,
    interleaving, or what else is in flight; different rngs vary."""
    rng = np.random.default_rng(84)
    prompts = [rng.integers(1, 256, (5,)).astype(np.int32) for _ in range(3)]

    def serve(seed, slots):
        engine = ContinuousBatcher(llama, batch_slots=slots, max_new_tokens=6,
                                   max_cache_len=256, temperature=1.0,
                                   rng=jax.random.key(seed),
                                   cache_dtype=jnp.float32, bucket_sizes=(8,))
        rids = [engine.submit(p) for p in prompts]
        return engine.run(), rids

    a, rids = serve(0, slots=2)
    b, _ = serve(0, slots=3)  # DIFFERENT traffic shape, same streams
    c, _ = serve(1, slots=2)
    for r in rids:
        np.testing.assert_array_equal(a[r], b[r], err_msg=f"rid {r}")
    assert any(not np.array_equal(a[r], c[r]) for r in rids)


@pytest.mark.parametrize("family,block_size", [
    ("llama", 4), ("llama", 16), ("gpt2", 4), ("gptx", 4)])
def test_prefix_caching_matches_solo_concat(family, block_size):
    """set_prefix: requests submit only suffixes, and each greedy output is
    token-identical to solo generate(prefix + suffix). With blocks of 4 the
    first request's 8-token chunk fills whole blocks of the 12-token prefix,
    and staggered admissions REUSE them (the aliased_blocks ledger proves
    sharing engaged, not just correctness); a block of 16 is never filled by
    a chunk of 8, so nothing is shared and every request prefills its own
    copy — same tokens. GPT-2 pins the learned-position path. A second wave
    through the same engine crosses the free/realloc path and stays exact."""
    model = _family(family)
    rng = np.random.default_rng(90)
    prefix = rng.integers(1, 256, (12,)).astype(np.int32)
    sufs = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (4, 7, 3, 6, 5)]
    engine = _paged(model, max_new_tokens=6, max_cache_len=256, bucket_sizes=(8,),
                    block_size=block_size, prefill_chunk=8, max_tokens_per_request=64)
    assert engine.set_prefix(prefix) == 12
    for wave in (sufs, sufs[:2]):
        rids = [engine.submit(s) for s in wave]
        outs = engine.run()
        for rid, s in zip(rids, wave):
            np.testing.assert_array_equal(
                outs[rid], _solo(model, np.concatenate([prefix, s]), 6), err_msg=f"rid {rid}")
        assert _free_list_is_full(engine)
    assert (engine.slo_report()["decisions"]["aliased_blocks"] > 0) == (block_size == 4)


def test_prefix_caching_survives_reset_and_guards(llama):
    """reset() keeps the prefix tokens (so the capacity-retry flow stays
    exact); reset(keep_prefix=False) drops them; set_prefix demands a fresh
    cache and rejects degenerate lengths."""
    rng = np.random.default_rng(91)
    prefix = rng.integers(1, 256, (10,)).astype(np.int32)
    engine = ContinuousBatcher(llama, batch_slots=1, max_new_tokens=4,
                               max_cache_len=128, cache_dtype=jnp.float32,
                               bucket_sizes=(8,), max_tokens_per_request=32)
    engine.set_prefix(prefix)
    with pytest.raises(RuntimeError, match="fresh cache"):
        engine.set_prefix(prefix)  # prefix already in place
    suffix = rng.integers(1, 256, (5,)).astype(np.int32)
    r1 = engine.submit(suffix)
    out1 = engine.run()[r1]
    np.testing.assert_array_equal(out1, _solo(llama, np.concatenate([prefix, suffix]), 4))
    engine.reset()  # keep_prefix=True default: the tokens stay
    np.testing.assert_array_equal(engine._prefix_tokens, prefix)
    r2 = engine.submit(suffix)
    np.testing.assert_array_equal(engine.run()[r2], out1)
    engine.reset(keep_prefix=False)
    assert engine._prefix_tokens is None
    r3 = engine.submit(suffix)  # the suffix alone now
    np.testing.assert_array_equal(engine.run()[r3], _solo(llama, suffix, 4))
    with pytest.raises(ValueError, match="empty"):
        engine.set_prefix(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="no room"):
        engine.set_prefix(np.arange(1, 30, dtype=np.int32))


def test_continuous_batching_waves_return_only_new_results(llama):
    rng = np.random.default_rng(85)
    engine = ContinuousBatcher(llama, batch_slots=2, max_new_tokens=4,
                               max_cache_len=512, cache_dtype=jnp.float32,
                               bucket_sizes=(8,))
    first = [engine.submit(rng.integers(1, 256, (5,)).astype(np.int32)) for _ in range(2)]
    w1 = engine.run()
    assert set(w1) == set(first)
    second = [engine.submit(rng.integers(1, 256, (5,)).astype(np.int32)) for _ in range(2)]
    w2 = engine.run()
    assert set(w2) == set(second)  # wave 1 results not replayed


def _windowed_waves_engine(block_size):
    """A sliding-window model with a shared 8-token prefix over a pool of 64
    token slots, about what one wave of two slots reserves: every later wave
    runs on blocks an earlier one gave back."""
    model = Llama(LlamaConfig.tiny(sliding_window=5, **TINY))
    model.init_params(jax.random.key(21))
    rng = np.random.default_rng(102)
    prefix = rng.integers(1, 256, (8,)).astype(np.int32)
    waves = [[rng.integers(1, 256, (n,)).astype(np.int32) for n in lengths]
             for lengths in ((5, 7, 4, 6), (3, 8, 5), (7, 2, 6, 4, 5))]
    engine = _paged(model, max_new_tokens=6, max_cache_len=64, bucket_sizes=(8,),
                    block_size=block_size, prefill_chunk=8, max_tokens_per_request=32)
    engine.set_prefix(prefix)
    return model, engine, prefix, waves


@pytest.mark.parametrize("block_size", [4, 16])
def test_waves_on_one_engine_reuse_the_pool_without_reset(block_size):
    """Three waves of heterogeneous lengths on ONE engine, never reset: a
    finished request's chain returns to the free list as its report is read,
    so each wave finds the whole pool again, the blocks it is handed have
    held other requests' keys (stale mask bits hidden by the chain
    frontier), and every output is still solo generate(prefix + suffix) —
    the hardest layout case: rope baked into K, valid-distance windows, a
    shared prefix whose blocks are aliased (blocks of 4) or private copies
    (blocks of 16)."""
    model, engine, prefix, waves = _windowed_waves_engine(block_size)
    for wave in waves:
        rids = [engine.submit(s) for s in wave]
        outs = engine.run()
        for rid, s in zip(rids, wave):
            np.testing.assert_array_equal(
                outs[rid], _solo(model, np.concatenate([prefix, s]), 6), err_msg=f"rid {rid}")
        assert _free_list_is_full(engine)
        assert sorted(engine._free_blocks) == list(range(1, engine.num_blocks + 1))
        assert not engine._share_index and not engine._block_ref.any()
    # One wave's two slots took most of the pool at once, so the later waves
    # ran on blocks that had been given back.
    assert engine.kv_consumed_slots_peak >= 0.75 * engine.num_blocks * block_size
    assert (engine.slo_report()["decisions"]["aliased_blocks"] > 0) == (block_size == 4)


def test_cache_utilization_holds_its_level_across_waves():
    """``cache_utilization`` is valid tokens over the slots chains hold, read
    while they hold them (here: at every stream event): what is not a token
    is a final chunk's bucket padding, a finished row's masked decode writes
    and the part of a reservation not written yet. Chains free whole, so the
    level a wave runs at does not sink from wave to wave on the same engine,
    and an idle engine reads 1.0."""
    _, engine, _, waves = _windowed_waves_engine(4)
    seen = []
    engine.stream = lambda rid, tokens, final: seen.append(engine.cache_utilization)
    assert engine.cache_utilization == 1.0  # fresh engine
    levels = []
    for _ in range(3):
        for s in waves[0]:  # the same wave each time
            engine.submit(s)
        del seen[:]
        engine.run()
        assert seen and all(0.0 < u <= 1.0 for u in seen)
        levels.append(min(seen))
        assert engine.cache_utilization == 1.0 and engine.cache_columns_used == 0
    assert levels[1] >= levels[0] and levels[2] >= levels[0], levels
    assert levels[0] > 0.2, levels  # tokens, not padding, are most of what chains hold


def test_peak_consumed_slots_are_the_chains_reservations(llama):
    """``kv_consumed_slots_peak`` counts what chains reserved at once, not a
    slots-by-columns rectangle: with four slots over eight mixed requests it
    is a whole number of blocks, at least the largest single reservation and
    at most the four largest together (bucketed prompt + max_new - 1 + three
    windows of slack, rounded up to blocks), and it outlives the wave."""
    rng = np.random.default_rng(205)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32)
               for n in (5, 14, 3, 12, 7, 4, 9, 6)]
    engine = _paged(llama, batch_slots=4, max_cache_len=1024)
    for p in prompts:
        engine.submit(p)
    engine.run()
    bs, slack = engine.block_size, 3 * engine.sync_every
    reserved = sorted(-(-(engine._bucket(p.size) + 8 - 1 + slack) // bs) * bs for p in prompts)
    peak = engine.kv_consumed_slots_peak
    assert peak % bs == 0 and reserved[-1] <= peak <= sum(reserved[-4:])
    assert engine.cache_columns_used == 0


def test_a_dead_end_with_a_prefix_set_recovers_through_reset_and_run(llama):
    """The loop raises its capacity dead end when a request waits and every
    slot is free. A request that reaches the queue just after a turn's admit,
    on an idle engine, is refused that way (a known fault: the queue is read
    after admission ran). The documented way out holds with a shared prefix
    in place: the queued request and the prefix tokens survive ``reset()``,
    and ``run()`` then serves generate(prefix + suffix)."""
    rng = np.random.default_rng(104)
    prefix = rng.integers(1, 256, (10,)).astype(np.int32)
    suffix = rng.integers(1, 256, (5,)).astype(np.int32)
    engine = _paged(llama, max_new_tokens=6, max_tokens_per_request=32)
    engine.set_prefix(prefix)
    admit, arrived = engine._admit_paged, []

    def admit_then_arrive(now):
        admit(now)
        if not arrived:
            arrived.append(engine.submit(suffix))

    engine._admit_paged = admit_then_arrive
    with pytest.raises(RuntimeError, match="capacity exhausted"):
        engine.run()
    engine._admit_paged = admit
    assert engine.in_flight() == 1  # still queued
    engine.reset()
    out = engine.run()[arrived[0]]
    np.testing.assert_array_equal(out, _solo(llama, np.concatenate([prefix, suffix]), 6))
    assert _free_list_is_full(engine)


def test_submit_refuses_a_prompt_past_the_request_ceiling(llama):
    """A prompt may exceed the largest bucket (it is chunked) but not
    ``max_tokens_per_request`` less the output it reserves; a shared prefix
    counts as part of every prompt, and a smaller per-request
    ``max_new_tokens`` leaves that much more room."""
    engine = _paged(llama, max_new_tokens=8, bucket_sizes=(8,), max_tokens_per_request=40)
    engine.submit(np.arange(1, 33, dtype=np.int32))  # 32 + 8 = 40: four chunks of 8
    with pytest.raises(ValueError, match="exceeds max_tokens_per_request=40"):
        engine.submit(np.arange(1, 34, dtype=np.int32))
    engine.submit(np.arange(1, 34, dtype=np.int32), max_new_tokens=7)
    engine.reset()
    engine._queue.clear()
    engine.set_prefix(np.arange(1, 11, dtype=np.int32))
    engine.submit(np.arange(1, 23, dtype=np.int32))  # 10 + 22 + 8
    with pytest.raises(ValueError, match="incl. prefix"):
        engine.submit(np.arange(1, 24, dtype=np.int32))


def test_prefix_match_tokens_reads_the_resident_shared_blocks(llama):
    """The router's affinity answer is a host-side lookup in the shared-block
    index: while a request that prefilled the prefix is in flight its whole
    blocks are resident and a prompt that starts with them matches that many
    tokens (capped one token short of the prompt, whose last token always
    runs through a chunk); nothing matches before the first chunk has landed
    or once the wave is over and the blocks are back on the free list."""
    rng = np.random.default_rng(105)
    prefix = rng.integers(1, 256, (8,)).astype(np.int32)
    engine = _paged(llama, bucket_sizes=(8,), prefill_chunk=8, max_tokens_per_request=32)
    engine.set_prefix(prefix)
    seen = []
    engine.stream = lambda rid, tokens, final: seen.append(
        (engine.prefix_match_tokens([5, 6, 7]), engine.prefix_match_tokens([])))
    engine.submit(rng.integers(1, 256, (5,)).astype(np.int32))
    assert engine.prefix_match_tokens([5, 6, 7]) == 0  # nothing resident yet
    engine.run()
    assert seen and all(match == (8, 4) for match in seen), seen
    assert engine.prefix_match_tokens([5, 6, 7]) == 0 and not engine._share_index


# --------------------------------------------------- per-request controls (r5)


def test_per_request_max_new_and_eos_heterogeneous(llama):
    """One wave mixing per-request max_new_tokens and eos overrides: each
    output equals the solo decode under that request's OWN settings."""
    rng = np.random.default_rng(95)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (5, 7, 4, 6)]
    solo8 = [_solo(llama, p, 8) for p in prompts]
    # A per-request eos that actually occurs for prompt 1.
    eos1 = int(solo8[1][2])
    engine = ContinuousBatcher(llama, batch_slots=2, max_new_tokens=8,
                               max_cache_len=512, cache_dtype=jnp.float32,
                               bucket_sizes=(8,), sync_every=2)
    r0 = engine.submit(prompts[0], max_new_tokens=3)
    r1 = engine.submit(prompts[1], eos_token_id=eos1)
    r2 = engine.submit(prompts[2])  # engine defaults
    r3 = engine.submit(prompts[3], max_new_tokens=5)
    outs = engine.run()
    np.testing.assert_array_equal(outs[r0], solo8[0][:3])
    ref1 = _solo(llama, prompts[1], 8, eos=eos1)
    trim1 = ref1[: int(np.argmax(ref1 == eos1)) + 1] if (ref1 == eos1).any() else ref1
    np.testing.assert_array_equal(outs[r1], trim1)
    np.testing.assert_array_equal(outs[r2], solo8[2])
    np.testing.assert_array_equal(outs[r3], solo8[3][:5])
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit(prompts[0], max_new_tokens=9)  # above the engine cap


def test_per_request_temperature_mixes_greedy_and_sampled(llama):
    """Greedy (temp 0) and sampled rows coexist in one wave: greedy rows stay
    token-identical to solo greedy; sampled rows are reproducible functions
    of (engine rng, request id) — an identically-configured engine replays
    them bit-for-bit."""
    rng = np.random.default_rng(96)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (5, 6, 7)]

    def wave():
        engine = ContinuousBatcher(llama, batch_slots=2, max_new_tokens=6,
                                   max_cache_len=512, cache_dtype=jnp.float32,
                                   rng=jax.random.key(7), bucket_sizes=(8,),
                                   sync_every=2)
        r_greedy = engine.submit(prompts[0])  # engine default temp 0
        r_hot = engine.submit(prompts[1], temperature=0.9)
        r_cool = engine.submit(prompts[2], temperature=0.3)
        outs = engine.run()
        return outs[r_greedy], outs[r_hot], outs[r_cool]

    g1, h1, c1 = wave()
    g2, h2, c2 = wave()
    np.testing.assert_array_equal(g1, _solo(llama, prompts[0], 6))
    np.testing.assert_array_equal(h1, h2)  # reproducible sampled stream
    np.testing.assert_array_equal(c1, c2)
    np.testing.assert_array_equal(g1, g2)


@pytest.mark.parametrize("sync_every", [1, 4])
def test_stop_sequences_truncate_exactly(llama, sync_every):
    """A stop sequence taken from the solo decode truncates the output at the
    exact first occurrence (stop included, like eos) — independent of the
    host-sync cadence, which only changes how early the slot frees."""
    from accelerate_tpu.serving import _first_stop_end

    rng = np.random.default_rng(97)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (6, 5)]
    solo = [_solo(llama, p, 8) for p in prompts]
    stop0 = solo[0][2:4]
    # Expected truncation: FIRST completed occurrence in the solo stream (may
    # end before index 4 if the model repeats tokens).
    end0 = _first_stop_end(solo[0], (stop0,))
    assert end0 is not None
    engine = ContinuousBatcher(llama, batch_slots=2, max_new_tokens=8,
                               max_cache_len=512, cache_dtype=jnp.float32,
                               bucket_sizes=(8,), sync_every=sync_every)
    r0 = engine.submit(prompts[0], stop_sequences=[stop0])
    r1 = engine.submit(prompts[1], stop_sequences=[[9999, 9998]])  # never occurs
    outs = engine.run()
    np.testing.assert_array_equal(outs[r0], solo[0][:end0])
    np.testing.assert_array_equal(outs[r1], solo[1])
    with pytest.raises(ValueError, match="empty stop"):
        engine.submit(prompts[0], stop_sequences=[[]])


def test_capacity_reservation_covers_longest_active_request(llama):
    """A short admit must reserve for the LONGEST remaining active run, not
    its own max_new: decode columns are consumed globally until the longest
    request drains, so under-reserving would clamp cache writes onto the last
    column and silently corrupt the neighbor (r5 review finding). With a
    tight cache, the short request defers (backpressure) or the engine raises
    — and the long request's output stays exact either way."""
    rng = np.random.default_rng(100)
    long_p = rng.integers(1, 256, (6,)).astype(np.int32)
    short_p = rng.integers(1, 256, (5,)).astype(np.int32)
    long_solo = _solo(llama, long_p, 24)
    # C: fits the long request alone (8 + 24 + sync - 1 = 33) plus part of a
    # second admit bucket, but NOT a second admit + the long run's columns.
    engine = ContinuousBatcher(llama, batch_slots=2, max_new_tokens=24,
                               max_cache_len=48, cache_dtype=jnp.float32,
                               bucket_sizes=(8,), sync_every=2)
    r_long = engine.submit(long_p)  # reserves 8 + 24
    r_short = engine.submit(short_p, max_new_tokens=2)
    # Unsound reservation would admit short (8 + 2 fits in the remainder) and
    # then overflow; sound reservation backpressures it and may legitimately
    # dead-end on this tight cache after the long one retires.
    try:
        outs = engine.run()
    except RuntimeError:
        outs = dict(engine._results) if engine._results else {}
        outs.update({})
    assert r_long in outs or engine._results, "long request never finished"
    got = outs.get(r_long)
    if got is not None:
        np.testing.assert_array_equal(got, long_solo[: len(got)])
        assert all(x == 0 for x in long_solo[len(got):])
    # The recoverable path still completes the short one exactly.
    engine.reset()
    outs2 = engine.run()
    if r_short in outs2:
        np.testing.assert_array_equal(outs2[r_short], _solo(llama, short_p, 24)[:2])


def test_prefix_caching_composes_with_per_request_controls(llama):
    """set_prefix + heterogeneous per-request settings in one wave: each
    output equals the solo decode of prefix + suffix under that request's own
    controls (the two r5 serving features compose)."""
    rng = np.random.default_rng(101)
    prefix = rng.integers(1, 256, (10,)).astype(np.int32)
    sufs = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (4, 6, 3)]
    solos = [_solo(llama, np.concatenate([prefix, s]), 8) for s in sufs]
    engine = ContinuousBatcher(llama, batch_slots=2, max_new_tokens=8,
                               max_cache_len=512, cache_dtype=jnp.float32,
                               bucket_sizes=(8,), sync_every=2,
                               max_tokens_per_request=32)
    engine.set_prefix(prefix)
    r0 = engine.submit(sufs[0], max_new_tokens=3)
    r1 = engine.submit(sufs[1], temperature=0.0)
    r2 = engine.submit(sufs[2], stop_sequences=[solos[2][1:3]])
    outs = engine.run()
    np.testing.assert_array_equal(outs[r0], solos[0][:3])
    np.testing.assert_array_equal(outs[r1], solos[1])  # full 8 tokens, no eos
    # Independent oracle for the stop cut: the earliest window of solos[2]
    # equal to the bigram, end-inclusive — computed here, not via the
    # engine's own helper.
    stop2 = solos[2][1:3]
    ends = [i + 2 for i in range(len(solos[2]) - 1)
            if np.array_equal(solos[2][i:i + 2], stop2)]
    np.testing.assert_array_equal(outs[r2], solos[2][: min(ends)])


# ------------------------------------------------------- the block pool (r13)


def test_paged_chunked_prefill_exact_and_bounds_stall(llama):
    """Chunked prefill: a long prompt admitted mid-wave lands chunk-by-chunk
    between decode windows. Exactness: identical to solo decode (chunk
    boundaries are invisible to K/V). Bounded stall, structurally: while a
    decoder was active, no two prefill chunks ever ran back-to-back, and no
    chunk exceeded prefill_chunk's bucket — so a decode step waits on at most
    ONE chunk's compute (vs the whole prompt under monolithic admit)."""
    rng = np.random.default_rng(204)
    short = rng.integers(1, 256, (5,)).astype(np.int32)
    long_p = rng.integers(1, 256, (21,)).astype(np.int32)
    engine = _paged(llama, max_new_tokens=6, bucket_sizes=(8,), prefill_chunk=8,
                    max_tokens_per_request=64)
    r_short = engine.submit(short)
    r_long = engine.submit(long_p)
    outs = engine.run()
    np.testing.assert_array_equal(outs[r_short], _solo(llama, short, 6)[: len(outs[r_short])])
    np.testing.assert_array_equal(outs[r_long], _solo(llama, long_p, 6)[: len(outs[r_long])])
    assert engine.slo_report()["decisions"]["chunked_prefills"] >= 1
    log = engine._dispatch_log
    assert any(e.startswith("chunk") for e in log) and "decode" in log
    # Every chunk bounded by the prefill_chunk bucket.
    for e in log:
        if e.startswith("chunk:"):
            assert int(e.split(":")[1]) <= 8
    # After the first decode window exists, chunks interleave one-per-window.
    first_decode = log.index("decode")
    tail = log[first_decode:]
    assert all(
        not (a.startswith("chunk") and b.startswith("chunk"))
        for a, b in zip(tail, tail[1:])
    ), log


def test_paged_steady_state_loop_has_zero_blocking_transfers(llama):
    """The one-window-lookahead sync: each window's report is fetched only
    after the NEXT window is dispatched, so the loop never waits to hand an
    input to the device and fetches a fixed number of arrays a window.
    Whether a fetch found its array ready (``blocking``) is the CPU's timing
    and is not pinned."""
    from accelerate_tpu.utils.transfer import reset_transfer_stats, transfer_stats

    engine = _paged(llama, batch_slots=1, max_new_tokens=24, bucket_sizes=(8,),
                    max_tokens_per_request=40)
    rid = engine.submit(np.arange(1, 6, dtype=np.int32))
    reset_transfer_stats()
    out = engine.run()[rid]
    stats = transfer_stats()
    assert stats["h2d_blocking"] == 0
    # 24 tokens in windows of 2: the first token comes from the chunk, the
    # other 23 take 12 windows and the finish shows in the 12th report, read
    # with a 13th window in flight; a report costs two fetches (active, n_out)
    # and the finish one more (the output buffer).
    assert stats["fetches"] == 2 * 13 + 1, stats
    np.testing.assert_array_equal(out, _solo(llama, np.arange(1, 6, dtype=np.int32), 24))


def test_paged_capacity_dead_end_and_backpressure(llama):
    """A pool that cannot fit even one request dead-ends loudly; a pool sized
    for ~one request serves a queue of them in one run() — retired chains
    free at collect (block-table surgery, no device permutation)."""
    p = np.arange(1, 6, dtype=np.int32)
    tiny = _paged(llama, batch_slots=1, max_cache_len=16, bucket_sizes=(8,),
                  sync_every=1)
    tiny.submit(p)
    with pytest.raises(RuntimeError, match="capacity"):
        tiny.run()
    small = _paged(llama, batch_slots=1, max_cache_len=48, bucket_sizes=(8,),
                   sync_every=1)
    r1, r2 = small.submit(p), small.submit(p)
    outs = small.run()
    assert set(outs) == {r1, r2}
    np.testing.assert_array_equal(outs[r1], outs[r2])
    np.testing.assert_array_equal(outs[r1], _solo(llama, p, 8)[: len(outs[r1])])


def test_paged_per_request_controls_and_sampled_streams(llama):
    """Per-request max_new/temperature/eos/stop compose with paging, and
    sampled streams stay functions of (engine rng, request id) — independent
    of slot count, sync cadence, and block layout."""
    rng = np.random.default_rng(206)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (5, 6, 7)]
    solo8 = [_solo(llama, p, 8) for p in prompts]

    def wave(slots, sync):
        engine = _paged(llama, batch_slots=slots, sync_every=sync,
                        bucket_sizes=(8,), rng=jax.random.key(7))
        r0 = engine.submit(prompts[0], max_new_tokens=3)
        r1 = engine.submit(prompts[1], temperature=0.9)
        r2 = engine.submit(prompts[2], stop_sequences=[solo8[2][1:3]])
        outs = engine.run()
        return outs[r0], outs[r1], outs[r2]

    a0, a1, a2 = wave(2, 2)
    b0, b1, b2 = wave(3, 1)  # different traffic shape, same streams
    np.testing.assert_array_equal(a0, solo8[0][:3])
    np.testing.assert_array_equal(a0, b0)
    np.testing.assert_array_equal(a1, b1)  # reproducible sampled stream
    np.testing.assert_array_equal(a2, b2)
    from accelerate_tpu.serving import _first_stop_end

    end2 = _first_stop_end(solo8[2], (solo8[2][1:3],))
    np.testing.assert_array_equal(a2, solo8[2][:end2])


def test_paged_slo_admission_decisions(llama):
    """SLO steering is observable and never breaks exactness: a tiny TTFT
    target escalates a chunked prefill to monolithic; a tiny TPOT budget
    defers prefill while decoders run. Outputs stay bit-exact either way."""
    rng = np.random.default_rng(207)
    long_p = rng.integers(1, 256, (21,)).astype(np.int32)
    short = rng.integers(1, 256, (5,)).astype(np.int32)
    # TTFT pressure -> escalation (prefill_chunk 8 < largest bucket 16).
    e1 = _paged(llama, bucket_sizes=(8, 16), prefill_chunk=8,
                max_tokens_per_request=64, slo=SLOTargets(ttft_s=1e-9))
    r = e1.submit(long_p)
    out = e1.run()[r]
    np.testing.assert_array_equal(out, _solo(llama, long_p, 8)[: len(out)])
    assert e1.slo_report()["decisions"]["escalated_monolithic"] >= 1
    # TPOT pressure -> prefill deferred while the short request decodes.
    e2 = _paged(llama, bucket_sizes=(8,), prefill_chunk=8,
                max_tokens_per_request=64, slo=SLOTargets(tpot_s=1e-12))
    r_short = e2.submit(short)
    r_long = e2.submit(long_p)
    outs = e2.run()
    np.testing.assert_array_equal(outs[r_short], _solo(llama, short, 8)[: len(outs[r_short])])
    np.testing.assert_array_equal(outs[r_long], _solo(llama, long_p, 8)[: len(outs[r_long])])
    report = e2.slo_report()
    assert report["decisions"]["deferred_prefills"] >= 1
    assert len(report["ttft_s"]) == 2  # both requests' TTFT observed


def test_paged_telemetry_histograms_and_gauges(llama):
    """TTFT/TPOT histograms and KV-pool gauges publish to the registry next
    to the existing request/token counters (docs/observability.md)."""
    from accelerate_tpu.telemetry.metrics import get_registry

    registry = get_registry()
    registry.reset()
    engine = _paged(llama, max_new_tokens=6, bucket_sizes=(8,))
    rng = np.random.default_rng(208)
    rids = [engine.submit(rng.integers(1, 256, (5,)).astype(np.int32))
            for _ in range(3)]
    engine.run()
    snap = registry.snapshot()
    assert snap["accelerate_serving_ttft_seconds_count"] == 3.0
    assert snap["accelerate_serving_requests_completed_total"] == 3.0
    assert "accelerate_serving_kv_pool_blocks_free" in snap
    util = snap["accelerate_serving_kv_pool_utilization"]
    assert 0.0 <= util <= 1.0
    assert snap["accelerate_serving_kv_pool_blocks_free"] == float(engine.num_blocks)
    assert all(r in engine._req_times for r in rids)


# ------------------------------------------- two-part paged cache (PR 31)


class _JoinedCache:
    """Test oracle: the joined form of the two-part cache. It takes
    the two-part cache, concatenates view and write window into one ordinary
    cache, runs the wrapped model's one-part forward over it and hands back
    the window's columns — same mathematics, one softmax over one array."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def apply(self, params, *, cache, **kwargs):
        view = cache["view"]
        t = view["k"].shape[2]
        joined = {
            "k": jnp.concatenate([view["k"], cache["k"]], axis=2),
            "v": jnp.concatenate([view["v"], cache["v"]], axis=2),
            "kv_mask": jnp.concatenate([view["kv_mask"], cache["kv_mask"]], axis=1),
            "pos": cache["pos"] + t,
        }
        out = self._inner.apply(params, cache=joined, **kwargs)
        new = out["cache"]
        out["cache"] = {"k": new["k"][:, :, t:], "v": new["v"][:, :, t:],
                        "kv_mask": new["kv_mask"][:, t:], "pos": new["pos"] - t}
        return out


@pytest.mark.parametrize("family", ["llama", "llama_windowed", "gpt2", "gptx"])
def test_two_part_cache_forward_equals_the_joined_forward(family):
    """``apply(cache={**window, "view": view})`` for each cached decoder
    family, called directly and without ``positions`` (the token positions
    then run on from the view's length): logits and the returned window
    equal the one-part forward over the joined cache, with holes in the view
    and a window that already holds a column."""
    from accelerate_tpu.models.gptx import GPTX, GPTXConfig

    if family == "gpt2":
        model = GPT2(GPT2Config(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                                num_attention_heads=2, max_position_embeddings=64))
    elif family == "gptx":
        model = GPTX(GPTXConfig.tiny())
    else:
        model = Llama(LlamaConfig.tiny(
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            sliding_window=3 if family == "llama_windowed" else None))
    params = model.init_params(jax.random.key(5))
    b, t, w, s = 2, 12, 6, 2
    layers, _, _, hkv, d = model.init_cache(b, w, dtype=jnp.float32)["k"].shape
    rng = np.random.default_rng(23)
    rand = lambda cols: jnp.asarray(rng.standard_normal((layers, b, cols, hkv, d)), jnp.float32)
    view_mask = np.ones((b, t), np.int32)
    view_mask[0, [1, 6]], view_mask[1, 9:] = 0, 0
    view = {"k": rand(t), "v": rand(t), "kv_mask": jnp.asarray(view_mask)}
    window = {"k": rand(w).at[:, :, 1:].set(0), "v": rand(w).at[:, :, 1:].set(0),
              "pos": jnp.int32(1), "kv_mask": jnp.zeros((b, w), jnp.int32).at[:, 0].set(1)}
    ids = jnp.asarray(rng.integers(1, 100, (b, s)), jnp.int32)
    two = model.apply(params, input_ids=ids, cache={**window, "view": view})
    one = _JoinedCache(model).apply(params, input_ids=ids, cache={**window, "view": view})
    np.testing.assert_allclose(two["logits"], one["logits"], rtol=2e-5, atol=2e-5)
    assert set(two["cache"]) == {"k", "v", "pos", "kv_mask"} and int(two["cache"]["pos"]) == 1 + s
    for name in ("k", "v", "kv_mask"):
        np.testing.assert_allclose(two["cache"][name], one["cache"][name], rtol=2e-5, atol=2e-5)


def _two_part_case(name):
    """(model, prompts, shared prefix or None, paged-engine overrides, max_new)."""
    rng = np.random.default_rng(310)
    ragged = [rng.integers(1, 120, (n,)).astype(np.int32) for n in (5, 9, 3, 12, 7, 4)]
    if name == "gpt2":
        model = GPT2(GPT2Config(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                                num_attention_heads=2, max_position_embeddings=64))
        model.init_params(jax.random.key(3))
        return model, ragged[:3], None, dict(max_cache_len=64, bucket_sizes=(16,)), 5
    window = 4 if name == "sliding_window" else None
    model = Llama(LlamaConfig.tiny(sliding_window=window, **TINY))
    model.init_params(jax.random.key(0))
    if name == "plain":  # bucket-sized prompts, one-step windows: no hole anywhere
        full = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (8, 16)]
        return model, full, None, dict(sync_every=1), 8
    if name == "shared_prefix":
        prefix = rng.integers(1, 256, (12,)).astype(np.int32)
        suffixes = [rng.integers(1, 256, (n,)).astype(np.int32) for n in (4, 7, 3, 6)]
        return model, suffixes, prefix, dict(
            bucket_sizes=(8,), prefill_chunk=8, max_tokens_per_request=64), 6
    if name == "kv_quant":
        return model, ragged, None, dict(kv_quant="int8", sync_every=4), 8
    return model, ragged, None, dict(sync_every=4), 8  # bucket_holes, sliding_window


def _serve(engine, prompts, prefix):
    if prefix is not None:
        engine.set_prefix(prefix)
    rids = [engine.submit(p) for p in prompts]
    outs = engine.run()
    return [outs[r] for r in rids]


@pytest.mark.parametrize(
    "case", ["plain", "bucket_holes", "shared_prefix", "sliding_window", "kv_quant", "gpt2"])
def test_paged_two_part_cache_serves_the_joined_cache_s_tokens(case):
    """The paged programs attend (read-only view, write window) without ever
    joining them. Their tokens equal those of the same engine over the joined
    cache (the oracle above) and solo generate()'s:
    with no hole, with bucket and finished-row holes, across aliased prefix
    blocks, with a window whose valid-slot rank crosses the seam, and for a
    model with learned positions. An int8 pool is lossy against
    generate()'s float cache, so it is held to the joined oracle alone (which
    reads and writes the same quantized rows)."""
    model, prompts, prefix, overrides, max_new = _two_part_case(case)
    served = _serve(_paged(model, max_new_tokens=max_new, **overrides), prompts, prefix)
    joined = _serve(_paged(_JoinedCache(model), max_new_tokens=max_new, **overrides),
                    prompts, prefix)
    for got, want in zip(served, joined):
        np.testing.assert_array_equal(got, want)
    if case == "kv_quant":
        return
    for got, p in zip(served, prompts):
        whole = p if prefix is None else np.concatenate([prefix, p])
        np.testing.assert_array_equal(got, _solo(model, whole, max_new)[: len(got)])


def test_paged_decode_window_straddling_blocks_scatters_to_its_chain(llama):
    """One slot, 6 tokens in its chain, blocks of 4 and a window of 8 steps:
    the window's columns are chain positions 6..13, the tail of block 1, all
    of block 2 and half of block 3 of a table that lists its blocks out of
    order. After the program those pool rows, and no others, hold the
    window's keys: the same rows as under the joined oracle."""
    def window_run(model):
        engine = _paged(model, batch_slots=1, sync_every=8, max_new_tokens=16)
        params, pool, tables, lens, commit, stop, state = engine._decode_args()
        rng = np.random.default_rng(7)
        chain = np.asarray([5, 2, 7, 3], np.int32)
        tables = np.zeros_like(np.asarray(tables))
        tables[0, :4] = chain
        pool = {k: jnp.asarray(rng.standard_normal(v.shape), v.dtype) for k, v in pool.items()}
        mask = np.zeros(pool["mask"].shape, np.int32)
        mask[5, :], mask[2, :2] = 1, 1      # 6 tokens, and stale bits beyond them:
        mask[2, 2:], mask[7, :] = 1, 1      # a reused block's, which the frontier hides
        pool["mask"] = jnp.asarray(mask)
        tok, pos, n_out, active, out_buf, keys, slot_max, slot_temp, slot_eos = state
        state = (tok.at[0].set(11), pos.at[0].set(6), n_out.at[0].set(1),
                 active.at[0].set(True), out_buf, keys, slot_max.at[0].set(16),
                 slot_temp, slot_eos)
        before = {k: np.asarray(v) for k, v in pool.items()}
        new_pool, new_state, _ = engine._decode()(
            params, pool, jnp.asarray(tables), jnp.asarray([6], jnp.int32),
            jnp.asarray([True]), stop, state)
        return before, {k: np.asarray(v) for k, v in new_pool.items()}, np.asarray(new_state[4])

    before, after, tokens = window_run(llama)
    written = [(2, 2), (2, 3), (7, 0), (7, 1), (7, 2), (7, 3), (3, 0), (3, 1)]
    touched = np.zeros(before["mask"].shape, bool)
    for blk, off in written:
        touched[blk, off] = True
        assert after["mask"][blk, off] == 1
        assert not np.array_equal(after["k"][:, blk, off], before["k"][:, blk, off])
    np.testing.assert_array_equal(after["mask"][~touched], before["mask"][~touched])
    np.testing.assert_array_equal(after["k"][:, ~touched], before["k"][:, ~touched])
    np.testing.assert_array_equal(after["v"][:, ~touched], before["v"][:, ~touched])
    _, joined, joined_tokens = window_run(_JoinedCache(llama))
    np.testing.assert_array_equal(tokens, joined_tokens)
    for name in ("k", "v"):
        np.testing.assert_allclose(after[name], joined[name], rtol=1e-5, atol=1e-6)


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner)


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunk", "spec_verify"])
def test_paged_programs_never_rewrite_the_gathered_view(llama, program):
    """Holds PR 31's gain. The gathered view ``(L, B, T, Hkv, D)`` is read-only
    in every paged program: nothing concatenates onto it, updates a slice of
    it or copies it (whole, or a layer's ``(B, T, Hkv, D)`` slice of it, with
    or without a write window appended), in the traced program and in the
    compiled one, and no scan carries an array of its size from step to step
    — the decode window's carry is the window buffer. A change that hands the
    model a joined cache again fails here before it costs 2 GB of copies a
    decode step on the chip."""
    spec = dict(speculative_k=2, draft_model=llama) if program == "spec_verify" else {}
    engine = _paged(llama, sync_every=3, **spec)
    if program == "prefill_chunk":
        fn, args, wide = engine._chunk_fn(8), engine._chunk_args(8), 8
    elif program == "spec_verify":
        fn, args, wide = engine._spec_verify(), engine._verify_args(), 3
    else:
        fn, args, wide = engine._decode(), engine._decode_args(), 3
    layers, _, bs, hkv, d = engine._pool["k"].shape
    t = engine.max_blocks_per_slot * bs
    rows = 1 if program == "prefill_chunk" else engine.B  # a chunk gathers its own slot alone
    per_layer = {(rows, cols, hkv, d) for cols in (t, t + wide)}
    view_shapes = per_layer | {(layers,) + shape for shape in per_layer}
    view_size = layers * rows * t * hkv * d

    rewrites = {"concatenate", "dynamic_update_slice", "copy", "pad", "scatter"}
    scans = 0
    for eqn in _walk_eqns(fn._audit_meta["jaxpr_thunk"](*args).jaxpr):
        shapes = {tuple(v.aval.shape) for v in list(eqn.invars) + list(eqn.outvars)
                  if hasattr(v.aval, "shape")}
        if eqn.primitive.name in rewrites:
            assert not shapes & view_shapes, (eqn.primitive.name, shapes & view_shapes)
        if eqn.primitive.name == "scan":
            scans += 1
            carried = eqn.outvars[:eqn.params["num_carry"]]
            assert all(v.aval.size < view_size for v in carried), [v.aval for v in carried]
    assert scans >= (1 if program == "prefill_chunk" else 2)  # the layer scan, inside the step scan

    pattern = "|".join(r"\[" + ",".join(map(str, shape)) + r"\]" for shape in view_shapes)
    for line in fn.lower(*args).compile().as_text().splitlines():
        op = re.search(r"= \S+ (copy|concatenate|dynamic-update-slice|pad)\(", line)
        if op:
            assert not re.search(pattern, line), line.strip()[:200]


# ------------------------------------------------ the chunk program at batch 1
CHUNKED = dict(batch_slots=3, max_new_tokens=6, bucket_sizes=(8,), prefill_chunk=8,
               max_tokens_per_request=64)


def _chunked_case(name):
    """(model, paged-engine overrides) of a family that serves paged."""
    if name == "gpt2":
        return _two_part_case("gpt2")[0], dict(max_cache_len=192)
    model = Llama(LlamaConfig.tiny(qk_norm=name == "qk_norm", **TINY))
    model.init_params(jax.random.key(0))
    if name == "kv_quant":
        return model, dict(kv_quant="int8")
    if name == "speculative":
        draft = Llama(LlamaConfig.tiny(**TINY))
        draft.init_params(jax.random.key(7))  # other weights: it mispredicts
        return model, dict(speculative_k=2, draft_model=draft)
    return model, {}


@pytest.mark.parametrize("case", ["llama", "qk_norm", "gpt2", "kv_quant", "speculative"])
def test_chunked_prefill_beside_occupied_slots_serves_each_request_s_solo_tokens(case):
    """The chunk program computes its own slot's row and no other: four
    requests of unequal prompt length (1 to 4 chunks of 8) over three slots,
    so that chunks run while other slots prefill and decode, and every
    request's tokens are those of its solo generation. An int8 pool is lossy
    against ``generate()``'s float cache, so its solo is the same engine
    serving the request alone."""
    model, overrides = _chunked_case(case)
    rng = np.random.default_rng(411)
    prompts = [rng.integers(1, 120, (n,)).astype(np.int32) for n in (21, 5, 30, 13)]
    engine = _paged(model, **CHUNKED, **overrides)
    reset_spans()
    served = _serve(engine, prompts, None)
    turns = [r.attrs for r in get_span_ring().snapshot() if r.name == "serve.iteration"]
    chunks = [e for e in engine._dispatch_log if e.startswith("chunk:")]
    assert chunks == ["chunk:8"] * 10  # 3 + 1 + 4 + 2
    assert sum(t["chunk"] > 0 and t["decoding"] + t["prefilling"] >= 2 for t in turns) >= 6
    assert any(t["chunk"] > 0 and t["decoding"] >= 1 for t in turns)
    for prompt, got in zip(prompts, served):
        if case == "kv_quant":
            (want,) = _serve(_paged(model, **CHUNKED, **overrides), [prompt], None)
        else:
            want = _solo(model, prompt, 6)
        np.testing.assert_array_equal(got, want)
    stats = engine.pool_stats()
    assert stats["blocks_free"] == stats["num_blocks"]


@pytest.mark.parametrize("pool_kind", ["float", "int8"])
def test_a_chunk_dispatch_writes_its_own_slot_s_chain_tail_and_nothing_else(llama, pool_kind):
    """One chunk dispatch changes the ``p`` columns at the target slot's chain
    tail: every other pool row (other slots' chains, free blocks, the trash
    block, the chain's earlier columns), their masks and scales, and every
    other slot's row of the decode state stay bit for bit as they were — over
    a pool filled with noise, for a first chunk (an empty view) and for a
    later one (its own chain in the view)."""
    quant = dict(kv_quant="int8") if pool_kind == "int8" else {}
    engine = _paged(llama, **CHUNKED, **quant)
    rng = np.random.default_rng(97)
    for n in (21, 13, 30):
        engine.submit(rng.integers(1, 256, (n,)).astype(np.int32))
    engine._admit_paged(time.monotonic())
    assert engine._slot_mode == ["prefill"] * 3
    noise = {}
    for name, held in engine._pool.items():
        if name == "mask":
            noise[name] = jnp.asarray(rng.integers(0, 2, held.shape), held.dtype)
        elif jnp.issubdtype(held.dtype, jnp.integer):
            noise[name] = jnp.asarray(rng.integers(-127, 128, held.shape), held.dtype)
        else:
            noise[name] = jnp.asarray(rng.standard_normal(held.shape), held.dtype)
    engine._pool = noise
    state = engine._state_tuple()
    bs = engine.block_size
    for slot in (1, 1, 0):
        before = {k: np.asarray(v) for k, v in engine._pool.items()}
        state_before = [np.asarray(leaf) for leaf in state[:5]]
        start = int(engine._slot_len[slot])
        p, tokens, _ = engine._next_chunk(slot)
        state = engine._dispatch_chunk(slot, state)
        after = {k: np.asarray(v) for k, v in engine._pool.items()}
        written = np.zeros(before["mask"].shape, bool)
        for col in range(start, start + p):
            written[engine._slot_blocks[slot][col // bs], col % bs] = True
        assert p == 8 and written.sum() == p and int(engine._slot_len[slot]) == start + p
        assert after["mask"][written].sum() == tokens  # a bucket's padding stays a hole
        np.testing.assert_array_equal(after["mask"][~written], before["mask"][~written])
        for name in set(before) - {"mask"}:  # (L, blocks, block_size, ...)
            assert not np.array_equal(after[name][:, written], before[name][:, written])
            np.testing.assert_array_equal(after[name][:, ~written], before[name][:, ~written])
        others = [s for s in range(engine.B) if s != slot]
        for was, now in zip(state_before, state[:5]):
            np.testing.assert_array_equal(np.asarray(now)[others], was[others])


@pytest.mark.parametrize("mode", ["plain", "speculative"])
def test_the_chunk_program_hands_the_model_one_row(llama, mode):
    """In the lowered ``serve_prefill_chunk_<p>`` the model sees ``input_ids``
    of ``(1, P)``: no array of shape ``(batch_slots, P, ...)`` and no view with
    a leading ``batch_slots`` exists anywhere in the program, for the target
    and under ``speculative_k`` for the draft alike; the one-row forms do."""
    spec = dict(speculative_k=2, draft_model=llama) if mode == "speculative" else {}
    engine = _paged(llama, batch_slots=3, max_new_tokens=6, bucket_sizes=(16,), **spec)
    slots, p = engine.B, 16
    fn, args = engine._chunk_fn(p), engine._chunk_args(p)
    assert fn.lower(*args).as_text().startswith(f"module @jit_serve_prefill_chunk_{p}")
    layers, _, bs, hkv, d = engine._pool["k"].shape
    t = engine.max_blocks_per_slot * bs
    hidden = llama.config.hidden_size
    assert slots > 1 and p not in (engine.max_new, engine.max_blocks_per_slot)
    seen = collections.Counter()
    for eqn in _walk_eqns(fn._audit_meta["jaxpr_thunk"](*args).jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            shape = tuple(getattr(v.aval, "shape", ()))
            assert shape[:2] != (slots, p), (eqn.primitive.name, shape)
            assert shape[:2] != (layers, slots) or len(shape) < 5, (eqn.primitive.name, shape)
            seen[shape] += 1
    copies = 2 if mode == "speculative" else 1
    assert seen[(1, p, hidden)] >= copies                # the residual stream, one row
    assert seen[(layers, 1, t, hkv, d)] >= 2 * copies    # the view's K and V
    assert seen[(layers, 1, p, hkv, d)] >= 2 * copies    # the write window's
    assert seen[(1, t)] >= copies and not seen[(slots, t)]  # the view's mask
