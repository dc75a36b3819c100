"""MiniCPM-SALA (``models/minicpm_sala.py``): block-sparse attention layers
beside lightning linear-attention layers, against the plain reference
(``chipbench/reference_minicpm_sala.py``) on seeded weights, and the paged
engine's second kind of state (a recurrent state held by slot):

(a) logits of the plain forward against the reference at the published block
    sizes, over more than 64 blocks of context and two periods;
(b) chunked prefill (chunks of two sizes) then paged decode through
    ``ContinuousBatcher`` against the reference's full forward, by logits;
(c) a slot mid-prefill keeps its state bit for bit through other rows' decode
    windows and other slots' chunks;
(d) a slot reused by a new request starts from a zero state;
(e) the selection equals the reference's on float32 inputs, for a plain
    sequence and across the view / new-keys split;
(f) prefix sharing, speculative decoding, chain export and the contiguous
    engine each refuse or stand down for this model, in words;
(g) the fingerprints of the Llama programs are what they were.
"""

import dataclasses
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import Llama, LlamaConfig, MiniCPMSALA, MiniCPMSALAConfig
from accelerate_tpu.ops import lightning_attention as la
from accelerate_tpu.ops import sparse_attention as sa
from accelerate_tpu.ops.paged_attention import (PLAIN_CACHE_LAYOUT, cache_layout, init_kv_pool,
                                                pool_bytes)
from accelerate_tpu.serving import ContinuousBatcher
from accelerate_tpu.telemetry import get_span_ring, reset_spans
from chipbench import reference_minicpm_sala as reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = dict(sparse_window=256, sparse_topk=8)  # a selection that bites at a thousand tokens


def build(**kw):
    cfg = MiniCPMSALAConfig.tiny(num_hidden_layers=8, residual_depth=32, **kw)
    model = MiniCPMSALA(cfg)
    return model, model.init(jax.random.key(1)), dataclasses.asdict(cfg)


@pytest.fixture(scope="module")
def small():
    return build(**SMALL)


def engine_for(model, params, **overrides):
    kw = dict(params=params, batch_slots=3, max_new_tokens=24, max_cache_len=3 * 1536,
              block_size=64, prefill_chunk=128, max_tokens_per_request=1400,
              cache_dtype=jnp.float32, bucket_sizes=(16, 32, 64, 128))
    kw.update(overrides)
    return ContinuousBatcher(model, **kw)


def prompts_of(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).astype(np.int32) for n in lengths]


def gaps_against_reference(params, cfg, prompt, served):
    """At each served token: the reference's best logit less its logit of the
    token served, and the reference's margin between its two best."""
    ids = jnp.asarray(np.concatenate([prompt, served]))
    logits, _ = reference.logits_at(params, ids, len(prompt) - 1, len(served), cfg)
    logits = np.asarray(logits)
    ranked = np.sort(logits, axis=-1)
    return logits.max(-1) - logits[np.arange(len(served)), served], ranked[:, -1] - ranked[:, -2]


# ------------------------------------------------------- (a) the plain forward
def test_plain_forward_agrees_with_the_reference_at_the_published_block_sizes():
    model, params, cfg = build()  # blocks of 64, top 64, window 2048, kernel 32 at stride 16
    assert (cfg["sparse_block_size"], cfg["sparse_topk"], cfg["sparse_window"]) == (64, 64, 2048)
    assert model.config.period == ("minicpm4",) + ("lightning-attn",) * 3  # two periods of four
    seq = 72 * 64 + 40  # more than 64 blocks of context: the selection discards keys
    ids = jax.random.randint(jax.random.key(2), (1, seq), 1, 256)
    with jax.default_matmul_precision("highest"):
        ours = np.asarray(jax.jit(lambda p, i: model.apply(p, input_ids=i)["logits"])(params, ids))[0]
    watch = (10, 2100, 4200, seq - 1)
    theirs, selected = reference.logits_at(params, ids[0], 0, seq, cfg, watch=watch)
    assert np.abs(ours - np.asarray(theirs)).max() < 2e-5 * max(1.0, np.abs(theirs).max())
    chosen = np.asarray(selected).sum(-1)  # (Ls, watched, G)
    assert (chosen[:, 0] == 1).all() and (chosen[:, 1] == 33).all() and (chosen[:, 2:] == 64).all()
    mine = np.asarray(model.selected_blocks(params, ids, jnp.asarray(watch)))
    assert (mine[:, 0] == np.asarray(selected)).all()


@pytest.mark.parametrize("types", [("lightning-attn", "minicpm4"),
                                   ("minicpm4", "minicpm4", "lightning-attn", "minicpm4")])
def test_plain_forward_with_other_type_lists(types):
    cfg = MiniCPMSALAConfig.tiny(num_hidden_layers=len(types), mixer_types=types, **SMALL)
    model = MiniCPMSALA(cfg)
    params = model.init(jax.random.key(3))
    ids = jax.random.randint(jax.random.key(4), (2, 700), 1, 256)
    with jax.default_matmul_precision("highest"):
        ours = np.asarray(model.apply(params, input_ids=ids)["logits"])
    for row in range(2):
        theirs, _ = reference.logits_at(params, ids[row], 0, 700, dataclasses.asdict(cfg))
        assert np.abs(ours[row] - np.asarray(theirs)).max() < 2e-5


def test_the_period_is_the_shortest_prefix_that_repeats():
    period = ("minicpm4", "lightning-attn", "lightning-attn", "lightning-attn")
    assert MiniCPMSALAConfig.tiny(num_hidden_layers=12).period == period
    irregular = ("minicpm4", "lightning-attn", "lightning-attn", "minicpm4")
    assert MiniCPMSALAConfig.tiny(num_hidden_layers=4, mixer_types=irregular).period == irregular
    with pytest.raises(ValueError, match="mixer_types has 3 entries for 4 layers"):
        MiniCPMSALAConfig.tiny(mixer_types=period[:3])
    with pytest.raises(ValueError, match="does not implement: attn_use_rope"):
        MiniCPMSALAConfig.tiny(attn_use_rope=True)


def test_the_plain_cache_and_padding_masks_are_refused_in_words(small):
    model, params, _ = small
    with pytest.raises(NotImplementedError, match="two-part cache"):
        model.apply(params, input_ids=jnp.ones((1, 4), jnp.int32), cache=model.init_cache(1, 64))
    with pytest.raises(ValueError, match="padding\\s+masks are implemented on the cached"):
        model.apply(params, input_ids=jnp.ones((1, 4), jnp.int32),
                    attention_mask=jnp.ones((1, 4), jnp.int32))


def test_int8_weights_reach_every_projection(small):
    """The serving engine's ``matmul_precision`` swaps ``_mm`` for this model as
    for the others: with int8 weights the logits move away from float32 by
    more than bf16 alone moves them."""
    from accelerate_tpu.generation import _precision_variant

    model, params, _ = small
    ids = jax.random.randint(jax.random.key(5), (1, 384), 1, 256)
    half = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    with jax.default_matmul_precision("highest"):
        exact = np.asarray(model.apply(
            jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), half), input_ids=ids)["logits"])
    as_configured = np.asarray(model.apply(half, input_ids=ids)["logits"])
    quantized = np.asarray(_precision_variant(model, "int8").apply(half, input_ids=ids)["logits"])
    assert as_configured.dtype == np.float32  # float32 logits and residual stream over bf16 weights
    assert np.abs(quantized - exact).mean() > 2 * np.abs(as_configured - exact).mean() > 0


# ------------------------------------------------------------ the two mixers
def test_lightning_attention_is_the_recurrence_and_masked_tokens_do_nothing():
    b, s, h, d = 2, 300, 4, 16
    keys = jax.random.split(jax.random.key(0), 4)
    q, k, v = (jax.random.normal(x, (b, s, h, d)) for x in keys[:3])
    state = jax.random.normal(keys[3], (b, h, d, d))
    slopes = la.decay_log_slopes(h)
    assert np.allclose(np.exp(slopes), np.exp(-2.0 ** (-8.0 * np.arange(1, h + 1) / h)))
    mask = np.ones((b, s), np.int32)
    mask[0, 17:40] = 0
    mask[1] = 0  # a row the engine rides along masked
    out, new = la.lightning_attention(q, k, v, state, slopes, mask=jnp.asarray(mask), block=64)
    want, want_out = np.asarray(state, np.float64), np.zeros((b, s, h, d))
    for t in range(s):
        for row in range(b):
            if mask[row, t]:
                want[row] = np.exp(slopes)[:, None, None] * want[row] + np.einsum(
                    "hd,he->hde", np.asarray(k[row, t], np.float64), np.asarray(v[row, t], np.float64))
            want_out[row, t] = np.einsum("hd,hde->he", np.asarray(q[row, t], np.float64), want[row])
    valid = mask.astype(bool)
    scale = np.abs(want_out[valid]).max()  # float32 against float64: a relative bound
    assert np.abs(np.asarray(out)[valid] - want_out[valid]).max() < 1e-5 * scale
    assert np.abs(np.asarray(new) - want).max() < 1e-5 * np.abs(want).max()
    assert np.array_equal(np.asarray(new[1]), np.asarray(state[1]))  # bit for bit
    one, stepped = la.lightning_attention(q[:, :1], k[:, :1], v[:, :1], state, slopes)  # a decode step
    first, _ = la.lightning_attention(q, k, v, state, slopes, block=64)
    assert np.abs(np.asarray(one) - np.asarray(first[:, :1])).max() < 1e-5 * scale


def reference_selection(q, k, positions, geo):
    """The reference's selection for queries ``q`` (n, H, D) at ``positions``
    over the keys ``k`` (S, G, D) of one sequence: (G, n, blocks) bool."""
    cfg = dict(sparse_block_size=geo.block, sparse_kernel_size=geo.kernel,
               sparse_kernel_stride=geo.stride, sparse_init_blocks=geo.init_blocks,
               sparse_window=geo.window, sparse_topk=geo.topk)
    seq, groups = k.shape[0], k.shape[1]
    windows = max(0, (seq - geo.kernel) // geo.stride + 1)
    inside = geo.stride * np.arange(windows)[:, None] + np.arange(geo.kernel)[None]
    kbar = jnp.asarray(k)[inside].mean(axis=1)
    grouped = q.reshape(q.shape[0], groups, -1, q.shape[-1])
    return np.asarray(reference.selected_blocks(grouped, kbar, jnp.asarray(positions), cfg,
                                                -(-seq // geo.block)))


# -------------------------------------------------------------- (e) selection
@pytest.mark.parametrize("geo,seq", [(sa.SparseGeometry(), 4800),
                                     (sa.SparseGeometry(topk=8, window=256), 1216)])
def test_selection_equals_the_reference_s_on_a_plain_sequence(geo, seq):
    heads, groups, dim = 4, 2, 16
    kq, kk = jax.random.split(jax.random.key(7))
    q = jax.random.normal(kq, (1, seq, heads, dim))
    k = jax.random.normal(kk, (1, seq, groups, dim))
    positions = jnp.arange(seq)[None]
    kbar = sa.compress_keys(k, geo)
    scores = sa.block_scores(q, positions, kbar, jnp.full((1,), kbar.shape[1]), geo)
    index, chosen, mask = sa.select_blocks(scores, positions, jnp.full((1,), seq), geo)
    theirs = reference_selection(q[0], k[0], np.arange(seq), geo)
    assert (np.asarray(mask[0]) == theirs).all()
    assert int(theirs.sum(-1).max()) == min(geo.topk, seq // geo.block)
    # The indices a decode step gathers are the mask's blocks.
    picked = np.zeros_like(theirs)
    g, t, c = np.nonzero(np.asarray(chosen[0]))
    picked[g, t, np.asarray(index[0])[g, t, c]] = True
    assert (picked == theirs).all()


@pytest.mark.parametrize("view_len,new", [(1000, 128), (1024, 64), (37, 100), (0, 128), (1111, 8)])
def test_selection_across_the_view_and_the_new_keys(view_len, new):
    """The engine's split: a dense view below ``view_len`` and ``new`` keys
    after it, some of them padding (left-aligned holes, as a final chunk has)."""
    geo = sa.SparseGeometry(topk=8, window=256)
    heads, groups, dim, columns = 4, 2, 16, 1280
    real = new - 5 if new > 8 else new
    total = view_len + real
    keys = jax.random.split(jax.random.key(11), 3)
    k_all = jax.random.normal(keys[0], (total, groups, dim))
    v_all = jax.random.normal(keys[1], (total, groups, dim))
    q_new = jax.random.normal(keys[2], (new, heads, dim))
    pad = lambda x, n: jnp.pad(x, ((0, n - x.shape[0]), (0, 0), (0, 0)))
    valid = jnp.asarray([0] * (new - real) + [1] * real)  # holes first
    place = lambda x: jnp.concatenate([jnp.zeros((new - real,) + x.shape[1:]), x])
    positions = view_len + jnp.cumsum(valid) - 1
    out, mask = sa.sparse_attention(
        q_new[None], positions[None], pad(k_all[:view_len], columns)[None],
        pad(v_all[:view_len], columns)[None], jnp.asarray([view_len]), geo,
        k_new=place(k_all[view_len:])[None], v_new=place(v_all[view_len:])[None],
        new_pos=positions[None], new_valid=valid[None], return_selection=True, query_tile=32)
    theirs = reference_selection(q_new[new - real:], k_all, np.arange(view_len, total), geo)
    in_view = -(-view_len // geo.block)
    assert (np.asarray(mask[0])[:, new - real:, :in_view] == theirs[:, :, :in_view]).all()
    assert not np.asarray(mask[0])[:, :, in_view:].any()
    # And the attention itself, against dense masked softmax over the reference's selection.
    column = np.arange(total)
    allowed = theirs[:, :, column // geo.block] & (column[None, None] <= np.arange(view_len, total)[None, :, None])
    qg = np.asarray(q_new[new - real:]).reshape(real, groups, heads // groups, dim)
    s = np.einsum("tgrd,sgd->grts", qg, np.asarray(k_all)) / np.sqrt(dim)
    s = np.where(allowed[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("grts,sgd->tgrd", p / p.sum(-1, keepdims=True), np.asarray(v_all))
    assert np.abs(np.asarray(out[0, new - real:]).reshape(want.shape) - want).max() < 1e-5


def test_sparse_attention_refuses_what_it_cannot_do_in_words():
    geo = sa.SparseGeometry(topk=8, window=256)
    q, k = jnp.zeros((1, 4, 4, 16)), jnp.zeros((1, 100, 2, 16))
    with pytest.raises(ValueError, match="view of whole blocks: 100 columns is not a multiple of 64"):
        sa.sparse_attention(q, jnp.zeros((1, 4), jnp.int32), k, k, jnp.asarray([100]), geo)
    wide = jnp.zeros((1, 192, 2, 16))
    with pytest.raises(ValueError, match="at most 160 new keys a program"):
        sa.sparse_attention(q, jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 128, 2, 16)),
                            jnp.zeros((1, 128, 2, 16)), jnp.asarray([100]), geo, k_new=wide,
                            v_new=wide, new_pos=jnp.zeros((1, 192), jnp.int32),
                            new_valid=jnp.ones((1, 192), jnp.int32))
    with pytest.raises(ValueError, match="kernel == 2 \\* stride"):
        sa.SparseGeometry(kernel=48)


# ------------------------------------------------- (b) through the paged engine
@pytest.fixture(scope="module")
def served(small):
    """One wave through the paged engine: five prompts over three slots, so
    that slots are reused; chunks of 128 and final chunks of smaller buckets."""
    model, params, cfg = small
    engine = engine_for(model, params)
    prompts = prompts_of(1100, 700, 333, 900, 70)
    news = (24, 10, 17, 24, 5)
    reset_spans()
    with jax.default_matmul_precision("highest"):
        rids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
        outputs = engine.run()
    return engine, prompts, [outputs[r] for r in rids], get_span_ring().snapshot()


@pytest.mark.parametrize("which", range(5))
def test_chunked_prefill_and_paged_decode_agree_with_the_reference(small, served, which):
    _, params, cfg = small
    engine, prompts, outputs, _ = served
    assert {d for d in engine._dispatch_log if d.startswith("chunk")} >= {"chunk:128", "chunk:64"}
    gaps, margins = gaps_against_reference(params, cfg, prompts[which], outputs[which])
    assert len(outputs[which]) == (24, 10, 17, 24, 5)[which]
    assert gaps.max() < 1e-4, (gaps.max(), margins.min())  # logits, not tokens: a near tie may flip


def test_the_engine_s_spans_carry_the_new_counts(served):
    engine, _, _, records = served
    windows = [r.attrs for r in records if r.name == "serve.dispatch_decode"]
    chunks = [r.attrs for r in records if r.name == "serve.dispatch_chunk"]
    # (a window whose only row finished before it began attends nothing of nothing)
    assert windows and all(0 <= w["attended_keys"] <= w["context_keys"] for w in windows)
    assert all(w["attended_keys"] > 0 for w in windows if w["context_keys"])
    assert any(w["attended_keys"] < 0.5 * w["context_keys"] for w in windows)
    assert chunks and all(c["rows_computed"] == c["p"] and c["tokens"] <= c["p"] for c in chunks)
    stats = engine.pool_stats()
    assert stats["state_slots_in_use"] == 0 and stats["blocks_free"] == stats["num_blocks"]
    # 2 sparse layers x 2 heads x 16 x (k, v) x 4 bytes; 6 lightning layers x 4 heads x 16 x 16 x 4.
    assert stats["pool_bytes"] == stats["kv_bytes"] + stats["state_bytes"] == engine.kv_cache_bytes
    assert stats["state_bytes"] == 3 * 6 * 4 * 16 * 16 * 4


def test_cache_layout_record_and_pool_layout(small):
    model, params, _ = small
    reset_spans()
    engine = engine_for(model, params)
    (record,) = [r for r in get_span_ring().snapshot() if r.name == "serve.cache_layout"]
    assert record.attrs == {"kv_bytes_per_token": 2 * 2 * 16 * 2 * 4, "state_bytes_per_slot": 6 * 4 * 16 * 16 * 4,
                            "kv_layers": 2, "state_layers": 6, "slot_bytes": {"state": 6 * 4 * 16 * 16 * 4}}
    assert cache_layout(model)["by_slot"] == ("state",) and cache_layout(object()) == PLAIN_CACHE_LAYOUT
    pool = init_kv_pool(model, 10, 64, dtype=jnp.bfloat16, slots=5)
    assert pool["k"].shape == (2, 11, 64, 2, 16) and pool["state"].shape == (6, 5, 4, 16, 16)
    assert pool["state"].dtype == jnp.float32
    assert pool_bytes(pool, cache_layout(model)) == {"kv": 2 * pool["k"].nbytes, "state": pool["state"].nbytes}
    llama = Llama(LlamaConfig.tiny())
    plain = init_kv_pool(llama, 10, 16)
    assert set(plain) == {"k", "v", "mask"} and pool_bytes(plain)["state"] == 0

    class Odd:
        cache_layout = {"by_token": ("keys",)}

    # A model names its own token-paged entries (PR 38: the refusal went with the k/v-only pool).
    assert cache_layout(Odd())["by_token"] == ("keys",)


# ------------------------------------------ (c) and (d): the state held by slot
def test_a_slot_mid_prefill_keeps_its_state_bit_for_bit(small):
    model, params, cfg = small
    engine = engine_for(model, params, batch_slots=2)
    long, short = prompts_of(700, 40, seed=3)
    with jax.default_matmul_precision("highest"):
        rid_long, rid_short = engine.submit(long, max_new_tokens=8), engine.submit(short, max_new_tokens=24)
        engine._admit_paged(time.monotonic())
        state = engine._state_tuple()
        for _ in range(2):  # two of the long prompt's six chunks
            state = engine._dispatch_chunk(0, state)
        held = {name: np.asarray(engine._pool[name][:, 0]) for name in ("state",)}
        chain = np.asarray(engine._pool["k"][:, engine._slot_blocks[0][:4]])
        assert np.abs(held["state"]).max() > 0 and engine._slot_len[0] == 256
        state = engine._dispatch_chunk(1, state)  # another slot's chunk (its only one)
        assert engine._slot_mode == ["prefill", "decode"]
        for _ in range(2):  # other rows' decode windows
            state, _ = engine._dispatch_decode(state, np.zeros((2,), bool))
        assert np.array_equal(np.asarray(engine._pool["state"][:, 0]), held["state"])
        assert np.array_equal(np.asarray(engine._pool["k"][:, engine._slot_blocks[0][:4]]), chain)
        assert np.abs(np.asarray(engine._pool["state"][:, 1])).max() > 0
        outputs = engine.run()  # and the wave still ends right
    for rid, prompt in ((rid_long, long), (rid_short, short)):
        gaps, _ = gaps_against_reference(params, cfg, prompt, outputs[rid])
        assert gaps.max() < 1e-4


def test_a_chunk_dispatch_leaves_other_slots_state_and_blocks_bit_for_bit(small):
    """The chunk program takes its own slot's state and chain and no other:
    with all three slots mid-prefill, one chunk of slot 1 changes slot 1's
    state and the 128 columns at its chain's tail; the other slots' state and
    every other pool row are bit for bit what they were."""
    model, params, _ = small
    engine = engine_for(model, params)
    with jax.default_matmul_precision("highest"):
        for prompt in prompts_of(700, 500, 600, seed=11):
            engine.submit(prompt, max_new_tokens=4)
        engine._admit_paged(time.monotonic())
        state = engine._state_tuple()
        for slot in (0, 2, 2, 1):  # every slot holds a state and a chain of its own
            state = engine._dispatch_chunk(slot, state)
        before = {k: np.asarray(v) for k, v in engine._pool.items()}
        state = engine._dispatch_chunk(1, state)
        after = {k: np.asarray(v) for k, v in engine._pool.items()}
    assert engine._slot_mode == ["prefill"] * 3 and list(engine._slot_len) == [128, 256, 256]
    assert all(np.abs(before["state"][:, s]).max() > 0 for s in range(3))
    np.testing.assert_array_equal(after["state"][:, [0, 2]], before["state"][:, [0, 2]])
    assert not np.array_equal(after["state"][:, 1], before["state"][:, 1])
    written = np.zeros(before["mask"].shape, bool)
    written[engine._slot_blocks[1][2:4]] = True  # columns 128..255 of its chain: two blocks of 64
    assert (after["mask"][written] == 1).all()
    np.testing.assert_array_equal(after["mask"][~written], before["mask"][~written])
    for name in ("k", "v"):
        np.testing.assert_array_equal(after[name][:, ~written], before[name][:, ~written])
        assert np.abs(after[name][:, written]).min() > 0


def test_the_chunk_program_hands_the_model_one_row_and_one_slot_s_state(small):
    """No array with a leading ``batch_slots`` reaches the model in the lowered
    chunk program: ids ``(1, P)``, the view and the state of one slot."""
    model, params, cfg = small
    engine = engine_for(model, params)
    slots, p = engine.B, 128
    text = engine._chunk_fn(p).lower(*engine._chunk_args(p)).as_text()
    sparse, _, bs, hkv, d = engine._pool["k"].shape
    lightning, _, heads, dl, _ = engine._pool["state"].shape
    t = engine.max_blocks_per_slot * bs
    assert text.startswith(f"module @jit_serve_prefill_chunk_{p}")
    assert slots == 3 and f"tensor<{slots}x{p}x" not in text and f"tensor<1x{p}xi32>" in text
    assert f"tensor<1x{p}x{cfg['hidden_size']}xf32>" in text
    assert f"tensor<{sparse}x1x{t}x{hkv}x{d}x" in text and f"tensor<{sparse}x{slots}x{t}x" not in text
    assert f"tensor<{lightning}x1x{heads}x{dl}x{dl}xf32>" in text  # beside the pool's own, an argument and a result


@pytest.mark.parametrize("which", [1, 2, 4])
def test_a_request_served_beside_others_gets_its_solo_tokens(small, served, which):
    """Requests of 700, 333 and 70 tokens of the wave above (five requests,
    three slots, chunks of 128 beside slots that prefill and decode) against
    the same engine serving each alone: the same tokens."""
    model, params, _ = small
    _, prompts, outputs, records = served
    turns = [r.attrs for r in records if r.name == "serve.iteration"]
    assert sum(t["chunk"] > 0 and t["decoding"] + t["prefilling"] >= 2 for t in turns) >= 10
    engine = engine_for(model, params)
    with jax.default_matmul_precision("highest"):
        rid = engine.submit(prompts[which], max_new_tokens=len(outputs[which]))
        alone = engine.run()[rid]
    np.testing.assert_array_equal(outputs[which], alone)


def test_a_reused_slot_starts_from_a_zero_state(small):
    model, params, cfg = small
    engine = engine_for(model, params, batch_slots=1)
    first, second = prompts_of(300, 500, seed=5)
    with jax.default_matmul_precision("highest"):
        engine.submit(first, max_new_tokens=8)
        engine.run()
        left_behind = np.asarray(engine._pool["state"][:, 0])
        assert np.abs(left_behind).max() > 0  # nothing scrubbed it at release
        rid = engine.submit(second, max_new_tokens=12)
        output = engine.run()[rid]
    gaps, _ = gaps_against_reference(params, cfg, second, output)
    assert gaps.max() < 1e-4


# ------------------------------------------------------------- (f) refusals
def test_prefix_sharing_stands_down_for_a_model_that_carries_state(small):
    from accelerate_tpu.serving import _serving_counters

    model, params, cfg = small
    engine = engine_for(model, params, batch_slots=2, prefill_chunk=64)
    (prompt,) = prompts_of(400, seed=9)
    refused = _serving_counters()[3]
    before = refused.value()
    with jax.default_matmul_precision("highest"):
        a = engine.submit(prompt, max_new_tokens=6)
        outputs = engine.run()
        assert engine.prefix_match_tokens(prompt) == 0 and engine.pool_stats()["shared_blocks"] == 0
        b = engine.submit(prompt, max_new_tokens=6)  # the same prompt again: prefilled again
        outputs.update(engine.run())
    assert engine.slo_report()["decisions"]["aliased_blocks"] == 0
    assert refused.value() >= before + 3  # two admissions and the lookup above
    assert np.array_equal(outputs[a], outputs[b])
    gaps, _ = gaps_against_reference(params, cfg, prompt, outputs[b])
    assert gaps.max() < 1e-4


def test_speculative_decoding_and_chain_export_are_refused_in_words(small):
    from accelerate_tpu.serving_net.handoff import export_chain

    model, params, _ = small
    with pytest.raises(ValueError, match="cannot roll back the recurrent state"):
        engine_for(model, params, speculative_k=2, draft_model=model)
    engine = engine_for(model, params)
    engine.submit(prompts_of(20)[0], max_new_tokens=4)
    with pytest.raises(ValueError, match="carries recurrent state held by slot"):
        export_chain(engine, 0)


# ---------------------------------------------------------- (g) fingerprints
@pytest.mark.parametrize("config", ["decode_paged", "decode_paged_int8", "prefill_paged"])
def test_the_llama_programs_fingerprints_did_not_move(config):
    from accelerate_tpu.analysis.fingerprint import canonical_json
    from accelerate_tpu.commands.fingerprint import extract_config

    with open(os.path.join(REPO, "tests", "goldens", f"fingerprint_{config}.json")) as f:
        assert canonical_json(extract_config(config)) == f.read()


def test_the_benchmark_s_configuration_builds_this_model():
    with open(os.path.join(REPO, "chipbench", "configs", "minicpm-sala-L12.json")) as f:
        config = json.load(f)
    fields = {f.name for f in dataclasses.fields(MiniCPMSALAConfig)}
    cfg = MiniCPMSALAConfig(**{k: v for k, v in config.items() if k in fields})
    assert cfg.period == tuple(config["mixer_types"][:4]) and cfg.residual_depth == 32
    assert MiniCPMSALA(cfg).num_params() == 3_930_007_808
