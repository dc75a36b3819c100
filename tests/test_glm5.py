"""GLM-5 (``models/glm5.py``): latent attention over keys chosen by a learned
indexer, sigmoid-routed experts held as a share beside a shared expert, against
the plain reference (``chipbench/reference_glm5.py``) on seeded weights, in
float32 at the highest matmul precision; the paged engine's named token-paged
entries (a latent row and an indexer's key of different widths); the sigmoid
router of ``ops/moe.py``:

(a) logits of the plain forward against the reference, whole and in query
    tiles, as a share, with what the indexers select and the routers choose;
(b) chunked prefill (chunks that cross blocks and buckets) then paged decode
    through ``ContinuousBatcher`` against the reference's full forward, slots
    reused; the cached forward's logits chunk by chunk, with its counts;
(c) the absorbed form against the expanded one, a context within
    ``index_topk`` against dense MLA, the selection's ties (and its causal edge
    in (a)), the rotation by hand;
(d) the sigmoid router, and the sixteen shares adding up to the uncut layer;
(e) the pool's two entries, prefix sharing, int8 blocks, chain handoff, the
    int8 weights' path; what is refused, in words;
(f) the engine's counters reach the spans; a free slot's row claims no expert.

Tolerances: 1e-4 on logits of unit scale in float32 at the highest precision
(the program's grouped products and tiles sum in another order than the
reference: readings are 1e-6 to 1e-5); int8 forms are held to be close, not equal.
"""

import dataclasses
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import Glm5, Glm5Config, Laguna, LagunaConfig
from accelerate_tpu.models import glm5
from accelerate_tpu.ops import moe
from accelerate_tpu.ops.paged_attention import cache_layout, init_kv_pool, pool_bytes, token_bytes
from accelerate_tpu.serving import ContinuousBatcher
from accelerate_tpu.telemetry import get_span_ring, reset_spans
from chipbench import reference_glm5 as reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPTS, NEWS = (5, 37, 61, 90, 23), (12, 9, 12, 10, 12)


def build(**kw):
    """The tiny preset: hidden 64, 3 layers as dense + 2, 4 heads (12 + 8 and
    16), ranks 32 and 16, an indexer of 2 heads of 16 that keeps 16 keys, 8
    experts top 2 beside one shared, scale 2.5."""
    cfg = Glm5Config.tiny(**kw)
    model = Glm5(cfg)
    return model, model.init(jax.random.key(1)), dataclasses.asdict(cfg)


@pytest.fixture(scope="module")
def small():
    return build()


def engine_for(model, params, **overrides):
    kw = dict(params=params, batch_slots=2, max_new_tokens=12, max_cache_len=3 * 128, block_size=8,
              prefill_chunk=24, max_tokens_per_request=110, cache_dtype=jnp.float32,
              bucket_sizes=(8, 16, 32))
    kw.update(overrides)
    return ContinuousBatcher(model, **kw)


def prompts_of(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).astype(np.int32) for n in lengths]


def reference_logits(params, cfg, ids, start, rows, watch=(0,)):
    logits, seen = reference.logits_at(params, jnp.asarray(ids), start, rows, cfg, watch=watch)
    return np.asarray(logits), seen


def gaps_against_reference(params, cfg, prompt, served):
    """At each served token: the reference's best logit less its logit of the
    token served."""
    logits, _ = reference_logits(params, cfg, np.concatenate([prompt, served]), len(prompt) - 1, len(served))
    return logits.max(-1) - logits[np.arange(len(served)), served]


# ------------------------------------------------- (a) the plain forward pass
@pytest.mark.parametrize("tile", [128, 16], ids=["whole", "query_tiles_of_16"])
def test_plain_forward_agrees_with_the_reference(monkeypatch, small, tile):
    monkeypatch.setattr(glm5, "QUERY_TILE", tile)
    model, params, cfg = small
    (ids,) = prompts_of(70, seed=tile)  # past index_topk 16: the selection discards keys
    with jax.default_matmul_precision("highest"):
        ours = np.asarray(jax.jit(lambda p, i: model.apply(p, i)["logits"])(params, ids[None]))[0]
    theirs, _ = reference_logits(params, cfg, ids, 0, 70)
    assert np.abs(theirs).max() > 1.0 and np.abs(ours - theirs).max() < 1e-4


@pytest.mark.parametrize("first", [0, 2, 6])
def test_plain_forward_of_a_share_agrees_with_the_reference_given_the_same_share(first):
    model, params, cfg = build(n_routed_experts=2, router_experts=8, first_expert=first)
    (ids,) = prompts_of(40, seed=first)
    with jax.default_matmul_precision("highest"):
        ours = np.asarray(model.apply(params, ids[None])["logits"])[0]
    theirs, _ = reference_logits(params, cfg, ids, 0, 40)
    assert np.abs(ours - theirs).max() < 1e-4


def test_selected_keys_and_routed_experts_are_what_the_reference_chooses(small):
    model, params, cfg = small
    (ids,) = prompts_of(64, seed=4)
    watch = np.asarray([3, 15, 16, 40, 63], np.int32)
    _, seen = reference_logits(params, cfg, ids, 0, 1, watch=watch)
    with jax.default_matmul_precision("highest"):
        keys = np.asarray(model.selected_keys(params, jnp.asarray(ids[None]), jnp.asarray(watch)))[:, 0]
        experts = np.asarray(model.routed_experts(params, jnp.asarray(ids[None]), jnp.asarray(watch)))[:, 0]
    theirs = np.asarray(seen["selected_keys"])
    assert keys.shape == theirs.shape == (3, 5, 64) and (keys == theirs).all()
    # All of its causal context while that is no more than index_topk, 16 of it after.
    assert (theirs.sum(-1) == np.minimum(watch + 1, 16)[None]).all()
    assert not theirs[:, 3, 41:].any()  # nothing past the query's own position
    chosen = np.asarray(seen["routed_experts"])  # (Lm, n, R) bool
    assert experts.shape == (2, 5, 2) and np.take_along_axis(chosen, experts, axis=-1).all()
    counted = reference.checks(model, params, jnp.asarray(ids), watch, seen)
    assert counted == {"selected_keys_shared_with_reference": (int(theirs.sum()),) * 2,
                       "routed_experts_shared_with_reference": (20, 20)}


# ------------------------------------- (b) through the paged engine and the cache
@pytest.fixture(scope="module")
def served():
    """One wave through the paged engine: five prompts over two slots, so that
    slots are reused; chunks of 24 over blocks of 8 and buckets of 8, 16, 32
    (final chunks of every bucket, padded at the front); contexts of up to 100
    tokens against an ``index_topk`` of 16."""
    model, params, cfg = build()
    engine = engine_for(model, params)
    prompts = prompts_of(*PROMPTS)
    reset_spans()
    with jax.default_matmul_precision("highest"):
        rids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, NEWS)]
        outputs = engine.run()
    return (model, params, cfg), engine, prompts, [outputs[r] for r in rids], get_span_ring().snapshot()


@pytest.mark.parametrize("which", range(5))
def test_chunked_prefill_and_paged_decode_agree_with_the_reference(served, which):
    (_, params, cfg), engine, prompts, outputs, _ = served
    assert {d for d in engine._dispatch_log if d.startswith("chunk")} >= {"chunk:32", "chunk:16", "chunk:8"}
    assert len(outputs[which]) == NEWS[which]
    gaps = gaps_against_reference(params, cfg, prompts[which], outputs[which])
    assert gaps.max() < 1e-4  # logits, not tokens: a near tie may flip


@pytest.mark.parametrize("chunks,tile", [((24, 24, 5, 1, 1, 1), 128), ((13, 22, 30, 1, 1), 8)],
                         ids=["whole_chunks", "query_tiles_of_8"])
def test_the_cached_forward_s_logits_chunk_by_chunk(monkeypatch, chunks, tile):
    """``_apply_cached`` driven by hand over a two-part cache (the view: what
    earlier chunks wrote, with holes where a bucket's padding sat; the window:
    this chunk's columns), final chunks padded at the front as the engine pads
    them: the logits of each chunk's last position against the reference's at
    that position, and the counts the forward returns."""
    monkeypatch.setattr(glm5, "QUERY_TILE", tile)
    model, params, cfg = build()
    total = sum(chunks)
    (ids,) = prompts_of(total, seed=total)
    want, _ = reference_logits(params, cfg, ids, 0, total)
    columns = 96  # the view's: what a slot's chain would hold, most of it not yet written
    cache = model.init_cache(1, columns, dtype=jnp.float32)
    view = {name: np.array(cache[name]) for name in ("latent", "index_k", "kv_mask")}
    step = jax.jit(lambda row, mask, two_part: model.apply(params, row, attention_mask=mask, cache=two_part))
    done = filled = 0
    for n in chunks:
        pad = (4 - n % 4) % 4 if n > 1 else 0  # a bucket's padding sits before the tokens
        row = np.concatenate([np.zeros(pad, np.int32), ids[done: done + n]])[None]
        mask = np.concatenate([np.zeros(pad, np.int32), np.ones(n, np.int32)])[None]
        two_part = {**model.init_cache(1, pad + n, dtype=jnp.float32),
                    "view": {k: jnp.asarray(v) for k, v in view.items()}}
        with jax.default_matmul_precision("highest"):
            out = step(jnp.asarray(row), jnp.asarray(mask), two_part)
        done += n
        assert np.abs(np.asarray(out["logits"][0, -1]) - want[done - 1]).max() < 1e-4, (done, n)
        new = out["cache"]
        for name in ("latent", "index_k"):
            view[name][:, :, filled: filled + pad + n] = np.asarray(new[name])
        view["kv_mask"][:, filled: filled + pad + n] = np.asarray(new["kv_mask"])
        filled += pad + n
        if n == 1:
            context = float(done)
            assert float(out["context_keys"][0]) == 3 * context
            assert float(out["attended_keys"][0]) == 3 * min(context, 16)
            assert float(out["experts_held"]) == 8 * 2 and 0 < float(out["experts_touched"]) <= 2 * 2
        else:
            assert float(out["expert_claims_mean"]) == pytest.approx(2 * n * 2 / 8)  # padding claims nothing
            assert float(out["expert_claims_max"]) >= float(out["expert_claims_mean"])
            positions = np.arange(done - n, done) + 1  # each real query's causal context
            assert float(out["keys_selected"]) == 3 * np.minimum(positions, 16).sum()
            assert float(out["keys_scored"]) == 3 * n * 16  # gathered rows: index_topk a query


def test_a_request_served_beside_others_gets_its_solo_tokens(served):
    (model, params, _), _, prompts, outputs, _ = served
    engine = engine_for(model, params, batch_slots=1)
    with jax.default_matmul_precision("highest"):
        rid = engine.submit(prompts[2], max_new_tokens=NEWS[2])
        solo = engine.run()[rid]
    assert np.array_equal(solo, outputs[2])


# ----------------------------------------- (c) forms, the selection, the rotation
def test_the_absorbed_form_equals_the_expanded_form(small):
    """``_absorbed`` (``W_uk`` folded into the query, ``W_uv`` behind the sum of
    latent rows) against the expanded form written out here: every row's keys
    and values made from its latent row, then attention as usual."""
    model, params, cfg = small
    w = jax.tree_util.tree_map(lambda x: x[1], params["layers"]["attn"])
    keys = jax.random.split(jax.random.key(3), 4)
    q_nope, q_rope = jax.random.normal(keys[0], (2, 5, 4, 12)), jax.random.normal(keys[1], (2, 5, 4, 8))
    rows = jax.random.normal(keys[2], (2, 5, 9, 24))  # each query its own nine rows
    seen = jax.random.bernoulli(keys[3], 0.7, (2, 5, 9)).at[..., 0].set(True)
    with jax.default_matmul_precision("highest"):
        ours = model._absorbed(w, q_nope, q_rope, rows, seen)
        kv = jnp.einsum("bqkc,chd->bqkhd", rows[..., :16], w["wkv_b"].reshape(16, 4, 28))
        k_nope, v = kv[..., :12], kv[..., 12:]
        scores = (jnp.einsum("bqhd,bqkhd->bqhk", q_nope, k_nope)
                  + jnp.einsum("bqhd,bqkd->bqhk", q_rope, rows[..., 16:])) / math.sqrt(20)
        probs = jax.nn.softmax(jnp.where(seen[:, :, None], scores, -jnp.inf), axis=-1)
        theirs = jnp.einsum("bqhk,bqkhd->bqhd", probs, v).reshape(2, 5, 64)
    assert np.abs(np.asarray(ours) - np.asarray(theirs)).max() < 1e-5


def test_a_context_within_index_topk_is_dense_latent_attention():
    """While no query has more than ``index_topk`` keys before it the selection
    keeps them all: the logits are those of the same weights with an indexer
    that keeps everything, whatever the indexer scores; past it they differ."""
    sparse, params, _ = build(index_topk=16)
    dense, _, _ = build(index_topk=4096)
    (ids,) = prompts_of(40, seed=6)
    with jax.default_matmul_precision("highest"):
        a = np.asarray(sparse.apply(params, ids[None])["logits"])[0]
        b = np.asarray(dense.apply(params, ids[None])["logits"])[0]
    assert np.abs(a[:16] - b[:16]).max() < 1e-5
    assert np.abs(a[16:] - b[16:]).max() > 1e-3


@pytest.mark.parametrize("path", ["plain_forward", "through_the_engine"])
def test_ties_go_to_the_lower_position_in_program_and_reference_alike(path):
    """An indexer whose head weights are zero scores every key 0: every query's
    selection is a tie throughout, and program (a chunk's queries and a decode
    token alike) and reference keep the lowest ``index_topk`` positions."""
    model, params, cfg = build()
    params = jax.tree_util.tree_map(lambda x: x, params)
    params["layers"]["indexer"]["w_proj"] = jnp.zeros_like(params["layers"]["indexer"]["w_proj"])
    (ids,) = prompts_of(50, seed=8)
    watch = np.asarray([10, 30, 49], np.int32)
    if path == "plain_forward":
        theirs, seen = reference_logits(params, cfg, ids, 0, 50, watch=watch)
        with jax.default_matmul_precision("highest"):
            out = model.apply(params, jnp.asarray(ids[None]), watch=jnp.asarray(watch))
        picked = np.asarray(out["selected_keys"])[:, 0]
        assert (picked == np.asarray(seen["selected_keys"])).all()
        assert picked[:, 1:, :16].all() and not picked[:, :, 16:].any() and picked[:, 0, :11].all()
        assert np.abs(np.asarray(out["logits"])[0] - theirs).max() < 1e-4
    else:
        engine = engine_for(model, params)
        with jax.default_matmul_precision("highest"):
            rid = engine.submit(ids, max_new_tokens=10)
            served = engine.run()[rid]
        assert gaps_against_reference(params, cfg, ids, served).max() < 1e-4


def test_the_selection_is_lax_top_k_s_and_never_approximate():
    """``lax.top_k`` keeps the lower index of equal values (its documented
    order), which is the tie rule; the model calls no approximate selection."""
    values = jnp.asarray([[0.5, 2.0, 0.5, 0.5, -jnp.inf, 2.0]])
    top, chosen = jax.lax.top_k(values, 4)
    assert np.asarray(chosen).tolist() == [[1, 5, 0, 2]] and np.asarray(top).tolist() == [[2.0, 2.0, 0.5, 0.5]]
    with open(glm5.__file__) as f:
        source = f.read()
    assert "approx_max_k(" not in source and source.count("jax.lax.top_k(") == 2


def test_the_rotation_is_over_interleaved_pairs():
    x = np.arange(1, 9, dtype=np.float32).reshape(1, 1, 1, 8)
    out = np.asarray(glm5.rope_interleaved(jnp.asarray(x), jnp.asarray([[3]]), 10000.0))[0, 0, 0]
    for i in range(4):
        angle = 3 * 10000.0 ** (-2 * i / 8)
        a, b = x[0, 0, 0, 2 * i], x[0, 0, 0, 2 * i + 1]
        assert out[2 * i] == pytest.approx(a * math.cos(angle) - b * math.sin(angle), abs=1e-5)
        assert out[2 * i + 1] == pytest.approx(b * math.cos(angle) + a * math.sin(angle), abs=1e-5)
    same = np.asarray(reference.rope(jnp.asarray(x[0]), jnp.asarray([3]), 10000.0))[0, 0]
    assert np.allclose(out, same, atol=1e-6)


# ------------------------------------------------ (d) the router and the share
def test_the_bias_moves_the_choice_and_not_the_weight():
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0], [0.1, 0.2, 0.3, 0.4]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 5.0])
    plain_w, plain_e = moe.route_top_k(logits, 2, scoring="sigmoid")
    w, e = moe.route_top_k(logits, 2, scoring="sigmoid", bias=bias)
    assert np.asarray(plain_e).tolist() == [[0, 1], [3, 2]] and np.asarray(e).tolist() == [[3, 0], [3, 2]]
    score = np.asarray(jax.nn.sigmoid(logits))
    # Expert 3 enters row 0 by its bias; its weight is its score without it, renormalised.
    assert np.allclose(np.asarray(w)[0], score[0, [3, 0]] / score[0, [3, 0]].sum(), atol=1e-6)
    assert np.allclose(np.asarray(w)[1], np.asarray(plain_w)[1], atol=1e-6)
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    raw, _ = moe.route_top_k(logits, 2, scoring="sigmoid", bias=bias, norm_topk_prob=False)
    assert np.allclose(np.asarray(raw)[0], score[0, [3, 0]], atol=1e-6)
    with pytest.raises(ValueError, match="'softmax' or 'sigmoid'"):
        moe.route_top_k(logits, 2, scoring="tanh")


def test_the_softmax_router_is_what_it_was():
    logits = jax.random.normal(jax.random.key(0), (9, 16))
    w, e = moe.route_top_k(logits, 4)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, 4)
    assert np.array_equal(np.asarray(e), np.asarray(top_e))
    assert np.array_equal(np.asarray(w), np.asarray(top_w / top_w.sum(-1, keepdims=True)))
    # Laguna routes through the defaults: softmax, no bias among its weights.
    assert "bias" not in Laguna(LagunaConfig.tiny()).init(jax.random.key(0))["layers"]["moe"]


def layer_weights(seed=0, hidden=64, inner=32, experts=16):
    keys = jax.random.split(jax.random.key(seed), 8)
    w = lambda k, *shape: jax.random.normal(k, shape) / math.sqrt(shape[-2])
    return {"router": w(keys[0], hidden, experts), "bias": 0.3 * jax.random.normal(keys[7], (experts,)),
            "w_gate": w(keys[1], experts, hidden, inner), "w_up": w(keys[2], experts, hidden, inner),
            "w_down": w(keys[3], experts, inner, hidden), "shared_gate": w(keys[4], hidden, inner),
            "shared_up": w(keys[5], hidden, inner), "shared_down": w(keys[6], inner, hidden)}


LAYER = {"num_experts_per_tok": 4, "routed_scaling_factor": 2.5}


@pytest.mark.parametrize("rows", [5, 200], ids=["a_step_s_rows", "a_chunk_s_rows"])
def test_the_sixteen_shares_add_up_to_the_uncut_layer(rows):
    """The guide's section 4: the routed parts that all sixteen shares give (an
    expert each), plus what every chip computes alike (the shared expert)
    counted once, are the uncut reference's whole layer; and each share's part
    is the reference's given the same share."""
    w = layer_weights()
    x = jax.random.normal(jax.random.key(9), (rows, 64))
    with jax.default_matmul_precision("highest"):
        whole, chosen = reference.experts(x, w, LAYER)
        unbiased, plain = reference.experts(x, {**w, "bias": jnp.zeros(16)}, LAYER)
        assert (np.asarray(chosen) != np.asarray(plain)).any()  # the bias changes choices here
        shared = reference.swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])
        parts, claims = [], []
        for first in range(16):
            held = slice(first, first + 1)
            part, got = moe.expert_share_ffn(
                x, w["router"], w["w_gate"][held], w["w_up"][held], w["w_down"][held],
                first=first, k=4, scale=2.5, scoring="sigmoid", bias=w["bias"])
            parts.append(part)
            claims.append(got)
            if first in (0, 7, 15):
                theirs, _ = reference.experts(
                    x, {**w, "w_gate": w["w_gate"][held], "w_up": w["w_up"][held], "w_down": w["w_down"][held]},
                    {**LAYER, "first_expert": first})
                np.testing.assert_allclose(np.asarray(part), np.asarray(theirs - shared), atol=2e-5)
        np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole), atol=5e-5)
    assert int(sum(c.sum() for c in claims)) == rows * 4  # every claim lands in exactly one share
    assert np.array_equal(np.asarray([int(c[0]) for c in claims]), np.asarray(chosen).sum(0))


# ------------------------------------------------- (e) the pool and the engine
def test_the_pool_holds_two_entries_of_different_widths(small):
    model, params, _ = small
    reset_spans()
    engine = engine_for(model, params)
    (record,) = [r for r in get_span_ring().snapshot() if r.name == "serve.cache_layout"]
    latent, index = 3 * 24 * 4, 3 * 16 * 4  # three layers, 16 + 8 and 16 numbers a token, float32
    assert record.attrs == {"kv_bytes_per_token": latent + index, "state_bytes_per_slot": 0, "kv_layers": 3,
                            "state_layers": 0, "slot_bytes": {},
                            "token_bytes": {"latent": latent, "index_k": index}}
    layout = cache_layout(model)
    assert layout["by_token"] == ("latent", "index_k") and layout["by_slot"] == () and layout["row_mask"]
    cache = model.init_cache(3, 40)
    assert cache["latent"].shape == (3, 3, 40, 1, 24) and cache["index_k"].shape == (3, 3, 40, 1, 16)
    pool = init_kv_pool(model, 10, 8, dtype=jnp.bfloat16, slots=5)
    assert set(pool) == {"latent", "index_k", "mask"}
    assert pool["latent"].shape == (3, 11, 8, 1, 24) and pool["index_k"].shape == (3, 11, 8, 1, 16)
    assert pool_bytes(pool, layout) == {"kv": pool["latent"].nbytes + pool["index_k"].nbytes, "state": 0}
    assert token_bytes(pool, layout) == {"latent": 3 * 24 * 2, "index_k": 3 * 16 * 2}
    quantized = init_kv_pool(model, 10, 8, dtype=jnp.bfloat16, quant="int8")
    assert quantized["latent"].dtype == jnp.int8 and quantized["index_k_scale"].shape == (3, 11, 8)
    assert token_bytes(quantized, layout) == {"latent": 3 * (24 + 4), "index_k": 3 * (16 + 4)}
    stats = engine.pool_stats()
    assert stats["kv_bytes"] == engine._pool["latent"].nbytes + engine._pool["index_k"].nbytes
    assert stats["state_bytes"] == 0 and stats["pool_bytes"] == engine.kv_cache_bytes


def test_the_pool_is_whole_after_a_drain(served):
    _, engine, _, _, _ = served
    stats = engine.pool_stats()
    assert stats["blocks_free"] == stats["num_blocks"] and stats["state_slots_in_use"] == 0
    assert not np.asarray(engine._pool["mask"][0]).any()  # the trash block is never valid


def test_prefix_sharing_aliases_blocks_and_keeps_the_tokens(small):
    """Every entry is a function of the token prefix alone, so full blocks are
    shared between requests as keys and values are: a request admitted while an
    earlier one with the same first 48 tokens is resident aliases its blocks,
    prefills the rest alone, and gets the tokens the reference gives it."""
    model, params, cfg = small
    engine = engine_for(model, params, prefill_chunk=16, batch_slots=2, max_new_tokens=40)
    (prompt,) = prompts_of(70, seed=9)
    other = np.concatenate([prompt[:48], prompts_of(20, seed=10)[0]])
    (short,) = prompts_of(9, seed=11)
    with jax.default_matmul_precision("highest"):
        a = engine.submit(prompt, max_new_tokens=40)
        engine.submit(short, max_new_tokens=2)  # leaves its slot while ``a`` still decodes
        b = engine.submit(prompt, max_new_tokens=12)
        c = engine.submit(other, max_new_tokens=6)
        outputs = engine.run()
    assert engine.slo_report()["decisions"]["aliased_blocks"] >= 6
    assert np.array_equal(outputs[a][:12], outputs[b])
    assert gaps_against_reference(params, cfg, other, outputs[c]).max() < 1e-4
    assert gaps_against_reference(params, cfg, prompt, outputs[a]).max() < 1e-4
    assert engine.pool_stats()["blocks_free"] == engine.num_blocks


def test_int8_blocks_hold_both_entries(small):
    """``kv_quant="int8"`` quantizes each named entry a token row with its own
    scale; the served tokens stay close to the reference's (a row's rounding is
    its largest number over 254)."""
    model, params, cfg = small
    engine = engine_for(model, params, kv_quant="int8", cache_dtype=jnp.float32)
    assert engine._pool["latent"].dtype == jnp.int8 and "index_k_scale" in engine._pool
    (prompt,) = prompts_of(60, seed=12)
    with jax.default_matmul_precision("highest"):
        rid = engine.submit(prompt, max_new_tokens=8)
        out = engine.run()[rid]
    gaps = gaps_against_reference(params, cfg, prompt, out)
    assert len(out) == 8 and (gaps < 1e-2).mean() >= 0.5  # a flipped key moves a logit of a model this small
    assert engine.pool_stats()["blocks_free"] == engine.num_blocks


def test_a_latent_chain_hands_off_between_tiers(small):
    from accelerate_tpu.serving_net.handoff import export_chain, import_chain, run_prefill_only

    model, params, _ = small
    (prompt,) = prompts_of(45, seed=13)
    with jax.default_matmul_precision("highest"):
        unified = engine_for(model, params)
        unified.submit(prompt, max_new_tokens=9)
        expected = unified.run()
        prefill, decode = engine_for(model, params), engine_for(model, params)
        rid = prefill.submit(prompt, max_new_tokens=9, tier="prefill")
        run_prefill_only(prefill, rid)
        payload = json.loads(json.dumps(export_chain(prefill, rid)))
        assert set(payload["chain"]) == {"latent", "index_k", "mask"}
        assert payload["model"]["by_token"] == {"latent": [3, 1, 24, "float32"], "index_k": [3, 1, 16, "float32"]}
        assert prefill.pool_stats()["blocks_free"] == prefill.num_blocks
        assert import_chain(decode, payload) == rid
        out = decode.run()[rid]
    assert np.array_equal(out, list(expected.values())[0])
    assert decode.pool_stats()["blocks_free"] == decode.num_blocks
    other = engine_for(model, params, cache_dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="layout mismatch"):
        import_chain(other, payload)


def test_int8_weights_reach_the_projections_and_the_experts(small):
    model, params, _ = small
    (ids,) = prompts_of(30, seed=5)
    int8 = Glm5(dataclasses.replace(model.config, matmul_precision="int8"))
    exact = np.asarray(model.apply(params, ids[None])["logits"])
    rough = np.asarray(int8.apply(params, ids[None])["logits"])
    assert np.abs(exact - rough).max() > 1e-4 and np.median(np.abs(exact - rough)) < 0.1
    engine = engine_for(model, params, matmul_precision="int8")
    assert engine.module.config.matmul_precision == "int8"
    rid = engine.submit(ids, max_new_tokens=4)
    assert len(engine.run()[rid]) == 4


def test_what_the_engine_and_the_model_do_not_do_is_refused_in_words(small):
    model, params, _ = small
    with pytest.raises(ValueError, match="cached forward returns the last"):
        engine_for(model, params, speculative_k=2, draft_model=model)
    with pytest.raises(NotImplementedError, match="two-part cache"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32), cache=model.init_cache(1, 16))
    with pytest.raises(ValueError, match="padding masks"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32), attention_mask=jnp.ones((1, 4), jnp.int32))


@pytest.mark.parametrize("change,words", [
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(num_nextn_predict_layers=1), "multi-token-prediction module"),
    (dict(n_group=8, topk_group=4), "expert groups"),
    (dict(scoring_func="softmax"), "score by sigmoid"),
    (dict(rope_interleave=False), "interleaved pairs"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn", "factor": 4}), "rope table other than the default"),
    (dict(qk_head_dim=24), "qk_head_dim other than"),
    (dict(first_k_dense_replace=3), "no expert layer after the dense ones"),
    (dict(n_routed_experts=8, router_experts=16, first_expert=12), "experts held outside the router's width"),
    (dict(num_key_value_heads=2), "one latent row"),
])
def test_what_the_configuration_cannot_express_is_refused_in_words(change, words):
    with pytest.raises(ValueError, match=words):
        Glm5Config.tiny(**change)


# --------------------------------------------------------------- (f) counters
def test_the_engine_carries_the_model_s_counters_to_the_spans(served):
    _, engine, _, _, records = served
    windows = [r.attrs for r in records if r.name == "serve.dispatch_decode"]
    chunks = [r.attrs for r in records if r.name == "serve.dispatch_chunk"]
    assert windows and all(0 <= w["attended_keys"] <= w["context_keys"] for w in windows)
    assert any(0 < w["attended_keys"] < 0.5 * w["context_keys"] for w in windows)  # contexts past index_topk
    # Held experts x expert layers x the window's steps; a decoding row claims 2 a layer and step.
    # Rows that do not decode claim nothing: a free slot's pad token reads no expert.
    assert all(w["experts_held"] == 8 * 2 * 8 and 0 <= w["experts_touched"] <= 2 * 2 * 8 * w["decoding"]
               for w in windows)
    assert any(w["experts_touched"] > 0 for w in windows)
    assert chunks and all(c["rows_computed"] == c["p"] and c["tokens"] <= c["p"] for c in chunks)
    assert all(c["expert_claims_mean"] == pytest.approx(2 * c["tokens"] * 2 / 8)
               and c["expert_claims_max"] >= c["expert_claims_mean"] for c in chunks)
    assert all(c["keys_scored"] == 3 * c["tokens"] * 16 and 0 < c["keys_selected"] <= c["keys_scored"]
               for c in chunks)
    assert any(c["keys_selected"] == 3 * c["tokens"] * 16 for c in chunks)  # a chunk wholly past index_topk


def test_a_free_slot_s_row_claims_no_expert(small):
    """One request over three slots: two rows of every decode step are free
    slots' pad tokens, and the experts touched are those of one row."""
    model, params, _ = small
    engine = engine_for(model, params, batch_slots=3)
    reset_spans()
    with jax.default_matmul_precision("highest"):
        engine.submit(prompts_of(20, seed=1)[0], max_new_tokens=12)
        engine.run()
    windows = [r.attrs for r in get_span_ring().snapshot() if r.name == "serve.dispatch_decode"]
    busy = [w for w in windows if w["decoding"] == 1 and w["experts_touched"] > 0]
    assert busy and all(w["experts_touched"] <= 2 * 2 * 8 for w in busy)  # one row: 2 claims a layer and step


def test_the_benchmark_s_configuration_builds_this_model():
    with open(os.path.join(REPO, "chipbench", "configs", "glm-5-L5-ep16.json")) as f:
        config = json.load(f)
    fields = {f.name for f in dataclasses.fields(Glm5Config)}
    cfg = Glm5Config(**{k: v for k, v in config.items() if k in fields})
    model = Glm5(cfg)
    assert model.num_params() == 3_909_632_768
    assert (cfg.n_routed_experts, cfg.router_experts, cfg.first_expert, cfg.num_experts_per_tok) == (16, 256, 0, 8)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes)) == 3_909_632_768
    whole = Glm5(Glm5Config())  # the published model, less its multi-token-prediction module
    assert whole.num_params() == 743_911_218_432
