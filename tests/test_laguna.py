"""Laguna (``models/laguna.py``): full-attention layers beside sliding-window
layers with more query heads, a gate a head, a leading dense feed-forward and
then routed experts held as a share beside a shared expert, against the plain
reference (``chipbench/reference_laguna.py``) on seeded weights; the serving
expert layer (``ops/moe.py`` ``expert_share_ffn``); and the paged engine's
third kind of state (a ring of the window's keys held by slot):

(a) logits of the plain forward against the reference, whole and as a share;
(b) chunked prefill (chunks that do not divide the window) then paged decode
    past three windows through ``ContinuousBatcher`` against the reference's
    full forward, slots reused; the cached forward's logits chunk by chunk;
(c) the two rope tables against values computed by hand;
(d) the share test of the ``model-configs`` guide's section 4, drop-free
    routing, and the renormalised weights;
(e) the rings: which position a column holds, a slot mid-prefill keeps its
    rings bit for bit, a reused slot starts from nothing;
(f) prefix sharing stands down; speculative decoding, chain export, the
    one-part cache and what the configuration cannot express are refused in words;
(g) the engine's counters reach the spans by one path.
"""

import dataclasses
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import Laguna, LagunaConfig
from accelerate_tpu.models.laguna import FULL, SLIDING, ring_positions
from accelerate_tpu.ops import moe
from accelerate_tpu.ops.paged_attention import cache_layout, init_kv_pool, pool_bytes
from accelerate_tpu.serving import ContinuousBatcher
from accelerate_tpu.telemetry import get_span_ring, reset_spans
from chipbench import reference_laguna as reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def build(**kw):
    """The tiny preset: hidden 64, 8 layers as dense + 7, 4 and 6 query heads,
    2 KV heads of 16, window 8, 16 experts top 4 beside one shared, scale 2.5."""
    cfg = LagunaConfig.tiny(**kw)
    model = Laguna(cfg)
    return model, model.init(jax.random.key(1)), dataclasses.asdict(cfg)


@pytest.fixture(scope="module")
def small():
    return build()


def engine_for(model, params, **overrides):
    kw = dict(params=params, batch_slots=2, max_new_tokens=12, max_cache_len=2 * 128, block_size=8,
              prefill_chunk=12, max_tokens_per_request=60, cache_dtype=jnp.float32,
              bucket_sizes=(4, 8, 12))
    kw.update(overrides)
    return ContinuousBatcher(model, **kw)


def prompts_of(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).astype(np.int32) for n in lengths]


def reference_logits(params, cfg, ids, start, rows):
    logits, _ = reference.logits_at(params, jnp.asarray(ids), start, rows, cfg)
    return np.asarray(logits)


def gaps_against_reference(params, cfg, prompt, served):
    """At each served token: the reference's best logit less its logit of the
    token served."""
    logits = reference_logits(params, cfg, np.concatenate([prompt, served]), len(prompt) - 1, len(served))
    return logits.max(-1) - logits[np.arange(len(served)), served]


# ------------------------------------------------------- (a) the plain forward
@pytest.mark.parametrize("tile", [256, 16], ids=["whole", "query_tiles_of_16"])
def test_plain_forward_agrees_with_the_reference(monkeypatch, small, tile):
    from accelerate_tpu.models import laguna

    monkeypatch.setattr(laguna, "QUERY_TILE", tile)
    model, params, cfg = small
    (ids,) = prompts_of(70)  # nine windows of context, the leading dense layer, a period and a tail
    with jax.default_matmul_precision("highest"):
        out = model.apply(params, jnp.asarray(ids)[None], labels=jnp.asarray(ids)[None])
    logits = reference_logits(params, cfg, ids, 0, 70)
    assert np.abs(np.asarray(out["logits"][0]) - logits).max() < 1e-4
    assert abs(logits).max() > 1.0 and np.isfinite(float(out["loss"]))


@pytest.mark.parametrize("first", [0, 4, 12])
def test_plain_forward_of_a_share_agrees_with_the_reference_given_the_same_share(first):
    model, params, cfg = build(num_experts=4, router_experts=16, first_expert=first)
    (ids,) = prompts_of(40, seed=first)
    with jax.default_matmul_precision("highest"):
        ours = np.asarray(model.apply(params, jnp.asarray(ids)[None])["logits"][0])
    assert np.abs(ours - reference_logits(params, cfg, ids, 0, 40)).max() < 1e-4
    assert params["layers"]["moe"]["w_gate"].shape[:2] == (7, 4)
    assert params["layers"]["moe"]["router"].shape == (7, 64, 16)


def test_the_layer_plan_of_the_published_configuration():
    cfg = LagunaConfig()  # 48 layers: the dense one, 11 whole periods, and three sliding layers more
    assert cfg.leading_dense == 1 and cfg.period == (SLIDING, SLIDING, SLIDING, FULL)
    assert (cfg.heads(FULL), cfg.heads(SLIDING)) == (48, 72)
    assert cfg.layer_types[:4] == (FULL, SLIDING, SLIDING, SLIDING) and cfg.router_experts == 256
    assert Laguna(cfg).num_params() == 117_561_953_280
    tiny = LagunaConfig.tiny()
    assert tiny.period == (SLIDING, SLIDING, SLIDING, FULL) and tiny.num_hidden_layers == 8
    assert dataclasses.replace(tiny, matmul_precision="int8").layer_types == tiny.layer_types


def test_routed_experts_are_what_the_reference_chooses(small):
    model, params, cfg = small
    (ids,) = prompts_of(33, seed=2)
    watch = np.array([0, 7, 32], np.int32)
    _, seen = reference.logits_at(params, jnp.asarray(ids), 0, 1, cfg, watch=watch)
    with jax.default_matmul_precision("highest"):
        ours = np.asarray(model.routed_experts(params, jnp.asarray(ids)[None], jnp.asarray(watch)))
    assert ours.shape == (7, 1, 3, 4) and np.asarray(seen).shape == (7, 3, 16)
    counted = reference.checks(model, params, jnp.asarray(ids), watch, seen)
    assert counted == {"routed_experts_shared_with_reference": (7 * 3 * 4, 7 * 3 * 4)}


# ------------------------------------------------- (b) through the paged engine
PROMPTS, NEWS = (30, 17, 41, 5, 26), (12, 7, 12, 12, 9)


@pytest.fixture(scope="module", params=[(8, 12), (16, 12)], ids=["window8", "window16"])
def served(request):
    """One wave through the paged engine: five prompts over two slots, so that
    slots are reused; chunks of 12 (over a window of 8, and under one of 16:
    neither divides the other) and final chunks of smaller buckets; contexts of
    up to 53 tokens, past three windows."""
    window, chunk = request.param
    model, params, cfg = build(sliding_window=window)
    engine = engine_for(model, params, prefill_chunk=chunk)
    prompts = prompts_of(*PROMPTS)
    reset_spans()
    with jax.default_matmul_precision("highest"):
        rids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, NEWS)]
        outputs = engine.run()
    return (model, params, cfg), engine, prompts, [outputs[r] for r in rids], get_span_ring().snapshot()


@pytest.mark.parametrize("which", range(5))
def test_chunked_prefill_and_paged_decode_agree_with_the_reference(served, which):
    (_, params, cfg), engine, prompts, outputs, _ = served
    assert {d for d in engine._dispatch_log if d.startswith("chunk")} >= {"chunk:12", "chunk:8"}
    assert len(outputs[which]) == NEWS[which]
    gaps = gaps_against_reference(params, cfg, prompts[which], outputs[which])
    assert gaps.max() < 1e-4  # logits, not tokens: a near tie may flip


@pytest.mark.parametrize("window,chunks,tile", [
    (8, (12, 12, 5, 1, 1, 1), 256), (16, (12, 9, 1, 1, 1), 256), (8, (13, 22, 1), 4)],
    ids=["window8", "window16", "query_tiles_of_4"])
def test_the_cached_forward_s_logits_chunk_by_chunk(monkeypatch, window, chunks, tile):
    """``_apply_cached`` driven by hand over a two-part cache (the view: what
    earlier chunks wrote, with holes where a bucket's padding sat; the window:
    this chunk's columns), final chunks padded at the front as the engine pads
    them: the logits of each chunk's last position against the reference's at
    that position. The last case cuts the queries into tiles of 4, as a chunk
    past 256 tokens is cut."""
    from accelerate_tpu.models import laguna

    monkeypatch.setattr(laguna, "QUERY_TILE", tile)
    model, params, cfg = build(sliding_window=window)
    total = sum(chunks)
    (ids,) = prompts_of(total, seed=window + total)
    want = reference_logits(params, cfg, ids, 0, total)
    columns = 48  # the view's: what a slot's chain would hold, most of it not yet written
    cache = model.init_cache(1, columns, dtype=jnp.float32)
    view = {name: np.array(cache[name]) for name in ("k", "v", "kv_mask")}
    rings = {name: cache[name] for name in ("ring_k", "ring_v")}
    step = jax.jit(lambda row, mask, two_part: model.apply(params, row, attention_mask=mask, cache=two_part))
    done = filled = 0
    for n in chunks:
        pad = (4 - n % 4) % 4 if n > 1 else 0  # a bucket's padding sits before the tokens
        row = np.concatenate([np.zeros(pad, np.int32), ids[done: done + n]])[None]
        mask = np.concatenate([np.zeros(pad, np.int32), np.ones(n, np.int32)])[None]
        window_cache = model.init_cache(1, pad + n, dtype=jnp.float32)
        two_part = {**{k: window_cache[k] for k in ("k", "v", "pos", "kv_mask")}, **rings,
                    "view": {k: jnp.asarray(v) for k, v in view.items()}}
        with jax.default_matmul_precision("highest"):
            out = step(jnp.asarray(row), jnp.asarray(mask), two_part)
        done += n
        assert np.abs(np.asarray(out["logits"][0, -1]) - want[done - 1]).max() < 1e-4, (done, n)
        new = out["cache"]
        rings = {name: new[name] for name in ("ring_k", "ring_v")}
        view["k"][:, :, filled: filled + pad + n] = np.asarray(new["k"])
        view["v"][:, :, filled: filled + pad + n] = np.asarray(new["v"])
        view["kv_mask"][:, filled: filled + pad + n] = np.asarray(new["kv_mask"])
        filled += pad + n
        if n == 1:
            context = float(done)
            assert float(out["context_keys"][0]) == 8 * context
            assert float(out["attended_keys"][0]) == 2 * context + 6 * min(context, window)
            assert float(out["experts_held"]) == 16 * 7 and 0 < float(out["experts_touched"]) <= 4 * 7
        else:
            assert float(out["expert_claims_mean"]) == pytest.approx(7 * n * 4 / 16)  # padding claims nothing
            assert float(out["expert_claims_max"]) >= float(out["expert_claims_mean"])


def test_a_request_served_beside_others_gets_its_solo_tokens(served):
    (model, params, _), _, prompts, outputs, _ = served
    engine = engine_for(model, params, batch_slots=1)
    with jax.default_matmul_precision("highest"):
        rid = engine.submit(prompts[2], max_new_tokens=NEWS[2])
        alone = engine.run()[rid]
    np.testing.assert_array_equal(outputs[2], alone)


# ------------------------------------------------------------ (c) rope tables
def test_the_two_rope_tables_against_values_computed_by_hand():
    """Full layers: the first half of a head (8 of 16 numbers: 4 pairs) by YaRN
    (theta 500000, factor 8, original 32, beta 32 and 1, its factor on cos and
    sin); sliding layers: the whole head (8 pairs) at theta 10000."""
    model, _, cfg = build()
    positions = jnp.asarray([[0, 1, 5, 40, 300]])
    tables = model._rope(positions)
    spec = cfg["rope_parameters"][FULL]
    rotated, theta, factor, original = 8, 500000.0, 8.0, 32
    assert tables[FULL][2] == rotated and tables[SLIDING][2] == 16
    turns = lambda n: rotated * math.log(original / (2 * math.pi * n)) / (2 * math.log(theta))
    low, high = max(math.floor(turns(32)), 0), min(math.ceil(turns(1)), rotated - 1)
    assert (low, high) == (0, 1)  # the ramp: pair 0 extrapolates, pairs 1 to 3 interpolate
    expected = []
    for i in range(rotated // 2):
        plain = theta ** (-2 * i / rotated)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        expected.append((1 - ramp) * plain + ramp * plain / factor)
    assert expected[0] == 1.0 and expected[1] == pytest.approx(theta ** -0.25 / 8)
    angles = np.asarray(positions, np.float64)[0][:, None] * np.asarray(expected)[None]
    np.testing.assert_allclose(np.asarray(tables[FULL][0][0]), spec["attention_factor"] * np.cos(angles),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(tables[FULL][1][0]), spec["attention_factor"] * np.sin(angles),
                               rtol=2e-5, atol=2e-5)
    plain = 10000.0 ** (-np.arange(0, 16, 2) / 16)
    angles = np.asarray(positions, np.float64)[0][:, None] * plain[None]
    np.testing.assert_allclose(np.asarray(tables[SLIDING][0][0]), np.cos(angles), rtol=2e-5, atol=2e-5)
    # Partial rotation: a head's second half passes as it is, its first half turns in pairs (i, i + 4).
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 16))
    turned = np.asarray(Laguna._rotate(x, tables[FULL]))
    np.testing.assert_array_equal(turned[..., 8:], np.asarray(x)[..., 8:])
    cos, sin = np.asarray(tables[FULL][0])[0, :, None, :], np.asarray(tables[FULL][1])[0, :, None, :]
    x1, x2 = np.asarray(x)[0, ..., :4], np.asarray(x)[0, ..., 4:8]
    np.testing.assert_allclose(turned[0, ..., :4], x1 * cos - x2 * sin, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(turned[0, ..., 4:8], x2 * cos + x1 * sin, rtol=1e-5, atol=1e-6)
    # The reference's tables, written from the same description, agree.
    theirs, factor_on = reference.inverse_frequencies(spec, rotated)
    np.testing.assert_allclose(np.asarray(theirs), expected, rtol=1e-6)
    assert factor_on == spec["attention_factor"]


# ----------------------------------------------- (d) the expert layer and its share
def layer_weights(seed=0, hidden=64, inner=32, experts=16):
    keys = jax.random.split(jax.random.key(seed), 8)
    w = lambda k, *shape: jax.random.normal(k, shape) / math.sqrt(shape[-2])
    return {"router": w(keys[0], hidden, experts), "w_gate": w(keys[1], experts, hidden, inner),
            "w_up": w(keys[2], experts, hidden, inner), "w_down": w(keys[3], experts, inner, hidden),
            "shared_gate": w(keys[4], hidden, inner), "shared_up": w(keys[5], hidden, inner),
            "shared_down": w(keys[6], inner, hidden)}


LAYER = {"num_experts_per_tok": 4, "moe_routed_scaling_factor": 2.5}


@pytest.mark.parametrize("rows", [5, 200], ids=["a_step_s_rows", "a_chunk_s_rows"])
def test_the_shares_add_up_to_the_uncut_layer(rows):
    """The guide's section 4: the routed parts that all the shares give (4
    shares of 4 experts), plus what every chip computes alike (the shared
    expert) counted once, are the uncut reference's whole layer."""
    w = layer_weights()
    x = jax.random.normal(jax.random.key(9), (rows, 64))
    with jax.default_matmul_precision("highest"):
        whole, _ = reference.experts(x, w, LAYER)
        parts, claims = [], []
        for first in (0, 4, 8, 12):
            held = slice(first, first + 4)
            part, got = moe.expert_share_ffn(
                x, w["router"], w["w_gate"][held], w["w_up"][held], w["w_down"][held],
                first=first, k=4, scale=2.5)
            parts.append(part)
            claims.append(got)
            theirs, _ = reference.experts(x, {**w, "w_gate": w["w_gate"][held], "w_up": w["w_up"][held],
                                              "w_down": w["w_down"][held]}, {**LAYER, "first_expert": first})
            shared = reference.swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])
            np.testing.assert_allclose(np.asarray(part), np.asarray(theirs - shared), atol=2e-5)
        shared = reference.swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])
        np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole), atol=5e-5)
    assert int(sum(c.sum() for c in claims)) == rows * 4  # every claim lands in exactly one share


@pytest.mark.parametrize("rows", [6, 96], ids=["a_step_s_rows", "a_chunk_s_rows"])
def test_no_claim_is_dropped_when_every_token_goes_to_one_expert(rows):
    w = layer_weights(seed=3)
    x = jnp.abs(jax.random.normal(jax.random.key(4), (rows, 64))) + 0.1
    router = w["router"].at[:, 5].set(10.0)  # positive rows: expert 5 is every token's first choice
    with jax.default_matmul_precision("highest"):
        out, claims = moe.expert_share_ffn(x, router, w["w_gate"], w["w_up"], w["w_down"], first=0, k=4,
                                           scale=2.5)
        theirs, _ = reference.experts(x, {**w, "router": router}, LAYER)
        shared = reference.swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])
    assert int(claims[5]) == rows and int(claims.sum()) == rows * 4
    np.testing.assert_allclose(np.asarray(out), np.asarray(theirs - shared), atol=5e-5)
    # A row the mask names as padding claims nothing.
    mask = jnp.arange(rows) % 2 == 0
    with jax.default_matmul_precision("highest"):
        masked, fewer = moe.expert_share_ffn(x, router, w["w_gate"], w["w_up"], w["w_down"], first=0,
                                             k=4, scale=2.5, row_mask=mask)
    assert int(fewer[5]) == (rows + 1) // 2 and float(jnp.abs(masked[1::2]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(masked[::2]), np.asarray(out[::2]), atol=5e-5)


def test_the_renormalised_weights_sum_to_one_before_the_scale():
    logits = jax.random.normal(jax.random.key(2), (50, 16)) * 3
    weights, experts = moe.route_top_k(logits, 4)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, rtol=1e-6)
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    assert all(set(np.argsort(-probs[i])[:4]) == set(np.asarray(experts[i])) for i in range(50))
    raw, _ = moe.route_top_k(logits, 4, norm_topk_prob=False)
    assert float(raw.sum(-1).max()) < 1.0 and weights.dtype == jnp.float32
    theirs, chosen = reference.routing(logits @ jnp.eye(16), jnp.eye(16), 4)
    np.testing.assert_allclose(np.asarray(theirs.sum(-1)), 1.0, rtol=1e-6)
    assert int(chosen.sum()) == 50 * 4


@pytest.mark.parametrize("rows", [5, 200], ids=["a_step_s_rows", "a_chunk_s_rows"])
def test_int8_weights_reach_the_experts_products(rows):
    w = layer_weights(seed=5)
    x = jax.random.normal(jax.random.key(6), (rows, 64))
    run = lambda precision: moe.expert_share_ffn(x, w["router"], w["w_gate"], w["w_up"], w["w_down"],
                                                 first=0, k=4, precision=precision)[0]
    exact, quantized = np.asarray(run("default")), np.asarray(run("int8"))
    apart = np.abs(exact - quantized).max()
    assert 1e-4 < apart < 0.1 * np.abs(exact).max()
    with pytest.raises(ValueError, match="'default' or 'int8'"):
        run("fp4")


def test_int8_weights_reach_every_projection(small):
    model, params, _ = small
    from accelerate_tpu.generation import _precision_variant

    (ids,) = prompts_of(24, seed=8)
    quantized = _precision_variant(model, "int8")
    assert quantized.config.matmul_precision == "int8" and quantized.config.period == model.config.period
    exact = np.asarray(model.apply(params, jnp.asarray(ids)[None])["logits"])
    moved = np.asarray(quantized.apply(params, jnp.asarray(ids)[None])["logits"])
    assert 1e-3 < np.abs(exact - moved).max() < 5.0  # a flipped expert moves a logit of unit scale by more


# ----------------------------------------------------------------- (e) the rings
def test_which_position_a_ring_column_holds():
    held = np.asarray(ring_positions(jnp.asarray([0, 5, 8, 10, 27]), 8))
    assert (held[0] < 0).all()  # nothing yet
    assert list(held[1]) == [0, 1, 2, 3, 4, -3, -2, -1]  # columns 5 to 7 hold no key
    assert list(held[2]) == [0, 1, 2, 3, 4, 5, 6, 7]
    assert list(held[3]) == [8, 9, 2, 3, 4, 5, 6, 7]  # positions 8 and 9 took columns 0 and 1
    assert sorted(held[4]) == list(range(19, 27)) and all(p % 8 == c for c, p in enumerate(held[4]))


def test_cache_layout_record_and_pool_layout(small):
    model, params, _ = small
    reset_spans()
    engine = engine_for(model, params)
    (record,) = [r for r in get_span_ring().snapshot() if r.name == "serve.cache_layout"]
    ring = 6 * 8 * 2 * 16 * 4  # six sliding layers, a window of 8, 2 KV heads of 16, float32
    assert record.attrs == {"kv_bytes_per_token": 2 * 2 * 16 * 2 * 4, "state_bytes_per_slot": 2 * ring,
                            "kv_layers": 2, "state_layers": 12,
                            "slot_bytes": {"ring_k": ring, "ring_v": ring}}
    layout = cache_layout(model)
    assert layout["by_slot"] == ("ring_k", "ring_v") and not layout["dense_chain"] and layout["row_mask"]
    assert set(layout["counters"]) == {"decode", "chunk"}
    cache = model.init_cache(3, 40)
    assert cache["k"].shape == (2, 3, 40, 2, 16) and cache["ring_k"].shape == (6, 3, 8, 2, 16)
    pool = init_kv_pool(model, 10, 8, dtype=jnp.bfloat16, slots=5)
    assert pool["k"].shape == (2, 11, 8, 2, 16) and pool["ring_v"].shape == (6, 5, 8, 2, 16)
    assert pool_bytes(pool, layout) == {"kv": 2 * pool["k"].nbytes, "state": 2 * pool["ring_k"].nbytes}
    stats = engine.pool_stats()
    assert stats["state_bytes"] == 2 * 2 * ring and stats["state_slots_in_use"] == 0


def test_a_slot_mid_prefill_keeps_its_rings_bit_for_bit(small):
    model, params, cfg = small
    engine = engine_for(model, params)
    long, short = prompts_of(40, 6, seed=3)
    with jax.default_matmul_precision("highest"):
        a = engine.submit(short, max_new_tokens=12)
        b = engine.submit(long, max_new_tokens=4)
        state = engine._state_tuple()
        engine._admit_paged(0.0)
        slot_a, slot_b = (next(s for s in range(2) if engine._slot_req[s].rid == r) for r in (a, b))
        state = engine._dispatch_chunk(slot_a, state)  # the short prompt's only chunk: it decodes
        state = engine._dispatch_chunk(slot_b, state)  # the long prompt's first of four
        before = {name: np.asarray(engine._pool[name][:, slot_b]) for name in ("ring_k", "ring_v")}
        assert np.abs(before["ring_k"]).max() > 0
        state, _ = engine._dispatch_decode(state, np.zeros((2,), bool))
        for name in ("ring_k", "ring_v"):
            np.testing.assert_array_equal(np.asarray(engine._pool[name][:, slot_b]), before[name])
        assert not np.array_equal(np.asarray(engine._pool["ring_k"][:, slot_a]),
                                  np.zeros_like(before["ring_k"]))
        engine._sync(state)
        outputs = engine.run()
    for rid, prompt in ((a, short), (b, long)):
        assert gaps_against_reference(params, cfg, prompt, outputs[rid]).max() < 1e-4


def test_a_reused_slot_starts_from_nothing(small):
    model, params, cfg = small
    engine = engine_for(model, params, batch_slots=1)
    first, second = prompts_of(30, 19, seed=5)
    with jax.default_matmul_precision("highest"):
        engine.submit(first, max_new_tokens=8)
        engine.run()
        assert np.abs(np.asarray(engine._pool["ring_k"][:, 0])).max() > 0  # nothing scrubbed it
        rid = engine.submit(second, max_new_tokens=12)
        output = engine.run()[rid]
    assert gaps_against_reference(params, cfg, second, output).max() < 1e-4


# ------------------------------------------------------------- (f) refusals
def test_prefix_sharing_stands_down_for_a_model_that_holds_rings(small):
    model, params, cfg = small
    engine = engine_for(model, params, prefill_chunk=8)
    (prompt,) = prompts_of(40, seed=9)
    with jax.default_matmul_precision("highest"):
        a = engine.submit(prompt, max_new_tokens=6)
        outputs = engine.run()
        assert engine.prefix_match_tokens(prompt) == 0 and engine.pool_stats()["shared_blocks"] == 0
        b = engine.submit(prompt, max_new_tokens=6)  # the same prompt again: prefilled again
        outputs.update(engine.run())
    assert engine.slo_report()["decisions"]["aliased_blocks"] == 0
    assert np.array_equal(outputs[a], outputs[b])


def test_speculative_decoding_chain_export_and_the_one_part_cache_are_refused_in_words(small):
    from accelerate_tpu.serving_net.handoff import export_chain

    model, params, _ = small
    with pytest.raises(ValueError, match="cannot roll back the recurrent state"):
        engine_for(model, params, speculative_k=2, draft_model=model)
    engine = engine_for(model, params)
    engine.submit(prompts_of(20)[0], max_new_tokens=4)
    with pytest.raises(ValueError, match="state held by slot"):
        export_chain(engine, 0)
    with pytest.raises(NotImplementedError, match="two-part cache"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32), cache=model.init_cache(1, 16))
    with pytest.raises(ValueError, match="padding masks"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32), attention_mask=jnp.ones((1, 4), jnp.int32))


@pytest.mark.parametrize("change,words", [
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(gating="per-element"), "gating other than per-head"),
    (dict(moe_router_logit_softcapping=30.0), "moe_router_logit_softcapping"),
    (dict(mlp_only_layers=(0, 3)), "dense feed-forwards that are not the leading layers"),
    (dict(num_attention_heads_per_layer=(4, 6, 6, 6, 4, 6, 6, 8)), "head counts that differ within a layer kind"),
    (dict(num_experts=8, router_experts=16, first_expert=12), "experts held outside the router's width"),
    (dict(layer_types=(FULL,) * 8), "layer types other than"),
])
def test_what_the_configuration_cannot_express_is_refused_in_words(change, words):
    with pytest.raises(ValueError, match=words):
        LagunaConfig.tiny(**change)


# --------------------------------------------------------------- (g) counters
def test_the_engine_carries_the_model_s_counters_to_the_spans(served):
    (_, _, cfg), engine, _, _, records = served
    windows = [r.attrs for r in records if r.name == "serve.dispatch_decode"]
    chunks = [r.attrs for r in records if r.name == "serve.dispatch_chunk"]
    window = cfg["sliding_window"]
    assert windows and all(0 <= w["attended_keys"] <= w["context_keys"] for w in windows)
    assert any(w["attended_keys"] < 0.6 * w["context_keys"] for w in windows)  # contexts past the window
    # Held experts x expert layers x the window's steps; a decoding row claims 4 a layer and step.
    # Rows that do not decode claim nothing: a free slot's pad token reads no expert.
    assert all(w["experts_held"] == 16 * 7 * 8 and 0 <= w["experts_touched"] <= 4 * 7 * 8 * w["decoding"]
               for w in windows)
    assert any(w["experts_touched"] > 0 for w in windows)
    assert chunks and all(c["rows_computed"] == c["p"] and c["tokens"] <= c["p"] for c in chunks)
    assert all(c["expert_claims_mean"] == pytest.approx(7 * c["tokens"] * 4 / 16)
               and c["expert_claims_max"] >= c["expert_claims_mean"] for c in chunks)
    stats = engine.pool_stats()
    assert stats["state_slots_in_use"] == 0 and stats["blocks_free"] == stats["num_blocks"]
    assert stats["pool_bytes"] == stats["kv_bytes"] + stats["state_bytes"] == engine.kv_cache_bytes


def test_a_model_without_counters_gets_none(small):
    from accelerate_tpu.models import Llama, LlamaConfig
    from accelerate_tpu.ops.paged_attention import PLAIN_CACHE_LAYOUT

    llama = Llama(LlamaConfig.tiny())
    assert cache_layout(llama) == PLAIN_CACHE_LAYOUT and PLAIN_CACHE_LAYOUT["counters"] == {}
    engine = ContinuousBatcher(llama, params=llama.init(jax.random.key(0)), batch_slots=2,
                               max_new_tokens=8, max_cache_len=128, block_size=8, bucket_sizes=(8, 16),
                               max_tokens_per_request=40)
    reset_spans()
    engine.submit(prompts_of(20)[0], max_new_tokens=8)
    engine.run()
    spans = [r for r in get_span_ring().snapshot() if r.name.startswith("serve.dispatch")]
    assert spans and not any("attended_keys" in r.attrs or "expert_claims_max" in r.attrs for r in spans)
    fn, args = engine._chunk_fn(8), engine._chunk_args(8)
    assert len(jax.eval_shape(fn, *args)) == 2  # pool and state: no third output


def test_the_benchmark_s_configuration_builds_this_model():
    with open(os.path.join(REPO, "chipbench", "configs", "laguna-s-2.1-L12-ep8.json")) as f:
        config = json.load(f)
    fields = {f.name for f in dataclasses.fields(LagunaConfig)}
    cfg = LagunaConfig(**{k: v for k, v in config.items() if k in fields})
    model = Laguna(cfg)
    assert model.num_params() == 4_325_526_528
    assert (cfg.num_experts, cfg.router_experts, cfg.first_expert, cfg.num_experts_per_tok) == (32, 256, 0, 10)
    assert cfg.leading_dense == 1 and cfg.period == (SLIDING, SLIDING, SLIDING, FULL)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes)) == 4_325_526_528
    cache = jax.eval_shape(lambda: model.init_cache(24, 64))
    assert cache["k"].shape == (3, 24, 64, 8, 128) and cache["ring_k"].shape == (9, 24, 512, 8, 128)
