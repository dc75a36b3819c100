"""The decode window's view ladder (``serving.py`` ``_decode``): one program
holds the gather and the steps at a half, three quarters and the whole of the
block table, and takes the narrowest that covers the longest chain among the
rows that decode, the branch chosen on the device:

(a) a greedy wave whose chains cross both boundaries returns the tokens of the
    same engine held to the full width, for ``Llama`` with and without a
    sliding window, through the Pallas gather and over an int8 pool, and for
    the tiny MiniCPM-SALA and Laguna shapes, with a long prompt mid-prefill
    beside rows that decode;
(b) the branch the device takes is the host's ``view_cols`` on every window
    dispatched (a model that serves the width of the view it is handed), while
    a row mid-prefill holds a chain longer than the width chosen;
(c) the ladder's widths, which engines get one (a table under 683 columns, or
    a view that is under a quarter of what a decode step reads), and an engine
    with one width has no switch;
(d) a dynamic-NTK ``Llama`` gets the same logits at the narrow and the full
    width (the rope's length is the table's, never the view's).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import (Laguna, LagunaConfig, Llama, LlamaConfig, MiniCPMSALA,
                                   MiniCPMSALAConfig)
from accelerate_tpu.serving import _MIN_VIEW_COLS, ContinuousBatcher, _view_ladder
from accelerate_tpu.telemetry import get_span_ring, reset_spans

LLAMA = dict(num_hidden_layers=2, hidden_size=32, intermediate_size=64,
             num_attention_heads=4, num_key_value_heads=2)


def llama(**kw):
    model = Llama(LlamaConfig.tiny(**LLAMA, **kw))
    return model, model.init(jax.random.key(0))


def llama_engine(model, params, **overrides):
    """A table of 66 entries of 16 tokens: views of 528, 800 and 1056 columns."""
    kw = dict(params=params, batch_slots=3, max_new_tokens=40, max_cache_len=4096, block_size=16,
              prefill_chunk=128, max_tokens_per_request=900, bucket_sizes=(16, 64, 128),
              cache_dtype=jnp.float32)
    kw.update(overrides)
    return ContinuousBatcher(model, **kw)


def prompts_of(*lengths, seed=0, vocab=250):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).astype(np.int32) for n in lengths]


def decode_spans():
    return [r for r in get_span_ring().snapshot() if r.name == "serve.dispatch_decode"]


def serve(engine, prompts):
    """The wave's tokens in the order submitted, and the ``view_cols`` of its
    decode windows in the order dispatched."""
    reset_spans()
    rids = [engine.submit(p) for p in prompts]
    with jax.default_matmul_precision("highest"):
        out = engine.run()
    assert len(engine._free_blocks) == engine.num_blocks
    return [out[rid] for rid in rids], [r.attrs["view_cols"] for r in decode_spans()]


# ------------------------------------------------- (a) tokens at every width


def _case(family):
    """(engine factory, prompts): a short request decodes while a long prompt
    prefills past the first boundary, then chains cross both boundaries."""
    if family.startswith("llama"):
        model, params = llama(sliding_window=24 if family == "llama_windowed" else None)
        # The Pallas gather (interpreted here) and the int8 pool's dequantizing
        # gather are handed the table's leading entries like the reference.
        extra = {"llama_gather_kernel": dict(kernels="paged_gather=interpret"),
                 "llama_int8_pool": dict(kv_quant="int8")}.get(family, {})
        lengths = (20, 750) if "kernel" in family else (40, 600, 30, 775, 100, 505)  # interpreted: slow
        return (lambda: llama_engine(model, params, **extra)), prompts_of(*lengths)
    if family == "minicpm_sala":
        model = MiniCPMSALA(MiniCPMSALAConfig.tiny(
            num_hidden_layers=4, residual_depth=32, sparse_window=256, sparse_topk=8))
        params = model.init(jax.random.key(1))
        # 25 entries of 64 tokens: views of 832, 1216 and 1600 columns.
        make = lambda: ContinuousBatcher(
            model, params=params, batch_slots=3, max_new_tokens=40, max_cache_len=3 * 1536,
            block_size=64, prefill_chunk=128, max_tokens_per_request=1448,
            cache_dtype=jnp.float32, bucket_sizes=(16, 32, 64, 128))
        return make, prompts_of(40, 900, 30, 1195, 815)
    model = Laguna(LagunaConfig.tiny())
    params = model.init(jax.random.key(1))
    # 130 entries of 8 tokens: views of 520, 784 and 1040 columns.
    make = lambda: ContinuousBatcher(
        model, params=params, batch_slots=3, max_new_tokens=40, max_cache_len=3 * 1024,
        block_size=8, prefill_chunk=64, max_tokens_per_request=952,
        cache_dtype=jnp.float32, bucket_sizes=(16, 32, 64))
    return make, prompts_of(40, 600, 30, 765, 500)


@pytest.mark.parametrize("family", ["llama", "llama_windowed", "llama_gather_kernel",
                                    "llama_int8_pool", "minicpm_sala", "laguna"])
def test_a_wave_across_both_boundaries_serves_the_full_width_s_tokens(family):
    make, prompts = _case(family)
    laddered = make()
    narrow, middle, full = (nb * laddered.block_size for nb in laddered._view_ladder)
    tokens, cols = serve(laddered, prompts)
    assert set(cols) == {narrow, middle, full}, "the wave has to take every width"
    held = make()
    held._view_ladder = held._view_ladder[-1:]  # before its first decode program is built
    want, held_cols = serve(held, prompts)
    assert set(held_cols) == {full}
    for got, ref in zip(tokens, want):
        np.testing.assert_array_equal(got, ref)
    assert all(len(t) == 40 for t in tokens)


# ------------------------------------- (b) the device's branch is the host's


class _ServesItsWidth:
    """A model whose every logit row is one-hot at the number of table entries
    in the view it was handed: a served token says which branch computed it."""

    def __init__(self, inner, block_size):
        self._inner, self._bs = inner, block_size

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def apply(self, params, *, cache, **kwargs):
        out = self._inner.apply(params, cache=cache, **kwargs)
        entries = cache["view"]["k"].shape[2] // self._bs
        told = jax.nn.one_hot(entries, out["logits"].shape[-1], dtype=out["logits"].dtype)
        return {**out, "logits": jnp.broadcast_to(told, out["logits"].shape)}


def test_the_branch_taken_on_the_device_is_the_host_s_view_cols_on_every_window():
    model, params = llama()
    engine = llama_engine(_ServesItsWidth(model, 16), params)
    bs, every, full = engine.block_size, engine._view_ladder, engine.max_blocks_per_slot
    assert len(every) == 3
    dispatch, windows = engine._dispatch_decode, []

    def watched(state, force_stop):
        prefilling = [int(engine._slot_len[s]) for s, m in enumerate(engine._slot_mode)
                      if m == "prefill"]
        # The program's own index, from the very arguments this window gets.
        commit = np.asarray([m == "decode" for m in engine._slot_mode])
        rung = int(engine._view_rung(jnp.asarray(engine._slot_len, jnp.int32), jnp.asarray(commit)))
        state, (report, req_map) = dispatch(state, force_stop)
        windows.append({"rids": req_map, "prefilling": max(prefilling, default=0), "rung": rung})
        return state, (report, req_map)

    engine._dispatch_decode = watched
    reset_spans()
    prompts = prompts_of(40, 600, 30, 775, 100, 505, seed=3)
    rids = [engine.submit(p) for p in prompts]
    out = engine.run()
    spans = decode_spans()
    assert len(spans) == len(windows) > 20
    for rec, seen in zip(spans, windows):
        assert rec.attrs["view_cols"] == every[seen["rung"]] * bs
        assert rec.attrs["view_cols_full"] == full * bs
    assert {rec.attrs["view_cols"] for rec in spans} == {nb * bs for nb in every}
    # Some window ran narrower than a chain that was still prefilling.
    assert any(seen["prefilling"] > rec.attrs["view_cols"] for rec, seen in zip(spans, windows))
    w = engine.sync_every
    for rid in rids:
        served = out[rid]
        assert served[0] == full  # a chunk program gathers the whole table
        mine = [rec.attrs["view_cols"] // bs for rec, seen in zip(spans, windows)
                if rid in seen["rids"]]
        for j, entries in enumerate(mine):
            np.testing.assert_array_equal(served[1 + w * j: 1 + w * (j + 1)], entries)
        assert 1 + w * len(mine) >= len(served) == 40


# ---------------------------------------------------------- (c) the ladder


@pytest.mark.parametrize("entries,block,share,want", [
    (98, 16, 0.39, (49, 74, 98)),      # the Qwen3 cells: 784, 1184, 1568 columns, 39% of a step's bytes
    (89, 64, 0.16, (89,)),             # Laguna's cell: the weights are most of a step, one width
    (417, 64, 0.04, (417,)),           # MiniCPM-SALA's cell
    (89, 64, 0.25, (45, 67, 89)),      # a quarter of a step is where the ladder begins
    (64, 16, 0.9, (32, 48, 64)),       # a half of exactly the least width is made
    (13, 64, 0.9, (10, 13)),           # 7 entries would be 448 columns: not made
    (42, 16, 0.9, (32, 42)),           # three quarters of 672 columns are 512: made; a half is not
    (16, 4, 0.9, (16,)),               # the tiny engines of the tests: one width, one body
    (1, 16, 0.9, (1,)),
])
def test_the_ladder_s_widths(entries, block, share, want):
    assert _view_ladder(entries, block, share) == want
    assert all(nb * block >= _MIN_VIEW_COLS for nb in want[:-1])


def test_the_cells_engines_take_the_ladder_their_view_s_share_of_a_step_gives():
    """The benchmark's three serving configurations at their published sizes,
    weights as shapes alone: Qwen3's view is 39% of what a decode step reads and
    gets three widths; Laguna's (16%) and MiniCPM-SALA's (4%) get one, so their
    decode programs and their set-up are what they were."""
    import json
    from pathlib import Path

    from chipbench import program

    want = {"qwen3-1.7b": (49, 74, 98), "laguna-s-2.1-L12-ep8": (89,), "minicpm-sala-L12": (417,)}
    for name, ladder in want.items():
        cell = json.loads((Path(__file__).parents[1] / f"chipbench/configs/{name}.json").read_text())
        model = program.build_model(cell)
        params = jax.eval_shape(lambda: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), model.init(jax.random.key(0))))
        # The ladder depends on the table, the slots and the widths, not on the pool's size.
        engine = ContinuousBatcher(model, params=params, **{**cell["engine"], "max_cache_len": 64 * 64})
        assert engine._view_ladder == ladder, name


def test_an_engine_with_one_width_has_no_switch_and_one_with_three_has_one():
    model, params = llama()
    lowered = lambda e: e._decode().lower(*e._decode_args()).as_text()
    one = llama_engine(model, params, max_tokens_per_request=400)
    assert one._view_ladder == (one.max_blocks_per_slot,) and one.max_blocks_per_slot * 16 < 683
    assert "stablehlo.case" not in lowered(one)
    three = llama_engine(model, params)
    assert three._view_ladder == (33, 50, 66)
    text = lowered(three)
    assert text.count("stablehlo.case") == 1 and text.startswith("module @jit_serve_decode_window")
    # What the benchmark's runner compiles for the peak is the program the loop runs.
    assert three._decode() is three._decode()


# ------------------------------------------- (d) a rope that reads a length


def test_a_dynamic_ntk_rope_reads_the_table_s_length_at_every_width():
    model, params = llama(rope_scaling={"rope_type": "dynamic", "factor": 2.0})
    engine = llama_engine(model, params, batch_slots=2)
    rng = np.random.default_rng(5)
    pool = {name: (jnp.ones(x.shape, x.dtype) if name == "mask"
                   else jnp.asarray(rng.standard_normal(x.shape), x.dtype))
            for name, x in engine._pool.items()}
    entries = engine.max_blocks_per_slot
    tables = jnp.asarray(1 + rng.permutation(2 * entries).reshape(2, entries), jnp.int32)
    lens = jnp.asarray([300, 180], jnp.int32)
    ids = jnp.asarray(rng.integers(1, 250, (2, 1)), jnp.int32)

    def logits(nb, keep_capacity=True):
        view, window = engine._paged_view_cache(pool, tables[:, :nb], lens, engine.sync_every)
        assert view["k"].shape[2] == nb * 16 and view["capacity"] == entries * 16
        if not keep_capacity:
            del view["capacity"]
        with jax.default_matmul_precision("highest"):
            out = model.apply(params, input_ids=ids, cache={**window, "view": view},
                              positions=lens[:, None])
        return np.asarray(out["logits"])

    narrow, full = engine._view_ladder[0], entries
    np.testing.assert_allclose(logits(narrow), logits(full), rtol=1e-5, atol=1e-5)
    # The test bites: a rope stretched by the view's own width answers otherwise.
    assert np.abs(logits(narrow, keep_capacity=False) - logits(full)).max() > 1e-3
