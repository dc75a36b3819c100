"""Paged (block-table) attention op (``ops/paged_attention.py``): the
reference gather lowering must be bit-identical to ``cached_attention`` over
the equivalent contiguous layout — this parity IS the drop-in contract a
future Pallas kernel must match (ROADMAP item 3), pinned here at the op level
so the serving engine's end-to-end parity tests never have to localize an
op-level drift."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops.attention import cached_attention
from accelerate_tpu.ops.paged_attention import (
    gather_block_mask,
    gather_block_view,
    init_kv_pool,
    paged_attention,
)


def _random_pool_and_contiguous(rng, *, b=3, m=4, bs=4, hkv=2, d=8, h=4):
    """A pool whose chains, gathered, equal a dense contiguous cache: chain j
    of slot s holds arbitrary K/V with a ragged valid length per slot."""
    n = b * m + 1  # distinct blocks per slot + trash
    k_pool = jnp.asarray(rng.standard_normal((n, bs, hkv, d)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((n, bs, hkv, d)), jnp.float32)
    # trash block 0 must never matter: poison it with huge values
    k_pool = k_pool.at[0].set(1e6)
    v_pool = v_pool.at[0].set(1e6)
    tables = jnp.asarray(
        1 + np.arange(b * m, dtype=np.int32).reshape(b, m)
    )  # slot s owns blocks [1 + s*m, 1 + (s+1)*m)
    lens = np.asarray([m * bs, m * bs - 3, 2 * bs - 1])[:b]
    mask_np = np.zeros((n, bs), np.int32)
    for s in range(b):
        for j in range(int(lens[s])):
            mask_np[int(tables[s, j // bs]), j % bs] = 1
    pool_mask = jnp.asarray(mask_np)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    return k_pool, v_pool, tables, pool_mask, lens, q


def test_gather_block_view_roundtrip():
    """The gather materializes each slot's chain in table order, for both a
    single layer and an L-stacked pool (the serving engine's layout)."""
    rng = np.random.default_rng(0)
    k_pool, _, tables, pool_mask, _, _ = _random_pool_and_contiguous(rng)
    view = gather_block_view(k_pool, tables)
    b, m, bs = tables.shape[0], tables.shape[1], k_pool.shape[1]
    assert view.shape == (b, m * bs, k_pool.shape[2], k_pool.shape[3])
    for s in range(b):
        for j in range(m):
            np.testing.assert_array_equal(
                view[s, j * bs:(j + 1) * bs], k_pool[int(tables[s, j])]
            )
    stacked = jnp.stack([k_pool, 2 * k_pool])  # fake 2-layer pool
    view2 = gather_block_view(stacked, tables)
    np.testing.assert_array_equal(view2[0], view)
    np.testing.assert_array_equal(view2[1], 2 * view)
    vmask = gather_block_mask(pool_mask, tables)
    assert vmask.shape == (b, m * bs)


def _assembly_case(name, **kw):
    return pytest.param(kw, id=name)


ASSEMBLY_CASES = [
    _assembly_case("one_layer_b3"),
    _assembly_case("one_layer_b1_a_chunks_view", b=1),
    _assembly_case("stacked_b3", layers=3),
    _assembly_case("stacked_b1", layers=2, b=1),
    _assembly_case("block64_kv2", layers=2, bs=64, hkv=2, m=3),
    _assembly_case("block16_kv8", layers=2, bs=16, hkv=8, d=16),
    _assembly_case("bf16_stacked", layers=2, dtype=jnp.bfloat16),
    # An entry over all layers passes the native slice size: gathered a layer at a time.
    _assembly_case("a_layer_a_slice_block64_kv8", layers=5, b=2, m=3, bs=64, hkv=8, d=128,
                   per_layer=True),
    _assembly_case("a_layer_a_slice_b1_bf16", layers=40, b=1, m=4, bs=16, hkv=8, d=128,
                   dtype=jnp.bfloat16, per_layer=True),
    _assembly_case("int8_scales_f32", layers=2, quant=True),
    _assembly_case("int8_scales_bf16_out", layers=2, quant=True, out_dtype=jnp.bfloat16),
    _assembly_case("out_of_range_ids_clamp", layers=2, wild=True),
]


@pytest.mark.parametrize("case", ASSEMBLY_CASES)
def test_view_assembly_equals_jnp_take_bit_for_bit(case):
    """``gather_block_view`` (one clamped gather whose slices span every
    layer of a block, or one layer where that would pass the size the TPU's
    compiler gathers natively) against ``jnp.take(pool, tables, axis=-4)``,
    bit for bit: tables with trash-block entries (0) and blocks shared
    between slots (aliased prefixes), a single layer and the stacked pool,
    one slot and several, the int8 pool with its scales and ``out_dtype``,
    and ``gather_block_mask``. An id outside the pool reads a block in range
    (clamped, as ``mode="clip"`` does), never a fill."""
    from accelerate_tpu.ops import paged_attention

    kw = dict(layers=None, b=3, m=5, bs=4, hkv=2, d=8, dtype=jnp.float32, quant=False,
              out_dtype=None, wild=False, per_layer=False)
    kw.update(case)
    rng = np.random.default_rng(7)
    b, m, bs, hkv, d = kw["b"], kw["m"], kw["bs"], kw["hkv"], kw["d"]
    n = b * m + 1
    lead = () if kw["layers"] is None else (kw["layers"],)
    shape = lead + (n, bs, hkv, d)
    if kw["quant"]:
        pool = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        scales = jnp.asarray(rng.uniform(0.01, 2.0, lead + (n, bs)), jnp.float32)
    else:
        pool = jnp.asarray(rng.standard_normal(shape), kw["dtype"])
        scales = None
    entry_bytes = int(np.prod(lead + (bs, hkv, d))) * pool.dtype.itemsize
    assert (entry_bytes > paged_attention._NATIVE_SLICE_BYTES) == kw["per_layer"]
    tables = rng.integers(1, n, (b, m)).astype(np.int32)
    tables[0, -1] = 0                      # an unassigned entry: the trash block
    tables[-1, 0] = tables[0, 0]           # a block two slots share
    tables[0, 2] = tables[0, 1]            # and one a slot holds twice
    if kw["wild"]:
        tables[0, 0], tables[-1, 1] = n + 5, -3
    tables = jnp.asarray(tables)

    want = jnp.take(pool, tables, axis=-4, mode="clip" if kw["wild"] else None)  # (..., B, M, bs, H, D)
    want = want.reshape(want.shape[:-4] + (m * bs,) + want.shape[-2:])
    if kw["quant"]:
        s = jnp.take(scales, tables, axis=-2).reshape(lead + (b, m * bs))
        want = (want.astype(jnp.float32) * s[..., None, None]).astype(kw["out_dtype"] or jnp.float32)
    got = jax.jit(lambda p, t, sc: gather_block_view(
        p, t, scales=sc, out_dtype=kw["out_dtype"]))(pool, tables, scales)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))

    pool_mask = jnp.asarray(rng.integers(0, 2, (n, bs)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(gather_block_mask(pool_mask, tables)),
        np.asarray(jnp.take(pool_mask, tables, axis=0, mode="clip" if kw["wild"] else None))
        .reshape(b, m * bs))


@pytest.mark.parametrize("window", [None, 3])
def test_paged_attention_matches_cached_attention(window):
    """paged_attention == cached_attention on the gathered-equivalent dense
    layout, bit-for-bit — including sliding windows measured in valid-slot
    distance across ragged chains. The trash block is poisoned, so equality
    also proves masked garbage never leaks into the softmax."""
    rng = np.random.default_rng(1)
    k_pool, v_pool, tables, pool_mask, lens, q = _random_pool_and_contiguous(rng)
    q_positions = jnp.asarray(lens, jnp.int32)[:, None]  # next slot per chain
    out = paged_attention(
        q, k_pool, v_pool, tables, q_positions=q_positions,
        pool_mask=pool_mask, window=window,
    )
    dense_k = gather_block_view(k_pool, tables)
    dense_v = gather_block_view(v_pool, tables)
    kv_mask = gather_block_mask(pool_mask, tables)
    ref = cached_attention(
        q, dense_k, dense_v, q_positions=q_positions, kv_mask=kv_mask,
        window=window,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert np.isfinite(np.asarray(out)).all()


def test_init_kv_pool_probes_model_layout():
    """The pool adopts the module's own cache layout (layers/kv-heads/dim)
    and reserves block 0 as the all-invalid trash block."""
    from accelerate_tpu.models import Llama, LlamaConfig

    model = Llama(LlamaConfig.tiny(num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2))
    model.init_params(jax.random.key(0))
    pool = init_kv_pool(model, num_blocks=6, block_size=4, dtype=jnp.float32)
    cfg = model.config
    assert pool["k"].shape == (2, 7, 4, cfg.num_key_value_heads, cfg.head_dim)
    assert pool["v"].shape == pool["k"].shape
    assert pool["mask"].shape == (7, 4)
    assert int(np.asarray(pool["mask"]).sum()) == 0


@pytest.mark.parametrize("softcap", [None, 5.0])
@pytest.mark.parametrize("chunk", [1, 4])
@pytest.mark.parametrize("window", [None, 3, 9])
def test_cached_attention_over_two_parts_equals_the_joined_cache(window, chunk, softcap):
    """``cached_attention(prefix=(k, v, mask))`` — the paged engine's read-only
    view before its write window — equals ``cached_attention`` over the
    concatenation within float32 rounding: holes in both parts, a query chunk
    written part-way into the window (later columns cut by causality), a row
    whose prefix is empty, and sliding windows shorter and longer than the
    columns already in the window, so the valid-slot rank crosses the seam."""
    rng = np.random.default_rng(17)
    b, t, w, hkv, g, d = 3, 12, 8, 2, 2, 8
    k_pre, v_pre = (jnp.asarray(rng.standard_normal((b, t, hkv, d)), jnp.float32) for _ in range(2))
    k_win, v_win = (jnp.asarray(rng.standard_normal((b, w, hkv, d)), jnp.float32) for _ in range(2))
    pre_mask = np.ones((b, t), np.int32)
    pre_mask[0, [2, 5, 11]] = 0       # bucket holes inside a chain
    pre_mask[1, 7:] = 0               # a short chain: stale columns past its frontier
    pre_mask[2, :] = 0                # an empty chain
    start = 2                         # two columns already in the window, one a hole
    win_mask = np.zeros((b, w), np.int32)
    win_mask[:, :start + chunk] = 1
    win_mask[0, 1] = 0
    q = jnp.asarray(rng.standard_normal((b, chunk, hkv * g, d)), jnp.float32)
    q_pos = jnp.broadcast_to(start + jnp.arange(chunk)[None], (b, chunk))
    two = cached_attention(
        q, k_win, v_win, q_positions=q_pos, kv_mask=jnp.asarray(win_mask), window=window,
        softcap=softcap, prefix=(k_pre, v_pre, jnp.asarray(pre_mask)),
    )
    one = cached_attention(
        q, jnp.concatenate([k_pre, k_win], axis=1), jnp.concatenate([v_pre, v_win], axis=1),
        q_positions=q_pos + t, kv_mask=jnp.asarray(np.concatenate([pre_mask, win_mask], axis=1)),
        window=window, softcap=softcap,
    )
    np.testing.assert_allclose(np.asarray(two), np.asarray(one), rtol=2e-6, atol=2e-6)
    # The poisoned columns (masked, or ahead of the query) never reach the sum.
    poisoned = cached_attention(
        q, jnp.where(jnp.asarray(win_mask)[..., None, None] > 0, k_win, 1e6),
        v_win.at[:, start + chunk:].set(1e6), q_positions=q_pos,
        kv_mask=jnp.asarray(win_mask), window=window, softcap=softcap,
        prefix=(jnp.where(jnp.asarray(pre_mask)[..., None, None] > 0, k_pre, 1e6), v_pre,
                jnp.asarray(pre_mask)),
    )
    np.testing.assert_allclose(np.asarray(poisoned), np.asarray(two), rtol=2e-6, atol=2e-6)
