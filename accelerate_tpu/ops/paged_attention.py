"""Paged (block-table) KV-cache attention — reference lowering + pool helpers.

The serving engine (``serving.ContinuousBatcher``)
keeps each layer's KV cache as a **block pool**: a device-resident
``(num_blocks, block_size, kv_heads, head_dim)`` array per layer plus per-slot
**block tables** mapping a request's logical token chain onto pool blocks
(vLLM's layout, shaped for XLA's static-compilation model — every shape here
is fixed at engine construction, so nothing recompiles as traffic changes).
Allocation and free are host-side free-list surgery; cross-request prefix
sharing is refcounted aliasing of full blocks.

This module is the op-level seam:

- :func:`init_kv_pool` / :func:`gather_block_view` / :func:`gather_block_mask`
  are the pool primitives the engine's compiled programs are built from. The
  gather is the **reference lowering**: one XLA gather over the block axis,
  its ids clamped to the pool and its slices kept to a size the TPU's
  compiler gathers in one pass (:func:`_gather_chains`), that materializes
  each slot's chain as a contiguous per-slot view. Table ids are in range by
  the pool's invariant (below), so no out-of-range read is masked, filled or
  selected on the device (``jnp.take``'s default did all three, over the
  whole view). The
  engine hands that view to the model READ-ONLY, as the ``prefix`` of a
  two-part cache whose second part is a write window the size of what the
  program writes; ``cached_attention(prefix=...)`` attends both under one
  softmax with the same hole-tolerant masks, so every model family — rope,
  learned wpe, sliding windows, softcap — stays exact and nothing the size
  of the view is written.
- :func:`export_chain_blocks` / :func:`import_chain_blocks` are the KV-chain
  handoff faces: a finished prefill's block chain leaves one host's pool and
  splices into another's (serving_net/handoff.py) as a bounded per-chain
  transfer — pool blocks are the unit of ownership, so disaggregated
  prefill/decode never copies a whole cache.
- :func:`paged_attention` is the fused op face: one call from query chunk +
  pools + block tables to attention output. The **reference lowering**
  (:func:`paged_attention_reference`) composes the gather with
  :func:`~.attention.cached_attention`; the ROADMAP item 3 Pallas
  ragged-decode kernel (``ops/pallas/paged_decode.py``) sits behind this
  exact signature via the kernel registry (``ops/registry.py``,
  ``ACCELERATE_KERNELS``) — it walks each slot's block chain in-kernel with
  no materialized gather view and skips padded slots, matching the
  reference bit-for-bit on active slots (tests/test_kernels.py pins it; see
  ``benchmarks/kernel_profile.py`` for the op-level attribution harness
  that measures the swap).

Block-size note for that kernel: TPU VMEM tiles are (sublane × 128-lane) with
an 8/16/32-row sublane minimum by dtype, so ``block_size`` should stay a
multiple of 16 (the bf16 sublane) for the eventual kernel to stream blocks
without repacking — the engine's default is 16.

Pool invariants (shared with serving.py):

- Block 0 is the **trash block**: never allocated, never referenced by a
  committed table entry, and its mask rows stay zero — so unassigned table
  entries (0) gather as masked garbage that attention provably ignores.
- ``pool["mask"]`` is per-token validity (1 = real token), the paged analog
  of the contiguous cache's ``kv_mask``: bucket-padding holes and
  inactive-step decode writes are masked out, and sliding windows measure
  VALID-slot distance (``cached_attention``), so holes never stretch a
  window.
- Rope/wpe rotations are baked into K at write time from the *token position
  channel*, not the chain slot — which is what makes a full block's K/V a
  pure function of (params, token prefix) and therefore shareable across any
  requests whose prompts start with the same tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import cached_attention


# What a module's ``init_cache`` dict holds, for the paged engine. ``by_token``
# names the entries that become block pools, paged by token through the block
# tables: each ``(layers, batch, columns, heads, dim)`` with its own layers,
# heads, dim and dtype (keys and values; a latent row beside an indexer's key);
# every one is gathered into the view and given a write window under its own
# name. ``by_slot`` entries are
# ``(layers, batch, ...)`` of any dtype and are held once a batch slot, whatever
# the sequence's length (a recurrent state). ``dense_chain`` asks the engine to
# keep a chain free of holes below its frontier, so that a key's column is its
# token's position. ``counters`` names the counts that the cached forward
# returns beside its logits, float32, one a row (B,) or one for the batch ():
# under ``"decode"`` those of one token a row, under ``"chunk"`` those of a
# prefill chunk; the engine sums them on the device and sets them on the span
# that dispatched the program. ``row_mask`` asks the decode step for
# ``attention_mask`` = the rows that decode (a model whose work depends on
# which rows are real: a free slot's pad token must claim no expert).
# ``speculative`` says whether the cached forward returns the logits of every
# position of a multi-token window, which the verify round compares (a model
# that returns the last position's alone is refused with ``speculative_k``). A
# module without a ``cache_layout`` is the plain case.
PLAIN_CACHE_LAYOUT = {"by_token": ("k", "v"), "by_slot": (), "dense_chain": False,
                      "counters": {}, "row_mask": False, "speculative": True}


def cache_layout(module) -> dict:
    """``module.cache_layout`` over the plain layout: which entries of its
    ``init_cache`` are paged by token and which are held by slot."""
    layout = {**PLAIN_CACHE_LAYOUT, **getattr(module, "cache_layout", {})}
    layout["by_token"] = tuple(layout["by_token"])
    return layout


def view_capacity(view) -> int:
    """The columns of the WHOLE block table behind a gathered view: the one
    length a generation has, whatever width this program's view was gathered
    at (the engine's decode window takes the narrowest of a few widths that
    covers its longest chain, so the view's columns differ from window to
    window). The engine states it as ``view["capacity"]``, a static int; a
    view built by hand without one is as wide as its table."""
    return view.get("capacity", view["kv_mask"].shape[1])


def init_kv_pool(module, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
                 quant: str | None = None, slots: int = 1):
    """Allocate the block pool for ``module``'s cache layout.

    Returns ``{name: (L, N, bs, H, D) for each by_token entry, "mask": (N, bs)
    int32}`` (``"k"`` and ``"v"`` for a model that names nothing) with ``N =
    num_blocks + 1`` — block 0 is the reserved trash block (see module
    docstring). Each entry's layer/head/dim axes and dtype are probed from the
    module's own ``init_cache`` (:func:`cache_layout`), so every cached decoder
    family (Llama/GPT-2/GPT-X) gets its exact layout without a second cache
    contract and two entries need not agree in any of them; ``L`` counts the
    layers that HAVE the entry.
    Each ``by_slot`` entry of the probe (``(layers, 1, ...)``) is allocated as
    ``(layers, slots, ...)`` zeros of its own dtype beside the blocks: one a
    batch slot, never paged.

    ``quant="int8"`` stores the payloads as int8 and adds per-block scale
    tables ``{"<name>_scale": (L, N, bs) float32}`` an entry — one scale
    per token row per layer (``ops/int8.quantize_kv``), so the pool costs
    ``1 + 8/(2·Hkv·D)`` bytes per bf16 element instead of 2: ~1.9x the
    chains per HBM byte at realistic head counts. Dequantization happens at
    view-assembly time (``gather_view`` / the Pallas DMA kernels), never in
    the pool itself."""
    if quant not in (None, "int8"):
        raise ValueError(f"kv pool quant must be None or 'int8', got {quant!r}")
    layout = cache_layout(module)
    probe = module.init_cache(1, block_size, dtype=dtype)
    n = num_blocks + 1
    pool = {"mask": jnp.zeros((n, block_size), jnp.int32)}
    for name in layout["by_token"]:
        L, _, _, heads, dim = probe[name].shape
        store = jnp.int8 if quant == "int8" else probe[name].dtype
        pool[name] = jnp.zeros((L, n, block_size, heads, dim), store)
        if quant == "int8":
            pool[name + "_scale"] = jnp.zeros((L, n, block_size), jnp.float32)
    for name in layout["by_slot"]:
        held = probe[name]
        pool[name] = jnp.zeros((held.shape[0], slots) + held.shape[2:], held.dtype)
    return pool


def pool_bytes(pool, layout=PLAIN_CACHE_LAYOUT) -> dict:
    """The cache's persistent device bytes by kind: ``"kv"`` (the entries
    paged by token and, where quantized, their scales) and ``"state"`` (the
    ``by_slot`` entries)."""
    paged = [name + tail for name in layout["by_token"] for tail in ("", "_scale")]
    return {"kv": sum(int(pool[name].nbytes) for name in paged if name in pool),
            "state": sum(int(pool[name].nbytes) for name in layout["by_slot"])}


def token_bytes(pool, layout=PLAIN_CACHE_LAYOUT) -> dict:
    """What one token costs in each ``by_token`` entry over its layers, bytes
    (a quantized entry's scale included)."""
    def one(x):
        return int(x.nbytes) // (x.shape[1] * x.shape[2])
    return {name: one(pool[name]) + (one(pool[name + "_scale"]) if name + "_scale" in pool else 0)
            for name in layout["by_token"]}


def pool_is_quantized(pool) -> bool:
    """Whether a pool carries int8 payloads + per-block scale tables."""
    return any(name.endswith("_scale") for name in pool)


def export_chain_blocks(pool, block_ids, names=PLAIN_CACHE_LAYOUT["by_token"]):
    """Extract one chain's block contents from the pool (the ``by_token``
    entries ``names`` and the mask): the device face of the prefill→decode KV
    handoff (serving_net/handoff.py).

    ``block_ids``: ``(n,)`` int32 pool block indices in chain order. Returns
    ``{name: (L, n, bs, H, D), "mask": (n, bs)}`` — a bounded
    per-chain payload (n blocks, never the pool), which is the whole point
    of the paged layout: ownership moves block-by-block without copying the
    cache. Pure gather; safe to jit or call eagerly."""
    ids = jnp.asarray(block_ids, jnp.int32)
    chain = {name: jnp.take(pool[name], ids, axis=1) for name in names}
    chain["mask"] = jnp.take(pool["mask"], ids, axis=0)
    if pool_is_quantized(pool):
        # Quantized chains ship int8 payloads + their scales: the handoff
        # wire cost drops with the pool, and the importer splices verbatim.
        for name in names:
            chain[name + "_scale"] = jnp.take(pool[name + "_scale"], ids, axis=1)
    return chain


def import_chain_blocks(pool, block_ids, chain, names=PLAIN_CACHE_LAYOUT["by_token"]):
    """Splice an exported chain's block contents into ``pool`` at freshly
    allocated ``block_ids`` — the decode-host half of the handoff. The
    caller (host free-list surgery in serving_net/handoff.py) guarantees the
    ids are allocated and disjoint from every live chain; the mask is written
    verbatim, so bucket-padding holes stay holes and stale bits of the
    reused blocks are overwritten rather than frontier-masked. Returns the
    updated pool (donation-friendly: one scatter per array)."""
    ids = jnp.asarray(block_ids, jnp.int32)
    out = {**pool, "mask": pool["mask"].at[ids].set(chain["mask"])}
    for name in names:
        out[name] = pool[name].at[:, ids].set(chain[name].astype(pool[name].dtype))
    if pool_is_quantized(pool):
        if names[0] + "_scale" not in chain:
            raise ValueError(
                "import_chain_blocks: quantized pool but the chain carries no "
                "scales — exporter and importer must agree on kv_quant"
            )
        for name in names:
            out[name + "_scale"] = pool[name + "_scale"].at[:, ids].set(chain[name + "_scale"])
    return out


# The largest gather slice the v5e's compiler was seen to gather natively (a
# fusion that reads every slice and writes the result once). A larger slice it
# copies one at a time in a serial loop into a buffer it fills first: 512 KiB
# slices compile to the first and 896 KiB slices to the second
# (tests/test_chip_compile.py holds the first for the gather below).
_NATIVE_SLICE_BYTES = 512 * 1024


def _gather_chains(pool, block_tables):
    """``pool`` (L, N, bs, H, D), ``block_tables`` (B, M) -> (L, B, M, bs, H, D):
    one gather, ids clamped to the pool (``mode="clip"``: no in-bounds mask is
    built and nothing is selected, where the default fill for out-of-range
    ids pays a select over the whole result).

    A slice is one table entry's block over every layer where that stays
    within what the compiler gathers natively, and one layer's block
    otherwise, with the layer as a second index: the bf16 pool of a 28-layer
    model (896 KiB an entry) is then read and written once, where its
    whole-entry gather ran as a fill, 1,176 serial copies and a select.
    Depths in between are not used: their result needs a further copy to
    bring the layers of a slice before the slots."""
    L, n, bs, h, d = pool.shape
    b, m = block_tables.shape
    depth = L if L * bs * h * d * pool.dtype.itemsize <= _NATIVE_SLICE_BYTES else 1
    groups = L // depth
    grid = (groups, b, m)
    starts = jnp.stack([
        jnp.broadcast_to(jnp.arange(groups, dtype=jnp.int32)[:, None, None], grid),
        jnp.broadcast_to(block_tables.astype(jnp.int32)[None], grid)], axis=-1)
    chains = jax.lax.gather(
        pool.reshape(groups, depth, n, bs, h, d), starts,
        jax.lax.GatherDimensionNumbers(
            offset_dims=(1, 4, 5, 6), collapsed_slice_dims=(0, 2), start_index_map=(0, 2)),
        slice_sizes=(1, depth, 1, bs, h, d), mode="clip")  # (groups, depth, B, M, bs, H, D)
    return chains.reshape(L, b, m, bs, h, d)


def gather_block_view(pool_kv, block_tables, *, active=None, scales=None,
                      out_dtype=None):
    """Materialize per-slot contiguous KV views from the pool.

    ``pool_kv``: ``(..., N, bs, H, D)`` (a single layer or the L-stacked
    pool); ``block_tables``: ``(B, M)`` int32 block ids. Returns
    ``(..., B, M*bs, H, D)`` — slot ``b``'s chain left-packed in table order.
    This is the reference lowering of paged attention's view assembly: one
    clamped gather (:func:`_gather_chains`), equal to
    ``jnp.take(pool_kv, block_tables, axis=-4)`` bit for bit on ids that are
    in range, which a table's are by the pool's invariant.

    ``scales`` (``(..., N, bs)`` per-block scale tables of a quantized pool)
    arms the dequant seam: the int8 view is gathered together with its
    scales and dequantized per token row (``q.astype(f32) * scale``, then a
    cast to ``out_dtype`` — float32 by default). This exact expression is
    what the Pallas chain-walk kernel replays after its DMA, so reference
    and kernel stay bit-identical on active slots.

    ``active`` (per-slot flags) is accepted for signature parity with the
    chain-walk kernel (``ops/pallas/paged_decode.gather_block_view_kernel``,
    which skips inactive slots); the reference gathers every slot — inactive
    rows are masked garbage either way, and only the kernel bothers to skip
    them. Use :func:`gather_view` for registry-dispatched assembly."""
    del active  # reference computes all slots; masks make the garbage inert
    b, m = block_tables.shape
    lead, (n, bs, h, d) = pool_kv.shape[:-4], pool_kv.shape[-4:]
    view = _gather_chains(pool_kv.reshape((-1, n, bs, h, d)), block_tables)
    view = view.reshape(lead + (b, m * bs, h, d))
    if scales is None:
        return view if out_dtype is None else view.astype(out_dtype)
    # A float a token row: small, so a plain clamped gather.
    s = jnp.take(scales, block_tables, axis=-2, mode="clip")  # (..., B, M, bs)
    s = s.reshape(s.shape[:-2] + (m * bs,))
    deq = view.astype(jnp.float32) * s[..., None, None].astype(jnp.float32)
    return deq.astype(out_dtype if out_dtype is not None else jnp.float32)


def gather_view(pool_kv, block_tables, *, active=None, scales=None,
                out_dtype=None, backend=None):
    """Registry-dispatched view assembly (op ``paged_gather``): the Pallas
    chain-walk kernel when ``ACCELERATE_KERNELS`` (or ``backend``) selects
    it, the clamped gather of :func:`gather_block_view` otherwise.
    Bit-identical for active slots
    (pure data movement, or gather+dequant when ``scales`` arms the int8
    path); the kernel skips ``active == 0`` slots."""
    from .registry import dispatch, resolve_backend

    if resolve_backend("paged_gather", backend) == "reference":
        return gather_block_view(pool_kv, block_tables, active=active,
                                 scales=scales, out_dtype=out_dtype)
    return dispatch(
        "paged_gather", pool_kv, block_tables, active=active, scales=scales,
        out_dtype=out_dtype, backend=backend,
    )


def gather_block_mask(pool_mask, block_tables):
    """Per-slot validity view: ``(N, bs)`` pool mask + ``(B, M)`` tables →
    ``(B, M*bs)`` — the paged analog of the contiguous cache's ``kv_mask``."""
    b, m = block_tables.shape
    return jnp.take(pool_mask, block_tables, axis=0, mode="clip").reshape(
        b, m * pool_mask.shape[1])


def paged_attention_reference(q, k_pool, v_pool, block_tables, *, q_positions,
                              pool_mask=None, window=None, softcap=None,
                              scale=None, active=None, k_scale=None,
                              v_scale=None):
    """The reference lowering: gather each slot's chain to a contiguous view,
    then run the hole-tolerant :func:`~.attention.cached_attention`
    (causality on chain-slot order, validity from the gathered mask, sliding
    windows in valid-slot distance). This is the committed parity seam — the
    Pallas kernel must match it bit-for-bit on active slots on the test
    vectors in tests/test_paged_attention.py and tests/test_kernels.py.
    ``active`` is accepted for kernel-signature parity and ignored (the
    reference computes masked garbage for inactive slots). ``k_scale`` /
    ``v_scale`` (``(N, bs)`` per-block scale tables) arm the int8-pool path:
    views dequantize to float32 before the shared attention math, mirroring
    the kernel's dequant-in-DMA step."""
    del active
    k_view = gather_block_view(k_pool, block_tables, scales=k_scale)
    v_view = gather_block_view(v_pool, block_tables, scales=v_scale)
    kv_mask = (
        gather_block_mask(pool_mask, block_tables) if pool_mask is not None else None
    )
    return cached_attention(
        q, k_view, v_view, q_positions=q_positions, kv_mask=kv_mask,
        window=window, softcap=softcap, scale=scale,
    )


def paged_attention(q, k_pool, v_pool, block_tables, *, q_positions,
                    pool_mask=None, window=None, softcap=None, scale=None,
                    active=None, k_scale=None, v_scale=None, backend=None):
    """Attention of a query chunk against block-table-addressed KV pools.

    q: ``(B, S, H, D)``; k_pool/v_pool: ``(N, bs, Hkv, D)`` (one layer);
    block_tables: ``(B, M)``; q_positions: ``(S,)`` or ``(B, S)`` positions in
    each slot's *chain-slot* index space (chain slot ``j`` of slot ``b`` is
    view column ``j``); pool_mask: ``(N, bs)`` per-token validity;
    ``active``: optional per-slot flags — the Pallas backend skips inactive
    (bucket-padded) slots entirely and returns zeros for them.

    Dispatches through the kernel registry (op ``paged_decode``): the Pallas
    ragged kernel walks each slot's block chain in VMEM with no materialized
    gather view when ``ACCELERATE_KERNELS`` (or ``backend``) selects it; the
    reference gather+``cached_attention`` composition otherwise."""
    from .registry import dispatch, resolve_backend

    if resolve_backend("paged_decode", backend) == "reference":
        return paged_attention_reference(
            q, k_pool, v_pool, block_tables, q_positions=q_positions,
            pool_mask=pool_mask, window=window, softcap=softcap, scale=scale,
            active=active, k_scale=k_scale, v_scale=v_scale,
        )
    return dispatch(
        "paged_decode", q, k_pool, v_pool, block_tables,
        q_positions=q_positions, pool_mask=pool_mask, window=window,
        softcap=softcap, scale=scale, active=active, k_scale=k_scale,
        v_scale=v_scale, backend=backend,
    )
