"""Paged/ragged decode-attention Pallas kernels — block chains, no gather view.

The reference lowering (``ops/paged_attention.paged_attention_reference``)
materializes every slot's chain as a contiguous HBM view via an XLA gather
over the block tables, then runs ``cached_attention`` on the view — the
explicitly-named slow path (ROADMAP item 3): the gather re-materializes the
whole chain's KV every decode window, and bucket-padded slots pay full price
for garbage.

Two kernels kill it:

- :func:`paged_attention_kernel` — the fused op seam. Grid over batch slots;
  each program walks ITS slot's block chain with per-block async DMA
  (HBM → VMEM scratch), assembles the chain in VMEM only, and computes the
  attention math there. No (B, T, H, D) gather view ever exists in HBM.
  Padded slots (``active == 0``) skip both the DMA walk and the compute.
- :func:`gather_block_view_kernel` — the chain-walk *assembly* kernel behind
  ``gather_block_view``: per-(layer, slot) DMA of pool blocks straight into
  the output view, skipping dead slots. This is the swap the serving
  engine's uniform-write-window design consumes today (the view is the
  read-only part of the model forward's two-part cache); the fused kernel
  above is the no-view seam the model-side paged-cache integration targets.

Bit-exactness: inside the attention kernel the assembled chain is fed to the
SAME ``cached_attention`` math the reference composes (a pure-jnp function —
Pallas traces it into the kernel body), so active-slot outputs are
bit-identical to the reference by construction, not by maintenance. Padded
slots return zeros (the reference computes masked garbage there; the engine
never reads either). Sliding windows, softcap, and GQA ride through
unchanged because the math is shared.

TPU layout note (module docstring of ops/paged_attention.py): ``block_size``
should stay a multiple of 16 (bf16 sublane) so block DMAs stream without
repacking; the engine default is 16. Compiled-Mosaic lowering of the
windowed (valid-slot cumsum) path gathers along the chain axis in-kernel —
validated in interpret mode everywhere, on-chip validation rides the
BENCH_KERNELS round.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import cached_attention
from ..registry import register_op


def _norm_positions(q_positions, batch: int):
    pos = jnp.asarray(q_positions)
    if pos.ndim == 1:
        pos = jnp.broadcast_to(pos[None], (batch, pos.shape[0]))
    return pos


def _norm_active(active, batch: int):
    if active is None:
        return jnp.ones((batch,), jnp.int32)
    return jnp.asarray(active).astype(jnp.int32).reshape(batch)


def paged_attention_kernel(q, k_pool, v_pool, block_tables, *, q_positions,
                           pool_mask=None, window=None, softcap=None,
                           scale=None, active=None, k_scale=None,
                           v_scale=None, interpret: bool = False):
    """Fused paged decode attention: q + pools + block tables → attention out.

    Signature-compatible with ``paged_attention_reference`` plus ``active``:
    a per-slot int/bool vector — slots with ``active == 0`` (bucket padding,
    drained slots) skip the chain walk entirely and return zeros. Shapes:
    q ``(B, S, H, D)``; pools ``(N, bs, Hkv, D)``; tables ``(B, M)``;
    q_positions ``(S,)`` or ``(B, S)``; pool_mask ``(N, bs)``.

    ``k_scale`` / ``v_scale`` (``(N, bs)`` float32) arm the **int8-pool
    dequant-in-DMA path**: the chain walk DMAs each int8 block *and its
    scale row* into VMEM scratch, dequantizes there (``q.astype(f32) *
    scale`` — the exact ``ops/int8.dequantize_kv`` expression the reference
    gather replays), and feeds the shared attention math float32 views. HBM
    traffic halves with the pool; nothing ever rematerializes the bf16
    chain in HBM.
    """
    B, S, H, D = q.shape
    N, bs, Hkv, _ = k_pool.shape
    M = block_tables.shape[-1]
    T = M * bs
    pos = _norm_positions(q_positions, B)
    act = _norm_active(active, B)
    tables = jnp.asarray(block_tables).astype(jnp.int32)
    has_mask = pool_mask is not None
    quant = k_scale is not None
    if quant and v_scale is None:
        raise ValueError("paged_decode: k_scale set without v_scale")
    out_dtype = (jnp.result_type(q.dtype, jnp.float32) if quant
                 else jnp.result_type(q.dtype, v_pool.dtype))

    def body(tbl_ref, act_ref, q_ref, pos_ref, k_ref, v_ref, *rest):
        rest = list(rest)
        m_ref = rest.pop(0) if has_mask else None
        ks_ref = rest.pop(0) if quant else None
        vs_ref = rest.pop(0) if quant else None
        o_ref = rest.pop(0)
        k_scr = rest.pop(0)
        v_scr = rest.pop(0)
        m_scr = rest.pop(0) if has_mask else None
        ks_scr = rest.pop(0) if quant else None
        vs_scr = rest.pop(0) if quant else None
        sems = rest.pop(0)
        b = pl.program_id(0)

        @pl.when(act_ref[b] != 0)
        def _():
            # Walk the slot's chain: per-block DMA from the HBM pools into
            # VMEM scratch. Copies for one chain slot start together (k, v,
            # mask and scales overlap each other); the chain itself is short
            # (M blocks).
            for j in range(M):
                idx = tbl_ref[b, j]
                copies = [
                    pltpu.make_async_copy(k_ref.at[idx], k_scr.at[j], sems.at[0]),
                    pltpu.make_async_copy(v_ref.at[idx], v_scr.at[j], sems.at[1]),
                ]
                if has_mask:
                    copies.append(
                        pltpu.make_async_copy(m_ref.at[idx], m_scr.at[j], sems.at[2])
                    )
                if quant:
                    copies.append(
                        pltpu.make_async_copy(ks_ref.at[idx], ks_scr.at[j], sems.at[3])
                    )
                    copies.append(
                        pltpu.make_async_copy(vs_ref.at[idx], vs_scr.at[j], sems.at[4])
                    )
                for c in copies:
                    c.start()
                for c in copies:
                    c.wait()
            k_view = k_scr[:].reshape(T, Hkv, D)
            v_view = v_scr[:].reshape(T, Hkv, D)
            if quant:
                # Dequant at the VMEM seam: identical expression to the
                # reference's gather_block_view(scales=...) lowering.
                k_view = k_view.astype(jnp.float32) * ks_scr[:].reshape(T)[:, None, None]
                v_view = v_view.astype(jnp.float32) * vs_scr[:].reshape(T)[:, None, None]
            kv_mask = m_scr[:].reshape(1, T) if has_mask else None
            # The reference's exact math on the assembled chain: per-slot
            # attention is independent across B, so the single-slot call is
            # bit-identical to the batched reference row.
            out = cached_attention(
                q_ref[:], k_view[None], v_view[None],
                q_positions=pos_ref[:], kv_mask=kv_mask,
                window=window, softcap=softcap, scale=scale,
            )
            o_ref[:] = out.astype(o_ref.dtype)

        @pl.when(act_ref[b] == 0)
        def _():
            o_ref[:] = jnp.zeros_like(o_ref)

    in_specs = [
        pl.BlockSpec((1, S, H, D), lambda b, tbl, act: (b, 0, 0, 0)),
        pl.BlockSpec((1, S), lambda b, tbl, act: (b, 0)),
        pl.BlockSpec(memory_space=pltpu.ANY),
        pl.BlockSpec(memory_space=pltpu.ANY),
    ]
    scratch = [
        pltpu.VMEM((M, bs, Hkv, D), k_pool.dtype),
        pltpu.VMEM((M, bs, Hkv, D), v_pool.dtype),
    ]
    operands = [q, pos, k_pool, v_pool]
    n_sems = 2
    if has_mask:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.ANY))
        scratch.append(pltpu.VMEM((M, bs), jnp.asarray(pool_mask).dtype))
        n_sems = 3
        operands.append(jnp.asarray(pool_mask))
    if quant:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.ANY))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.ANY))
        scratch.append(pltpu.VMEM((M, bs), jnp.float32))
        scratch.append(pltpu.VMEM((M, bs), jnp.float32))
        n_sems = 5  # scale sems sit at fixed indices 3/4 past the mask's
        operands.append(jnp.asarray(k_scale).astype(jnp.float32))
        operands.append(jnp.asarray(v_scale).astype(jnp.float32))
    scratch.append(pltpu.SemaphoreType.DMA((n_sems,)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, S, H, D), lambda b, tbl, act: (b, 0, 0, 0)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((B, S, H, D), out_dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_decode_kernel",
    )(tables, act, *operands)


def gather_block_view_kernel(pool_kv, block_tables, *, active=None,
                             scales=None, out_dtype=None,
                             interpret: bool = False):
    """Chain-walk view assembly: pool + tables → per-slot contiguous views.

    Bit-identical to ``gather_block_view``'s XLA gather for every slot whose
    ``active`` flag is set (pure data movement), zeros for skipped slots.
    ``pool_kv`` is ``(L, N, bs, H, D)`` (the engine's L-stacked pool) or
    ``(N, bs, H, D)`` (a single layer); output matches the reference shape
    ``(..., B, M*bs, H, D)``.

    ``scales`` (``(..., N, bs)`` float32, the quantized pool's per-block
    scale tables) arms the **dequant-in-DMA** path: each int8 block and its
    scale row DMA into VMEM scratch, dequantize there (``q.astype(f32) *
    scale`` — exactly ``gather_block_view``'s lowering), and the view lands
    in ``out_dtype`` (float32 default). The serving engine compiles THIS
    kernel into its decode program when ``kv_quant="int8"`` — the
    fingerprint config ``decode_paged_int8`` pins its presence."""
    squeeze = pool_kv.ndim == 4
    if squeeze:
        pool_kv = pool_kv[None]
        if scales is not None:
            scales = scales[None]
    L, N, bs, Hkv, D = pool_kv.shape
    B, M = block_tables.shape
    T = M * bs
    act = _norm_active(active, B)
    tables = jnp.asarray(block_tables).astype(jnp.int32)
    quant = scales is not None
    # Quant path casts in-kernel (dequant writes o_ref.dtype); the plain path
    # is a pure DMA, so any requested out_dtype applies after the call.
    out_dt = ((out_dtype if out_dtype is not None else jnp.float32)
              if quant else pool_kv.dtype)

    def body(tbl_ref, act_ref, pool_ref, *rest):
        if quant:
            s_ref, o_ref, blk_scr, s_scr, sems = rest
        else:
            (o_ref, sems) = rest
        l = pl.program_id(0)
        b = pl.program_id(1)

        @pl.when(act_ref[b] != 0)
        def _():
            for j in range(M):
                idx = tbl_ref[b, j]
                if quant:
                    copies = [
                        pltpu.make_async_copy(pool_ref.at[l, idx], blk_scr,
                                              sems.at[0]),
                        pltpu.make_async_copy(s_ref.at[l, idx], s_scr.at[0],
                                              sems.at[1]),
                    ]
                    for c in copies:
                        c.start()
                    for c in copies:
                        c.wait()
                    deq = blk_scr[:].astype(jnp.float32) * s_scr[0][:, None, None]
                    o_ref[0, 0, pl.ds(j * bs, bs)] = deq.astype(o_ref.dtype)
                else:
                    dma = pltpu.make_async_copy(
                        pool_ref.at[l, idx],
                        o_ref.at[0, 0, pl.ds(j * bs, bs)],
                        sems.at[0],
                    )
                    dma.start()
                    dma.wait()

        @pl.when(act_ref[b] == 0)
        def _():
            o_ref[:] = jnp.zeros_like(o_ref)

    in_specs = [pl.BlockSpec(memory_space=pltpu.ANY)]
    operands = [pool_kv]
    scratch: list = []
    if quant:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.ANY))
        operands.append(jnp.asarray(scales).astype(jnp.float32))
        scratch = [pltpu.VMEM((bs, Hkv, D), pool_kv.dtype),
                   pltpu.VMEM((1, bs), jnp.float32)]
    scratch.append(pltpu.SemaphoreType.DMA((2,)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L, B),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, T, Hkv, D), lambda l, b, tbl, act: (l, b, 0, 0, 0)
        ),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((L, B, T, Hkv, D), out_dt),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_gather_dequant_kernel" if quant else "paged_gather_kernel",
    )(tables, act, *operands)
    if not quant and out_dtype is not None:
        out = out.astype(out_dtype)
    return out[0] if squeeze else out


def _register():
    from ..paged_attention import gather_block_view, paged_attention_reference

    register_op(
        "paged_decode", paged_attention_reference, paged_attention_kernel,
        doc="ragged decode attention over block-table chains (no gather view)",
    )
    register_op(
        "paged_gather", gather_block_view, gather_block_view_kernel,
        doc="chain-walk assembly of per-slot KV views (skips padded slots)",
    )


_register()
