"""Block-sparse softmax attention with a learned-free block selection
(InfLLM v2, as in the MiniCPM4 report, arXiv:2506.07900), in plain
``jax.numpy``.

For a query at position ``t`` and a key-value head ``g``:

1. **Compressed keys.** ``kbar_j`` is the mean of the keys of positions
   ``[stride*j, stride*j + kernel)``; only windows that end at or before
   ``t`` take part.
2. **Scores.** Each query head of the group takes a softmax over the
   compressed positions, ``p = softmax_j(q . kbar_j / sqrt(d))``; the group's
   score of ``j`` is the sum over its heads; a block's score is the maximum
   over the windows that overlap it.
3. **Selection.** Block 0, the blocks that hold the last ``window`` tokens,
   and the best of the rest, up to ``topk`` blocks in all.
4. **Attention.** Ordinary causal softmax attention over the tokens of the
   selected blocks.

Keys come in two parts, as the paged engine hands them over
(``serving.py`` ``_paged_view_cache``): a read-only **view** whose column
``c`` holds the token of position ``c`` (a dense chain: no holes below
``view_len``), and the **new** keys this program writes (a prefill chunk, or
a decode window's columns so far), which follow the view. New keys are never
selected: with at most ``window - block - kernel`` of them they all lie inside
the last ``window`` tokens of every query, so they are always attended and only
count against the budget of ``topk``. A plain forward pass is the view alone
(``k_new=None``), masked causally.

Scoring runs in float32 at the highest matmul precision (the compressed
products are small; nearly level scores would otherwise flip blocks between
bf16 and float32). A chunk of queries (prefill) masks a tile's dense scores
by the selection, tile by tile, so that no ``(heads, chunk, context)`` tensor
is ever whole; one query (decode) gathers the selected blocks. No kernel: the
Pallas block-sparse product is a later ``perf_opt`` PR's (ROADMAP.md, Queue
2, B5).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
NEG = -1e30  # finite: a row with nothing to attend stays finite garbage


@dataclass(frozen=True)
class SparseGeometry:
    block: int = 64     # tokens a block
    topk: int = 64      # blocks a query and key-value head, forced ones included
    window: int = 2048  # the blocks that hold the last `window` tokens are always selected
    init_blocks: int = 1
    kernel: int = 32    # tokens a compressed key averages
    stride: int = 16

    def __post_init__(self):
        if self.kernel != 2 * self.stride or self.block % self.stride:
            raise ValueError(
                "block-sparse selection needs kernel == 2 * stride and a block "
                f"that is a multiple of the stride, got {self}")

    @property
    def windows_per_block(self) -> int:
        return self.block // self.stride

    @property
    def max_new(self) -> int:
        """The most new keys a program may bring: all of them must lie inside
        the last ``window`` tokens of each of its queries."""
        return self.window - self.block - self.kernel


def compress_keys(k, geo: SparseGeometry):
    """k: (B, T, G, D), T a multiple of the stride -> (B, T // stride, G, D)
    float32; entry ``j`` is the mean over ``[stride*j, stride*j + kernel)``
    (the last entry has no second half and is never valid)."""
    b, t, g, d = k.shape
    halves = k.astype(jnp.float32).reshape(b, t // geo.stride, geo.stride, g, d).mean(axis=2)
    return 0.5 * (halves + jnp.concatenate([halves[:, 1:], halves[:, -1:]], axis=1))


def _recent_compressed(k_view, view_len, k_new_dense, geo: SparseGeometry):
    """Compressed keys of the windows that touch the new keys.

    Returns ``(kbar_recent (B, Jr, G, D), j0 (B,))``: entry ``i`` is window
    ``j0 + i``. ``j0 * stride`` is the last multiple of the stride at or
    below ``view_len - kernel``, so windows below ``j0`` lie in the view alone
    and those from ``j0`` on are taken from here."""
    b, t, g, d = k_view.shape
    p = k_new_dense.shape[1]
    head = geo.kernel + geo.stride  # view columns from j0*stride on: < head of them
    a = geo.stride * (jnp.maximum(view_len - geo.kernel, 0) // geo.stride)  # (B,)
    take = jnp.minimum(a[:, None] + jnp.arange(head)[None], t - 1)
    tail = jnp.take_along_axis(k_view, take[:, :, None, None], axis=1)  # (B, head, G, D)
    both = jnp.concatenate([tail, k_new_dense.astype(tail.dtype)], axis=1)
    length = geo.stride * (-(-(head + p) // geo.stride))
    position = a[:, None] + jnp.arange(length)[None]  # (B, length)
    source = jnp.where(position < view_len[:, None], position - a[:, None],
                       head + position - view_len[:, None])
    source = jnp.clip(source, 0, head + p - 1)
    recent = jnp.take_along_axis(both, source[:, :, None, None], axis=1)
    return compress_keys(recent, geo)[:, :-1], a // geo.stride


def block_scores(q, q_pos, kbar_view, j_split, geo: SparseGeometry, kbar_recent=None, j0=None):
    """The group's score of every view block, (B, G, S, NB) float32, >= 0.

    q: (B, S, H, D); q_pos: (B, S); kbar_view: (B, J, G, D), J = 4 NB;
    j_split: (B,): view windows below it are read from ``kbar_view``, the
    rest (global index ``j0 + i``) from ``kbar_recent``."""
    b, s, h, d = q.shape
    j, g = kbar_view.shape[1], kbar_view.shape[2]
    qg = q.astype(jnp.float32).reshape(b, s, g, h // g, d) * (d ** -0.5)
    ends = lambda index: geo.stride * index + geo.kernel - 1  # a window's last position

    index_v = jnp.arange(j)
    valid_v = ((index_v[None, None] < j_split[:, None, None])
               & (ends(index_v)[None, None] <= q_pos[:, :, None]))  # (B, S, J)
    s_v = jnp.einsum("bsgrd,bjgd->bgrsj", qg, kbar_view, precision=_HI)
    s_v = jnp.where(valid_v[:, None, None], s_v, NEG)
    top = s_v.max(axis=-1)
    if kbar_recent is not None:
        index_r = j0[:, None] + jnp.arange(kbar_recent.shape[1])[None]  # (B, Jr)
        valid_r = ((index_r[:, None] >= j_split[:, None, None])
                   & (ends(index_r)[:, None] <= q_pos[:, :, None]))  # (B, S, Jr)
        s_r = jnp.einsum("bsgrd,bjgd->bgrsj", qg, kbar_recent, precision=_HI)
        s_r = jnp.where(valid_r[:, None, None], s_r, NEG)
        top = jnp.maximum(top, s_r.max(axis=-1))
    e_v = jnp.where(valid_v[:, None, None], jnp.exp(s_v - top[..., None]), 0.0)
    z = e_v.sum(axis=-1)
    if kbar_recent is not None:
        z = z + jnp.where(valid_r[:, None, None], jnp.exp(s_r - top[..., None]), 0.0).sum(axis=-1)
    group = (e_v / jnp.maximum(z, 1e-30)[..., None]).sum(axis=2)  # (B, G, S, J)
    w = geo.windows_per_block
    per_block = group.reshape(b, g, s, j // w, w)
    # Block n overlaps windows w*n - 1 .. w*n + w - 1: its own and the last of the block before.
    before = jnp.pad(per_block[..., :-1, w - 1], ((0, 0),) * 3 + ((1, 0),))
    return jnp.maximum(per_block.max(axis=-1), before)


def select_blocks(scores, q_pos, view_len, geo: SparseGeometry):
    """Top-k over the view's blocks with the forced ones first.

    scores: (B, G, S, NB) from :func:`block_scores`. Returns ``(index, chosen,
    mask)``: ``index`` (B, G, S, topk) block numbers, best first; ``chosen``
    (same shape) says which of them are selected (the budget that is left
    after the blocks of new keys, and a block that exists); ``mask``
    (B, G, S, NB) the same selection as a mask over blocks."""
    nb = scores.shape[-1]
    first = geo.block * jnp.arange(nb)  # a block's first position
    t = q_pos[:, None, :, None]
    forced = (jnp.arange(nb) < geo.init_blocks) | (first + geo.block - 1 >= t - (geo.window - 1))
    exists = (first <= t) & (first < view_len[:, None, None, None])
    ranked = jnp.where(exists, jnp.where(forced, jnp.inf, scores), -jnp.inf)
    # Blocks that hold new keys alone are always attended and use up budget.
    new_blocks = jnp.maximum(q_pos // geo.block - (-(-view_len // geo.block))[:, None] + 1, 0)
    budget = jnp.maximum(geo.topk - new_blocks, 0)[:, None, :, None]  # (B, 1, S, 1)
    k = min(geo.topk, nb)
    values, index = jax.lax.top_k(ranked, k)
    chosen = (jnp.arange(k) < budget) & (values > -jnp.inf)
    # The same selection as a mask: everything above the last chosen score, and
    # of its equals (two blocks that share their best window tie exactly) those
    # at or below its block number, which is the order top_k breaks ties in.
    at_last = jnp.clip(budget - 1, 0, k - 1)
    last = jnp.take_along_axis(values, at_last, axis=-1)
    last_block = jnp.take_along_axis(index, at_last, axis=-1)
    mask = (ranked > last) | ((ranked == last) & (jnp.arange(nb) <= last_block))
    mask = mask & (ranked > -jnp.inf) & (budget > 0)
    return index, chosen, mask


def _softmax_two_parts(s_view, ok_view, v_view, s_new, ok_new, v_new, out_dtype):
    """One softmax over view and new columns. s_*: (B, G, R, S, cols) float32;
    ok_*: broadcastable masks; v_view: (B, cols, G, D) (the whole view, or each
    group's gathered columns); v_new: (B, P, G, D) or None."""
    s_view = jnp.where(ok_view, s_view, NEG)
    top = s_view.max(axis=-1)
    if s_new is not None:
        s_new = jnp.where(ok_new, s_new, NEG)
        top = jnp.maximum(top, s_new.max(axis=-1))
    e_view = jnp.where(ok_view, jnp.exp(s_view - top[..., None]), 0.0)
    z = e_view.sum(axis=-1)
    out = jnp.einsum("bgrst,btgd->bsgrd", e_view.astype(v_view.dtype), v_view,
                     preferred_element_type=jnp.float32)
    if s_new is not None:
        e_new = jnp.where(ok_new, jnp.exp(s_new - top[..., None]), 0.0)
        z = z + e_new.sum(axis=-1)
        out = out + jnp.einsum("bgrsp,bpgd->bsgrd", e_new.astype(v_new.dtype), v_new,
                               preferred_element_type=jnp.float32)
    z = jnp.moveaxis(jnp.maximum(z, 1e-30), 3, 1)  # (B, S, G, R)
    out = out / z[..., None]
    b, s = out.shape[:2]
    return out.reshape(b, s, -1, out.shape[-1]).astype(out_dtype)


def sparse_attention(q, q_pos, k_view, v_view, view_len, geo: SparseGeometry, *,
                     kbar_view=None, k_new=None, v_new=None, new_pos=None, new_valid=None,
                     query_tile: int = 64, return_selection: bool = False):
    """Block-sparse attention of ``q`` over a view and the new keys.

    q: (B, S, H, D); q_pos: (B, S) token positions; k_view, v_view:
    (B, T, G, D), T a multiple of the block, column = position, valid below
    ``view_len`` (B,); ``kbar_view``: :func:`compress_keys` of ``k_view`` where
    the caller has it already; k_new, v_new: (B, P, G, D) with positions
    ``new_pos`` (B, P), which continue the view's (``view_len + rank``), and
    validity ``new_valid`` (B, P).

    Returns ``(out (B, S, H, D), counts)``; ``counts`` is ``(attended,
    context)``, each (B,) float32, for one query (S == 1): the keys the query
    attended and the keys of its causal context, summed over key-value heads;
    ``None`` for a chunk. With ``return_selection`` the block mask
    (B, G, S, NB) is returned in place of ``counts``."""
    b, s, h, d = q.shape
    t, g = k_view.shape[1], k_view.shape[2]
    if t % geo.block:
        raise ValueError(
            f"block-sparse attention needs a view of whole blocks: {t} columns is not a "
            f"multiple of {geo.block} (choose a pool block_size that {geo.block} divides "
            "into the per-slot table, e.g. block_size == the sparse block)")
    if k_new is not None and k_new.shape[1] > geo.max_new:
        raise ValueError(
            f"block-sparse attention takes at most {geo.max_new} new keys a program "
            f"(window {geo.window} less a block and a compressed key), got {k_new.shape[1]}")
    if kbar_view is None:
        kbar_view = compress_keys(k_view, geo)
    scale = d ** -0.5
    if k_new is None:
        j_split = jnp.full((b,), kbar_view.shape[1], jnp.int32)
        kbar_recent = j0 = None
    else:
        p = k_new.shape[1]
        rank = jnp.where(new_valid > 0, new_pos - view_len[:, None], p)  # invalid: dropped
        dense = jnp.zeros_like(k_new).at[jnp.arange(b)[:, None], rank].set(k_new, mode="drop")
        kbar_recent, j0 = _recent_compressed(k_view, view_len, dense, geo)
        j_split = j0

    def select(q_part, pos_part):
        scores = block_scores(q_part, pos_part, kbar_view, j_split, geo, kbar_recent, j0)
        return select_blocks(scores, pos_part, view_len, geo)

    def new_part(qg, pos_part):
        if k_new is None:
            return None, None
        s_new = jnp.einsum("bsgrd,bpgd->bgrsp", qg, k_new,
                           preferred_element_type=jnp.float32) * scale
        ok = (new_valid[:, None, :] > 0) & (new_pos[:, None, :] <= pos_part[:, :, None])
        return s_new, ok[:, None, None]  # (B, 1, 1, S, P)

    if s == 1 and not return_selection:
        index, chosen, _ = select(q, q_pos)  # (B, G, 1, K)
        index, chosen = index[:, :, 0], chosen[:, :, 0]
        nb = t // geo.block
        rows, heads = jnp.arange(b)[:, None, None], jnp.arange(g)[None, :, None]
        # Each group's selected blocks, laid out as a view of its own: (B, K * block, G, D),
        # in float32 (the values are bf16's own, so the products are what bf16 operands
        # give; XLA's CPU backend has no bf16 x bf16 -> f32 product in this layout).
        gather = lambda x: jnp.swapaxes(
            x.reshape(b, nb, geo.block, g, d)[rows, index, :, heads].reshape(b, g, -1, d), 1, 2
        ).astype(jnp.float32)
        k_sel, v_sel = gather(k_view), gather(v_view)
        column = (index[..., None] * geo.block + jnp.arange(geo.block)).reshape(b, g, -1)
        ok = (jnp.repeat(chosen, geo.block, axis=-1) & (column < view_len[:, None, None])
              & (column <= q_pos[:, :, None]))  # (B, G, K * block)
        qg = q.reshape(b, 1, g, h // g, d)
        s_view = jnp.einsum("bsgrd,btgd->bgrst", qg.astype(jnp.float32), k_sel) * scale
        s_new, ok_new = new_part(qg, q_pos)
        out = _softmax_two_parts(s_view, ok[:, :, None, None], v_sel, s_new, ok_new, v_new, q.dtype)
        attended = ok.sum(axis=(1, 2)).astype(jnp.float32)
        if ok_new is not None:
            attended = attended + g * ok_new.sum(axis=(1, 2, 3, 4)).astype(jnp.float32)
        context = g * (q_pos[:, 0] + 1).astype(jnp.float32)
        return out, (attended, context)

    tile = min(query_tile, s)
    pad = -s % tile
    q_pad = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pos_pad = jnp.pad(q_pos, ((0, 0), (0, pad)))
    n = (s + pad) // tile
    column = jnp.arange(t)

    def one_tile(xs):
        q_part, pos_part = xs  # (B, tile, H, D), (B, tile)
        _, _, mask = select(q_part, pos_part)  # (B, G, tile, NB)
        ok = (jnp.repeat(mask, geo.block, axis=-1)
              & (column[None, None, None] < view_len[:, None, None, None])
              & (column[None, None, None] <= pos_part[:, None, :, None]))
        qg = q_part.reshape(b, tile, g, h // g, d)
        s_view = jnp.einsum("bsgrd,btgd->bgrst", qg, k_view,
                            preferred_element_type=jnp.float32) * scale
        s_new, ok_new = new_part(qg, pos_part)
        out = _softmax_two_parts(s_view, ok[:, :, None], v_view, s_new, ok_new, v_new, q.dtype)
        return out, mask

    fold = lambda x: jnp.moveaxis(x.reshape(b, n, tile, *x.shape[2:]), 1, 0)
    out, mask = jax.lax.map(one_tile, (fold(q_pad), fold(pos_pad)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s + pad, h, d)[:, :s]
    if return_selection:
        mask = jnp.moveaxis(mask, 0, 2).reshape(b, g, s + pad, -1)[:, :, :s]
        return out, mask
    return out, None
