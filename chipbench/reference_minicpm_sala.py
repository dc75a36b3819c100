"""The plain reference for MiniCPM-SALA: its forward pass in float32
``jax.numpy``, every matrix product under
``jax.default_matmul_precision("highest")``, nothing imported from the program
under test. No cache, no batching, no kernel: one sequence, whole.

**The equations.** With ``r = scale_depth / sqrt(residual_depth)``, per layer
``x = x + r * mixer(norm1(x))`` then ``x = x + r * swiglu(norm2(x))``;
embeddings times ``scale_emb``; after the final norm the hidden state is
divided by ``hidden_size / dim_model_base`` before the untied head. RMSNorm
with ``rms_norm_eps``.

*Sparse layer* (``"minicpm4"``). ``q = hW_q`` (H heads of D), ``k, v = hW_k,
hW_v`` (G heads of D), no rotation. For query position ``t`` and key-value
head ``g``: compressed keys ``kbar_j = mean(k[stride*j : stride*j + kernel])``
for the windows that end at or before ``t``; for each of the H/G query heads of
the group ``p = softmax_j(q_t . kbar_j / sqrt(D))``; the group's score of ``j``
is the sum over its heads; a block's score is the maximum over the ``j`` whose
window overlaps it; the selected set is block 0, the blocks that hold the last
``sparse_window`` tokens, and the best of the rest up to ``sparse_topk`` blocks
in all; causal softmax attention at scale ``1/sqrt(D)`` over the tokens of the
selected blocks; the output times ``sigmoid(hW_g)``, then ``W_o``.

*Lightning layer* (``"lightning-attn"``). ``q, k, v = hW_q, hW_k, hW_v`` (Hl
heads of Dl each), RMSNorm over each head of ``q`` and ``k``, rotation (theta
``rope_theta``, half-split pairs) on both, ``q / sqrt(Dl)``; ``S_t = lambda_h
S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t`` with ``lambda_h = exp(-2^(-8(h+1)/Hl))``;
RMSNorm of ``o`` over the concatenated heads, times ``sigmoid(hW_g)``, then
``W_o``. Computed block by block (inside a block the decayed causal products,
across blocks the carried ``S``), which is the recurrence itself reordered.

**The weights** are read in the layout the program keeps them (a fact about
data, not an import): ``embed.weight (V, h)``; under ``layers`` what every
layer has, stacked over all L layers (``input_norm.weight``,
``post_attn_norm.weight (L, h)``, ``mlp.w_gate``, ``mlp.w_up (L, h, I)``,
``mlp.w_down (L, I, h)``), the sparse layers' mixers stacked over those layers
alone, in the order they occur (``sparse.wq``, ``sparse.wg (Ls, h, H·D)``,
``sparse.wk``, ``sparse.wv (Ls, h, G·D)``, ``sparse.wo (Ls, H·D, h)``), the
lightning layers' likewise (``lightning.wq``, ``wk``, ``wv``, ``wg (Ll, h,
Hl·Dl)``, ``lightning.wo``, ``lightning.q_norm``, ``lightning.k_norm (Ll,
Dl)``, ``lightning.o_norm (Ll, Hl·Dl)``); ``final_norm.weight (h,)``;
``lm_head.weight (V, h)``. Weights of any dtype are cast to float32 one layer
at a time. ``cfg`` is the configuration file's own dict: the published keys,
``mixer_types`` as run, and the assumed sizes (``residual_depth``,
``sparse_block_size``, ``sparse_topk``, ``sparse_window``,
``sparse_init_blocks``, ``sparse_kernel_size``, ``sparse_kernel_stride``).

Rows are computed in tiles (projections and feed-forward ``ROW_TILE`` rows at
a time, attention ``QUERY_TILE`` queries at a time against the whole
sequence), so that 25,600 positions fit beside 7.86 GB of weights.

**Departures from the published description**, each also in the
configuration file's ``assumed``:

- the sparse layer's sizes are not in ``config.json``: blocks of 64, 64
  blocks a query and key-value head, block 0 and the last 2048 tokens' blocks
  always among them, keys compressed by a mean over 32 at stride 16, block
  score the maximum over overlapping windows (InfLLM v2 as in the MiniCPM4
  report, arXiv:2506.07900);
- no ``dense_len`` switch: the selection is applied at every position, and
  is the dense result wherever ``sparse_topk`` blocks cover the causal context;
- the decay is Lightning Attention-2's (arXiv:2401.04658) with no per-layer
  factor; the output norm runs over the concatenated heads;
- both gates are ``sigmoid(norm1(x) W_g)`` on the mixer's output before
  ``W_o``; ``mup_denominator`` and ``rand_init`` are initialisation only;
- a cut in depth keeps the residual scale of the published depth
  (``residual_depth``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROW_TILE = 2048
QUERY_TILE = 128
LIGHTNING_BLOCK = 256
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def check_supported(cfg: dict) -> None:
    refused = [k for k in ("attn_use_rope", "attention_bias", "tie_word_embeddings") if cfg.get(k)]
    if refused or cfg.get("hidden_act", "silu") != "silu" or cfg.get("lightning_nkv") != cfg.get(
            "lightning_nh") or cfg.get("lightning_scale", "1/sqrt(d)") != "1/sqrt(d)":
        raise ValueError(f"the plain MiniCPM-SALA reference does not implement this "
                         f"configuration (set: {refused})")
    if set(cfg["mixer_types"]) - {SPARSE, LIGHTNING}:
        raise ValueError(f"unknown mixer types in {cfg['mixer_types']}")


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta):
    """x: (S, heads, D); rotates the pairs (i, i + D/2)."""
    dim = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def by_rows(fn, tile: int, *arrays):
    """``fn`` over tiles of rows (the leading axis), one tile at a time."""
    rows = arrays[0].shape[0]
    pad = -rows % tile
    padded = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) for a in arrays]
    tiles = [a.reshape((rows + pad) // tile, tile, *a.shape[1:]) for a in padded]
    out = jax.lax.map(lambda xs: fn(*xs), tuple(tiles))
    return jax.tree_util.tree_map(lambda o: o.reshape(rows + pad, *o.shape[2:])[:rows], out)


def overlapping_windows(blocks: int, windows: int, block: int, kernel: int, stride: int):
    """For each block the compressed windows that overlap it, from the
    definition: ``(index (blocks, most), there (blocks, most))``."""
    lists = [[j for j in range(windows)
              if stride * j < block * (n + 1) and stride * j + kernel > block * n]
             for n in range(blocks)]
    most = max((len(js) for js in lists), default=1) or 1
    index = np.zeros((blocks, most), np.int32)
    there = np.zeros((blocks, most), bool)
    for n, js in enumerate(lists):
        index[n, : len(js)] = js
        there[n, : len(js)] = True
    return index, there


def selected_blocks(q, kbar, t, cfg: dict, blocks: int):
    """Which blocks the queries ``q`` (tile, G, R, D) at positions ``t``
    (tile,) select, per key-value head: (G, tile, blocks) bool."""
    block, kernel, stride = (cfg["sparse_block_size"], cfg["sparse_kernel_size"],
                             cfg["sparse_kernel_stride"])
    windows, dim = kbar.shape[0], q.shape[-1]
    first = block * jnp.arange(blocks)
    exists = first[None, :] <= t[:, None]  # (tile, blocks)
    forced = ((jnp.arange(blocks) < cfg["sparse_init_blocks"])[None, :]
              | (first[None, :] + block - 1 >= t[:, None] - (cfg["sparse_window"] - 1)))
    if windows:
        ended = stride * jnp.arange(windows)[None, :] + kernel - 1 <= t[:, None]  # (tile, J)
        s = jnp.einsum("tgrd,jgd->grtj", q, kbar) / math.sqrt(dim)
        s = jnp.where(ended[None, None], s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(ended[None, None], jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
        p = e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
        group = p.sum(axis=1)  # (G, tile, J)
        index, there = overlapping_windows(blocks, windows, block, kernel, stride)
        seen = jnp.asarray(there)[None, None] & ended[:, index][None]  # (1, tile, blocks, most)
        score = jnp.where(seen, group[:, :, index], -jnp.inf).max(axis=-1)  # (G, tile, blocks)
    else:
        score = jnp.full((q.shape[1], q.shape[0], blocks), -jnp.inf)
    score = jnp.where(forced[None], jnp.inf, score)
    score = jnp.where(exists[None], score, -jnp.inf)
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < cfg["sparse_topk"]) & exists[None]


def sparse_mixer(h, w, cfg: dict, watch):
    """h: (S, hidden) -> ((S, hidden), selection at the watched positions
    (len(watch), G, blocks) bool)."""
    heads, groups, dim = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    block, kernel, stride = (cfg["sparse_block_size"], cfg["sparse_kernel_size"],
                             cfg["sparse_kernel_stride"])
    seq = h.shape[0]
    project = lambda name: by_rows(lambda rows: rows @ w[name], ROW_TILE, h)
    q = project("wq").reshape(seq, groups, heads // groups, dim)
    k = project("wk").reshape(seq, groups, dim)
    v = project("wv").reshape(seq, groups, dim)
    windows = max(0, (seq - kernel) // stride + 1)
    inside = stride * jnp.arange(windows)[:, None] + jnp.arange(kernel)[None, :]
    kbar = k[inside].mean(axis=1) if windows else jnp.zeros((0, groups, dim), jnp.float32)
    blocks = -(-seq // block)
    column = jnp.arange(seq)

    def attend(q_tile, t):
        chosen = selected_blocks(q_tile, kbar, t, cfg, blocks)  # (G, tile, blocks)
        allowed = chosen[:, :, column // block] & (column[None, None, :] <= t[None, :, None])
        s = jnp.einsum("tgrd,sgd->grts", q_tile, k) / math.sqrt(dim)
        s = jnp.where(allowed[:, None], s, -jnp.inf)
        return jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, axis=-1), v)

    out = by_rows(attend, QUERY_TILE, q, jnp.arange(seq)).reshape(seq, heads * dim)
    gate = by_rows(lambda rows: jax.nn.sigmoid(rows @ w["wg"]), ROW_TILE, h)
    mixed = by_rows(lambda rows: rows @ w["wo"], ROW_TILE, out * gate)
    watched = selected_blocks(q[watch], kbar, watch, cfg, blocks)  # (G, n, blocks)
    return mixed, jnp.moveaxis(watched, 0, 1)


def lightning_mixer(h, w, cfg: dict):
    heads, dim, eps = cfg["lightning_nh"], cfg["lightning_head_dim"], cfg["rms_norm_eps"]
    seq = h.shape[0]
    project = lambda name: by_rows(lambda rows: rows @ w[name], ROW_TILE, h).reshape(seq, heads, dim)
    q, k, v = project("wq"), project("wk"), project("wv")
    if cfg.get("qk_norm", True):
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    if cfg.get("lightning_use_rope", True):
        positions = jnp.arange(seq)
        q, k = rope(q, positions, cfg["rope_theta"]), rope(k, positions, cfg["rope_theta"])
    q = q / math.sqrt(dim)
    log_decay = -jnp.exp2(-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32) / heads)  # (H,)
    size = LIGHTNING_BLOCK
    pad = -seq % size
    fold = lambda x: jnp.pad(x, ((0, pad), (0, 0), (0, 0))).reshape(-1, size, heads, dim)
    offset = jnp.arange(size)
    apart = offset[:, None] - offset[None, :]  # t - s
    within = jnp.where(apart[None] >= 0,
                       jnp.exp(jnp.maximum(apart, 0)[None] * log_decay[:, None, None]), 0.0)  # (H, t, s)

    def one_block(state, xs):  # state: (H, D, D): S at the end of the block before
        qb, kb, vb = xs  # (size, H, D); padded rows are zero keys: they add nothing
        o = jnp.einsum("hts,she->the", within * jnp.einsum("thd,shd->hts", qb, kb), vb)
        carried = jnp.exp((offset + 1)[:, None] * log_decay[None, :])  # lambda^(t+1): (size, H)
        o = o + carried[..., None] * jnp.einsum("thd,hde->the", qb, state)
        left = jnp.exp((size - 1 - offset)[:, None] * log_decay[None, :])  # lambda^(size-1-s)
        state = (jnp.exp(size * log_decay)[:, None, None] * state
                 + jnp.einsum("sh,shd,she->hde", left, kb, vb))
        return state, o

    state = jnp.zeros((heads, dim, dim), jnp.float32)
    _, o = jax.lax.scan(one_block, state, (fold(q), fold(k), fold(v)))
    o = o.reshape(-1, heads * dim)[:seq]
    if cfg.get("use_output_norm", True):
        o = rms_norm(o, w["o_norm"], eps)
    if cfg.get("use_output_gate", True):
        o = o * by_rows(lambda rows: jax.nn.sigmoid(rows @ w["wg"]), ROW_TILE, h)
    return by_rows(lambda rows: rows @ w["wo"], ROW_TILE, o)


def swiglu(h, w):
    return by_rows(lambda rows: (jax.nn.silu(rows @ w["w_gate"]) * (rows @ w["w_up"])) @ w["w_down"],
                   ROW_TILE, h)


def _frozen(cfg: dict):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "lightning_nh",
            "lightning_nkv", "lightning_head_dim", "lightning_use_rope", "qk_norm",
            "use_output_norm", "use_output_gate", "rms_norm_eps", "rope_theta", "scale_depth",
            "residual_depth", "sparse_block_size", "sparse_topk", "sparse_window",
            "sparse_init_blocks", "sparse_kernel_size", "sparse_kernel_stride")
    return tuple((k, cfg[k]) for k in keys if k in cfg)


@functools.partial(jax.jit, static_argnames=("frozen", "kind"))
def _layer(x, common, mixer, watch, *, frozen, kind):
    cfg = dict(frozen)
    common, mixer = _f32(common), _f32(mixer)
    r = cfg["scale_depth"] / math.sqrt(cfg["residual_depth"])
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, common["input_norm"]["weight"], cfg["rms_norm_eps"])
        if kind == SPARSE:
            mixed, watched = sparse_mixer(h, mixer, cfg, watch)
        else:
            mixed, watched = lightning_mixer(h, mixer, cfg), None
        x = x + r * mixed
        h = rms_norm(x, common["post_attn_norm"]["weight"], cfg["rms_norm_eps"])
        return x + r * swiglu(h, common["mlp"]), watched


@functools.partial(jax.jit, static_argnames=("rows", "eps", "divide"))
def _head(hidden, final_norm, head, start, *, rows, eps, divide):
    with jax.default_matmul_precision("highest"):
        hidden = jax.lax.dynamic_slice_in_dim(hidden, start, rows)
        hidden = rms_norm(hidden, final_norm.astype(jnp.float32), eps) / divide
        return hidden @ head.astype(jnp.float32).T


def hidden_states(params, ids, cfg: dict, watch=(0,)):
    """The residual stream after the last layer, (S, hidden) float32, and for
    the positions ``watch`` the blocks each sparse layer selected:
    (Ls, len(watch), G, blocks) bool."""
    check_supported(cfg)
    cfg = {"residual_depth": len(cfg["mixer_types"]), **cfg}
    layers = params["layers"]
    x = jnp.take(params["embed"]["weight"], ids, axis=0).astype(jnp.float32) * cfg["scale_emb"]
    watch = jnp.asarray(watch, jnp.int32)
    at = lambda tree, i: jax.tree_util.tree_map(lambda t: t[i], tree)
    common = {k: layers[k] for k in ("input_norm", "post_attn_norm", "mlp")}
    seen, count = [], {SPARSE: 0, LIGHTNING: 0}
    for i, kind in enumerate(cfg["mixer_types"]):
        mixer = at(layers["sparse" if kind == SPARSE else "lightning"], count[kind])
        count[kind] += 1
        x, watched = _layer(x, at(common, i), mixer, watch, frozen=_frozen(cfg), kind=kind)
        if watched is not None:
            seen.append(watched)
    return x, jnp.stack(seen)


def logits_at(params, ids, start: int, rows: int, cfg: dict, watch=(0,)):
    """Logits of positions ``start .. start + rows`` of one sequence (float32),
    and the selection at the watched positions (:func:`hidden_states`)."""
    hidden, seen = hidden_states(params, ids, cfg, watch)
    logits = _head(hidden, params["final_norm"]["weight"], params["lm_head"]["weight"], start,
                   rows=rows, eps=cfg["rms_norm_eps"],
                   divide=cfg["hidden_size"] / cfg["dim_model_base"])
    return logits, seen
