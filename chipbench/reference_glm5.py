"""The plain reference for GLM-5 (zai-org/GLM-5, ``glm_moe_dsa``): its forward
pass in float32 ``jax.numpy``, every matrix product under
``jax.default_matmul_precision("highest")``, nothing imported from the program
under test. No cache, no absorbed form, no gather, no grouped product, no
kernel: one sequence, whole, the **expanded** form of the attention under
whole-chain masks built from the indexer's exact top ``index_topk``.

**The equations** (``x = RMSNorm(h)``, eps ``rms_norm_eps``; H heads; ranks
``Rq = q_lora_rank``, ``Rkv = kv_lora_rank``; head sizes ``Dn =
qk_nope_head_dim``, ``Dr = qk_rope_head_dim``, ``Dv = v_head_dim``):

*Attention (MLA).* ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb``, a head ``[q_nope
(Dn) ; q_rope (Dr)]``, ``q_rope`` rotated. ``[c_kv ; k_r] = x W_kva``; ``c_kv <-
RMSNorm(c_kv)``; ``k_r`` rotated, one head shared by all. ``[k_nope_n ; v_n] =
c_kv W_kvb`` a head ``n``; ``score_n(t, s) = (q_nope_n(t) . k_nope_n(s) +
q_rope_n(t) . k_r(s)) / sqrt(Dn + Dr)``; ``a_n(t) = sum_{s in S_t} softmax_s(score_n(t,
.)) v_n(s)``; ``h <- h + concat_n(a_n) W_o``.

*The indexer* (``Hi = index_n_heads`` heads of ``Di = index_head_dim``, every
layer its own). ``q_I = c_q W_Iq`` (Hi x Di); ``k_I = LayerNorm(x W_Ik)`` (Di,
one head; weight, bias, eps 1e-6); the first ``Dr`` numbers of each rotated;
``w = (x W_Iw) (Hi Di)^-1/2`` (Hi numbers). ``I(t, s) = sum_j w_j(t) ReLU(q_I,j(t)
. k_I(s))`` for ``s <= t``. ``S_t`` = the ``index_topk`` keys of largest ``I(t,
.)`` among ``s <= t`` (all of them while ``t < index_topk``), ties to the lower
position: the threshold is the ``index_topk``-th largest value (a sort), keys
above it are in, and of the keys equal to it the first ones by position.

*Rotation.* Interleaved pairs ``(2i, 2i+1)`` of the ``d`` rotated numbers,
``inv_freq_i = theta^(-2i/d)``, the ``default`` table (no scaling factor).

*Feed-forward.* ``x2 = RMSNorm(h)``. Layers ``0 .. first_k_dense_replace - 1``:
``h <- h + W_down(silu(x2 W_gate) * x2 W_up)``. The others: ``s = sigmoid(x2
W_r)`` over the router's whole width R; the ``num_experts_per_tok`` experts of
largest ``s + b`` (``b``: the selection bias, a weight); ``w = s_top /
sum(s_top)`` (``norm_topk_prob``) ``* routed_scaling_factor``; ``h <- h +
Shared(x2) + sum_{e in top, e held} w_e E_e(x2)``, ``Shared`` and ``E_e``
SwiGLU. After the last layer RMSNorm and the untied head.

**The share.** The program holds experts ``first_expert .. first_expert +
n_routed_experts`` of the ``router_experts`` the router scores (a chip's share
of a layer, ``model-configs`` guide, section 4), and so does this reference:
every token is routed over all R, **each held expert is applied to every
token** and weighted by its routing weight, or by zero where the token did not
choose it; what the absent experts would have added is left out, here as there.

**The weights** are read in the layout the program keeps them (a fact about
data, not an import), stacked over the layers they belong to: ``embed.weight
(V, h)``; ``layers.input_norm.weight``, ``layers.post_attn_norm.weight (L, h)``;
``layers.attn``: ``wq_a (L, h, Rq)``, ``q_norm (L, Rq)``, ``wq_b (L, Rq,
H(Dn+Dr))``, ``wkv_a (L, h, Rkv+Dr)``, ``kv_norm (L, Rkv)``, ``wkv_b (L, Rkv,
H(Dn+Dv))`` (a head's columns are ``[k_nope ; v]``), ``wo (L, H Dv, h)``;
``layers.indexer``: ``wq (L, Rq, Hi Di)``, ``wk (L, h, Di)``, ``k_norm_weight``,
``k_norm_bias (L, Di)``, ``w_proj (L, h, Hi)``; ``layers.dense_mlp``:
``w_gate``, ``w_up (Ld, h, I)``, ``w_down (Ld, I, h)``; ``layers.moe``: ``router
(Lm, h, R)``, ``bias (Lm, R)``, ``w_gate``, ``w_up (Lm, E, h, Im)``, ``w_down
(Lm, E, Im, h)``, ``shared_gate``, ``shared_up (Lm, h, Is)``, ``shared_down (Lm,
Is, h)``; ``final_norm.weight``; ``lm_head.weight (V, h)``. Weights of any dtype
are cast to float32 one layer at a time (one expert at a time inside it).
``cfg`` is the configuration file's own dict.

Rows are computed in tiles (projections and feed-forwards ``ROW_TILE`` rows at
a time; the selection ``QUERY_TILE`` queries at a time against the whole
sequence, kept as one (S, S) mask a layer; the attention ``HEAD_GROUP`` heads
at a time, ``QUERY_TILE`` queries at a time), so that 25,088 positions fit
beside 7.82 GB of weights.

**Departures from the published description**, each also in the
configuration file's ``assumed``:

- ``k_I`` is normed by a LayerNorm with weight and bias (eps 1e-6) and ``w``
  carries ``Hi^-1/2 Di^-1/2``, as DeepSeek-V3.2's published inference code has
  them; the first ``qk_rope_head_dim`` numbers of an indexer head are rotated;
- that code's Hadamard rotation of ``q_I`` and ``k_I`` and their fp8 storage
  are left out (an orthogonal map leaves the products as they are);
- no YaRN factor on the softmax scale (``rope_type`` default), no attention bias;
- ``n_group`` 1 and ``topk_group`` 1: no expert groups;
- the multi-token-prediction module is not part of the next-token forward;
- a cut in experts (``n_routed_experts`` held of ``router_experts``) leaves out
  what the absent experts would add; a sliced vocabulary is a smaller one.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

ROW_TILE = 1024
QUERY_TILE = 128
HEAD_GROUP = 16


def check_supported(cfg: dict) -> None:
    refused = [k for k in ("attention_bias", "tie_word_embeddings", "num_nextn_predict_layers") if cfg.get(k)]
    plain = (cfg.get("scoring_func", "sigmoid") == "sigmoid" and cfg.get("n_group", 1) == 1
             and cfg.get("topk_group", 1) == 1 and cfg.get("norm_topk_prob", True)
             and cfg.get("rope_parameters", {}).get("rope_type", "default") == "default"
             and cfg.get("rope_interleave", True) and cfg.get("indexer_rope_interleave", True))
    if refused or not plain:
        raise ValueError(f"the plain GLM-5 reference does not implement this configuration (set: {refused}; "
                         "or a router, groups or rope other than sigmoid, none and default interleaved)")


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def layer_norm(x, weight, bias, eps=1e-6):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps) * weight + bias


def rope(x, positions, theta: float):
    """x: (S, heads, d). Rotates the interleaved pairs (2i, 2i+1) of the last axis."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def by_rows(fn, tile: int, *arrays):
    """``fn`` over tiles of rows (the leading axis), one tile at a time."""
    rows = arrays[0].shape[0]
    tile = min(tile, rows)
    pad = -rows % tile
    padded = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) for a in arrays]
    tiles = [a.reshape((rows + pad) // tile, tile, *a.shape[1:]) for a in padded]
    out = jax.lax.map(lambda xs: fn(*xs), tuple(tiles))
    return jax.tree_util.tree_map(lambda o: o.reshape(rows + pad, *o.shape[2:])[:rows], out)


def selection(x, c_q, wi, cfg: dict):
    """The indexer's choice for every query of one layer: (S, S) bool, row t the
    ``index_topk`` keys of largest index score among ``s <= t``."""
    seq, positions = x.shape[0], jnp.arange(x.shape[0])
    heads, dim, rotated = cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
    theta, k = float(cfg["rope_parameters"]["rope_theta"]), cfg["index_topk"]
    first = lambda t: jnp.concatenate([rope(t[..., :rotated], positions, theta), t[..., rotated:]], axis=-1)
    q_i = first(by_rows(lambda rows: rows @ wi["wq"], ROW_TILE, c_q).reshape(seq, heads, dim))
    k_i = layer_norm(by_rows(lambda rows: rows @ wi["wk"], ROW_TILE, x),
                     wi["k_norm_weight"], wi["k_norm_bias"])
    k_i = first(k_i[:, None, :])[:, 0]
    weight = by_rows(lambda rows: rows @ wi["w_proj"], ROW_TILE, x) * (heads * dim) ** -0.5  # (S, Hi)

    def choose(q_tile, w_tile, t):
        score = jnp.einsum("tj,jts->ts", w_tile, jax.nn.relu(jnp.einsum("tjd,sd->jts", q_tile, k_i)))
        score = jnp.where(positions[None, :] <= t[:, None], score, -jnp.inf)
        if seq <= k:
            return score > -jnp.inf
        threshold = jnp.sort(score, axis=-1)[:, seq - k][:, None]  # the k-th largest
        above, ties = score > threshold, (score == threshold) & (score > -jnp.inf)
        need = k - above.sum(axis=-1, keepdims=True)
        return above | (ties & (jnp.cumsum(ties, axis=-1) <= need))

    return by_rows(choose, QUERY_TILE, q_i, weight, positions)


def attention(x, w, wi, cfg: dict):
    """x: (S, hidden), the layer's normed input -> ``(out (S, hidden), selected
    (S, S) bool)``: latent attention, expanded, over the selected keys alone."""
    seq, positions = x.shape[0], jnp.arange(x.shape[0])
    heads, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rank, theta, eps = cfg["kv_lora_rank"], float(cfg["rope_parameters"]["rope_theta"]), cfg["rms_norm_eps"]
    c_q = rms_norm(by_rows(lambda rows: rows @ w["wq_a"], ROW_TILE, x), w["q_norm"], eps)
    q = by_rows(lambda rows: rows @ w["wq_b"], ROW_TILE, c_q).reshape(seq, heads, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], positions, theta)
    kv = by_rows(lambda rows: rows @ w["wkv_a"], ROW_TILE, x)
    c_kv = rms_norm(kv[:, :rank], w["kv_norm"], eps)
    k_r = rope(kv[:, None, rank:], positions, theta)[:, 0]
    selected = selection(x, c_q, wi, cfg)
    group = math.gcd(heads, HEAD_GROUP)
    wkv_b = jnp.moveaxis(w["wkv_b"].reshape(rank, heads // group, group, dn + dv), 1, 0)

    def heads_of(xs):  # one group of heads: their keys and values whole, queries in tiles
        w_group, qn, qr = xs
        expanded = by_rows(lambda rows: jnp.einsum("sc,cgd->sgd", rows, w_group), ROW_TILE, c_kv)
        k_nope, v = expanded[..., :dn], expanded[..., dn:]

        def attend(qn_t, qr_t, chosen):
            s = (jnp.einsum("tgd,sgd->gts", qn_t, k_nope) + jnp.einsum("tgd,sd->gts", qr_t, k_r)) \
                / math.sqrt(dn + dr)
            return jnp.einsum("gts,sgd->tgd", jax.nn.softmax(jnp.where(chosen[None], s, -jnp.inf), axis=-1), v)

        return by_rows(attend, QUERY_TILE, qn, qr, selected)

    fold = lambda t: jnp.moveaxis(t.reshape(seq, heads // group, group, t.shape[-1]), 1, 0)
    out = jax.lax.map(heads_of, (wkv_b, fold(q_nope), fold(q_rope)))  # (H/g, S, g, Dv)
    out = jnp.moveaxis(out, 0, 1).reshape(seq, heads * dv)
    return by_rows(lambda rows: rows @ w["wo"], ROW_TILE, out), selected


def swiglu(h, gate, up, down):
    return by_rows(lambda rows: (jax.nn.silu(rows @ gate) * (rows @ up)) @ down, ROW_TILE, h)


def routing(h, router, bias, k: int):
    """``(weights (S, R): w_e where the token chose e, else 0; chosen (S, R)
    bool)``: sigmoid scores, the k largest of score plus bias, the chosen
    scores renormalised to sum to 1."""
    score = jax.nn.sigmoid(by_rows(lambda rows: rows @ router, ROW_TILE, h))
    biased = score + bias[None]
    rank = jnp.argsort(jnp.argsort(-biased, axis=-1, stable=True), axis=-1, stable=True)
    chosen = rank < k
    top = jnp.where(chosen, score, 0.0)
    return top / top.sum(axis=-1, keepdims=True), chosen


def experts(h, w, cfg: dict):
    """The shared expert plus the held experts' part of the routed sum, and
    the experts every token chose (S, R) bool."""
    weights, chosen = routing(h, w["router"].astype(jnp.float32), w["bias"].astype(jnp.float32),
                              cfg["num_experts_per_tok"])
    first = cfg.get("first_expert", 0)
    held = weights[:, first: first + w["w_gate"].shape[0]] * cfg["routed_scaling_factor"]

    def one_expert(total, xs):  # every token through the expert, weighted by w_e or zero
        gate, up, down, weight = xs
        return total + weight[:, None] * swiglu(h, *_f32((gate, up, down))), None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                             (w["w_gate"], w["w_up"], w["w_down"], held.T))
    shared = swiglu(h, *_f32((w["shared_gate"], w["shared_up"], w["shared_down"])))
    return shared + routed, chosen


def _frozen(cfg: dict):
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
            "rms_norm_eps", "index_n_heads", "index_head_dim", "index_topk", "num_experts_per_tok",
            "routed_scaling_factor", "first_expert")
    return tuple((k, cfg[k]) for k in keys if k in cfg) + (
        ("rope_parameters", tuple(sorted(cfg["rope_parameters"].items()))),)


@functools.partial(jax.jit, static_argnames=("frozen", "dense"))
def _layer(x, norms, attn, indexer, mlp, watch, *, frozen, dense):
    cfg = dict(frozen)
    cfg["rope_parameters"] = dict(cfg["rope_parameters"])
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, norms["input_norm"]["weight"].astype(jnp.float32), cfg["rms_norm_eps"])
        mixed, selected = attention(h, _f32(attn), _f32(indexer), cfg)
        x = x + mixed
        h = rms_norm(x, norms["post_attn_norm"]["weight"].astype(jnp.float32), cfg["rms_norm_eps"])
        if dense:
            return x + swiglu(h, *_f32((mlp["w_gate"], mlp["w_up"], mlp["w_down"]))), selected[watch], None
        out, chosen = experts(h, mlp, cfg)
        return x + out, selected[watch], chosen[watch]


@functools.partial(jax.jit, static_argnames=("rows", "eps"))
def _head(hidden, final_norm, head, start, *, rows, eps):
    with jax.default_matmul_precision("highest"):
        hidden = jax.lax.dynamic_slice_in_dim(hidden, start, rows)
        return rms_norm(hidden, final_norm.astype(jnp.float32), eps) @ head.astype(jnp.float32).T


def hidden_states(params, ids, cfg: dict, watch=(0,)):
    """The residual stream after the last layer, (S, hidden) float32, and for
    the positions ``watch``: the keys each layer's indexer selected, (L,
    len(watch), S) bool, and the experts each expert layer's router chose, (Lm,
    len(watch), R) bool."""
    check_supported(cfg)
    layers = params["layers"]
    x = jnp.take(params["embed"]["weight"], ids, axis=0).astype(jnp.float32)
    watch = jnp.asarray(watch, jnp.int32)
    at = lambda tree, i: jax.tree_util.tree_map(lambda t: t[i], tree)
    norms = {k: layers[k] for k in ("input_norm", "post_attn_norm")}
    lead = cfg["first_k_dense_replace"]
    keys, chosen = [], []
    for i in range(cfg["num_hidden_layers"]):
        dense = i < lead
        mlp = at(layers["dense_mlp"], i) if dense else at(layers["moe"], i - lead)
        x, selected, routed = _layer(x, at(norms, i), at(layers["attn"], i), at(layers["indexer"], i), mlp,
                                     watch, frozen=_frozen(cfg), dense=dense)
        keys.append(selected)
        if routed is not None:
            chosen.append(routed)
    return x, {"selected_keys": jnp.stack(keys), "routed_experts": jnp.stack(chosen)}


def logits_at(params, ids, start: int, rows: int, cfg: dict, watch=(0,)):
    """Logits of positions ``start .. start + rows`` of one sequence (float32),
    and what was selected and routed at the watched positions
    (:func:`hidden_states`)."""
    hidden, seen = hidden_states(params, ids, cfg, watch)
    logits = _head(hidden, params["final_norm"]["weight"], params["lm_head"]["weight"], start,
                   rows=rows, eps=cfg["rms_norm_eps"])
    return logits, seen


def checks(model, params, ids, watch, seen) -> dict:
    """What this reference prints beside the gaps (``runners/serve_ref.py``
    passes it through under ``checks``, compared with nothing): of the keys the
    reference's indexers selected at the watched positions, and of the experts
    its routers chose there, how many the program's own forward pass over the
    same sequence selected and chose too."""
    import numpy as np

    watch = jnp.asarray(watch, jnp.int32)
    ours = np.asarray(model.selected_keys(params, ids[None], watch))[:, 0]  # (L, n, S) bool
    theirs = np.asarray(seen["selected_keys"])
    experts_ours = np.asarray(model.routed_experts(params, ids[None], watch))[:, 0]  # (Lm, n, k) ids
    experts_theirs = np.asarray(seen["routed_experts"])  # (Lm, n, R) bool
    return {
        "selected_keys_shared_with_reference": (int((ours & theirs).sum()), int(theirs.sum())),
        "routed_experts_shared_with_reference": (
            int(np.take_along_axis(experts_theirs, experts_ours, axis=-1).sum()), int(experts_theirs.sum())),
    }
