"""GLM-5 (``glm_moe_dsa``): latent attention (MLA) whose keys are chosen, a
query at a time, by a learned indexer (the ``index_topk`` keys of largest
index score), a few leading dense feed-forwards and then sigmoid-routed
experts beside a shared one (docs/glm5.md has the equations).

**Two entries paged by token, of different widths.** A token's cache row in a
layer is the latent row ``[c_kv ; k_r]`` (``kv_lora_rank + qk_rope_head_dim``
numbers, one "head", written after its norm and its rotation) and the
indexer's key ``k_I`` (``index_head_dim`` numbers, one head): ``init_cache``
returns ``"latent"`` and ``"index_k"`` of ``(L, B, T, 1, ·)`` and
``cache_layout`` names them, so the paged engine holds one block array each,
gathers both into the view and hands both a write window
(ops/paged_attention.py). Every entry is a function of the token prefix alone:
blocks are shareable between requests as keys and values are.

**Two forms of one attention.** Keys and values of a head are ``c_kv W_kvb``;
the *expanded* form makes them and attends as usual (the plain reference's),
the *absorbed* form folds ``W_uk`` into the query and ``W_uv`` behind the
weighted sum of latent rows, and attends the latent rows themselves: the same
numbers, and the form this program runs. Every query scores all the keys
before it with the indexer, takes the exact top ``index_topk`` (``lax.top_k``:
ties to the lower position; never ``approx_max_k``), **gathers those latent
rows** and attends them absorbed: a decode token over its row's view and
window, a chunk's queries (and the plain forward's) in tiles of ``QUERY_TILE``
over view ++ chunk, each query its own rows. ``PERF.md`` (PR 38) has the forms
that were timed on the chip and lost: masked tiles over the whole context,
expanded and absorbed.

Weights (the plain reference, ``chipbench/reference_glm5.py``, reads this
layout), stacked over the layers they belong to: ``embed.weight (V, h)``;
``layers.input_norm.weight``, ``layers.post_attn_norm.weight (L, h)``;
``layers.attn``: ``wq_a (L, h, Rq)``, ``q_norm (L, Rq)``, ``wq_b (L, Rq,
H·(Dn+Dr))``, ``wkv_a (L, h, Rkv+Dr)``, ``kv_norm (L, Rkv)``, ``wkv_b (L, Rkv,
H·(Dn+Dv))`` (a head's columns are ``[k_nope ; v]``), ``wo (L, H·Dv, h)``;
``layers.indexer``: ``wq (L, Rq, Hi·Di)``, ``wk (L, h, Di)``, ``k_norm_weight``,
``k_norm_bias (L, Di)``, ``w_proj (L, h, Hi)``; ``layers.dense_mlp``:
``w_gate``, ``w_up (Ld, h, I)``, ``w_down (Ld, I, h)``; ``layers.moe``:
``router (Lm, h, R)``, ``bias (Lm, R)``, ``w_gate``, ``w_up (Lm, E, h, Im)``,
``w_down (Lm, E, Im, h)``, ``shared_gate``, ``shared_up (Lm, h, Is)``,
``shared_down (Lm, Is, h)``; ``final_norm.weight (h,)``; ``lm_head.weight (V,
h)``. **E is the experts held here** (``n_routed_experts``), experts
``first_expert .. first_expert + E`` of the ``router_experts`` (R) the router
scores (``ops/moe.py`` ``expert_share_ffn``); by default all of them.

The multi-token-prediction module (``num_nextn_predict_layers``) is not
implemented: the next-token forward does not contain it.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from ..modules import ModelOutput, Module
from ..ops.losses import cross_entropy_loss
from ..ops.moe import expert_share_ffn, route_top_k, router_logits
from .laguna import _at, _put
from .llama import Llama, rms_norm

QUERY_TILE = 128  # queries a tile of a chunk's (or a whole sequence's) selection and attention
NEG = -1e30


@dataclass
class Glm5Config:
    # Published keys (zai-org/GLM-5 config.json), defaults as published.
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    num_attention_heads: int = 64
    num_key_value_heads: int = 64
    head_dim: int = 64  # the rotated part of a head (= qk_rope_head_dim)
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    qk_head_dim: int = 256
    v_head_dim: int = 256
    index_head_dim: int = 128
    index_n_heads: int = 32
    index_topk: int = 2048
    indexer_rope_interleave: bool = True
    n_routed_experts: int = 256  # the experts HELD HERE: the published count unless a share is cut
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 3
    moe_layer_freq: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    rope_interleave: bool = True
    rope_parameters: dict | None = None
    max_position_embeddings: int = 202752
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    ep_size: int = 1
    num_nextn_predict_layers: int = 0  # published 1: the module is not implemented (docs/glm5.md)
    # The program's own.
    router_experts: int | None = None  # experts the router scores; None = n_routed_experts
    first_expert: int = 0              # the first expert held here
    matmul_precision: str = "default"  # 'default' | 'int8' (ops/int8.py)

    def __post_init__(self):
        if self.rope_parameters is None:
            self.rope_parameters = {"rope_theta": 1000000, "rope_type": "default"}
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts
        refused = {
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            "a multi-token-prediction module (num_nextn_predict_layers other than 0)":
                self.num_nextn_predict_layers != 0,
            "expert groups (n_group or topk_group other than 1)":
                self.n_group != 1 or self.topk_group != 1,
            "moe_layer_freq other than 1": self.moe_layer_freq != 1,
            "a router that does not score by sigmoid with a selection bias (noaux_tc)":
                self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc",
            "an activation other than silu": self.hidden_act != "silu",
            "rotation other than over interleaved pairs":
                not (self.rope_interleave and self.indexer_rope_interleave),
            "a rope table other than the default type":
                self.rope_parameters.get("rope_type", "default") != "default",
            "qk_head_dim other than qk_nope_head_dim + qk_rope_head_dim":
                self.qk_head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim,
            "head_dim other than qk_rope_head_dim": self.head_dim != self.qk_rope_head_dim,
            "key-value heads other than the query heads (MLA has one latent row)":
                self.num_key_value_heads != self.num_attention_heads,
            "an indexer head narrower than the rotated part":
                self.index_head_dim < self.qk_rope_head_dim,
            "no expert layer after the dense ones":
                not 0 <= self.first_k_dense_replace < self.num_hidden_layers,
            "experts held outside the router's width":
                self.first_expert < 0
                or self.first_expert + self.n_routed_experts > self.router_experts,
            "more experts a token than the router scores":
                self.num_experts_per_tok > self.router_experts,
        }
        if any(refused.values()):
            raise ValueError("Glm5 does not implement: "
                             + ", ".join(k for k, v in refused.items() if v))

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @classmethod
    def tiny(cls, **kw):
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4, head_dim=8,
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8,
            qk_head_dim=20, v_head_dim=16, index_head_dim=16, index_n_heads=2, index_topk=16,
            n_routed_experts=8, num_experts_per_tok=2, first_k_dense_replace=1,
            max_position_embeddings=4096)
        defaults.update(kw)
        return cls(**defaults)


def rope_interleaved(x, positions, theta: float):
    """Rotate the pairs ``(2i, 2i+1)`` of the last axis of ``x`` (B, S, ..., d)
    by ``positions`` (B, S): ``inv_freq_i = theta^(-2i/d)``. Float32 inside."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (B, S, d/2)
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape).astype(x.dtype)


class Glm5(Module):
    # The two entries the paged engine pages by token, that a decode step is to
    # be told which rows decode (a free slot's pad token claims no expert), the
    # counts the cached forward returns beside its logits, and that it returns
    # the last position's logits alone (ops/paged_attention.py ``cache_layout``).
    cache_layout = {
        "by_token": ("latent", "index_k"), "row_mask": True, "speculative": False,
        "counters": {"decode": ("attended_keys", "context_keys", "experts_touched", "experts_held"),
                     "chunk": ("expert_claims_max", "expert_claims_mean",
                               "keys_selected", "keys_scored")}}

    def __init__(self, config: Glm5Config):
        self.config = config
        self.params = None

    # ------------------------------------------------------------------- init
    def init(self, rng, *example_inputs, **kwargs):
        cfg = self.config
        h, n = cfg.hidden_size, cfg.num_hidden_layers
        ld, lm = cfg.first_k_dense_replace, n - cfg.first_k_dense_replace
        heads, rq, rkv = cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank
        e, im = cfg.n_routed_experts, cfg.moe_intermediate_size
        shared, inter = cfg.n_shared_experts * im, cfg.intermediate_size
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        keys = iter(jax.random.split(rng, 32))

        def dense(shape, fan_in):
            return jax.random.normal(next(keys), shape, jnp.float32) / np.sqrt(fan_in)

        return {
            "embed": {"weight": dense((cfg.vocab_size, h), h)},
            "layers": {
                "input_norm": {"weight": jnp.ones((n, h), jnp.float32)},
                "post_attn_norm": {"weight": jnp.ones((n, h), jnp.float32)},
                "attn": {"wq_a": dense((n, h, rq), h), "q_norm": jnp.ones((n, rq), jnp.float32),
                         "wq_b": dense((n, rq, heads * cfg.qk_head_dim), rq),
                         "wkv_a": dense((n, h, cfg.latent_dim), h),
                         "kv_norm": jnp.ones((n, rkv), jnp.float32),
                         "wkv_b": dense((n, rkv, heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)), rkv),
                         "wo": dense((n, heads * cfg.v_head_dim, h), heads * cfg.v_head_dim)},
                "indexer": {"wq": dense((n, rq, hi * di), rq), "wk": dense((n, h, di), h),
                            "k_norm_weight": jnp.ones((n, di), jnp.float32),
                            "k_norm_bias": jnp.zeros((n, di), jnp.float32),
                            "w_proj": dense((n, h, hi), h)},
                "dense_mlp": {"w_gate": dense((ld, h, inter), h), "w_up": dense((ld, h, inter), h),
                              "w_down": dense((ld, inter, h), inter)},
                "moe": {"router": dense((lm, h, cfg.router_experts), h),
                        "bias": 0.01 * jax.random.normal(next(keys), (lm, cfg.router_experts), jnp.float32),
                        "w_gate": dense((lm, e, h, im), h), "w_up": dense((lm, e, h, im), h),
                        "w_down": dense((lm, e, im, h), im),
                        "shared_gate": dense((lm, h, shared), h),
                        "shared_up": dense((lm, h, shared), h),
                        "shared_down": dense((lm, shared, h), shared)},
            },
            "final_norm": {"weight": jnp.ones((h,), jnp.float32)},
            "lm_head": {"weight": dense((cfg.vocab_size, h), h)},
        }

    def num_params(self) -> int:
        cfg = self.config
        h, n, heads = cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads
        lm, im = n - cfg.first_k_dense_replace, cfg.moe_intermediate_size
        attention = (h * cfg.q_lora_rank + cfg.q_lora_rank + cfg.q_lora_rank * heads * cfg.qk_head_dim
                     + h * cfg.latent_dim + cfg.kv_lora_rank
                     + cfg.kv_lora_rank * heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
                     + heads * cfg.v_head_dim * h)
        indexer = (cfg.q_lora_rank * cfg.index_n_heads * cfg.index_head_dim
                   + h * cfg.index_head_dim + 2 * cfg.index_head_dim + h * cfg.index_n_heads)
        moe = (h * cfg.router_experts + cfg.router_experts
               + 3 * h * im * (cfg.n_routed_experts + cfg.n_shared_experts))
        return (n * (attention + indexer + 2 * h) + cfg.first_k_dense_replace * 3 * h * cfg.intermediate_size
                + lm * moe + 2 * cfg.vocab_size * h + h)

    def _mm(self, a, b):
        """Every projection goes through the precision dispatcher, as Llama's
        (the serving engine's ``matmul_precision`` swaps it; embedding, head,
        router, the indexer's head weights and the absorbed products stay as they are)."""
        from ..ops.int8 import matmul

        return matmul(a, b, precision=self.config.matmul_precision)

    # -------------------------------------------------------------- attention
    def _queries_and_rows(self, w, wi, x, positions, cache_dtype):
        """A layer's projections of its normed input ``x`` (B, S, h): the
        queries' two parts, the indexer's queries and head weights, and the two
        cache rows of each token (latent, index key) in ``cache_dtype``."""
        cfg = self.config
        b, s, _ = x.shape
        heads, dn, dr = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        theta = cfg.rope_parameters["rope_theta"]
        c_q = rms_norm(self._mm(x, w["wq_a"]), w["q_norm"], cfg.rms_norm_eps)
        q = self._mm(c_q, w["wq_b"]).reshape(b, s, heads, dn + dr)
        q_nope, q_rope = q[..., :dn], rope_interleaved(q[..., dn:], positions, theta)
        kv = self._mm(x, w["wkv_a"])
        c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], w["kv_norm"], cfg.rms_norm_eps)
        k_r = rope_interleaved(kv[..., cfg.kv_lora_rank:], positions, theta)
        latent = jnp.concatenate([c_kv, k_r], axis=-1).astype(cache_dtype)

        def rotate_first(t):  # the first qk_rope_head_dim numbers of an indexer head
            return jnp.concatenate([rope_interleaved(t[..., :dr], positions, theta), t[..., dr:]], axis=-1)

        q_i = rotate_first(self._mm(c_q, wi["wq"]).reshape(b, s, cfg.index_n_heads, cfg.index_head_dim))
        k_i = self._mm(x, wi["wk"]).astype(jnp.float32)
        k_i = (k_i - k_i.mean(axis=-1, keepdims=True)) * jax.lax.rsqrt(k_i.var(axis=-1, keepdims=True) + 1e-6)
        k_i = (k_i * wi["k_norm_weight"].astype(jnp.float32)
               + wi["k_norm_bias"].astype(jnp.float32)).astype(x.dtype)
        k_i = rotate_first(k_i).astype(cache_dtype)
        # One weight an index head, float32 (a near tie decides which key is kept).
        w_head = jax.lax.dot_general(
            x.astype(jnp.float32), wi["w_proj"].astype(jnp.float32), (((2,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST) * (cfg.index_n_heads * cfg.index_head_dim) ** -0.5
        return q_nope, q_rope, q_i, w_head, latent, k_i

    @staticmethod
    def _index_scores(q_i, w_head, k_i):
        """``I(t, s) = sum_j w_j(t) ReLU(q_I,j(t) . k_I(s))``: ``q_i`` (B, S, Hi,
        Di), ``w_head`` (B, S, Hi) float32, ``k_i`` (B, K, Di) -> (B, S, K) float32."""
        dots = jnp.einsum("bshd,bkd->bhsk", q_i, k_i.astype(q_i.dtype),
                          preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(dots) * jnp.moveaxis(w_head, 2, 1)[..., None], axis=1)

    def _split_kvb(self, wkv_b):
        """``wkv_b`` (Rkv, H·(Dn+Dv)) as ``W_uk`` (Rkv, H, Dn) and ``W_uv`` (Rkv, H, Dv)."""
        cfg = self.config
        per_head = wkv_b.reshape(cfg.kv_lora_rank, cfg.num_attention_heads,
                                 cfg.qk_nope_head_dim + cfg.v_head_dim)
        return per_head[..., :cfg.qk_nope_head_dim], per_head[..., cfg.qk_nope_head_dim:]

    def _absorbed(self, w, q_nope, q_rope, rows, seen):
        """Softmax attention of each query over ITS OWN latent rows, absorbed:
        ``q_nope``, ``q_rope`` (..., H, ·), ``rows`` (..., k, Rkv+Dr), ``seen``
        (..., k) bool -> (..., H·Dv)."""
        cfg = self.config
        rkv = cfg.kv_lora_rank
        w_uk, w_uv = self._split_kvb(w["wkv_b"])
        q_all = jnp.concatenate([jnp.einsum("...hd,chd->...hc", q_nope, w_uk.astype(q_nope.dtype)), q_rope],
                                axis=-1)  # (..., H, Rkv+Dr)
        scores = jnp.einsum("...hc,...kc->...hk", q_all, rows.astype(q_all.dtype),
                            preferred_element_type=jnp.float32) * cfg.qk_head_dim ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[..., None, :], scores, NEG), axis=-1).astype(q_all.dtype)
        mixed = jnp.einsum("...hk,...kc->...hc", probs, rows[..., :rkv].astype(q_all.dtype))
        out = jnp.einsum("...hc,chd->...hd", mixed, w_uv.astype(q_all.dtype))
        return out.reshape(out.shape[:-2] + (cfg.num_attention_heads * cfg.v_head_dim,))

    def _attend_tiles(self, w, q_nope, q_rope, q_i, w_head, latent, k_i, allowed_of, watch=None):
        """Attention of ``S`` queries over ``K`` keys given by their latent
        rows (B, K, Rkv+Dr) and index keys (B, K, Di), a tile of queries at a
        time: the indexer's scores of the tile over all K, each query's exact
        top ``index_topk`` among ``allowed_of(first, size) -> (B, size, K)
        bool`` (causal and valid), a gather of each query's rows, and the
        absorbed form over them. Returns ``(out (B, S, H·Dv), selection at
        watch (B, n, K) bool or None)``."""
        b, s, keys = q_nope.shape[0], q_nope.shape[1], latent.shape[1]
        k = min(self.config.index_topk, keys)

        def select(q_i_t, w_t, first):
            index = jnp.where(allowed_of(first, q_i_t.shape[1]), self._index_scores(q_i_t, w_t, k_i), -jnp.inf)
            top, chosen = jax.lax.top_k(index, k)  # (B, size, k): ties to the lower column
            return chosen, top > -jnp.inf

        def attend(qn, qr, q_i_t, w_t, first):
            chosen, seen = select(q_i_t, w_t, first)
            # (ids are in range: clamping them spares the fill's select over the rows)
            rows = jax.vmap(lambda of_row, which: jnp.take(of_row, which, axis=0, mode="clip"))(latent, chosen)
            return self._absorbed(w, qn, qr, rows, seen)

        tile = min(QUERY_TILE, s)
        pad = -s % tile  # padded queries attend what the last one does, and are cut off
        fold = lambda t: jnp.moveaxis(
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2), mode="edge")
            .reshape(b, (s + pad) // tile, tile, *t.shape[2:]), 1, 0)
        if s + pad == tile:
            out = attend(q_nope, q_rope, q_i, w_head, jnp.int32(0))
        else:
            firsts = jnp.arange(0, s + pad, tile, dtype=jnp.int32)
            out = jnp.moveaxis(jax.lax.map(
                lambda xs: attend(*xs), (fold(q_nope), fold(q_rope), fold(q_i), fold(w_head), firsts)), 0, 1)
            out = out.reshape(b, s + pad, -1)[:, :s]
        selected = None
        if watch is not None:  # the watched queries' selections, a query at a time, as masks over K
            def one(at):
                chosen, seen = select(jax.lax.dynamic_slice_in_dim(q_i, at, 1, 1),
                                      jax.lax.dynamic_slice_in_dim(w_head, at, 1, 1), at)
                put = jax.vmap(lambda which, real: jnp.zeros((keys,), bool).at[which].max(real))
                return put(chosen[:, 0], seen[:, 0])
            selected = jnp.moveaxis(jax.lax.map(one, watch.astype(jnp.int32)), 0, 1)
        return out, selected

    def _attend_decode(self, w, q_nope, q_rope, q_i, w_head, view, layer, window, ctx):
        """One decode token a row (S = 1): the indexer over the row's view and
        window apart (joining them would copy the view), the exact top
        ``index_topk`` of both, a gather of those latent rows straight from the
        view's stack (``view``: both entries over all layers, ``layer`` a traced
        index: slicing the layer out first would copy it), and the window's few
        columns attended where they were selected."""
        k = self.config.index_topk
        (view_latent, view_k_i), (win_latent, win_k_i) = view, window
        view_k_i = _at(view_k_i, layer)[:, :, 0]
        b, t, cols = view_latent.shape[1], view_latent.shape[2], win_latent.shape[1]
        allowed = ctx["allowed_of"](0, 1)[:, 0]  # (B, T+W)
        index = jnp.concatenate([self._index_scores(q_i, w_head, view_k_i),
                                 self._index_scores(q_i, w_head, win_k_i)], axis=-1)[:, 0]
        top, chosen = jax.lax.top_k(jnp.where(allowed, index, -jnp.inf), min(k, t + cols))
        in_view = (chosen < t) & (top > -jnp.inf)
        rows = view_latent.at[layer, jnp.arange(b)[:, None], jnp.minimum(chosen, t - 1), 0].get(
            mode="promise_in_bounds")  # (B, k, R)
        # A window column is attended iff it is among the chosen.
        in_window = jnp.any(chosen[:, :, None] == t + jnp.arange(cols)[None, None], axis=1) & allowed[:, t:]
        return self._absorbed(w, q_nope[:, 0], q_rope[:, 0], jnp.concatenate([rows, win_latent], axis=1),
                              jnp.concatenate([in_view, in_window], axis=1))[:, None]

    def _swiglu(self, h, gate, up, down):
        return self._mm(jax.nn.silu(self._mm(h, gate)) * self._mm(h, up), down)

    def _experts(self, moe, layer, h, ctx):
        """The shared expert plus the held experts' part of the routed sum, of
        expert layer ``layer`` of the stacked ``moe`` weights. Returns ``(out,
        claims (E,), chosen at ctx["watch"] or None)``."""
        cfg = self.config
        b, s, hidden = h.shape
        rows = h.reshape(b * s, hidden)
        valid = ctx.get("valid")
        w = _at({name: moe[name] for name in ("router", "bias", "shared_gate", "shared_up",
                                              "shared_down")}, layer)
        routing = dict(k=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
                       scoring="sigmoid", bias=w["bias"])
        routed, claims = expert_share_ffn(
            rows, w["router"], moe["w_gate"], moe["w_up"], moe["w_down"], layer=layer,
            first=cfg.first_expert, scale=cfg.routed_scaling_factor, precision=cfg.matmul_precision,
            row_mask=None if valid is None else valid.reshape(b * s), **routing)
        shared = self._swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
        chosen = None
        if ctx.get("watch") is not None:
            _, chosen = route_top_k(router_logits(h[:, ctx["watch"]], w["router"]), **routing)
        return shared + routed.reshape(b, s, hidden), claims, chosen

    # ----------------------------------------------------------------- layers
    def _layer(self, layers, dense: bool, index, state, ctx, view):
        """Layer ``index["layer"]`` on ``state``: the residual stream ``x``, on
        the cached path the two write stacks, and the counts it adds to."""
        cfg = self.config
        x = state["x"]
        i = index["layer"]
        norms = _at({name: layers[name] for name in ("input_norm", "post_attn_norm")}, i)
        w, wi = _at(layers["attn"], i), _at(layers["indexer"], i)
        h = rms_norm(x, norms["input_norm"]["weight"], cfg.rms_norm_eps)
        cached = view is not None
        cache_dtype = state["latent"].dtype if cached else h.dtype
        with jax.named_scope("mla"):
            q_nope, q_rope, q_i, w_head, latent, k_i = self._queries_and_rows(
                w, wi, h, ctx["rope_positions"], cache_dtype)
            seen = None
            if not cached:
                mixed, seen = self._attend_tiles(w, q_nope, q_rope, q_i, w_head, latent, k_i,
                                                 ctx["allowed_of"], ctx.get("watch"))
            else:
                at = ctx["at"]
                win_latent = jax.lax.dynamic_update_slice(_at(state["latent"], i)[:, :, 0], latent, (0, at, 0))
                win_k_i = jax.lax.dynamic_update_slice(_at(state["index_k"], i)[:, :, 0], k_i, (0, at, 0))
                state = dict(state, latent=_put(state["latent"], i, win_latent[:, :, None]),
                             index_k=_put(state["index_k"], i, win_k_i[:, :, None]))
                if h.shape[1] == 1:
                    mixed = self._attend_decode(w, q_nope, q_rope, q_i, w_head, view, i,
                                                (win_latent, win_k_i), ctx)
                else:
                    view_latent, view_k_i = (_at(t, i)[:, :, 0] for t in view)
                    mixed, _ = self._attend_tiles(
                        w, q_nope, q_rope, q_i, w_head,
                        jnp.concatenate([view_latent, win_latent], axis=1),
                        jnp.concatenate([view_k_i, win_k_i], axis=1), ctx["allowed_of"])
            mixed = self._mm(mixed, w["wo"])
        state = dict(state, x=x + mixed.astype(x.dtype))
        if seen is not None:
            state["selected"] = _put(state["selected"], i, seen)
        h = rms_norm(state["x"], norms["post_attn_norm"]["weight"], cfg.rms_norm_eps)
        if dense:
            mlp = _at(layers["dense_mlp"], index["mlp"])
            with jax.named_scope("dense_mlp"):
                out = self._swiglu(h, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
        else:
            with jax.named_scope("experts"):
                out, claims, chosen = self._experts(layers["moe"], index["mlp"], h, ctx)
            claims = claims.astype(jnp.float32)
            state["experts_touched"] = state["experts_touched"] + jnp.sum(claims > 0)
            state["expert_claims_max"] = state["expert_claims_max"] + jnp.max(claims)
            state["expert_claims_mean"] = state["expert_claims_mean"] + jnp.mean(claims)
            if chosen is not None:
                state["chosen"] = _put(state["chosen"], index["mlp"], chosen)
        state["x"] = state["x"] + out.astype(x.dtype)
        return state

    def _run_layers(self, layers, x, ctx, cache=None):
        """The leading dense layers, then the scan over the expert layers.
        ``cache``: None, or the write stacks ``latent``, ``index_k`` and the
        read-only ``view_latent``, ``view_index_k``. Returns the final state:
        ``x``, the stacks as written, the expert counts and, with
        ``ctx["watch"]``, the experts chosen (Lm, B, n, k) and the keys
        selected (L, B, n, S) there."""
        cfg = self.config
        lead, n = cfg.first_k_dense_replace, cfg.num_hidden_layers
        zero = jnp.zeros((), jnp.float32)
        state = {"x": x, "experts_touched": zero, "expert_claims_max": zero,
                 "expert_claims_mean": zero}
        view = None
        if cache is not None:
            state.update(latent=cache["latent"], index_k=cache["index_k"])
            view = (cache["view_latent"], cache["view_index_k"])
        if ctx.get("watch") is not None:
            b, watched = x.shape[0], ctx["watch"].shape[0]
            state["chosen"] = jnp.zeros((n - lead, b, watched, cfg.num_experts_per_tok), jnp.int32)
            state["selected"] = jnp.zeros((n, b, watched, x.shape[1]), bool)
        for i in range(lead):
            state = self._layer(layers, True, {"layer": i, "mlp": i}, state, ctx, view)

        def step(state, j):
            return self._layer(layers, False, {"layer": lead + j, "mlp": j}, state, ctx, view), None

        state, _ = jax.lax.scan(step, state, jnp.arange(n - lead))
        return state

    def _embed(self, params, input_ids):
        from ..parallel.sharding import embedding_lookup

        return embedding_lookup(params["embed"]["weight"], input_ids)

    def _head(self, params, x, labels=None):
        with jax.named_scope("lm_head"):
            x = rms_norm(x, params["final_norm"]["weight"], self.config.rms_norm_eps)
            x = x.astype(params["lm_head"]["weight"].dtype)
            # float32 logits from operands of the weights' dtype.
            out = ModelOutput(logits=jax.lax.dot_general(
                x, params["lm_head"]["weight"], (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))
            if labels is not None:
                out["loss"] = cross_entropy_loss(out["logits"], Llama._shift_labels(labels, None))
            return out

    # ---------------------------------------------------------------- forward
    def apply(self, params, input_ids=None, labels=None, attention_mask=None, positions=None,
              cache=None, train: bool = False, rngs=None, watch=None, **kwargs):
        if cache is not None:
            return self._apply_cached(params, input_ids, attention_mask, cache, positions=positions)
        if attention_mask is not None:
            raise ValueError("Glm5's plain forward takes whole sequences: padding masks "
                             "are implemented on the cached (serving) path only")
        b, s = input_ids.shape
        q_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        order = jnp.arange(s, dtype=jnp.int32)

        def allowed_of(first, size):  # causal over the sequence's own keys
            return jnp.broadcast_to(
                order[None, None, :] <= (first + jnp.arange(size, dtype=jnp.int32))[None, :, None],
                (b, size, s))

        ctx = {"rope_positions": q_pos if positions is None else positions,
               "allowed_of": allowed_of, "watch": watch}
        state = self._run_layers(params["layers"], self._embed(params, input_ids), ctx)
        out = self._head(params, state["x"], labels=labels)
        if watch is not None:
            out["routed_experts"], out["selected_keys"] = state["chosen"], state["selected"]
        return out

    def _watched(self, params, input_ids, watch):
        if "_watched_fn" not in self.__dict__:
            self._watched_fn = jax.jit(lambda params, ids, watch: {
                name: value for name, value in self.apply(params, ids, watch=watch).items()
                if name in ("routed_experts", "selected_keys")})
        return self._watched_fn(params, input_ids, watch)

    def routed_experts(self, params, input_ids, watch):
        """The experts each expert layer's router chooses at the positions
        ``watch`` (n,) of whole sequences ``input_ids`` (B, S), by the plain
        forward pass: (Lm, B, n, k) ids among the router's width."""
        return self._watched(params, input_ids, watch)["routed_experts"]

    def selected_keys(self, params, input_ids, watch):
        """The keys each layer's indexer selects for the queries at the
        positions ``watch`` (n,), by the plain forward pass: (L, B, n, S) bool."""
        return self._watched(params, input_ids, watch)["selected_keys"]

    # ------------------------------------------------------------------ cache
    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        """The latent row and the indexer's key of every layer
        (``cache_layout``): two entries of one head each and different widths."""
        cfg = self.config
        return {
            "latent": jnp.zeros((cfg.num_hidden_layers, batch_size, max_len, 1, cfg.latent_dim), dtype),
            "index_k": jnp.zeros((cfg.num_hidden_layers, batch_size, max_len, 1, cfg.index_head_dim), dtype),
            "pos": jnp.zeros((), jnp.int32),
            "kv_mask": jnp.zeros((batch_size, max_len), jnp.int32),
        }

    def prepare_view(self, view):
        """Nothing is derived from the gathered view."""
        return view

    def _apply_cached(self, params, input_ids, attention_mask, cache, positions=None):
        """One chunk (or one decode token a row) through the two-part cache:
        the read-only ``cache["view"]`` (``"latent"``, ``"index_k"``,
        ``"kv_mask"``) and the write window (the same, and ``"pos"``). The
        chunk's rows are written at ``cache["pos"]``; a token whose
        ``attention_mask`` is 0 is no key anywhere. Causality is on the order
        of the columns (view, then window), validity from the masks, and a
        token's position follows from its row's valid columns where
        ``positions`` are not given. Returns the advanced window, the logits
        of the last position, and the counts ``cache_layout`` names."""
        view = cache.get("view")
        if view is None:
            raise NotImplementedError(
                "Glm5 serves through the engine's two-part cache (ContinuousBatcher); "
                "a plain one-part cache is not implemented")
        cfg = self.config
        b, s = input_ids.shape
        at = cache["pos"]
        valid = (jnp.ones((b, s), jnp.int32) if attention_mask is None
                 else attention_mask.astype(jnp.int32))
        kv_mask = jax.lax.dynamic_update_slice(cache["kv_mask"], valid, (0, at))
        cols = kv_mask.shape[1]
        written = jnp.where(jnp.arange(cols)[None] < at, kv_mask, 0)
        count = (view["kv_mask"].sum(axis=1) + written.sum(axis=1)).astype(jnp.int32)
        q_pos = count[:, None] + jnp.cumsum(valid, axis=1, dtype=jnp.int32) - 1
        t = view["kv_mask"].shape[1]
        key_valid = jnp.concatenate([view["kv_mask"], kv_mask], axis=1).astype(bool)  # (B, T+W)
        order = jnp.arange(t + cols, dtype=jnp.int32)

        def allowed_of(first, size):  # valid, and no later than the query's own column
            mine = t + at + first + jnp.arange(size, dtype=jnp.int32)
            return key_valid[:, None, :] & (order[None, None, :] <= mine[None, :, None])

        ctx = {"rope_positions": q_pos if positions is None else positions, "at": at,
               "valid": valid.astype(bool), "allowed_of": allowed_of}
        layer_cache = {"latent": cache["latent"], "index_k": cache["index_k"],
                       "view_latent": view["latent"], "view_index_k": view["index_k"]}
        state = self._run_layers(params["layers"], self._embed(params, input_ids), ctx, layer_cache)
        out = self._head(params, state["x"][:, -1:])
        out["cache"] = {"latent": state["latent"], "index_k": state["index_k"],
                        "pos": at + s, "kv_mask": kv_mask}
        layers = cfg.num_hidden_layers
        context = (q_pos + 1).astype(jnp.float32)  # keys in each query's causal context
        if s == 1:
            out["context_keys"] = layers * context[:, 0]
            out["attended_keys"] = layers * jnp.minimum(context[:, 0], cfg.index_topk)
            out["experts_touched"] = state["experts_touched"]
            out["experts_held"] = jnp.float32(cfg.n_routed_experts * (layers - cfg.first_k_dense_replace))
        else:
            out["expert_claims_max"] = state["expert_claims_max"]
            out["expert_claims_mean"] = state["expert_claims_mean"]
            # Over the real queries and the layers: the keys each selected, and
            # the keys whose attention scores were computed for it (the rows gathered).
            real = valid.astype(jnp.float32)
            out["keys_selected"] = layers * jnp.sum(real * jnp.minimum(context, cfg.index_topk))
            out["keys_scored"] = layers * jnp.sum(real) * min(cfg.index_topk, t + cols)
        return out
