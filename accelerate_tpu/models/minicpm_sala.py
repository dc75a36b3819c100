"""MiniCPM-SALA: block-sparse softmax attention layers beside lightning
(linear) attention layers, one SwiGLU feed-forward in every layer, and the
MiniCPM family's muP scalings (docs/minicpm_sala.md).

The first model here with a per-layer type list. ``mixer_types`` names each
layer's mixer (``"minicpm4"``: block-sparse attention, ``ops/sparse_attention.py``;
``"lightning-attn"``: linear attention with a per-head decay,
``ops/lightning_attention.py``); the layer scan runs over whole **periods** of
that list (the shortest prefix that repeats to the whole list), so a regular
list compiles one period and an irregular one is unrolled once.

Weights (the plain reference, ``chipbench/reference_minicpm_sala.py``, reads
this layout): ``embed.weight (V, h)``; under ``layers`` what every layer has,
stacked over all L layers: ``input_norm.weight``, ``post_attn_norm.weight
(L, h)``, ``mlp.w_gate``, ``mlp.w_up (L, h, I)``, ``mlp.w_down (L, I, h)``;
the sparse layers' mixers stacked over those layers alone: ``sparse.wq``,
``sparse.wg (Ls, h, H·D)``, ``sparse.wk``, ``sparse.wv (Ls, h, G·D)``,
``sparse.wo (Ls, H·D, h)``; the lightning layers' likewise: ``lightning.wq``,
``wk``, ``wv``, ``wg (Ll, h, Hl·Dl)``, ``lightning.wo (Ll, Hl·Dl, h)``,
``lightning.q_norm``, ``lightning.k_norm (Ll, Dl)``, ``lightning.o_norm
(Ll, Hl·Dl)``; ``final_norm.weight (h,)``; ``lm_head.weight (V, h)`` (rows of the vocabulary,
as the embedding: the layout the decode step reads without a transposed copy).

**Two kinds of state in one cache.** ``init_cache`` returns keys and values
for the sparse layers only (``"k"``, ``"v"`` of ``(Ls, B, T, G, D)``) and one
float32 matrix a lightning layer and row (``"state"`` of ``(Ll, B, Hl, Dl,
Dl)``). ``cache_layout`` says which entry is which, and that the model needs
dense chains (a key's column is its token's position), for
``ops/paged_attention.py`` ``init_kv_pool`` and the paged engine. The cached
forward takes the engine's two-part cache (a read-only ``"view"`` and the
write window, as ``Llama._apply_cached``); a plain contiguous cache is not
implemented and is refused in words. Its logits are those of the last
position alone (the engine reads no other).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from ..modules import ModelOutput, Module
from ..ops.lightning_attention import decay_log_slopes, lightning_attention
from ..ops.losses import cross_entropy_loss
from ..ops.sparse_attention import SparseGeometry, compress_keys, sparse_attention
from .llama import Llama, apply_rope, rms_norm, rope_tables

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
QUERY_TILE = 64        # queries a tile of the prefill's masked dense scores
LIGHTNING_BLOCK = 128  # tokens a block of the chunked recurrence


@dataclass
class MiniCPMSALAConfig:
    # Published keys (openbmb/MiniCPM-SALA config.json), defaults as published.
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    mixer_types: tuple | None = None  # None: one sparse layer, then three lightning ones, repeated
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attn_use_rope: bool = False
    attn_use_output_gate: bool = True
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_use_rope: bool = True
    lightning_scale: str = "1/sqrt(d)"
    qk_norm: bool = True           # lightning layers: per-head RMSNorm on q and k
    use_output_norm: bool = True   # lightning layers: RMSNorm on the mixer's output
    use_output_gate: bool = True   # lightning layers: sigmoid gate on the mixer's output
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    max_position_embeddings: int = 524288
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    attention_bias: bool = False
    # Not in the published file (docs/minicpm_sala.md, "What is assumed").
    residual_depth: int | None = None  # layers the residual scale counts; None = num_hidden_layers
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_window: int = 2048
    sparse_init_blocks: int = 1
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    # The program's own.
    matmul_precision: str = "default"  # 'default' | 'int8' (ops/int8.py)

    def __post_init__(self):
        if self.mixer_types is None:
            period = (SPARSE, LIGHTNING, LIGHTNING, LIGHTNING)
            self.mixer_types = (period * self.num_hidden_layers)[: self.num_hidden_layers]
        self.mixer_types = tuple(self.mixer_types)
        if len(self.mixer_types) != self.num_hidden_layers:
            raise ValueError(f"mixer_types has {len(self.mixer_types)} entries for "
                             f"{self.num_hidden_layers} layers")
        if set(self.mixer_types) != {SPARSE, LIGHTNING}:
            raise ValueError(f"mixer_types must hold both {SPARSE!r} and {LIGHTNING!r} and "
                             f"nothing else, got {sorted(set(self.mixer_types))}")
        refused = {
            "attn_use_rope": self.attn_use_rope, "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            "lightning_nkv != lightning_nh": self.lightning_nkv != self.lightning_nh,
            "hidden_act other than silu": self.hidden_act != "silu",
            "lightning_scale other than 1/sqrt(d)": self.lightning_scale != "1/sqrt(d)",
        }
        if any(refused.values()):
            raise ValueError("MiniCPMSALA does not implement: "
                             + ", ".join(k for k, v in refused.items() if v))
        if self.residual_depth is None:
            self.residual_depth = self.num_hidden_layers

    @property
    def geometry(self) -> SparseGeometry:
        return SparseGeometry(self.sparse_block_size, self.sparse_topk, self.sparse_window,
                              self.sparse_init_blocks, self.sparse_kernel_size,
                              self.sparse_kernel_stride)

    @property
    def period(self) -> tuple:
        """The shortest prefix of ``mixer_types`` that repeats to the whole list."""
        types, n = self.mixer_types, self.num_hidden_layers
        for p in range(1, n + 1):
            if n % p == 0 and types == types[:p] * (n // p):
                return types[:p]
        return types

    @classmethod
    def tiny(cls, **kw):
        defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
                        head_dim=16, lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
                        dim_model_base=32, max_position_embeddings=4096)
        defaults.update(kw)
        return cls(**defaults)


class MiniCPMSALA(Module):
    # What of ``init_cache``'s dict the paged engine pages by token, what it
    # holds by slot, and that a key's chain column must be its token's
    # position (ops/paged_attention.py ``cache_layout``).
    cache_layout = {"by_token": ("k", "v"), "by_slot": ("state",), "dense_chain": True,
                    "counters": {"decode": ("attended_keys", "context_keys")}}

    def __init__(self, config: MiniCPMSALAConfig):
        self.config = config
        self.params = None

    # ------------------------------------------------------------------- init
    def _counts(self):
        types = self.config.mixer_types
        return types.count(SPARSE), types.count(LIGHTNING)

    def init(self, rng, *example_inputs, **kwargs):
        cfg = self.config
        h, inter, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
        ls, ll = self._counts()
        qd, kvd = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
        ld = cfg.lightning_nh * cfg.lightning_head_dim
        keys = iter(jax.random.split(rng, 16))

        def dense(shape, fan_in):
            return jax.random.normal(next(keys), shape, jnp.float32) / np.sqrt(fan_in)

        return {
            "embed": {"weight": dense((cfg.vocab_size, h), h)},
            "layers": {
                "input_norm": {"weight": jnp.ones((L, h), jnp.float32)},
                "post_attn_norm": {"weight": jnp.ones((L, h), jnp.float32)},
                "mlp": {"w_gate": dense((L, h, inter), h), "w_up": dense((L, h, inter), h),
                        "w_down": dense((L, inter, h), inter)},
                "sparse": {"wq": dense((ls, h, qd), h), "wk": dense((ls, h, kvd), h),
                           "wv": dense((ls, h, kvd), h), "wg": dense((ls, h, qd), h),
                           "wo": dense((ls, qd, h), qd)},
                "lightning": {"wq": dense((ll, h, ld), h), "wk": dense((ll, h, ld), h),
                              "wv": dense((ll, h, ld), h), "wg": dense((ll, h, ld), h),
                              "wo": dense((ll, ld, h), ld),
                              "q_norm": jnp.ones((ll, cfg.lightning_head_dim), jnp.float32),
                              "k_norm": jnp.ones((ll, cfg.lightning_head_dim), jnp.float32),
                              "o_norm": jnp.ones((ll, ld), jnp.float32)},
            },
            "final_norm": {"weight": jnp.ones((h,), jnp.float32)},
            "lm_head": {"weight": dense((cfg.vocab_size, h), h)},
        }

    def num_params(self) -> int:
        cfg = self.config
        h, ls_ll = cfg.hidden_size, self._counts()
        qd, kvd = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
        ld = cfg.lightning_nh * cfg.lightning_head_dim
        sparse = h * (2 * qd + 2 * kvd) + qd * h
        lightning = 4 * h * ld + ld * h + 2 * cfg.lightning_head_dim + ld
        common = 3 * h * cfg.intermediate_size + 2 * h
        return (ls_ll[0] * sparse + ls_ll[1] * lightning + cfg.num_hidden_layers * common
                + 2 * cfg.vocab_size * h + h)

    def _mm(self, a, b):
        """Every projection goes through the precision dispatcher, as Llama's
        (the serving engine's ``matmul_precision`` swaps it; embedding and head stay exact)."""
        from ..ops.int8 import matmul

        return matmul(a, b, precision=self.config.matmul_precision)

    # ----------------------------------------------------------------- mixers
    def _sparse_mixer(self, w, h, ctx, part):
        """Block-sparse attention. ``part``: None (plain forward) or the
        layer's ``(window_k, window_v, view_k, view_v, kbar)``. Returns
        ``(out, (window_k, window_v) or None, extra)``; ``extra`` is the keys
        one decode token attended and had in context, or, on a plain forward
        with ``ctx["watch"]``, the selection at those positions, else None."""
        cfg = self.config
        b, s, _ = h.shape
        heads, groups, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        geo = cfg.geometry
        q = self._mm(h, w["wq"]).reshape(b, s, heads, d)
        k = self._mm(h, w["wk"]).reshape(b, s, groups, d)
        v = self._mm(h, w["wv"]).reshape(b, s, groups, d)
        if part is None:
            pad = -s % geo.block  # the sequence is its own view, in whole blocks
            k_view, v_view = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (k, v))
            whole, kbar = jnp.full((b,), s, jnp.int32), compress_keys(k_view, geo)
            out, counts = sparse_attention(q, ctx["q_pos"], k_view, v_view, whole, geo,
                                           kbar_view=kbar, query_tile=QUERY_TILE)
            if ctx.get("watch") is not None:
                watch = ctx["watch"]
                _, counts = sparse_attention(q[:, watch], ctx["q_pos"][:, watch], k_view, v_view,
                                             whole, geo, kbar_view=kbar, return_selection=True)
            window = None
        else:
            win_k, win_v, view_k, view_v, kbar = part
            at = (0, ctx["write_at"], 0, 0)
            win_k = jax.lax.dynamic_update_slice(win_k, k.astype(win_k.dtype), at)
            win_v = jax.lax.dynamic_update_slice(win_v, v.astype(win_v.dtype), at)
            out, counts = sparse_attention(
                q, ctx["q_pos"], view_k, view_v, ctx["view_len"], geo, kbar_view=kbar,
                k_new=win_k, v_new=win_v, new_pos=ctx["new_pos"], new_valid=ctx["new_valid"],
                query_tile=QUERY_TILE)
            window = (win_k, win_v)
        out = out.reshape(b, s, heads * d)
        if cfg.attn_use_output_gate:
            out = out * jax.nn.sigmoid(self._mm(h, w["wg"]))
        return self._mm(out, w["wo"]), window, counts

    def _lightning_mixer(self, w, h, ctx, state):
        """Lightning attention from ``state`` (B, Hl, Dl, Dl) float32. Returns
        ``(out, new_state)``."""
        cfg = self.config
        b, s, _ = h.shape
        heads, d = cfg.lightning_nh, cfg.lightning_head_dim
        q, k, v = (self._mm(h, w[name]).reshape(b, s, heads, d) for name in ("wq", "wk", "wv"))
        if cfg.qk_norm:
            q = rms_norm(q, w["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, w["k_norm"], cfg.rms_norm_eps)
        if cfg.lightning_use_rope:
            q, k = apply_rope(q, ctx["cos"], ctx["sin"]), apply_rope(k, ctx["cos"], ctx["sin"])
        out, state = lightning_attention(
            q.astype(jnp.float32) * d ** -0.5, k, v, state, decay_log_slopes(heads),
            mask=ctx["token_mask"], block=LIGHTNING_BLOCK)
        out = out.reshape(b, s, heads * d)
        if cfg.use_output_norm:
            out = rms_norm(out, w["o_norm"], cfg.rms_norm_eps)
        if cfg.use_output_gate:
            out = out * jax.nn.sigmoid(self._mm(h, w["wg"]))
        return self._mm(out, w["wo"]), state

    def _mlp(self, w, h):
        return self._mm(jax.nn.silu(self._mm(h, w["w_gate"])) * self._mm(h, w["w_up"]), w["w_down"])

    # ----------------------------------------------------------- layer driver
    def _run_layers(self, layers, x, ctx, cache=None):
        """The layer scan over whole periods. ``cache``: None, or ``{"k", "v",
        "view_k", "view_v", "kbar"}`` stacked over the sparse layers and
        ``"state"`` over the lightning ones. Returns ``(x, new_cache, counts)``.

        The scan carries the period's number alone: weights and the read-only
        view are closed over whole and each layer takes its own slice by a
        dynamic index, which the compiler reads inside the product that uses
        it (a period's weights handed over as scan inputs are copied whole,
        once a step). What a layer writes (window, state) goes through the
        scan's inputs and outputs."""
        cfg = self.config
        pattern = cfg.period
        p, n = len(pattern), cfg.num_hidden_layers // len(pattern)
        sp, lp = pattern.count(SPARSE), pattern.count(LIGHTNING)
        r = jnp.asarray(cfg.scale_depth / np.sqrt(cfg.residual_depth), x.dtype)
        b = x.shape[0]
        common = {k: layers[k] for k in ("input_norm", "post_attn_norm", "mlp")}
        at = lambda tree, i: jax.tree_util.tree_map(
            lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False), tree)
        fold = lambda per: (lambda t: t.reshape(n, per, *t.shape[1:]))
        xs = {"period": jnp.arange(n)}
        if cache is not None:
            read_only = {name: cache[name] for name in ("view_k", "view_v", "kbar")}
            xs.update(k=fold(sp)(cache["k"]), v=fold(sp)(cache["v"]),
                      state=fold(lp)(cache["state"]))
        counted = cache is not None and x.shape[1] == 1
        watched = cache is None and ctx.get("watch") is not None

        def period_step(x, xs):
            new_k, new_v, new_state, selections = [], [], [], []
            counts = jnp.zeros((2, b), jnp.float32)
            si = li = 0
            for j, kind in enumerate(pattern):
                layer = at(common, xs["period"] * p + j)
                compute = layer["mlp"]["w_gate"].dtype  # the residual stream itself is float32
                h = rms_norm(x, layer["input_norm"]["weight"], cfg.rms_norm_eps).astype(compute)
                if kind == SPARSE:
                    which = xs["period"] * sp + si
                    part = None
                    if cache is not None:
                        part = (xs["k"][si], xs["v"][si]) + tuple(
                            at(read_only[name], which) for name in ("view_k", "view_v", "kbar"))
                    with jax.named_scope("sparse_attn"):
                        mixed, window, seen = self._sparse_mixer(
                            at(layers["sparse"], which), h, ctx, part)
                    if window is not None:
                        new_k.append(window[0])
                        new_v.append(window[1])
                    if counted:
                        counts = counts + jnp.stack(seen)
                    if watched:
                        selections.append(seen)
                    si += 1
                else:
                    state = (xs["state"][li] if cache is not None else jnp.zeros(
                        (b, cfg.lightning_nh, cfg.lightning_head_dim, cfg.lightning_head_dim),
                        jnp.float32))
                    with jax.named_scope("lightning_attn"):
                        mixed, state = self._lightning_mixer(
                            at(layers["lightning"], xs["period"] * lp + li), h, ctx, state)
                    new_state.append(state)
                    li += 1
                x = x + r * mixed.astype(x.dtype)
                h = rms_norm(x, layer["post_attn_norm"]["weight"], cfg.rms_norm_eps).astype(compute)
                with jax.named_scope("mlp"):
                    x = x + r * self._mlp(layer["mlp"], h).astype(x.dtype)
            ys = {"counts": counts}
            if watched:
                ys["selected"] = jnp.stack(selections)
            if cache is not None:
                ys.update(k=jnp.stack(new_k), v=jnp.stack(new_v), state=jnp.stack(new_state))
            return x, ys

        x, ys = jax.lax.scan(period_step, x, xs)
        unfold = lambda t: t.reshape(t.shape[0] * t.shape[1], *t.shape[2:])
        if cache is None:
            return x, None, (unfold(ys["selected"]) if watched else None)
        new_cache = {name: unfold(ys[name]) for name in ("k", "v", "state")}
        return x, new_cache, (ys["counts"].sum(axis=0) if counted else None)

    def _embed(self, params, input_ids):
        from ..parallel.sharding import embedding_lookup

        # The residual stream is float32 whatever the weights are: the muP
        # scalings make it large (embeddings times 12) beside what a layer adds
        # (times 0.25), and a bf16 stream rounds those additions away.
        rows = embedding_lookup(params["embed"]["weight"], input_ids)
        return rows.astype(jnp.float32) * self.config.scale_emb

    def _head(self, params, x, labels=None):
        cfg = self.config
        with jax.named_scope("lm_head"):
            x = rms_norm(x, params["final_norm"]["weight"], cfg.rms_norm_eps)
            x = (x / (cfg.hidden_size / cfg.dim_model_base)).astype(params["lm_head"]["weight"].dtype)
            # float32 logits from operands of the weights' dtype.
            out = ModelOutput(logits=jax.lax.dot_general(
                x, params["lm_head"]["weight"], (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))
            if labels is not None:
                out["loss"] = cross_entropy_loss(out["logits"], Llama._shift_labels(labels, None))
            return out

    def _rope(self, positions):
        cfg = self.config
        return rope_tables(positions, cfg.lightning_head_dim, cfg.rope_theta)

    # ---------------------------------------------------------------- forward
    def apply(self, params, input_ids=None, labels=None, attention_mask=None, positions=None,
              cache=None, train: bool = False, rngs=None, **kwargs):
        if cache is not None:
            return self._apply_cached(params, input_ids, attention_mask, cache, labels=labels,
                                      positions=positions)
        if attention_mask is not None:
            raise ValueError("MiniCPMSALA's plain forward takes whole sequences: padding "
                             "masks are implemented on the cached (serving) path only")
        b, s = input_ids.shape
        q_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        cos, sin = self._rope(q_pos if positions is None else positions)
        ctx = {"q_pos": q_pos, "cos": cos, "sin": sin, "token_mask": None}
        x, _, _ = self._run_layers(params["layers"], self._embed(params, input_ids), ctx)
        return self._head(params, x, labels=labels)

    def selected_blocks(self, params, input_ids, watch):
        """The blocks each sparse layer selects at the positions ``watch`` (n,)
        of whole sequences ``input_ids`` (B, S), by the plain forward pass:
        (Ls, B, n, G, blocks) bool. For comparisons with the reference."""
        if "_selected_blocks_fn" not in self.__dict__:
            def run(params, input_ids, watch):
                b, s = input_ids.shape
                q_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
                cos, sin = self._rope(q_pos)
                ctx = {"q_pos": q_pos, "cos": cos, "sin": sin, "token_mask": None, "watch": watch}
                _, _, selected = self._run_layers(params["layers"], self._embed(params, input_ids), ctx)
                return jnp.swapaxes(selected, 2, 3)  # (Ls, B, G, n, blocks) -> (Ls, B, n, G, blocks)

            self._selected_blocks_fn = jax.jit(run)
        return self._selected_blocks_fn(params, input_ids, watch)

    # ------------------------------------------------------------------ cache
    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        """Keys and values for the sparse layers alone, one float32 state
        matrix a lightning layer, row and head (``cache_layout``)."""
        cfg = self.config
        ls, ll = self._counts()
        kv = (ls, batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim)
        return {
            "k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
            "pos": jnp.zeros((), jnp.int32),
            "kv_mask": jnp.zeros((batch_size, max_len), jnp.int32),
            "state": jnp.zeros((ll, batch_size, cfg.lightning_nh, cfg.lightning_head_dim,
                                cfg.lightning_head_dim), jnp.float32),
        }

    def prepare_view(self, view):
        """What a paged program derives from its gathered view once, before any
        step reads it: the sparse layers' compressed keys."""
        k = view["k"]
        kbar = compress_keys(k.reshape(-1, *k.shape[2:]), self.config.geometry)
        return {**view, "kbar": kbar.reshape(*k.shape[:2], *kbar.shape[1:])}

    def _apply_cached(self, params, input_ids, attention_mask, cache, labels=None,
                      positions=None):
        """One chunk (or one decode token) through the two-part cache: the
        read-only ``cache["view"]`` (``"k"``, ``"v"`` of the sparse layers,
        ``"kv_mask"``; dense: column = position) and the write window
        (``"k"``, ``"v"``, ``"kv_mask"``, ``"pos"``) beside the lightning
        layers' ``"state"``. The chunk's keys are written at ``cache["pos"]``;
        a token whose ``attention_mask`` is 0 is no key, and neither updates
        nor decays a state. Returns the advanced window and state, and the
        logits of the last position."""
        view = cache.get("view")
        if view is None:
            raise NotImplementedError(
                "MiniCPMSALA serves through the engine's two-part cache "
                "(ContinuousBatcher); a plain one-part cache is not implemented")
        if "kbar" not in view:
            view = self.prepare_view(view)
        b, s = input_ids.shape
        at = cache["pos"]
        token_mask = (jnp.ones((b, s), jnp.int32) if attention_mask is None
                      else attention_mask.astype(jnp.int32))
        new_valid = jax.lax.dynamic_update_slice(cache["kv_mask"], token_mask, (0, at))
        view_len = view["kv_mask"].sum(axis=1).astype(jnp.int32)
        new_pos = view_len[:, None] + jnp.cumsum(new_valid, axis=1, dtype=jnp.int32) - 1
        q_pos = jax.lax.dynamic_slice_in_dim(new_pos, at, s, axis=1)
        cos, sin = self._rope(q_pos if positions is None else positions)
        ctx = {"q_pos": q_pos, "cos": cos, "sin": sin, "token_mask": token_mask,
               "write_at": at, "view_len": view_len, "new_pos": new_pos, "new_valid": new_valid}
        layer_cache = {"k": cache["k"], "v": cache["v"], "view_k": view["k"], "view_v": view["v"],
                       "kbar": view["kbar"], "state": cache["state"]}
        x, new, counts = self._run_layers(params["layers"], self._embed(params, input_ids), ctx,
                                          layer_cache)
        out = self._head(params, x[:, -1:], labels=None)
        out["cache"] = {**new, "pos": at + s, "kv_mask": new_valid}
        if counts is not None:
            out["attended_keys"], out["context_keys"] = counts[0], counts[1]
        return out
