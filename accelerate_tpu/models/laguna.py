"""Laguna: full-attention layers beside sliding-window layers that have MORE
query heads, a sigmoid gate a query head, a leading dense feed-forward and
then routed experts beside one shared expert (docs/laguna.md).

``layer_types`` names each layer's attention (``"full_attention"`` /
``"sliding_attention"``), ``num_attention_heads_per_layer`` its query heads
(uniform within a kind), ``mlp_layer_types`` its feed-forward (``"dense"``
for the leading layers, ``"sparse"`` after). The leading dense layers run
before the layer scan; the scan runs over whole **periods** of what follows
(the shortest prefix that repeats), and what is left of a last, partial period
is unrolled after it, as ``models/minicpm_sala.py`` scans its periods: weights
are closed over whole and each layer takes its own slice by a dynamic index.

Weights (the plain reference, ``chipbench/reference_laguna.py``, reads this
layout), stacked by kind in the order the layers occur: ``embed.weight (V,
h)``; ``layers.input_norm.weight``, ``layers.post_attn_norm.weight (L, h)``;
``layers.full`` and ``layers.sliding``, each ``wq (Lk, h, Hk·D)``, ``wk``,
``wv (Lk, h, G·D)``, ``wg (Lk, h, Hk)``, ``wo (Lk, Hk·D, h)``;
``layers.dense_mlp.w_gate``, ``w_up (Ld, h, I)``, ``w_down (Ld, I, h)``;
``layers.moe.router (Lm, h, R)``, ``w_gate``, ``w_up (Lm, E, h, Im)``,
``w_down (Lm, E, Im, h)``, ``shared_gate``, ``shared_up (Lm, h, Is)``,
``shared_down (Lm, Is, h)``; ``final_norm.weight (h,)``; ``lm_head.weight
(V, h)``. **E is the experts held here** (``num_experts``), experts
``first_expert .. first_expert + E`` of the ``router_experts`` (R) that the
router scores: a chip's share of a layer (``ops/moe.py``
``expert_share_ffn``); by default all of them.

**Three kinds of state in one cache.** ``init_cache`` returns keys and values
for the full layers alone (``"k"``, ``"v"`` of ``(Lf, B, T, G, D)``, paged by
token) and, for the sliding layers, a **ring** of ``sliding_window``
positions a row (``"ring_k"``, ``"ring_v"`` of ``(Ls, B, W, G, D)``, held by
slot): the key of position ``p`` lives at column ``p mod W`` with its rotation
baked in, so a window layer holds nothing behind its window and never enters
the engine's gathered view. Which columns are live, and their positions,
follow from the row's token count alone. The cached forward takes the
engine's two-part cache (read-only ``"view"``, write window) as
``Llama._apply_cached``; its logits are those of the last position.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from ..modules import ModelOutput, Module
from ..ops.attention import write_and_attend
from ..ops.losses import cross_entropy_loss
from ..ops.moe import expert_share_ffn, route_top_k, router_logits
from .llama import Llama, apply_rope, rms_norm, rope_tables

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
QUERY_TILE = 256  # queries a tile of a sliding layer's chunk against ring ++ chunk

_PUBLISHED_ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
           "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
           "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
}


@dataclass
class LagunaConfig:
    # Published keys (poolside/Laguna-S-2.1 config.json), defaults as published.
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 1048576
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256  # the experts HELD HERE: the published count unless a share is cut
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = (0,)
    tie_word_embeddings: bool = False
    gating: str = "per-head"
    sliding_window: int = 512
    rope_parameters: dict | None = None
    layer_types: tuple | None = None  # None: one full layer, then three sliding ones, repeated
    moe_apply_router_weight_on_input: bool = False
    mlp_layer_types: tuple | None = None  # None: dense for mlp_only_layers, sparse after
    gating_types: tuple | None = None
    moe_routed_scaling_factor: float = 2.5
    num_attention_heads_per_layer: tuple | None = None  # None: full as published, sliding 3/2 of it
    moe_router_logit_softcapping: float = 0
    # The program's own.
    router_experts: int | None = None  # experts the router scores; None = num_experts
    first_expert: int = 0              # the first expert held here
    matmul_precision: str = "default"  # 'default' | 'int8' (ops/int8.py)

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = ((FULL, SLIDING, SLIDING, SLIDING) * n)[:n]
        self.layer_types = tuple(self.layer_types)
        self.mlp_only_layers = tuple(self.mlp_only_layers)
        if self.mlp_layer_types is None:
            self.mlp_layer_types = tuple(DENSE if i in self.mlp_only_layers else SPARSE
                                         for i in range(n))
        self.mlp_layer_types = tuple(self.mlp_layer_types)
        if self.num_attention_heads_per_layer is None:
            self.num_attention_heads_per_layer = tuple(
                self.num_attention_heads * (2 if kind == FULL else 3) // 2
                for kind in self.layer_types)
        self.num_attention_heads_per_layer = tuple(self.num_attention_heads_per_layer)
        if self.gating_types is None:
            self.gating_types = ("per_head",) * n
        self.gating_types = tuple(self.gating_types)
        if self.rope_parameters is None:
            self.rope_parameters = {k: dict(v) for k, v in _PUBLISHED_ROPE.items()}
        if self.router_experts is None:
            self.router_experts = self.num_experts
        for name in ("layer_types", "mlp_layer_types", "gating_types",
                     "num_attention_heads_per_layer"):
            if len(getattr(self, name)) != n:
                raise ValueError(f"{name} has {len(getattr(self, name))} entries for {n} layers")
        lead = self.leading_dense
        heads = {kind: {h for k, h in zip(self.layer_types, self.num_attention_heads_per_layer)
                        if k == kind} for kind in (FULL, SLIDING)}
        refused = {
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            "moe_apply_router_weight_on_input": self.moe_apply_router_weight_on_input,
            "moe_router_logit_softcapping": bool(self.moe_router_logit_softcapping),
            "decoder_sparse_step other than 1": self.decoder_sparse_step != 1,
            "gating other than per-head": self.gating != "per-head"
            or set(self.gating_types) != {"per_head"},
            "layer types other than full_attention and sliding_attention":
                set(self.layer_types) != {FULL, SLIDING},
            "dense feed-forwards that are not the leading layers":
                self.mlp_layer_types != (DENSE,) * lead + (SPARSE,) * (n - lead)
                or self.mlp_only_layers != tuple(range(lead)) or lead == n,
            "head counts that differ within a layer kind": any(len(h) != 1 for h in heads.values()),
            "experts held outside the router's width":
                self.first_expert < 0 or self.first_expert + self.num_experts > self.router_experts,
            "more experts a token than the router scores":
                self.num_experts_per_tok > self.router_experts,
        }
        if any(refused.values()):
            raise ValueError("Laguna does not implement: "
                             + ", ".join(k for k, v in refused.items() if v))

    @property
    def leading_dense(self) -> int:
        """The layers before the scan: the leading run of dense feed-forwards."""
        lead = 0
        while lead < self.num_hidden_layers and self.mlp_layer_types[lead] == DENSE:
            lead += 1
        return lead

    def heads(self, kind: str) -> int:
        return self.num_attention_heads_per_layer[self.layer_types.index(kind)]

    @property
    def period(self) -> tuple:
        """The shortest prefix of the layer kinds after the leading dense layers
        that repeats to all of them, a last partial period allowed."""
        rest = self.layer_types[self.leading_dense:]
        for p in range(1, len(rest) + 1):
            if all(rest[i] == rest[i % p] for i in range(len(rest))):
                return rest[:p]
        return rest

    @classmethod
    def tiny(cls, **kw):
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=8,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_attention_heads_per_layer=(4, 6, 6, 6) * 2, sliding_window=8,
            num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, max_position_embeddings=4096,
            rope_parameters={
                FULL: {**_PUBLISHED_ROPE[FULL], "factor": 8, "original_max_position_embeddings": 32},
                SLIDING: dict(_PUBLISHED_ROPE[SLIDING])})
        defaults.update(kw)
        return cls(**defaults)


def _at(tree, i):
    """Slice ``i`` (a Python or a traced index) of every stacked leaf."""
    return jax.tree_util.tree_map(
        lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False), tree)


def _put(stack, i, value):
    return jax.lax.dynamic_update_index_in_dim(stack, value.astype(stack.dtype), i, 0)


def ring_positions(count, window: int):
    """The position each ring column holds for rows that have ``count`` (B,)
    keys so far: the largest ``p < count`` with ``p mod window == column``;
    negative where the column holds no key yet. (B, window) int32."""
    last = count.astype(jnp.int32)[:, None] - 1
    return last - jnp.mod(last - jnp.arange(window, dtype=jnp.int32)[None], window)


def attention_by_position(q, k, v, q_pos, k_pos, k_valid=None, window: int | None = None):
    """Softmax attention of ``q`` (B, S, H, D) over keys ``k``, ``v`` (B, K, G,
    D) given by position: key j is seen from query i iff it is valid and ``0
    <= q_pos[i] - k_pos[j]``, ``< window`` where there is one. Float32 scores;
    queries in tiles of ``QUERY_TILE`` so that a long chunk's or sequence's
    scores are never whole at once."""
    b, s, heads, d = q.shape
    groups = k.shape[2]
    qg = q.reshape(b, s, groups, heads // groups, d)

    def attend(q_tile, pos_tile):
        scores = jnp.einsum("bsgrd,bkgd->bgrsk", q_tile, k).astype(jnp.float32) * d ** -0.5
        apart = pos_tile[:, :, None] - k_pos[:, None, :]
        keep = apart >= 0
        if window is not None:
            keep = keep & (apart < window)
        if k_valid is not None:
            keep = keep & k_valid[:, None, :]
        probs = jax.nn.softmax(scores + jnp.where(keep, 0.0, -1e30)[:, None, None], axis=-1)
        return jnp.einsum("bgrsk,bkgd->bsgrd", probs.astype(q.dtype), v)

    if s <= QUERY_TILE:
        return attend(qg, q_pos).reshape(b, s, heads, d)
    pad = -s % QUERY_TILE  # padded queries attend what the last one does, and are cut off
    fold = lambda t: jnp.moveaxis(
        jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2), mode="edge")
        .reshape(b, (s + pad) // QUERY_TILE, QUERY_TILE, *t.shape[2:]), 1, 0)
    out = jnp.moveaxis(jax.lax.map(lambda xs: attend(*xs), (fold(qg), fold(q_pos))), 0, 1)
    return out.reshape(b, s + pad, heads, d)[:, :s]


class Laguna(Module):
    # What of ``init_cache``'s dict the paged engine pages by token and what it
    # holds by slot, that a decode step is to be told which rows decode (a free
    # slot's pad token claims no expert), and the counts the cached forward
    # returns beside its logits (ops/paged_attention.py ``cache_layout``).
    cache_layout = {
        "by_token": ("k", "v"), "by_slot": ("ring_k", "ring_v"), "row_mask": True,
        "counters": {"decode": ("attended_keys", "context_keys", "experts_touched", "experts_held"),
                     "chunk": ("expert_claims_max", "expert_claims_mean")}}

    def __init__(self, config: LagunaConfig):
        self.config = config
        self.params = None

    # ------------------------------------------------------------------- init
    def _counts(self) -> dict:
        cfg = self.config
        return {FULL: cfg.layer_types.count(FULL), SLIDING: cfg.layer_types.count(SLIDING),
                DENSE: cfg.leading_dense, SPARSE: cfg.num_hidden_layers - cfg.leading_dense}

    def init(self, rng, *example_inputs, **kwargs):
        cfg = self.config
        h, n, count = cfg.hidden_size, cfg.num_hidden_layers, self._counts()
        kvd = cfg.num_key_value_heads * cfg.head_dim
        e, im, shared = cfg.num_experts, cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
        keys = iter(jax.random.split(rng, 32))

        def dense(shape, fan_in):
            return jax.random.normal(next(keys), shape, jnp.float32) / np.sqrt(fan_in)

        def attention(kind):
            lk, heads = count[kind], cfg.heads(kind)
            qd = heads * cfg.head_dim
            return {"wq": dense((lk, h, qd), h), "wk": dense((lk, h, kvd), h),
                    "wv": dense((lk, h, kvd), h), "wg": dense((lk, h, heads), h),
                    "wo": dense((lk, qd, h), qd)}

        ld, lm, inter = count[DENSE], count[SPARSE], cfg.intermediate_size
        return {
            "embed": {"weight": dense((cfg.vocab_size, h), h)},
            "layers": {
                "input_norm": {"weight": jnp.ones((n, h), jnp.float32)},
                "post_attn_norm": {"weight": jnp.ones((n, h), jnp.float32)},
                "full": attention(FULL), "sliding": attention(SLIDING),
                "dense_mlp": {"w_gate": dense((ld, h, inter), h), "w_up": dense((ld, h, inter), h),
                              "w_down": dense((ld, inter, h), inter)},
                "moe": {"router": dense((lm, h, cfg.router_experts), h),
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
        h, count = cfg.hidden_size, self._counts()
        kvd = cfg.num_key_value_heads * cfg.head_dim
        attention = lambda heads: h * (2 * heads * cfg.head_dim + 2 * kvd + heads)
        moe = (h * cfg.router_experts + 3 * h * cfg.moe_intermediate_size * cfg.num_experts
               + 3 * h * cfg.shared_expert_intermediate_size)
        return (count[FULL] * attention(cfg.heads(FULL)) + count[SLIDING] * attention(cfg.heads(SLIDING))
                + count[DENSE] * 3 * h * cfg.intermediate_size + count[SPARSE] * moe
                + cfg.num_hidden_layers * 2 * h + 2 * cfg.vocab_size * h + h)

    def _mm(self, a, b):
        """Every projection goes through the precision dispatcher, as Llama's
        (the serving engine's ``matmul_precision`` swaps it; embedding, head,
        router and gates stay exact)."""
        from ..ops.int8 import matmul

        return matmul(a, b, precision=self.config.matmul_precision)

    # ----------------------------------------------------------------- layers
    def _rope(self, positions):
        """``{kind: (cos, sin, rotated dims)}``: the full layers rotate the first
        ``partial_rotary_factor`` of each head by their table (YaRN: its
        attention factor rides on cos and sin), the sliding layers theirs."""
        cfg, tables = self.config, {}
        for kind in (FULL, SLIDING):
            spec = dict(cfg.rope_parameters[kind])
            rotated = int(cfg.head_dim * spec.pop("partial_rotary_factor", 1))
            theta = spec.pop("rope_theta")
            scaling = spec if spec.get("rope_type", "default") != "default" else None
            tables[kind] = (*rope_tables(positions, rotated, theta, scaling), rotated)
        return tables

    @staticmethod
    def _rotate(x, table):
        cos, sin, rotated = table
        if rotated == x.shape[-1]:
            return apply_rope(x, cos, sin)
        return jnp.concatenate([apply_rope(x[..., :rotated], cos, sin), x[..., rotated:]], axis=-1)

    def _attention(self, kind, w, h, ctx, part):
        """One layer's gated attention. ``part``: None (plain forward); a full
        layer's ``(window_k, window_v, view_k, view_v)``; a sliding layer's
        ``(ring_k, ring_v)``. Returns ``(out, written)``: the advanced window
        or ring."""
        cfg = self.config
        b, s, _ = h.shape
        heads, groups, d = cfg.heads(kind), cfg.num_key_value_heads, cfg.head_dim
        q = self._rotate(self._mm(h, w["wq"]).reshape(b, s, heads, d), ctx["rope"][kind])
        k = self._rotate(self._mm(h, w["wk"]).reshape(b, s, groups, d), ctx["rope"][kind])
        v = self._mm(h, w["wv"]).reshape(b, s, groups, d)
        window = cfg.sliding_window if kind == SLIDING else None
        written = None
        if part is None:
            out = attention_by_position(q, k, v, ctx["q_pos"], ctx["q_pos"], window=window)
        elif kind == FULL:
            win_k, win_v, view_k, view_v = part
            out, new = write_and_attend(q, k, v, {"k": win_k, "v": win_v, "prefix": (view_k, view_v)},
                                        ctx["full"])
            written = (new["k"], new["v"])
        else:
            # The ring as the row's last keys, then the chunk's own; afterwards
            # the chunk's last ``window`` keys take their columns (padding and
            # keys already behind the window take none).
            ring_k, ring_v = part
            held = ring_positions(ctx["count"], window)
            keys, values = (jnp.concatenate([ring.astype(new.dtype), new], axis=1)
                            for ring, new in ((ring_k, k), (ring_v, v)))
            out = attention_by_position(
                q, keys, values, ctx["q_pos"], jnp.concatenate([held, ctx["q_pos"]], axis=1),
                jnp.concatenate([held >= 0, ctx["valid"]], axis=1), window)
            column = jnp.where(ctx["valid"] & (ctx["q_pos"] > ctx["last"][:, None] - window),
                               jnp.mod(ctx["q_pos"], window), window)  # = window: dropped
            rows = jnp.arange(b)[:, None]
            written = tuple(ring.at[rows, column].set(new.astype(ring.dtype), mode="drop")
                            for ring, new in ((ring_k, k), (ring_v, v)))
        # One gate a query head, float32, from the layer's normed input.
        gate = jax.nn.sigmoid(jax.lax.dot_general(
            h.astype(jnp.float32), w["wg"].astype(jnp.float32), (((2,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST))
        out = (out * gate[..., None].astype(out.dtype)).reshape(b, s, heads * d)
        return self._mm(out, w["wo"]), written

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
        w = _at({name: moe[name] for name in ("router", "shared_gate", "shared_up", "shared_down")},
                layer)
        routed, claims = expert_share_ffn(
            rows, w["router"], moe["w_gate"], moe["w_up"], moe["w_down"], layer=layer,
            first=cfg.first_expert, k=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
            scale=cfg.moe_routed_scaling_factor, precision=cfg.matmul_precision,
            row_mask=None if valid is None else valid.reshape(b * s))
        shared = self._swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
        chosen = None
        if ctx.get("watch") is not None:
            _, chosen = route_top_k(router_logits(h[:, ctx["watch"]], w["router"]),
                                    cfg.num_experts_per_tok)
        return shared + routed.reshape(b, s, hidden), claims, chosen

    def _layer(self, layers, kind, mlp_kind, index, state, ctx, view):
        """Layer ``index["layer"]`` on ``state``: the residual stream ``x``
        and, on the cached path, the stacks it writes (``k``, ``v``, ``ring_k``,
        ``ring_v``) and the counts it adds to."""
        cfg = self.config
        x = state["x"]
        norms = _at({name: layers[name] for name in ("input_norm", "post_attn_norm")}, index["layer"])
        a = index["attention"]
        h = rms_norm(x, norms["input_norm"]["weight"], cfg.rms_norm_eps)
        part = None
        if view is not None:
            part = _at((state["k"], state["v"], *view) if kind == FULL
                       else (state["ring_k"], state["ring_v"]), a)
        with jax.named_scope(kind):
            mixed, written = self._attention(
                kind, _at(layers["full" if kind == FULL else "sliding"], a), h, ctx, part)
        state = dict(state, x=x + mixed.astype(x.dtype))
        if written is not None:
            names = ("k", "v") if kind == FULL else ("ring_k", "ring_v")
            state.update({name: _put(state[name], a, new) for name, new in zip(names, written)})
        h = rms_norm(state["x"], norms["post_attn_norm"]["weight"], cfg.rms_norm_eps)
        if mlp_kind == DENSE:
            w = _at(layers["dense_mlp"], index["mlp"])
            with jax.named_scope("dense_mlp"):
                out = self._swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
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
        """The leading dense layers, the scan over whole periods of what
        follows, and the last partial period unrolled. ``cache``: None, or the
        write stacks ``k``, ``v`` (full layers), ``ring_k``, ``ring_v``
        (sliding layers) and the read-only ``view_k``, ``view_v``. Returns the
        final state: ``x``, the stacks as written, the expert counts and, with
        ``ctx["watch"]``, the experts chosen there (Lm, B, n, k)."""
        cfg = self.config
        lead, pattern = cfg.leading_dense, cfg.period
        rest = cfg.num_hidden_layers - lead
        p, periods = len(pattern), rest // len(pattern)
        zero = jnp.zeros((), jnp.float32)
        state = {"x": x, "experts_touched": zero, "expert_claims_max": zero,
                 "expert_claims_mean": zero}
        view = None
        if cache is not None:
            state.update({name: cache[name] for name in ("k", "v", "ring_k", "ring_v")})
            view = (cache["view_k"], cache["view_v"])
        if ctx.get("watch") is not None:
            state["chosen"] = jnp.zeros((rest, x.shape[0], ctx["watch"].shape[0],
                                         cfg.num_experts_per_tok), jnp.int32)
        before = {kind: cfg.layer_types[:lead].count(kind) for kind in (FULL, SLIDING)}
        each = {kind: pattern.count(kind) for kind in (FULL, SLIDING)}

        def index(period, j):
            """Layer ``lead + period·p + j``'s place in each stack."""
            kind = pattern[j]
            return {"layer": lead + period * p + j, "mlp": period * p + j,
                    "attention": before[kind] + period * each[kind] + pattern[:j].count(kind)}

        for i in range(lead):
            at = {"layer": i, "mlp": i, "attention": cfg.layer_types[:i].count(cfg.layer_types[i])}
            state = self._layer(layers, cfg.layer_types[i], DENSE, at, state, ctx, view)

        def period_step(state, period):
            for j, kind in enumerate(pattern):
                state = self._layer(layers, kind, SPARSE, index(period, j), state, ctx, view)
            return state, None

        if periods:
            state, _ = jax.lax.scan(period_step, state, jnp.arange(periods))
        for j in range(rest - periods * p):
            state = self._layer(layers, pattern[j], SPARSE, index(periods, j), state, ctx, view)
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
            raise ValueError("Laguna's plain forward takes whole sequences: padding masks "
                             "are implemented on the cached (serving) path only")
        b, s = input_ids.shape
        q_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        ctx = {"q_pos": q_pos, "rope": self._rope(q_pos if positions is None else positions),
               "watch": watch}
        state = self._run_layers(params["layers"], self._embed(params, input_ids), ctx)
        out = self._head(params, state["x"], labels=labels)
        if watch is not None:
            out["routed_experts"] = state["chosen"]
        return out

    def routed_experts(self, params, input_ids, watch):
        """The experts each expert layer's router chooses at the positions
        ``watch`` (n,) of whole sequences ``input_ids`` (B, S), by the plain
        forward pass: (Lm, B, n, k) ids among the router's width. For
        comparisons with the reference."""
        if "_routed_experts_fn" not in self.__dict__:
            self._routed_experts_fn = jax.jit(
                lambda params, ids, watch: self.apply(params, ids, watch=watch)["routed_experts"])
        return self._routed_experts_fn(params, input_ids, watch)

    # ------------------------------------------------------------------ cache
    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        """Keys and values for the full layers alone, and a ring of
        ``sliding_window`` positions a row for each sliding layer
        (``cache_layout``)."""
        cfg, count = self.config, self._counts()
        kv = (count[FULL], batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim)
        ring = (count[SLIDING], batch_size, cfg.sliding_window, cfg.num_key_value_heads, cfg.head_dim)
        return {
            "k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
            "pos": jnp.zeros((), jnp.int32),
            "kv_mask": jnp.zeros((batch_size, max_len), jnp.int32),
            "ring_k": jnp.zeros(ring, dtype), "ring_v": jnp.zeros(ring, dtype),
        }

    def prepare_view(self, view):
        """Nothing is derived from the gathered view (the engine asks every
        model that holds state by slot)."""
        return view

    def _apply_cached(self, params, input_ids, attention_mask, cache, positions=None):
        """One chunk (or one decode token) through the two-part cache: the
        read-only ``cache["view"]`` (``"k"``, ``"v"`` of the full layers,
        ``"kv_mask"``) and the write window (``"k"``, ``"v"``, ``"kv_mask"``,
        ``"pos"``) beside the sliding layers' rings. The chunk's full-layer keys
        are written at ``cache["pos"]``; a token whose ``attention_mask`` is 0
        is no key anywhere. A row's token count is the valid columns of its
        view and window, and a token's position follows from it. Returns the
        advanced window and rings, the logits of the last position, and the
        counts ``cache_layout`` names."""
        view = cache.get("view")
        if view is None:
            raise NotImplementedError(
                "Laguna serves through the engine's two-part cache (ContinuousBatcher); "
                "a plain one-part cache is not implemented")
        cfg = self.config
        b, s = input_ids.shape
        at = cache["pos"]
        valid = (jnp.ones((b, s), jnp.int32) if attention_mask is None
                 else attention_mask.astype(jnp.int32))
        kv_mask = jax.lax.dynamic_update_slice(cache["kv_mask"], valid, (0, at))
        written = jnp.where(jnp.arange(kv_mask.shape[1])[None] < at, kv_mask, 0)
        count = (view["kv_mask"].sum(axis=1) + written.sum(axis=1)).astype(jnp.int32)
        q_pos = count[:, None] + jnp.cumsum(valid, axis=1, dtype=jnp.int32) - 1
        slots = jnp.broadcast_to(at + jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        ctx = {"q_pos": q_pos, "rope": self._rope(q_pos if positions is None else positions),
               "valid": valid.astype(bool), "count": count, "last": count + valid.sum(axis=1) - 1,
               "full": {"positions": slots, "kv_mask": kv_mask, "cache_pos": at,
                        "prefix_mask": view["kv_mask"]}}
        layer_cache = {"k": cache["k"], "v": cache["v"], "view_k": view["k"], "view_v": view["v"],
                       "ring_k": cache["ring_k"], "ring_v": cache["ring_v"]}
        state = self._run_layers(params["layers"], self._embed(params, input_ids), ctx, layer_cache)
        out = self._head(params, state["x"][:, -1:])
        out["cache"] = {**{name: state[name] for name in ("k", "v", "ring_k", "ring_v")},
                        "pos": at + s, "kv_mask": kv_mask}
        if s == 1:
            # Keys in a decode token's context, and of them those a layer
            # attends: all for a full layer, at most the window for a sliding one.
            context = (q_pos[:, 0] + 1).astype(jnp.float32)
            count_of = self._counts()
            out["context_keys"] = cfg.num_hidden_layers * context
            out["attended_keys"] = (count_of[FULL] * context
                                    + count_of[SLIDING] * jnp.minimum(context, cfg.sliding_window))
            out["experts_touched"] = state["experts_touched"]
            out["experts_held"] = jnp.float32(cfg.num_experts * count_of[SPARSE])
        else:
            out["expert_claims_max"] = state["expert_claims_max"]
            out["expert_claims_mean"] = state["expert_claims_mean"]
        return out
