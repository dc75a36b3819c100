"""Classic-GPT decoder family — GPT-NeoX, GPT-J, and OPT in one skeleton.

These are the three architectures the reference's headline big-model-inference
benchmark tables are built on (BASELINE.md: GPT-J-6B / GPT-NeoX-20B / OPT-30B
load-time and s/token; reference driver
``benchmarks/big_model_inference/big_model_inference.py``) — the reference
itself never defines them (they come from transformers). One configurable
skeleton covers all three because they differ only along documented axes:

- **positions**: rotary half-split (NeoX, partial ``rotary_pct``), rotary
  interleaved-pairs (GPT-J ``rotary_dim``), or a learned table with a lookup
  offset (OPT's +2 rows).
- **residual topology**: parallel attn+MLP off the same input (NeoX two norms,
  GPT-J one shared norm) vs sequential pre-LN blocks (OPT).
- **activation**: exact gelu (NeoX), tanh-gelu (GPT-J), relu (OPT).
- **head**: untied (NeoX), untied with bias (GPT-J), tied (OPT).

Same TPU-first shape as ``GPT2``/``Llama``: stacked-layer ``lax.scan``, the
embed/block/head stage protocol (pipeline- and layer-stream-capable), fused QKV
projection for one MXU matmul (converters de-interleave NeoX's per-head fused
layout), Megatron-style tp sharding rules, and the mask-derived ``positions``
channel that keeps ragged generation exact for both rotary and learned-table
variants.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..modules import ModelOutput, Module
from ..ops.attention import attention as _attention
from ..ops.losses import cross_entropy_loss
from ..ops.paged_attention import view_capacity
from .gpt2 import GPT2, _layer_norm
from .llama import rope_tables, apply_rope


def apply_rope_interleaved(x, cos, sin):
    """GPT-J rotary: pairs are adjacent lanes (0,1),(2,3),… — the
    ``rotate_every_two`` convention — vs the half-split Llama/NeoX layout.
    ``x``: (B, S, H, D_rot); ``cos``/``sin``: (B, S, D_rot/2)."""
    x1, x2 = x[..., ::2], x[..., 1::2]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


_POSITION_STYLES = ("rotary_neox", "rotary_gptj", "learned")
_ACTS = {
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}


@dataclass
class GPTXConfig:
    vocab_size: int = 50432
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 2048
    layer_norm_eps: float = 1e-5
    # 'rotary_neox' (half-split, partial width) | 'rotary_gptj' (interleaved
    # pairs) | 'learned' (OPT table with `position_offset` extra leading rows).
    position_style: str = "rotary_neox"
    rotary_dim: int | None = None  # None = full head_dim (rotary styles only)
    rope_theta: float = 10000.0
    # Length-independent rope scaling (linear/llama3/yarn dicts, the HF config
    # field) applied over the rotary lanes. 'dynamic' (NTK-by-length) is NOT
    # supported here — it would need the cache-capacity pinning Llama carries.
    rope_scaling: dict | None = None
    # True: x + attn(ln1(x)) + mlp(ln2(x)) — NeoX/GPT-J. False: sequential
    # pre-LN (OPT, and NeoX checkpoints with use_parallel_residual=False).
    parallel_residual: bool = True
    # GPT-J feeds attn and MLP the SAME ln_1 output (no ln_2 parameters).
    shared_layernorm: bool = False
    hidden_act: str = "gelu"
    attention_bias: bool = True  # NeoX/OPT yes; GPT-J projects bias-free
    position_offset: int = 0  # OPT's learned table starts at row 2
    tie_word_embeddings: bool = False  # OPT ties; NeoX/GPT-J don't
    lm_head_bias: bool = False  # GPT-J's untied head carries a bias
    remat: bool = False
    remat_policy: str = "nothing_saveable"
    attention_impl: str = "auto"
    matmul_precision: str = "default"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def __post_init__(self):
        if self.position_style not in _POSITION_STYLES:
            raise ValueError(
                f"position_style must be one of {_POSITION_STYLES}, got {self.position_style!r}"
            )
        if self.hidden_act not in _ACTS:
            raise ValueError(f"hidden_act must be one of {sorted(_ACTS)}, got {self.hidden_act!r}")
        if self.position_style == "learned":
            if self.rotary_dim is not None:
                raise ValueError("rotary_dim is meaningless with learned positions")
        elif self.rotary_dim is None:
            self.rotary_dim = self.head_dim
        if self.rotary_dim is not None and self.rotary_dim % 2:
            raise ValueError(f"rotary_dim must be even, got {self.rotary_dim}")
        if self.rope_scaling:
            if self.position_style == "learned":
                raise ValueError("rope_scaling is meaningless with learned positions")
            rope_type = self.rope_scaling.get("rope_type", self.rope_scaling.get("type"))
            if rope_type == "dynamic":
                raise ValueError(
                    "dynamic (NTK-by-length) rope scaling is not supported by the "
                    "classic-GPT zoo model (its rope has no cache-capacity pinning); "
                    "linear/llama3/yarn are supported"
                )
        if self.shared_layernorm and not self.parallel_residual:
            raise ValueError("shared_layernorm requires parallel_residual (the GPT-J topology)")

    @classmethod
    def tiny(cls, **kw):
        defaults = dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=2,
            num_attention_heads=4,
            max_position_embeddings=128,
        )
        defaults.update(kw)
        return cls(**defaults)


class GPTX(Module):
    # embed/block/head stage protocol — GPipe-eligible (parallel/pipeline.py).
    pipeline_capable = True
    scan_aux_keys: tuple = ()

    def __init__(self, config: GPTXConfig):
        self.config = config
        self.params = None

    # ------------------------------------------------------------------- init
    def init(self, rng, *example_inputs, **kwargs):
        cfg = self.config
        h, inter, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
        keys = jax.random.split(rng, 8)

        def dense(key, shape, scale_dim=None):
            fan_in = scale_dim if scale_dim is not None else (shape[-2] if len(shape) >= 3 else shape[0])
            return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(jnp.float32)

        embed = {"wte": dense(keys[0], (cfg.vocab_size, h), h)}
        if cfg.position_style == "learned":
            rows = cfg.max_position_embeddings + cfg.position_offset
            embed["wpe"] = dense(keys[1], (rows, h), h)
        attn = {"w_qkv": dense(keys[2], (L, h, 3 * h)), "wo": dense(keys[3], (L, h, h))}
        if cfg.attention_bias:
            attn["b_qkv"] = jnp.zeros((L, 3 * h), jnp.float32)
            attn["bo"] = jnp.zeros((L, h), jnp.float32)
        ln = lambda: {"scale": jnp.ones((L, h), jnp.float32), "bias": jnp.zeros((L, h), jnp.float32)}
        layers = {
            "attn": attn,
            "mlp": {
                "w_in": dense(keys[4], (L, h, inter)),
                "b_in": jnp.zeros((L, inter), jnp.float32),
                "w_out": dense(keys[5], (L, inter, h)),
                "b_out": jnp.zeros((L, h), jnp.float32),
            },
            "ln_1": ln(),
        }
        if not cfg.shared_layernorm:
            layers["ln_2"] = ln()
        params = {
            "embed": embed,
            "layers": layers,
            "ln_f": {"scale": jnp.ones((h,), jnp.float32), "bias": jnp.zeros((h,), jnp.float32)},
        }
        if not cfg.tie_word_embeddings:
            head = {"weight": dense(keys[6], (h, cfg.vocab_size))}
            if cfg.lm_head_bias:
                head["bias"] = jnp.zeros((cfg.vocab_size,), jnp.float32)
            params["lm_head"] = head
        return params

    # --------------------------------------------------------------- sharding
    def sharding_rules(self):
        """Fused QKV column-split on tp (GSPMD keeps the downstream split/head
        reshape correct for any layout); wo/w_out row-parallel; layer stack on
        pp — same scheme as ``GPT2.sharding_rules``."""
        return [
            (r"embed/wte", P("tp", "fsdp")),
            (r"embed/wpe", P(None, "fsdp")),
            (r"attn/w_qkv", P("pp", "fsdp", "tp")),
            (r"attn/b_qkv", P("pp", "tp")),
            (r"attn/wo", P("pp", "tp", "fsdp")),
            (r"attn/bo", P("pp")),
            (r"mlp/w_in", P("pp", "fsdp", "tp")),
            (r"mlp/b_in", P("pp", "tp")),
            (r"mlp/w_out", P("pp", "tp", "fsdp")),
            (r"mlp/b_out", P("pp")),
            (r"layers/ln_", P("pp")),
            (r"ln_f", P()),
            (r"lm_head/weight", P("fsdp", "tp")),
            (r"lm_head/bias", P("tp")),
        ]

    # ---------------------------------------------------------------- forward
    def embed(self, params, input_ids, positions=None, attention_mask=None):
        cfg = self.config
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        from ..parallel.sharding import embedding_lookup

        x = embedding_lookup(params["embed"]["wte"], input_ids)
        ctx = {"attention_mask": attention_mask}
        if cfg.position_style == "learned":
            if S > cfg.max_position_embeddings:
                raise ValueError(
                    f"sequence length {S} exceeds max_position_embeddings "
                    f"{cfg.max_position_embeddings}"
                )
            x = x + embedding_lookup(params["embed"]["wpe"], positions + cfg.position_offset)
        else:
            cos, sin = rope_tables(
                positions, cfg.rotary_dim, cfg.rope_theta, cfg.rope_scaling,
                max_position_embeddings=cfg.max_position_embeddings,
            )
            ctx["cos"], ctx["sin"] = cos, sin
        return x.astype(params["embed"]["wte"].dtype), ctx

    def _mm(self, a, b):
        from ..ops.int8 import matmul

        return matmul(a, b, precision=self.config.matmul_precision)

    def _rope(self, x, ctx):
        cfg = self.config
        if cfg.position_style == "learned":
            return x
        rot = apply_rope if cfg.position_style == "rotary_neox" else apply_rope_interleaved
        d = cfg.rotary_dim
        if d == cfg.head_dim:
            return rot(x, ctx["cos"], ctx["sin"])
        return jnp.concatenate([rot(x[..., :d], ctx["cos"], ctx["sin"]), x[..., d:]], axis=-1)

    def block(self, layer, x, ctx, cache_layer=None):
        cfg = self.config
        nh, hd = cfg.num_attention_heads, cfg.head_dim
        B, S, h = x.shape
        ln1 = _layer_norm(x, layer["ln_1"]["scale"], layer["ln_1"]["bias"], cfg.layer_norm_eps)
        a = layer["attn"]
        qkv = self._mm(ln1, a["w_qkv"])
        if "b_qkv" in a:
            qkv = qkv + a["b_qkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = self._rope(q.reshape(B, S, nh, hd), ctx)
        k = self._rope(k.reshape(B, S, nh, hd), ctx)
        v = v.reshape(B, S, nh, hd)
        new_cache = None
        if cache_layer is not None:
            from ..ops.attention import write_and_attend

            attn, new_cache = write_and_attend(q, k, v, cache_layer, ctx)
        else:
            attn = _attention(
                q, k, v, causal=True, mask=ctx["attention_mask"], impl=cfg.attention_impl
            )
        attn = self._mm(attn.reshape(B, S, h), layer["attn"]["wo"])
        if "bo" in layer["attn"]:
            attn = attn + layer["attn"]["bo"]
        act = _ACTS[cfg.hidden_act]
        if cfg.parallel_residual:
            # NeoX/GPT-J: both sub-blocks read the SAME input x, summed into one
            # residual add (GPT-J additionally shares ln_1's output).
            ln2 = ln1 if cfg.shared_layernorm else _layer_norm(
                x, layer["ln_2"]["scale"], layer["ln_2"]["bias"], cfg.layer_norm_eps
            )
            mid = act(self._mm(ln2, layer["mlp"]["w_in"]) + layer["mlp"]["b_in"])
            x = x + attn + self._mm(mid, layer["mlp"]["w_out"]) + layer["mlp"]["b_out"]
        else:
            x = x + attn
            ln2 = _layer_norm(x, layer["ln_2"]["scale"], layer["ln_2"]["bias"], cfg.layer_norm_eps)
            mid = act(self._mm(ln2, layer["mlp"]["w_in"]) + layer["mlp"]["b_in"])
            x = x + self._mm(mid, layer["mlp"]["w_out"]) + layer["mlp"]["b_out"]
        return x if new_cache is None else (x, new_cache)

    # Shared with GPT2/Llama: the head/loss contract the 1F1B schedule reads.
    _shift_labels = staticmethod(GPT2._shift_labels)

    def head(self, params, x, labels=None, attention_mask=None):
        cfg = self.config
        x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"], cfg.layer_norm_eps)
        if cfg.tie_word_embeddings:
            logits = x @ params["embed"]["wte"].T.astype(x.dtype)
        else:
            logits = x @ params["lm_head"]["weight"].astype(x.dtype)
            if "bias" in params["lm_head"]:
                logits = logits + params["lm_head"]["bias"].astype(logits.dtype)
        logits = logits.astype(jnp.float32)
        out = ModelOutput(logits=logits)
        if labels is not None:
            out["loss"] = cross_entropy_loss(logits, self._shift_labels(labels, attention_mask))
        return out

    # ------------------------------------------------------------------ cache
    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.config
        if cfg.position_style == "learned" and max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"cache length {max_len} exceeds max_position_embeddings "
                f"{cfg.max_position_embeddings}: the learned table cannot extend"
            )
        shape = (cfg.num_hidden_layers, batch_size, max_len, cfg.num_attention_heads, cfg.head_dim)
        return {
            "k": jnp.zeros(shape, dtype),
            "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32),
            "kv_mask": jnp.zeros((batch_size, max_len), jnp.int32),
        }

    def _apply_cached(self, params, input_ids, attention_mask, cache, labels=None,
                      positions=None):
        """``positions`` are *token* positions (rope angles / wpe rows); causal
        masking always uses cache slot indices — same split as Llama/GPT2."""
        B, S = input_ids.shape
        pos = cache["pos"]
        slot_positions = jnp.broadcast_to(
            pos + jnp.arange(S, dtype=jnp.int32)[None], (B, S)
        )
        token_positions = slot_positions if positions is None else positions
        view = cache.get("view")  # paged serving's read-only chains (Llama._apply_cached)
        if view is not None and positions is None:
            token_positions = slot_positions + view_capacity(view)
        chunk_mask = (
            attention_mask.astype(jnp.int32)
            if attention_mask is not None
            else jnp.ones((B, S), jnp.int32)
        )
        kv_mask = jax.lax.dynamic_update_slice(cache["kv_mask"], chunk_mask, (0, pos))
        x, ctx = self.embed(params, input_ids, token_positions, attention_mask)
        ctx["positions"] = slot_positions
        ctx["kv_mask"] = kv_mask
        ctx["cache_pos"] = pos
        if view is not None:
            ctx["prefix_mask"] = view["kv_mask"]

        def scan_step(x, inp):
            layer, ck, cv, pre = inp
            x, new = self.block(layer, x, ctx, cache_layer={"k": ck, "v": cv, "prefix": pre})
            return x, (new["k"], new["v"])

        x, (nk, nv) = jax.lax.scan(
            scan_step, x,
            (params["layers"], cache["k"], cache["v"],
             None if view is None else (view["k"], view["v"])),
        )
        out = self.head(params, x, labels=labels, attention_mask=attention_mask)
        out["cache"] = {"k": nk, "v": nv, "pos": pos + S, "kv_mask": kv_mask}
        return out

    def apply(
        self,
        params,
        input_ids=None,
        labels=None,
        attention_mask=None,
        positions=None,
        cache=None,
        train: bool = False,
        rngs=None,
        pipeline=None,
        **kwargs,
    ):
        cfg = self.config
        if cache is not None:
            return self._apply_cached(
                params, input_ids, attention_mask, cache, labels=labels, positions=positions
            )
        x, ctx = self.embed(params, input_ids, positions, attention_mask)
        if pipeline is not None:
            x, _aux = pipeline.run(self, params["layers"], x, ctx)
        else:
            body = lambda x, layer: self.block(layer, x, ctx)
            if cfg.remat:
                from ..utils.dataclasses import resolve_remat_policy

                policy = resolve_remat_policy(cfg.remat_policy, getattr(cfg, "remat_save_names", ()))
                body = jax.checkpoint(body, policy=policy)

            def scan_step(x, layer):
                return body(x, layer), None

            x, _ = jax.lax.scan(scan_step, x, params["layers"])
        return self.head(params, x, labels=labels, attention_mask=attention_mask)

    # -------------------------------------------------------------- estimation
    def num_params(self) -> int:
        cfg = self.config
        h, inter, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
        layer = 4 * h * h + h * inter * 2 + inter + h
        if cfg.attention_bias:
            layer += 4 * h
        layer += (2 if cfg.shared_layernorm else 4) * h
        total = L * layer + cfg.vocab_size * h + 2 * h
        if cfg.position_style == "learned":
            total += (cfg.max_position_embeddings + cfg.position_offset) * h
        if not cfg.tie_word_embeddings:
            total += h * cfg.vocab_size + (cfg.vocab_size if cfg.lm_head_bias else 0)
        return total

    def flops_per_token(self) -> float:
        cfg = self.config
        attn_extra = 12 * cfg.num_hidden_layers * cfg.hidden_size * cfg.max_position_embeddings
        return 6 * self.num_params() + attn_extra
