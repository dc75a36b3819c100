"""GPT-2-family decoder — learned positions, pre-LN blocks, tied LM head.

Fills the GPT slot of the reference's Megatron model trio (Bert/GPT/T5 train
steps, ``utils/megatron_lm.py:587``); the reference never defines the
architecture itself (it comes from transformers/Megatron). Same TPU-first
skeleton as ``Llama``: stacked-layer scan, stage protocol (embed/block/head)
for pipelined and layer-streamed execution, Megatron-style tp sharding rules,
remat, and the ``matmul_precision`` dispatch.
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


from ..ops.norms import layer_norm as _layer_norm


@dataclass
class GPT2Config:
    vocab_size: int = 50257
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 1024
    layer_norm_eps: float = 1e-5
    remat: bool = False
    remat_policy: str = "nothing_saveable"
    attention_impl: str = "auto"
    matmul_precision: str = "default"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

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


class GPT2(Module):
    # embed/block/head stage protocol — GPipe-eligible (parallel/pipeline.py).
    # (No scan_aux_keys: the GPT-2 block sows nothing; models that do must also
    # collect aux in their scan path as Llama does.)
    pipeline_capable = True

    def __init__(self, config: GPT2Config):
        self.config = config
        self.params = None

    # ------------------------------------------------------------------- init
    def init(self, rng, *example_inputs, **kwargs):
        cfg = self.config
        h, inter, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
        keys = jax.random.split(rng, 8)

        def dense(key, shape, scale_dim=None):
            # Stacked-layer weights are (L, fan_in, fan_out): the fan-in is the
            # second-to-last dim, not the layer count.
            fan_in = scale_dim if scale_dim is not None else (shape[-2] if len(shape) >= 3 else shape[0])
            scale = 1.0 / np.sqrt(fan_in)
            return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.float32)

        return {
            "embed": {
                "wte": dense(keys[0], (cfg.vocab_size, h), h),
                "wpe": dense(keys[1], (cfg.max_position_embeddings, h), h),
            },
            "layers": {
                "attn": {
                    "w_qkv": dense(keys[2], (L, h, 3 * h)),
                    "b_qkv": jnp.zeros((L, 3 * h), jnp.float32),
                    "wo": dense(keys[3], (L, h, h)),
                    "bo": jnp.zeros((L, h), jnp.float32),
                },
                "mlp": {
                    "w_in": dense(keys[4], (L, h, inter)),
                    "b_in": jnp.zeros((L, inter), jnp.float32),
                    "w_out": dense(keys[5], (L, inter, h)),
                    "b_out": jnp.zeros((L, h), jnp.float32),
                },
                "ln_1": {"scale": jnp.ones((L, h), jnp.float32), "bias": jnp.zeros((L, h), jnp.float32)},
                "ln_2": {"scale": jnp.ones((L, h), jnp.float32), "bias": jnp.zeros((L, h), jnp.float32)},
            },
            "ln_f": {"scale": jnp.ones((h,), jnp.float32), "bias": jnp.zeros((h,), jnp.float32)},
        }  # LM head tied to wte (GPT-2 convention)

    # --------------------------------------------------------------- sharding
    def sharding_rules(self):
        """Fused QKV is column-split on tp; under GSPMD the downstream
        ``jnp.split``/head reshape stays correct for any layout (the partitioner
        inserts any needed resharding — unlike Megatron's manual fused-QKV
        interleave requirement). wo/w_out are row-parallel; layer stack on pp."""
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
        ]

    # ---------------------------------------------------------------- forward
    def embed(self, params, input_ids, positions=None, attention_mask=None):
        B, S = input_ids.shape
        if S > self.config.max_position_embeddings:
            # Learned positions have a hard table limit; jnp.take would silently
            # clamp out-of-range rows to the last position otherwise.
            raise ValueError(
                f"sequence length {S} exceeds max_position_embeddings "
                f"{self.config.max_position_embeddings}"
            )
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        from ..parallel.sharding import embedding_lookup

        x = embedding_lookup(params["embed"]["wte"], input_ids) + embedding_lookup(
            params["embed"]["wpe"], positions
        )
        return x.astype(params["embed"]["wte"].dtype), {"attention_mask": attention_mask}

    def _mm(self, a, b):
        from ..ops.int8 import matmul

        return matmul(a, b, precision=self.config.matmul_precision)

    def block(self, layer, x, ctx, cache_layer=None):
        cfg = self.config
        nh, hd = cfg.num_attention_heads, cfg.head_dim
        B, S, h = x.shape
        ln1 = _layer_norm(x, layer["ln_1"]["scale"], layer["ln_1"]["bias"], cfg.layer_norm_eps)
        qkv = self._mm(ln1, layer["attn"]["w_qkv"]) + layer["attn"]["b_qkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, S, nh, hd)
        k = k.reshape(B, S, nh, hd)
        v = v.reshape(B, S, nh, hd)
        new_cache = None
        if cache_layer is not None:
            from ..ops.attention import write_and_attend

            attn, new_cache = write_and_attend(q, k, v, cache_layer, ctx)
        else:
            attn = _attention(
                q, k, v, causal=True, mask=ctx["attention_mask"], impl=cfg.attention_impl
            )
        x = x + self._mm(attn.reshape(B, S, h), layer["attn"]["wo"]) + layer["attn"]["bo"]
        ln2 = _layer_norm(x, layer["ln_2"]["scale"], layer["ln_2"]["bias"], cfg.layer_norm_eps)
        mid = jax.nn.gelu(self._mm(ln2, layer["mlp"]["w_in"]) + layer["mlp"]["b_in"], approximate=True)
        x = x + self._mm(mid, layer["mlp"]["w_out"]) + layer["mlp"]["b_out"]
        return x if new_cache is None else (x, new_cache)

    @staticmethod
    def _shift_labels(labels, attention_mask):
        """Next-token targets with the padding guards — same contract as
        ``Llama._shift_labels`` (the 1F1B pipeline reads this to renormalize
        per-microbatch losses, so head and schedule share one definition)."""
        B = labels.shape[0]
        shifted = jnp.concatenate(
            [labels[:, 1:], jnp.full((B, 1), -100, labels.dtype)], axis=1
        )
        if attention_mask is not None:
            # A position trains only if it is itself real (left-padding
            # guard) AND its target token t+1 is real (right-padding guard).
            target_valid = jnp.concatenate(
                [attention_mask[:, 1:], jnp.zeros((B, 1), attention_mask.dtype)], axis=1
            )
            valid = target_valid.astype(bool) & attention_mask.astype(bool)
            shifted = jnp.where(valid, shifted, -100)
        return shifted

    def head(self, params, x, labels=None, attention_mask=None):
        cfg = self.config
        x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"], cfg.layer_norm_eps)
        logits = (x @ params["embed"]["wte"].T.astype(x.dtype)).astype(jnp.float32)
        out = ModelOutput(logits=logits)
        if labels is not None:
            out["loss"] = cross_entropy_loss(logits, self._shift_labels(labels, attention_mask))
        return out

    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        """Pre-allocated decode cache (same layout/contract as Llama's)."""
        cfg = self.config
        if max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"cache length {max_len} exceeds max_position_embeddings "
                f"{cfg.max_position_embeddings}: learned positions cannot extend "
                "past the table (decode steps would silently reuse the last row)"
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
        """``positions`` (optional) are the *token* positions for the learned
        ``wpe`` lookup — essential for ragged batches, where the cache slot
        index ≠ the token's real position (VERDICT r2 #6). Causal masking
        always uses slot indices."""
        B, S = input_ids.shape
        pos = cache["pos"]
        slot_positions = pos + jnp.arange(S, dtype=jnp.int32)[None]
        slot_positions = jnp.broadcast_to(slot_positions, (B, S))
        wpe_positions = slot_positions if positions is None else positions
        view = cache.get("view")  # paged serving's read-only chains (Llama._apply_cached)
        if view is not None and positions is None:
            wpe_positions = slot_positions + view_capacity(view)
        chunk_mask = (
            attention_mask.astype(jnp.int32)
            if attention_mask is not None
            else jnp.ones((B, S), jnp.int32)
        )
        kv_mask = jax.lax.dynamic_update_slice(cache["kv_mask"], chunk_mask, (0, pos))
        x, ctx = self.embed(params, input_ids, wpe_positions, attention_mask)
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
        layer = 3 * h * h + 3 * h + h * h + h + 2 * h * inter + inter + h + 4 * h
        return L * layer + cfg.vocab_size * h + cfg.max_position_embeddings * h + 2 * h
