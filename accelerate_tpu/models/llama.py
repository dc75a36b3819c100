"""Llama-family decoder — the flagship model (BASELINE.json fsdp2 target).

Designed TPU-first rather than translated:

- **scan over stacked layers**: all per-layer weights carry a leading ``L`` dim and
  the block runs under ``jax.lax.scan`` — one compilation of one block instead of
  ``L`` inlined copies (fast compiles, and the natural substrate for pipeline
  parallelism later).
- **MXU-shaped matmuls**: weights stored (in_dim, out_dim) so every projection is
  a single ``x @ W``; attention uses one fused einsum per score/mix; all compute
  in bf16 under mixed precision with fp32 softmax/logits.
- **GQA**: ``n_kv_heads <= n_heads`` with repeated KV — matches Llama-2/3 shapes.
- **remat**: optional ``jax.checkpoint`` around each scanned block trades FLOPs
  for HBM (the reference delegates this to torch's activation checkpointing,
  ``accelerator.py:1698-1712``).
- **sharding rules**: Megatron-style tp (column-parallel QKV/up, row-parallel
  O/down), fsdp on the complementary dim, seq axis ``sp`` for long context.

Reference context: the reference trains Llama through FSDP2 wrappers
(``benchmarks/fsdp2/main.py``), never defining the model itself (it comes from
transformers). Here the model is part of the framework so the full stack —
kernels to collectives — is TPU-native.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax.ad_checkpoint import checkpoint_name

from ..modules import ModelOutput, Module
from ..ops.losses import cross_entropy_loss
from ..ops.paged_attention import view_capacity
from ..utils.dataclasses import resolve_remat_policy


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 2048
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    remat: bool = False
    remat_policy: str = "nothing_saveable"  # any jax.checkpoint_policies name
    attention_impl: str = "auto"  # 'auto' | 'dense' | 'flash' | 'ring' | 'ulysses'
    matmul_precision: str = "default"  # 'default' | 'int8' (QAT w/ STE bwd, ops/int8.py)
    # QKV projection biases (the Qwen2 recipe; Llama proper is bias-free).
    attention_bias: bool = False
    # Per-head RMSNorm on Q and K after the head reshape, before rope — the
    # Qwen3 recipe (weights are head_dim-wide, shared across heads).
    qk_norm: bool = False
    # Sliding-window attention (the Mistral recipe): each query attends only
    # the previous `sliding_window` positions. None = full causal.
    sliding_window: int | None = None
    # RoPE scaling for long-context checkpoints: None, or a dict with
    # rope_type 'linear' (positions/factor) or 'llama3' (frequency-banded
    # scaling, the Llama-3.1 recipe). Matches the HF config field.
    rope_scaling: dict | None = None
    # Per-head width; None = hidden/heads. Gemma decouples it (e.g. 2048/8
    # hidden/heads with 256-wide heads).
    head_dim: int | None = None
    # FFN activation: 'silu' (SwiGLU, the Llama recipe) or 'gelu_tanh'
    # (GeGLU, the Gemma recipe).
    hidden_act: str = "silu"
    # Embedding-lookup scale (Gemma multiplies by sqrt(hidden)); the tied LM
    # head is NOT scaled, so this cannot be baked into the table.
    embedding_multiplier: float = 1.0
    # Per-layer window sizes (None entry = full attention) for models mixing
    # attention regimes across depth: Gemma-2 alternates local/global, Qwen2
    # windows only layers >= max_window_layers. None = uniform sliding_window.
    # The layer scan splits into segments per regime (see _attention_segments).
    layer_windows: tuple | None = None
    # Gemma-2 score shaping: tanh softcap on attention scores / final logits,
    # and a query scaling override (query_pre_attn_scalar ** -0.5 instead of
    # head_dim ** -0.5).
    attn_logit_softcap: float | None = None
    final_logit_softcap: float | None = None
    query_pre_attn_scalar: float | None = None
    # Gemma-2 sandwich norms: post-attention and post-feedforward RMSNorms on
    # each sub-block's OUTPUT (before the residual add), with a separate
    # pre-feedforward norm — four norms per layer instead of two.
    sandwich_norms: bool = False
    # Compute the training loss by vocab-chunked streaming logsumexp straight
    # from hidden states (ops/losses.fused_cross_entropy_loss): the (B·S, V)
    # fp32 logit tensor never materializes. Training-memory lever for large
    # vocab x long context; outputs carry loss but NO logits when it engages.
    # The companion knobs are the vocab128k tuning surface (swept by
    # benchmarks/vocab128k_profile.py; ACCELERATE_FUSED_LOSS_* envs override
    # per-run without touching the config).
    fused_loss: bool = False
    fused_loss_chunk: int = 8192  # vocab tile per scan step
    fused_loss_dtype: str = "fp32"  # 'fp32' | 'bf16' (bf16 chunk exp, fp32 accum)
    fused_loss_unroll: int = 1  # chunk-scan unroll factor; 0 = fully unrolled
    fused_loss_backward: str = "custom"  # 'custom' (single-pass VJP) | 'ad'
    # Intermediates saved under remat_policy='names_saveable' — must be a
    # subset of the checkpoint_name tags the block plants ('attn_out',
    # 'mlp_out'). Saving only the residual-stream contributions costs 2·(B,S,h)
    # per layer where dots-saveable keeps every projection (q/k/v/gate/up ≈
    # (3h + 2·intermediate)·B·S) — the policy for shapes like h2048/i8192
    # where the MLP dots alone exceed the HBM the policy was meant to save.
    remat_save_names: tuple = ("attn_out", "mlp_out")

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.hidden_act not in ("silu", "gelu_tanh"):
            raise ValueError(f"hidden_act must be silu|gelu_tanh, got {self.hidden_act!r}")
        if self.fused_loss_chunk <= 0:
            raise ValueError(f"fused_loss_chunk must be > 0, got {self.fused_loss_chunk}")
        if self.fused_loss_dtype not in ("fp32", "bf16"):
            raise ValueError(
                f"fused_loss_dtype must be fp32|bf16, got {self.fused_loss_dtype!r}"
            )
        if self.fused_loss_unroll < 0:
            raise ValueError(
                f"fused_loss_unroll must be >= 0, got {self.fused_loss_unroll}"
            )
        if self.fused_loss_backward not in ("custom", "ad"):
            raise ValueError(
                f"fused_loss_backward must be custom|ad, got {self.fused_loss_backward!r}"
            )
        self.remat_save_names = tuple(self.remat_save_names)
        if self.layer_windows is not None:
            self.layer_windows = tuple(self.layer_windows)
            if len(self.layer_windows) != self.num_hidden_layers:
                raise ValueError(
                    f"layer_windows has {len(self.layer_windows)} entries for "
                    f"{self.num_hidden_layers} layers"
                )
            if len(set(self.layer_windows)) == 1:
                # Uniform per-layer windows ARE the plain sliding_window — fold
                # them so every consumer that reads only sliding_window (the
                # pipeline's stage scan, the sp guard) sees the truth.
                self.sliding_window = self.layer_windows[0]
                self.layer_windows = None

    @classmethod
    def tiny(cls, **kw):
        defaults = dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            max_position_embeddings=128,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def llama2_7b(cls, **kw):
        return cls(**{**dict(), **kw})

    @classmethod
    def llama3_8b(cls, **kw):
        defaults = dict(
            vocab_size=128256,
            hidden_size=4096,
            intermediate_size=14336,
            num_hidden_layers=32,
            num_attention_heads=32,
            num_key_value_heads=8,
            rope_theta=500000.0,
            max_position_embeddings=8192,
        )
        defaults.update(kw)
        return cls(**defaults)


def _fused_loss_overrides(cfg) -> dict:
    """Fused-loss tuning knobs with per-run env overrides — the sweep surface
    (``ACCELERATE_FUSED_LOSS_{CHUNK,DTYPE,UNROLL,BACKWARD}``) used by bench.py
    and benchmarks/vocab128k_profile.py without touching the config object."""
    chunk = int(os.environ.get("ACCELERATE_FUSED_LOSS_CHUNK", "0") or 0)
    unroll = os.environ.get("ACCELERATE_FUSED_LOSS_UNROLL", "")
    return {
        "vocab_chunk": chunk if chunk > 0 else cfg.fused_loss_chunk,
        "chunk_dtype": os.environ.get("ACCELERATE_FUSED_LOSS_DTYPE", "") or cfg.fused_loss_dtype,
        "unroll": int(unroll) if unroll else cfg.fused_loss_unroll,
        "custom_backward": (
            os.environ.get("ACCELERATE_FUSED_LOSS_BACKWARD", "") or cfg.fused_loss_backward
        ) == "custom",
    }


def rms_norm(x, weight, eps):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * weight).astype(dtype)


SUPPORTED_ROPE_TYPES = ("default", "linear", "llama3", "yarn", "dynamic")


def _llama3_scale_inv_freq(inv_freq, scaling: dict):
    """Llama-3.1 frequency-banded RoPE scaling (the public llama3 recipe, as in
    transformers' Llama3RotaryEmbedding): low-frequency components are divided
    by ``factor``, high-frequency kept, the band between smoothly interpolated."""
    factor = scaling.get("factor", 8.0)
    low = scaling.get("low_freq_factor", 1.0)
    high = scaling.get("high_freq_factor", 4.0)
    original_max = scaling.get("original_max_position_embeddings", 8192)

    wavelen = 2.0 * np.pi / inv_freq
    low_freq_wavelen = original_max / low
    high_freq_wavelen = original_max / high
    smooth = (original_max / wavelen - low) / (high - low)
    smoothed = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
    scaled = np.where(wavelen > low_freq_wavelen, inv_freq / factor, inv_freq)
    is_medium = (wavelen >= high_freq_wavelen) & (wavelen <= low_freq_wavelen)
    return np.where(is_medium, smoothed, scaled).astype(np.float32)


def _yarn_inv_freq(head_dim, theta, scaling: dict):
    """YaRN frequency blending (the public recipe, as in transformers'
    ``_compute_yarn_parameters``): low-frequency components interpolate
    (divide by ``factor``), high-frequency extrapolate (unchanged), with a
    linear ramp between the correction dims derived from beta_fast/beta_slow.
    Returns ``(inv_freq, attention_factor)`` — the factor scales cos/sin."""
    import math

    dim = head_dim
    factor = float(scaling.get("factor", 1.0))
    original_max = scaling.get("original_max_position_embeddings") or scaling.get(
        "max_position_embeddings", 4096
    )
    beta_fast = scaling.get("beta_fast") or 32
    beta_slow = scaling.get("beta_slow") or 1

    attention_factor = scaling.get("attention_factor")
    mscale, mscale_all = scaling.get("mscale"), scaling.get("mscale_all_dim")

    def get_mscale(scale, m=1):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    if attention_factor is None:
        if mscale and mscale_all:
            attention_factor = get_mscale(factor, mscale) / get_mscale(factor, mscale_all)
        else:
            attention_factor = get_mscale(factor)

    def correction_dim(num_rot):
        return (dim * math.log(original_max / (num_rot * 2 * math.pi))) / (2 * math.log(theta))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if scaling.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001

    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    extrapolation_factor = 1.0 - ramp
    inv_freq = interpolation * (1 - extrapolation_factor) + extrapolation * extrapolation_factor
    return inv_freq.astype(np.float32), float(attention_factor)


def rope_tables(positions, head_dim, theta, scaling: dict | None = None,
                seq_len: int | None = None, max_position_embeddings: int | None = None):
    """cos/sin tables for rotary embeddings, fp32. positions: (B, S) int.

    ``seq_len``/``max_position_embeddings`` feed the ``dynamic`` (NTK-aware)
    rope type, whose base stretches when the (static) forward length exceeds
    the pretraining window; shorter forwards use the unmodified base — the
    transformers semantic for a single forward pass. During cached decode the
    chunk length is 1, so frequencies stay fixed (consistent with the cache)."""
    attention_factor = 1.0
    if scaling:
        rope_type = scaling.get("rope_type", scaling.get("type", "default"))
    else:
        rope_type = "default"
    if rope_type == "dynamic" and scaling:
        max_pos = max_position_embeddings or scaling.get("max_position_embeddings", 2048)
        eff = max(seq_len or max_pos, max_pos)
        factor = float(scaling.get("factor", 1.0))
        dim = head_dim
        theta = theta * ((factor * eff / max_pos) - (factor - 1)) ** (dim / (dim - 2))
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    if scaling:
        if rope_type == "linear":
            inv_freq = inv_freq / float(scaling.get("factor", 1.0))
        elif rope_type == "llama3":
            inv_freq = _llama3_scale_inv_freq(inv_freq, scaling)
        elif rope_type == "yarn":
            if "original_max_position_embeddings" not in scaling and max_position_embeddings:
                scaling = {**scaling, "max_position_embeddings": max_position_embeddings}
            inv_freq, attention_factor = _yarn_inv_freq(head_dim, theta, scaling)
        elif rope_type not in (None, "default", "dynamic"):
            raise ValueError(
                f"Unsupported rope_type {rope_type!r} (supported: {SUPPORTED_ROPE_TYPES})"
            )
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (B,S,D/2)
    return jnp.cos(angles) * attention_factor, jnp.sin(angles) * attention_factor


def apply_rope(x, cos, sin):
    """x: (B, S, H, D). Rotate pairs (even, odd) halves interleaved as
    [:D/2], [D/2:] (Llama convention)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


from ..ops.attention import attention as _attention


class Llama(Module):
    # Stage protocol (embed/block/head with a context-dict block) — eligible
    # for the GPipe training schedule (parallel/pipeline.py) when pp > 1.
    pipeline_capable = True
    # Context keys a block sows per layer that must surface as scan outputs
    # (MoE router aux loss); empty for the dense model.
    scan_aux_keys: tuple = ()

    def aux_loss_coefs(self) -> dict:
        """How each ``scan_aux_keys`` entry enters the total loss (coefficient
        per key). The 1F1B pipeline schedule reads this to seed the aux-loss
        gradients inside the schedule — it must agree with ``finalize_aux``."""
        return {}

    def __init__(self, config: LlamaConfig):
        self.config = config
        self.params = None

    # ------------------------------------------------------------------- init
    def init(self, rng, *example_inputs, **kwargs):
        cfg = self.config
        h, inter = cfg.hidden_size, cfg.intermediate_size
        hd = cfg.head_dim
        nh, nkv, L = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.num_hidden_layers
        keys = jax.random.split(rng, 10)

        def dense(key, shape, scale_dim=None):
            # Stacked-layer weights are (L, fan_in, fan_out): the fan-in is the
            # second-to-last dim, not the layer count.
            fan_in = scale_dim if scale_dim is not None else (shape[-2] if len(shape) >= 3 else shape[0])
            scale = 1.0 / np.sqrt(fan_in)
            return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.float32)

        params = {
            "embed": {"weight": dense(keys[0], (cfg.vocab_size, h), h)},
            "layers": {
                "attn": {
                    "wq": dense(keys[1], (L, h, nh * hd)),
                    "wk": dense(keys[2], (L, h, nkv * hd)),
                    "wv": dense(keys[3], (L, h, nkv * hd)),
                    "wo": dense(keys[4], (L, nh * hd, h)),
                    **(
                        {
                            "bq": jnp.zeros((L, nh * hd), jnp.float32),
                            "bk": jnp.zeros((L, nkv * hd), jnp.float32),
                            "bv": jnp.zeros((L, nkv * hd), jnp.float32),
                        }
                        if cfg.attention_bias
                        else {}
                    ),
                    **(
                        {
                            "q_norm": jnp.ones((L, hd), jnp.float32),
                            "k_norm": jnp.ones((L, hd), jnp.float32),
                        }
                        if cfg.qk_norm
                        else {}
                    ),
                },
                "mlp": {
                    "w_gate": dense(keys[5], (L, h, inter)),
                    "w_up": dense(keys[6], (L, h, inter)),
                    "w_down": dense(keys[7], (L, inter, h)),
                },
                "input_norm": {"weight": jnp.ones((L, h), jnp.float32)},
                "post_attn_norm": {"weight": jnp.ones((L, h), jnp.float32)},
                **(
                    {
                        "pre_ffw_norm": {"weight": jnp.ones((L, h), jnp.float32)},
                        "post_ffw_norm": {"weight": jnp.ones((L, h), jnp.float32)},
                    }
                    if cfg.sandwich_norms
                    else {}
                ),
            },
            "final_norm": {"weight": jnp.ones((h,), jnp.float32)},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"weight": dense(keys[8], (h, cfg.vocab_size))}
        return params

    # --------------------------------------------------------------- sharding
    def sharding_rules(self):
        """Megatron-style tp + complementary fsdp + pipeline stages.

        The leading scan (layer-stack) dim is sharded on ``pp``: each pipeline
        stage owns a contiguous block of layers (GSPMD inserts the stage-to-stage
        transfers as the scan crosses shard boundaries). With ``pp=1`` the axis
        is trivial and the spec degenerates to unsharded — one rule set serves
        every mesh. Per-layer norm scales ride the same ``pp`` placement.
        """
        return [
            (r"embed/weight", P("tp", "fsdp")),
            (r"attn/w[qkv]", P("pp", "fsdp", "tp")),
            (r"attn/b[qkv]", P("pp", "tp")),
            (r"attn/wo", P("pp", "tp", "fsdp")),
            (r"mlp/w_(gate|up)", P("pp", "fsdp", "tp")),
            (r"mlp/w_down", P("pp", "tp", "fsdp")),
            (r"layers/.*norm", P("pp")),
            (r"norm", P()),
            (r"lm_head/weight", P("fsdp", "tp")),
        ]

    # ---------------------------------------------------------------- forward
    # The forward is decomposed into embed/block/head so the same code paths serve
    # the fused scan (training) and the layer-streamed offloaded-inference runtime
    # (``big_modeling.StreamedScanModel`` runs ``block`` once per layer with weights
    # DMA'd in just-in-time).
    def embed(self, params, input_ids, positions=None, attention_mask=None,
              rope_seq_len=None):
        """Token embedding + rotary tables. Returns (hidden, ctx).

        ``rope_seq_len`` overrides the effective length fed to length-dependent
        rope types (dynamic NTK): the cached decode path pins it to the cache
        capacity so every chunk — prefill and single-token steps alike — is
        rotated with ONE consistent set of frequencies."""
        cfg = self.config
        B, S = input_ids.shape
        from ..parallel.sharding import embedding_lookup

        with jax.named_scope("embed"):
            x = embedding_lookup(params["embed"]["weight"], input_ids)
            x = x.astype(params["embed"]["weight"].dtype)
            if cfg.embedding_multiplier != 1.0:
                # Gemma scales the lookup only — the tied head stays unscaled.
                x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
            cos, sin = rope_tables(
                positions, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling,
                seq_len=rope_seq_len if rope_seq_len is not None else S,
                max_position_embeddings=cfg.max_position_embeddings,
            )
        return x, {"cos": cos, "sin": sin, "attention_mask": attention_mask}

    _WINDOW_FROM_CONFIG = object()  # sentinel: use cfg.sliding_window

    def block(self, layer, x, ctx, cache_layer=None, window=_WINDOW_FROM_CONFIG):
        """One decoder layer on the residual stream (runs under scan or streamed).

        With ``cache_layer`` (``{"k","v"}`` of shape (B, K, n_kv, D) plus
        ``ctx["cache_pos"]``) the layer writes this chunk's K/V into the cache at
        the write offset and attends against the whole cache — the incremental
        decoding path (reference counterpart: transformers' KV cache driven by
        the big_model_inference benchmark,
        ``benchmarks/big_model_inference/big_model_inference.py``). Returns
        ``(x, new_cache_layer)`` in that mode.

        ``window`` is the per-layer attention window (static); the default
        sentinel reads the uniform config value — the segmented layer driver
        (``_run_layers``) passes each segment's own window for mixed-regime
        models (Gemma-2, Qwen2 max_window_layers).
        """
        cfg = self.config
        if window is Llama._WINDOW_FROM_CONFIG:
            window = cfg.sliding_window
        nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        B, S, _ = x.shape
        cos, sin = ctx["cos"], ctx["sin"]
        scale = (
            cfg.query_pre_attn_scalar ** -0.5
            if cfg.query_pre_attn_scalar is not None
            else None
        )
        with jax.named_scope("attn"):
            h = rms_norm(x, layer["input_norm"]["weight"], cfg.rms_norm_eps)
            a = layer["attn"]
            q = self._mm(h, a["wq"])
            k = self._mm(h, a["wk"])
            v = self._mm(h, a["wv"])
            if "bq" in a:  # Qwen2-style QKV biases (static pytree structure)
                q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
            q = q.reshape(B, S, nh, hd)
            k = k.reshape(B, S, nkv, hd)
            v = v.reshape(B, S, nkv, hd)
            if "q_norm" in a:  # Qwen3 per-head QK norm (static pytree structure)
                q = rms_norm(q, a["q_norm"], cfg.rms_norm_eps)
                k = rms_norm(k, a["k_norm"], cfg.rms_norm_eps)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            new_cache = None
            if cache_layer is not None:
                from ..ops.attention import write_and_attend

                attn_out, new_cache = write_and_attend(
                    q, k, v, cache_layer, ctx, window=window,
                    softcap=cfg.attn_logit_softcap, scale=scale,
                )
            else:
                if nkv != nh:
                    rep = nh // nkv
                    k = jnp.repeat(k, rep, axis=2)
                    v = jnp.repeat(v, rep, axis=2)
                attn_out = _attention(
                    q, k, v, causal=True, mask=ctx["attention_mask"],
                    impl=cfg.attention_impl, window=window,
                    softcap=cfg.attn_logit_softcap, scale=scale,
                )
            attn_out = self._mm(attn_out.reshape(B, S, nh * hd), layer["attn"]["wo"])
            attn_out = checkpoint_name(attn_out, "attn_out")
        if cfg.sandwich_norms:
            # Gemma-2: norm each sub-block's OUTPUT before the residual add.
            x = x + rms_norm(attn_out, layer["post_attn_norm"]["weight"], cfg.rms_norm_eps)
            h2 = rms_norm(x, layer["pre_ffw_norm"]["weight"], cfg.rms_norm_eps)
            m = self.mlp(layer, h2, ctx)
            x = x + rms_norm(m, layer["post_ffw_norm"]["weight"], cfg.rms_norm_eps)
        else:
            x = x + attn_out
            h2 = rms_norm(x, layer["post_attn_norm"]["weight"], cfg.rms_norm_eps)
            x = x + self.mlp(layer, h2, ctx)
        return x if new_cache is None else (x, new_cache)

    def mlp(self, layer, h2, ctx=None):
        """SwiGLU FFN on the normed residual. The MoE variant overrides this and
        sows its router aux loss into ``ctx`` (per-call dict, so no state leaks
        across traces)."""
        act = (
            jax.nn.silu
            if self.config.hidden_act == "silu"
            else lambda x: jax.nn.gelu(x, approximate=True)
        )
        with jax.named_scope("mlp"):
            gated = act(self._mm(h2, layer["mlp"]["w_gate"])) * self._mm(h2, layer["mlp"]["w_up"])
            return checkpoint_name(self._mm(gated, layer["mlp"]["w_down"]), "mlp_out")

    def _mm(self, a, b):
        """Block matmul through the precision dispatcher (ops/int8.py). The
        embedding and LM head stay exact — the usual QAT skip list."""
        from ..ops.int8 import matmul

        return matmul(a, b, precision=self.config.matmul_precision)

    @staticmethod
    def _shift_labels(labels, attention_mask):
        """Next-token targets: predict t+1 from t; final position untargeted.
        A position trains only if it is itself real (left-padding guard) AND
        its target token t+1 is real (right-padding guard)."""
        B = labels.shape[0]
        shifted = jnp.concatenate(
            [labels[:, 1:], jnp.full((B, 1), -100, labels.dtype)], axis=1
        )
        if attention_mask is not None:
            target_valid = jnp.concatenate(
                [attention_mask[:, 1:], jnp.zeros((B, 1), attention_mask.dtype)], axis=1
            )
            valid = target_valid.astype(bool) & attention_mask.astype(bool)
            shifted = jnp.where(valid, shifted, -100)
        return shifted

    def head(self, params, x, labels=None, attention_mask=None):
        """Final norm + LM head (+ shifted-label loss).

        The tied head keeps the embed table in its native (V, h) layout all
        the way into the matmul/fused loss: the old ``.T`` materialized a
        transposed copy of the table every step (~0.5 GB at V=128k bf16)
        whose cast/transpose gradient ops no dot-oriented remat policy could
        name."""
        cfg = self.config
        with jax.named_scope("lm_head"):
            x = rms_norm(x, params["final_norm"]["weight"], cfg.rms_norm_eps)
            if cfg.tie_word_embeddings:
                head_w = params["embed"]["weight"].astype(x.dtype)  # (V, h)
            else:
                head_w = params["lm_head"]["weight"]  # (h, V)
            if labels is not None and cfg.fused_loss:
                # Streaming-logsumexp loss from hidden states: the full logit
                # tensor never exists (see LlamaConfig.fused_loss).
                from ..ops.losses import fused_cross_entropy_loss

                knobs = _fused_loss_overrides(cfg)
                loss = fused_cross_entropy_loss(
                    x, head_w, self._shift_labels(labels, attention_mask),
                    logit_cap=cfg.final_logit_softcap,
                    head_transposed=cfg.tie_word_embeddings,
                    **knobs,
                )
                return ModelOutput(loss=loss)
            if cfg.tie_word_embeddings:
                logits = jax.lax.dot_general(x, head_w, (((2,), (1,)), ((), ())))
            else:
                logits = x @ head_w
            if cfg.final_logit_softcap is not None:
                from ..ops.attention import softcap_scores

                logits = softcap_scores(logits.astype(jnp.float32), cfg.final_logit_softcap)
            out = ModelOutput(logits=logits)
            if labels is not None:
                out["loss"] = cross_entropy_loss(
                    logits, self._shift_labels(labels, attention_mask)
                )
            return out

    # ------------------------------------------------------------------ cache
    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        """Pre-allocated decode cache: static shapes so every decode step hits
        the same compiled program. K/V stacked over layers to ride the same
        ``lax.scan`` as training. ``kv_mask`` tracks which slots hold real
        tokens (padding-aware); ``pos`` is the write offset."""
        cfg = self.config
        shape = (cfg.num_hidden_layers, batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim)
        return {
            "k": jnp.zeros(shape, dtype),
            "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32),
            "kv_mask": jnp.zeros((batch_size, max_len), jnp.int32),
        }

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
        aux_keys = tuple(self.scan_aux_keys)

        if pipeline is not None:
            # GPipe schedule over the pp mesh axis: stationary stage weights,
            # ppermuted activations (parallel/pipeline.py).
            x, aux = pipeline.run(self, params["layers"], x, ctx)
        else:
            x, aux = self._run_layers(params["layers"], x, ctx, aux_keys)
        out = self.head(params, x, labels=labels, attention_mask=attention_mask)
        return self.finalize_aux(out, aux)

    # --------------------------------------------------------- layer driver
    def _attention_segments(self):
        """Split the layer stack into scan segments by attention regime.

        Returns ``[(start, length, pattern)]`` where ``pattern`` is the tuple
        of per-layer windows the segment's scan body unrolls (length divisible
        by ``len(pattern)``). Uniform models are one segment with a period-1
        pattern — exactly the classic single scan. Gemma-2's alternating
        local/global folds into one scan over layer PAIRS (period 2), keeping
        compile time at one body; Qwen2's ``max_window_layers`` split yields
        two runs (VERDICT r2 #5).
        """
        cfg = self.config
        ws = cfg.layer_windows
        if ws is None:
            return [(0, cfg.num_hidden_layers, (cfg.sliding_window,))]
        from ..parallel.pipeline import _window_segments

        return _window_segments(ws)

    def _run_layers(self, stacked, x, ctx, aux_keys=()):
        """Run the stacked layers through per-regime scan segments; returns
        ``(x, aux_dict)`` with each aux key's mean over layers."""
        cfg = self.config
        L = cfg.num_hidden_layers
        aux_sums = {k: jnp.zeros((), jnp.float32) for k in aux_keys}

        for seg_start, seg_len, pattern in self._attention_segments():
            p = len(pattern)
            seg = stacked
            if not (seg_start == 0 and seg_len == L):
                seg = jax.tree_util.tree_map(
                    lambda t: jax.lax.slice_in_dim(t, seg_start, seg_start + seg_len), stacked
                )
            if p > 1:
                seg = jax.tree_util.tree_map(
                    lambda t: t.reshape(seg_len // p, p, *t.shape[1:]), seg
                )

            def scan_step(x, group, _pattern=pattern, _p=p):
                auxes = []
                for j in range(_p):
                    layer = (
                        jax.tree_util.tree_map(lambda t: t[j], group) if _p > 1 else group
                    )
                    ctx_call = dict(ctx) if aux_keys else ctx
                    x = self.block(layer, x, ctx_call, window=_pattern[j])
                    # Sown aux must become a real scan output *inside* any
                    # checkpoint boundary (no tracer leak across remat).
                    auxes.append(tuple(ctx_call.pop(k) for k in aux_keys))
                return x, auxes

            if cfg.remat:
                policy = resolve_remat_policy(cfg.remat_policy, cfg.remat_save_names)
                scan_step = jax.checkpoint(scan_step, policy=policy)

            x, aux_stack = jax.lax.scan(scan_step, x, seg)
            for j in range(p):
                for i, k in enumerate(aux_keys):
                    aux_sums[k] = aux_sums[k] + jnp.sum(aux_stack[j][i])
        return x, {k: v / L for k, v in aux_sums.items()}

    def finalize_aux(self, out, aux: dict):
        """Fold per-layer scan aux (``scan_aux_keys``) into the output; the
        dense model has none. MoE adds the router loss here so the dense and
        pipelined forwards share one seam."""
        return out

    def _apply_cached(self, params, input_ids, attention_mask, cache, labels=None,
                      positions=None):
        """Prefill/decode forward through the KV cache. The chunk is written at
        ``cache['pos']``; the output carries the advanced cache.

        ``positions`` (optional, (B,S)) are the *token* positions used for
        RoPE; causal masking always uses the cache *slot* indices. For RoPE a
        per-row constant offset between the two cancels, but ragged batches
        give absolute-position models (GPT-2 wpe) mask-derived token positions
        through this split (VERDICT r2 #6).

        A cache with a ``"view"`` (``{"k", "v", "kv_mask"}``, the paged
        engine's gathered chains) is two-part: the view's columns precede
        column 0 of ``cache["k"]``, every layer attends both and writes only
        ``cache["k"]``/``["v"]`` — the view is an input of the layer scan that
        is never returned, so nothing of its size is copied. The returned
        cache is the advanced write window alone; the view stays the caller's."""
        B, S = input_ids.shape
        pos = cache["pos"]
        view = cache.get("view")
        capacity = cache["k"].shape[2]
        slot_positions = pos + jnp.arange(S, dtype=jnp.int32)[None]
        slot_positions = jnp.broadcast_to(slot_positions, (B, S))
        rope_positions = slot_positions if positions is None else positions
        if view is not None:
            # The table's width, not this view's: the engine gathers a decode
            # window's view at one of a few widths (ops/paged_attention.py).
            capacity += view_capacity(view)
            if positions is None:
                rope_positions = slot_positions + view_capacity(view)
        chunk_mask = (
            attention_mask.astype(jnp.int32)
            if attention_mask is not None
            else jnp.ones((B, S), jnp.int32)
        )
        kv_mask = jax.lax.dynamic_update_slice(cache["kv_mask"], chunk_mask, (0, pos))

        # Length-dependent rope (dynamic NTK) must see ONE length for the whole
        # generation — the static cache capacity — or a decode chunk (S=1)
        # would be rotated with the unstretched base while the prefilled keys
        # used the stretched one (advisor r3 finding).
        x, ctx = self.embed(
            params, input_ids, rope_positions, attention_mask,
            rope_seq_len=capacity,
        )
        ctx["positions"] = slot_positions
        ctx["kv_mask"] = kv_mask
        ctx["cache_pos"] = pos
        if view is not None:
            ctx["prefix_mask"] = view["kv_mask"]

        # Same per-regime segmentation as training (_run_layers): each
        # segment's scan applies its own static window to cached_attention.
        L = self.config.num_hidden_layers
        nk_parts, nv_parts = [], []
        for seg_start, seg_len, pattern in self._attention_segments():
            p = len(pattern)

            def sl(t):
                if seg_start == 0 and seg_len == L:
                    return t
                return jax.lax.slice_in_dim(t, seg_start, seg_start + seg_len)

            seg_layers = jax.tree_util.tree_map(sl, params["layers"])
            seg_k, seg_v = sl(cache["k"]), sl(cache["v"])
            seg_view = None if view is None else (sl(view["k"]), sl(view["v"]))
            if p > 1:
                fold = lambda t: t.reshape(seg_len // p, p, *t.shape[1:])
                seg_layers = jax.tree_util.tree_map(fold, seg_layers)
                seg_k, seg_v = fold(seg_k), fold(seg_v)
                seg_view = jax.tree_util.tree_map(fold, seg_view)

            def scan_step(x, inp, _pattern=pattern, _p=p):
                layer, ck, cv, pre = inp
                if _p == 1:
                    x, new = self.block(
                        layer, x, ctx, cache_layer={"k": ck, "v": cv, "prefix": pre},
                        window=_pattern[0],
                    )
                    return x, (new["k"], new["v"])
                nks, nvs = [], []
                for j in range(_p):
                    lj, pj = jax.tree_util.tree_map(lambda t: t[j], (layer, pre))
                    x, new = self.block(
                        lj, x, ctx, cache_layer={"k": ck[j], "v": cv[j], "prefix": pj},
                        window=_pattern[j],
                    )
                    nks.append(new["k"])
                    nvs.append(new["v"])
                return x, (jnp.stack(nks), jnp.stack(nvs))

            x, (nk, nv) = jax.lax.scan(scan_step, x, (seg_layers, seg_k, seg_v, seg_view))
            if p > 1:
                nk = nk.reshape(seg_len, *nk.shape[2:])
                nv = nv.reshape(seg_len, *nv.shape[2:])
            nk_parts.append(nk)
            nv_parts.append(nv)
        nk = nk_parts[0] if len(nk_parts) == 1 else jnp.concatenate(nk_parts)
        nv = nv_parts[0] if len(nv_parts) == 1 else jnp.concatenate(nv_parts)
        out = self.head(params, x, labels=labels, attention_mask=attention_mask)
        out["cache"] = {"k": nk, "v": nv, "pos": pos + S, "kv_mask": kv_mask}
        return out

    # -------------------------------------------------------------- estimation
    def num_params(self) -> int:
        cfg = self.config
        h, inter, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
        attn = h * (cfg.num_attention_heads + 2 * cfg.num_key_value_heads) * cfg.head_dim + cfg.num_attention_heads * cfg.head_dim * h
        if cfg.attention_bias:
            attn += (cfg.num_attention_heads + 2 * cfg.num_key_value_heads) * cfg.head_dim
        if cfg.qk_norm:
            attn += 2 * cfg.head_dim
        mlp = 3 * h * inter
        norms = 2 * h
        total = L * (attn + mlp + norms) + cfg.vocab_size * h + h
        if not cfg.tie_word_embeddings:
            total += h * cfg.vocab_size
        return total

    def flops_per_token(self) -> float:
        """Approximate forward+backward FLOPs per token (6N + attention)."""
        cfg = self.config
        n = self.num_params()
        attn_extra = 12 * cfg.num_hidden_layers * cfg.hidden_size * cfg.max_position_embeddings
        return 6 * n + attn_extra
