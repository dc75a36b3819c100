"""The plain reference: a decoder-only transformer in float32 ``jax.numpy``.

RMSNorm, rotary embeddings (half-split convention, as the Hugging Face Llama,
Mistral and Qwen3 models use), grouped-query attention with an optional
per-head RMSNorm on queries and keys before the rotation (Qwen3), a SwiGLU
feed-forward, and a tied or untied output head. Dense causal attention over
the whole sequence: no kernel, no cache, no batching tricks, nothing imported
from the program under test. Every matrix product runs under
``jax.default_matmul_precision("highest")``, since a TPU otherwise multiplies
float32 operands in bf16 passes.

It reads the weights in the layout the program keeps them in (a fact about
data, not an import): ``embed.weight (V, h)``; under ``layers`` everything
stacked on a leading layer axis: ``attn.wq (L, h, H·D)``, ``attn.wk`` and
``attn.wv (L, h, Hkv·D)``, ``attn.wo (L, H·D, h)``, optional ``attn.q_norm``
and ``attn.k_norm (L, D)``, ``mlp.w_gate``, ``mlp.w_up (L, h, I)``,
``mlp.w_down (L, I, h)``, ``input_norm.weight`` and ``post_attn_norm.weight
(L, h)``; ``final_norm.weight (h,)``; ``lm_head.weight (h, V)`` unless tied.
Weights of any dtype are cast to float32 one layer at a time, so that a bf16
model needs no second full copy.

Departures from the published models: none in the mathematics. Sliding
windows, biases and rope scaling are not implemented; a configuration that
sets one is refused.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

UNSUPPORTED = ("sliding_window", "rope_scaling", "attention_bias")


def check_supported(cfg: dict) -> None:
    set_keys = [k for k in UNSUPPORTED if cfg.get(k)]
    if set_keys:
        raise ValueError(f"the plain reference does not implement {set_keys}")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the plain reference implements SwiGLU (silu) only")


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta):
    """x: (S, heads, D); rotates the pairs (i, i + D/2)."""
    dim = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = positions[:, None].astype(jnp.float32) * inv_freq  # (S, D/2)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def decoder_layer(x, layer, cfg: dict):
    """One layer on one sequence. x: (S, h) float32."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    eps, seq = cfg["rms_norm_eps"], x.shape[0]
    attn, mlp = layer["attn"], layer["mlp"]
    positions = jnp.arange(seq)

    h = rms_norm(x, _f32(layer["input_norm"]["weight"]), eps)
    q = (h @ _f32(attn["wq"])).reshape(seq, heads, dim)
    k = (h @ _f32(attn["wk"])).reshape(seq, kv_heads, dim)
    v = (h @ _f32(attn["wv"])).reshape(seq, kv_heads, dim)
    if "q_norm" in attn:
        q = rms_norm(q, _f32(attn["q_norm"]), eps)
        k = rms_norm(k, _f32(attn["k_norm"]), eps)
    q = rope(q, positions, cfg["rope_theta"])
    k = rope(k, positions, cfg["rope_theta"])
    # Query head i reads key/value head i // (heads / kv_heads).
    q = q.reshape(seq, kv_heads, heads // kv_heads, dim)
    scores = jnp.einsum("sgrd,tgd->grst", q, k) / jnp.sqrt(jnp.float32(dim))
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    mix = jnp.einsum("grst,tgd->sgrd", jax.nn.softmax(scores, axis=-1), v)
    x = x + mix.reshape(seq, heads * dim) @ _f32(attn["wo"])

    h = rms_norm(x, _f32(layer["post_attn_norm"]["weight"]), eps)
    gated = jax.nn.silu(h @ _f32(mlp["w_gate"])) * (h @ _f32(mlp["w_up"]))
    return x + gated @ _f32(mlp["w_down"])


def hidden_states(params, ids, cfg: dict):
    """Final-normed hidden states of one sequence. ids: (S,) -> (S, h)."""
    x = _f32(jnp.take(params["embed"]["weight"], ids, axis=0))

    def body(x, layer):
        return decoder_layer(x, layer, cfg), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return rms_norm(x, _f32(params["final_norm"]["weight"]), cfg["rms_norm_eps"])


def head_logits(params, hidden, cfg: dict):
    if cfg.get("tie_word_embeddings"):
        return hidden @ _f32(params["embed"]["weight"]).T
    return hidden @ _f32(params["lm_head"]["weight"])


def _frozen(cfg: dict):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "hidden_size",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings")
    return tuple((k, cfg.get(k)) for k in keys)


@functools.partial(jax.jit, static_argnames=("frozen", "rows"))
def _logits_at(params, ids, start, *, frozen, rows):
    cfg = dict(frozen)
    with jax.default_matmul_precision("highest"):
        hidden = hidden_states(params, ids, cfg)
        hidden = jax.lax.dynamic_slice_in_dim(hidden, start, rows)
        return head_logits(params, hidden, cfg)


@functools.partial(jax.jit, static_argnames=("frozen",))
def _sequence_loss(params, ids, *, frozen):
    cfg = dict(frozen)
    with jax.default_matmul_precision("highest"):
        logits = head_logits(params, hidden_states(params, ids, cfg), cfg)[:-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))


def logits_at(params, ids, start: int, rows: int, cfg: dict):
    """Logits of positions ``start .. start + rows`` of one sequence (float32)."""
    check_supported(cfg)
    return _logits_at(params, ids, start, frozen=_frozen(cfg), rows=rows)


def next_token_loss(params, batch_ids, cfg: dict) -> float:
    """Mean next-token cross-entropy over a batch of equally long sequences,
    one sequence at a time (position t predicts token t + 1; the last position
    has no target)."""
    check_supported(cfg)
    losses = [_sequence_loss(params, jnp.asarray(row), frozen=_frozen(cfg))
              for row in batch_ids]
    return float(sum(float(x) for x in losses) / len(losses))
