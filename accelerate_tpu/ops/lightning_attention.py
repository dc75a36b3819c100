"""Lightning (linear) attention with a per-head decay, in plain ``jax.numpy``.

The recurrence, per head with decay ``lambda_h`` and a state ``S`` of
``(head_dim, head_dim)`` kept in float32::

    S_t = lambda_h * S_{t-1} + k_t^T v_t        o_t = q_t S_t

:func:`lightning_attention` computes it block by block (Lightning Attention-2,
arXiv:2401.04658): inside a block of ``block`` tokens the decayed causal
products, across blocks the carried ``S``. One call serves prefill (a chunk of
tokens from a given state) and decode (one token: a block of one).

A token whose ``mask`` is 0 (bucket padding, a row the engine rides along
masked) adds nothing and does not decay: the decay between two tokens is
``lambda_h`` to the number of *valid* tokens between them, so a row with no
valid token returns its state bit for bit (``S * exp(0) + 0``).

No kernel: the products are small beside the layer's projections (a few
GFLOP a chunk), and they run in float32 at the highest matmul precision so
that the state never rounds through bf16. The Pallas kernel for the chunked
scan is a later ``perf_opt`` PR's (ROADMAP.md, Queue 2, B5).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def decay_log_slopes(num_heads: int) -> np.ndarray:
    """``log(lambda_h)`` for ``lambda_h = exp(-2^(-8(h+1)/H))``, h = 0..H-1:
    the slopes of Lightning Attention-2, with no per-layer factor."""
    h = np.arange(1, num_heads + 1, dtype=np.float64)
    return (-np.exp2(-8.0 * h / num_heads)).astype(np.float32)


def _block(q, k, v, mask, state, log_decay):
    """One block. q, k, v: (B, C, H, D) float32; mask: (B, C) float32;
    state: (B, H, D, D) float32; log_decay: (H,). Returns (o, new_state)."""
    # b[t]: log of the decay from the block's start through token t
    # (inclusive), counting valid tokens only.
    b = jnp.cumsum(mask[:, :, None] * log_decay[None, None, :], axis=1)  # (B, C, H)
    c = q.shape[1]
    causal = jnp.tril(jnp.ones((c, c), bool))
    # exp(b_t - b_s) for s <= t: <= 1, so nothing overflows; masked to 0 above
    # the diagonal before the exponent can grow.
    gap = b[:, :, None, :] - b[:, None, :, :]  # (B, t, s, H)
    decay = jnp.where(causal[None, :, :, None], jnp.exp(jnp.minimum(gap, 0.0)), 0.0)
    k = k * mask[:, :, None, None]
    scores = jnp.einsum("bthd,bshd->btsh", q, k, precision=_HI) * decay
    o = jnp.einsum("btsh,bshd->bthd", scores, v, precision=_HI)
    o = o + jnp.exp(b)[..., None] * jnp.einsum("bthd,bhde->bthe", q, state, precision=_HI)
    total = b[:, -1]  # (B, H)
    k_tail = k * jnp.exp(total[:, None] - b)[..., None]
    state = (jnp.exp(total)[..., None, None] * state
             + jnp.einsum("bshd,bshe->bhde", k_tail, v, precision=_HI))
    return o, state


def lightning_attention(q, k, v, state, log_decay, mask=None, block: int = 128):
    """q, k, v: (B, S, H, D), ``q`` already scaled; state: (B, H, D, D)
    float32; log_decay: (H,) float32; mask: (B, S) or None (all valid).
    Returns ``(o, new_state)`` with ``o`` of (B, S, H, D) in ``v``'s dtype."""
    bsz, s = q.shape[:2]
    out_dtype = v.dtype
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    mask = jnp.ones((bsz, s), jnp.float32) if mask is None else mask.astype(jnp.float32)
    log_decay = jnp.asarray(log_decay, jnp.float32)
    state = state.astype(jnp.float32)
    if s <= block:
        o, state = _block(q, k, v, mask, state, log_decay)
        return o.astype(out_dtype), state
    pad = -s % block
    if pad:  # padded tokens are masked: they add nothing and do not decay
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (s + pad) // block
    fold = lambda x: jnp.moveaxis(x.reshape(bsz, n, block, *x.shape[2:]), 1, 0)

    def step(state, xs):
        o, state = _block(*xs, state, log_decay)
        return state, o

    state, o = jax.lax.scan(step, state, (fold(q), fold(k), fold(v), fold(mask)))
    o = jnp.moveaxis(o, 0, 1).reshape(bsz, s + pad, *o.shape[3:])[:, :s]
    return o.astype(out_dtype), state
