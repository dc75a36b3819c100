"""The plain reference for Laguna (poolside/Laguna-S-2.1): its forward pass in
float32 ``jax.numpy``, every matrix product under
``jax.default_matmul_precision("highest")``, nothing imported from the program
under test. No ring, no cache, no batching, no grouped product, no kernel: one
sequence, whole, under whole-chain masks.

**The equations** (``h = RMSNorm(x)``, eps ``rms_norm_eps``). Layer ``l`` has
``H`` query heads (``num_attention_heads_per_layer[l]``), ``G`` key-value heads
of ``D``:

``q = h W_q`` (H·D), ``k = h W_k``, ``v = h W_v`` (G·D); rotation by the layer's
kind (below); ``a = softmax(q k^T / sqrt(D) + mask) v`` with the causal mask,
and for a ``sliding_attention`` layer key ``j`` seen from query ``i`` only if
``i - sliding_window < j <= i``; ``g = sigmoid(h W_g)``, one number a query
head, head ``n`` of ``a`` times ``g_n``; ``x <- x + concat(a) W_o``. Then ``h2
= RMSNorm(x)``. A ``dense`` layer: ``x <- x + W_down(silu(h2 W_gate) * h2
W_up)``. A ``sparse`` layer: ``p = softmax_R(h2 W_r)`` over the router's whole
width R, the ``num_experts_per_tok`` largest, ``w = p_top / sum(p_top)``
(``norm_topk_prob``), ``x <- x + S(h2) + moe_routed_scaling_factor * sum_{e in
top, e held} w_e E_e(h2)``, ``S`` and ``E_e`` SwiGLU. After the last layer
RMSNorm and the untied head.

*Rotation.* Half-split pairs ``(i, i + d/2)`` over the first ``d = D *
partial_rotary_factor`` numbers of a head; the rest pass unrotated. A
``default`` table: ``inv_freq_i = theta^(-2i/d)``. A ``yarn`` table:
``inv_freq_i = (1 - r_i) theta^(-2i/d) + r_i theta^(-2i/d) / factor`` with the
ramp ``r_i = clip((i - low) / (high - low), 0, 1)``, ``low = floor(c(beta_fast))``,
``high = ceil(c(beta_slow))``, ``c(n) = d ln(original_max / (2 pi n)) / (2 ln
theta)``, both held to ``[0, d - 1]``; cos and sin times ``attention_factor``.

**The share.** The program holds experts ``first_expert .. first_expert +
num_experts`` of the ``router_experts`` the router scores (a chip's share of a
layer, ``model-configs`` guide, section 4), and so does this reference: every
token is routed over all R, **each held expert is applied to every token** and
weighted by its routing weight, or by zero where the token did not choose it;
what the absent experts would have added is left out, here as there.

**The weights** are read in the layout the program keeps them (a fact about
data, not an import), stacked by kind in the order the layers occur:
``embed.weight (V, h)``; ``layers.input_norm.weight``,
``layers.post_attn_norm.weight (L, h)``; ``layers.full`` and
``layers.sliding``: ``wq (Lk, h, H·D)``, ``wk``, ``wv (Lk, h, G·D)``, ``wg (Lk,
h, H)``, ``wo (Lk, H·D, h)``; ``layers.dense_mlp``: ``w_gate``, ``w_up (Ld, h,
I)``, ``w_down (Ld, I, h)``; ``layers.moe``: ``router (Lm, h, R)``, ``w_gate``,
``w_up (Lm, E, h, Im)``, ``w_down (Lm, E, Im, h)``, ``shared_gate``,
``shared_up (Lm, h, Is)``, ``shared_down (Lm, Is, h)``; ``final_norm.weight``;
``lm_head.weight (V, h)``. Weights of any dtype are cast to float32 one layer
at a time (one expert at a time inside it). ``cfg`` is the configuration
file's own dict.

Rows are computed in tiles (projections and feed-forwards ``ROW_TILE`` rows at
a time, attention ``QUERY_TILE`` queries at a time against the whole
sequence), so that 4,608 positions fit beside 8.65 GB of weights.

**Departures from the published description**, each also in the
configuration file's ``assumed``:

- the gate is the head-wise sigmoid gate on the attention output, computed
  from the layer's normed input (Gated Attention, arXiv:2505.06708):
  ``gating: per-head`` names no more than its granularity;
- the router scores by softmax over all R (the config's key names are
  Qwen2-MoE's, whose router is softmax; it names no scoring function, no
  groups, no bias; ``moe_router_logit_softcapping`` 0 is none);
- the shared expert is added ungated (no key for a gate); no QK norm (no key);
- the window counts the query's own position;
- a cut in experts (``num_experts`` held of ``router_experts``) leaves out
  what the absent experts would add; a sliced vocabulary is a smaller one.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

ROW_TILE = 1024
QUERY_TILE = 128
FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


def check_supported(cfg: dict) -> None:
    refused = [k for k in ("attention_bias", "tie_word_embeddings", "moe_apply_router_weight_on_input",
                           "moe_router_logit_softcapping") if cfg.get(k)]
    if refused or cfg.get("gating", "per-head") != "per-head" or not cfg.get("norm_topk_prob", True):
        raise ValueError(f"the plain Laguna reference does not implement this configuration "
                         f"(set: {refused}, gating {cfg.get('gating')!r})")
    if set(cfg["layer_types"]) - {FULL, SLIDING} or set(cfg["mlp_layer_types"]) - {DENSE, SPARSE}:
        raise ValueError(f"unknown layer kinds in {cfg['layer_types']} / {cfg['mlp_layer_types']}")


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def inverse_frequencies(spec: dict, dim: int):
    """``(inv_freq (dim/2,), factor on cos and sin)`` of one rope table."""
    theta = float(spec["rope_theta"])
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if spec.get("rope_type", "default") == "default":
        return plain, 1.0
    if spec["rope_type"] != "yarn":
        raise ValueError(f"the plain Laguna reference has no rope table of type {spec['rope_type']!r}")
    original = spec["original_max_position_embeddings"]
    turns = lambda n: dim * math.log(original / (2 * math.pi * n)) / (2 * math.log(theta))
    low = max(math.floor(turns(spec["beta_fast"])), 0)
    high = min(math.ceil(turns(spec["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return (1.0 - ramp) * plain + ramp * plain / spec["factor"], float(spec["attention_factor"])


def rope(x, positions, spec: dict):
    """x: (S, heads, D). Rotates the pairs (i, i + d/2) of the first ``d = D *
    partial_rotary_factor`` numbers of each head; the rest pass."""
    dim = int(x.shape[-1] * spec.get("partial_rotary_factor", 1))
    inv_freq, factor = inverse_frequencies(spec, dim)
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = factor * jnp.cos(angles)[:, None, :], factor * jnp.sin(angles)[:, None, :]
    x1, x2, rest = x[..., : dim // 2], x[..., dim // 2: dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def by_rows(fn, tile: int, *arrays):
    """``fn`` over tiles of rows (the leading axis), one tile at a time."""
    rows = arrays[0].shape[0]
    tile = min(tile, rows)
    pad = -rows % tile
    padded = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) for a in arrays]
    tiles = [a.reshape((rows + pad) // tile, tile, *a.shape[1:]) for a in padded]
    out = jax.lax.map(lambda xs: fn(*xs), tuple(tiles))
    return jax.tree_util.tree_map(lambda o: o.reshape(rows + pad, *o.shape[2:])[:rows], out)


def attention(h, w, heads: int, window, spec: dict, cfg: dict):
    """h: (S, hidden) -> (S, hidden): gated softmax attention of one layer."""
    groups, dim = cfg["num_key_value_heads"], cfg["head_dim"]
    seq = h.shape[0]
    positions = jnp.arange(seq)
    project = lambda name, n: by_rows(lambda rows: rows @ w[name], ROW_TILE, h).reshape(seq, n, dim)
    q = rope(project("wq", heads), positions, spec).reshape(seq, groups, heads // groups, dim)
    k, v = rope(project("wk", groups), positions, spec), project("wv", groups)

    def attend(q_tile, t):
        apart = t[:, None] - positions[None, :]  # query's position less key's
        seen = (apart >= 0) if window is None else (apart >= 0) & (apart < window)
        s = jnp.einsum("tgrd,sgd->grts", q_tile, k) / math.sqrt(dim)
        return jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v)

    out = by_rows(attend, QUERY_TILE, q, positions).reshape(seq, heads, dim)
    gate = by_rows(lambda rows: jax.nn.sigmoid(rows @ w["wg"]), ROW_TILE, h)  # (S, heads)
    return by_rows(lambda rows: rows @ w["wo"], ROW_TILE, (out * gate[..., None]).reshape(seq, -1))


def swiglu(h, gate, up, down):
    return by_rows(lambda rows: (jax.nn.silu(rows @ gate) * (rows @ up)) @ down, ROW_TILE, h)


def routing(h, router, k: int):
    """``(weights (S, R): w_e where the token chose e, else 0; chosen (S, R)
    bool)``: softmax over all R, the k largest, renormalised to sum to 1."""
    p = jax.nn.softmax(by_rows(lambda rows: rows @ router, ROW_TILE, h), axis=-1)
    rank = jnp.argsort(jnp.argsort(-p, axis=-1, stable=True), axis=-1, stable=True)
    chosen = rank < k
    top = jnp.where(chosen, p, 0.0)
    return top / top.sum(axis=-1, keepdims=True), chosen


def experts(h, w, cfg: dict):
    """The shared expert plus the held experts' part of the routed sum, and
    the experts every token chose (S, R) bool."""
    weights, chosen = routing(h, w["router"].astype(jnp.float32), cfg["num_experts_per_tok"])
    first = cfg.get("first_expert", 0)
    held = weights[:, first: first + w["w_gate"].shape[0]] * cfg["moe_routed_scaling_factor"]

    def one_expert(total, xs):  # every token through the expert, weighted by w_e or zero
        gate, up, down, weight = xs
        return total + weight[:, None] * swiglu(h, *_f32((gate, up, down))), None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                             (w["w_gate"], w["w_up"], w["w_down"], held.T))
    shared = swiglu(h, *_f32((w["shared_gate"], w["shared_up"], w["shared_down"])))
    return shared + routed, chosen


def _frozen(cfg: dict, kind: str):
    keys = ("num_key_value_heads", "head_dim", "rms_norm_eps", "sliding_window",
            "num_experts_per_tok", "moe_routed_scaling_factor", "first_expert")
    spec = tuple(sorted(cfg["rope_parameters"][kind].items()))
    return tuple((k, cfg[k]) for k in keys if k in cfg) + (("rope", spec),)


@functools.partial(jax.jit, static_argnames=("frozen", "kind", "mlp_kind", "heads"))
def _layer(x, norms, mixer, mlp, watch, *, frozen, kind, mlp_kind, heads):
    cfg = dict(frozen)
    spec = dict(cfg.pop("rope"))
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, norms["input_norm"]["weight"].astype(jnp.float32), cfg["rms_norm_eps"])
        window = cfg["sliding_window"] if kind == SLIDING else None
        x = x + attention(h, _f32(mixer), heads, window, spec, cfg)
        h = rms_norm(x, norms["post_attn_norm"]["weight"].astype(jnp.float32), cfg["rms_norm_eps"])
        if mlp_kind == DENSE:
            return x + swiglu(h, *_f32((mlp["w_gate"], mlp["w_up"], mlp["w_down"]))), None
        out, chosen = experts(h, mlp, cfg)
        return x + out, chosen[watch]


@functools.partial(jax.jit, static_argnames=("rows", "eps"))
def _head(hidden, final_norm, head, start, *, rows, eps):
    with jax.default_matmul_precision("highest"):
        hidden = jax.lax.dynamic_slice_in_dim(hidden, start, rows)
        return rms_norm(hidden, final_norm.astype(jnp.float32), eps) @ head.astype(jnp.float32).T


def hidden_states(params, ids, cfg: dict, watch=(0,)):
    """The residual stream after the last layer, (S, hidden) float32, and for
    the positions ``watch`` the experts each expert layer's router chose:
    (Lm, len(watch), R) bool."""
    check_supported(cfg)
    layers = params["layers"]
    x = jnp.take(params["embed"]["weight"], ids, axis=0).astype(jnp.float32)
    watch = jnp.asarray(watch, jnp.int32)
    at = lambda tree, i: jax.tree_util.tree_map(lambda t: t[i], tree)
    norms = {k: layers[k] for k in ("input_norm", "post_attn_norm")}
    seen, count = [], {FULL: 0, SLIDING: 0, DENSE: 0, SPARSE: 0}
    for i, (kind, mlp_kind) in enumerate(zip(cfg["layer_types"], cfg["mlp_layer_types"])):
        mixer = at(layers["full" if kind == FULL else "sliding"], count[kind])
        mlp = at(layers["dense_mlp" if mlp_kind == DENSE else "moe"], count[mlp_kind])
        count[kind] += 1
        count[mlp_kind] += 1
        x, chosen = _layer(x, at(norms, i), mixer, mlp, watch, frozen=_frozen(cfg, kind), kind=kind,
                           mlp_kind=mlp_kind, heads=cfg["num_attention_heads_per_layer"][i])
        if chosen is not None:
            seen.append(chosen)
    return x, jnp.stack(seen)


def logits_at(params, ids, start: int, rows: int, cfg: dict, watch=(0,)):
    """Logits of positions ``start .. start + rows`` of one sequence (float32),
    and the experts chosen at the watched positions (:func:`hidden_states`)."""
    hidden, seen = hidden_states(params, ids, cfg, watch)
    logits = _head(hidden, params["final_norm"]["weight"], params["lm_head"]["weight"], start,
                   rows=rows, eps=cfg["rms_norm_eps"])
    return logits, seen


def checks(model, params, ids, watch, seen) -> dict:
    """What this reference prints beside the gaps (``runners/serve_ref.py``
    passes it through under ``checks``, compared with nothing): of the experts
    the reference chose at the watched positions, how many the program's own
    forward pass over the same sequence chose too."""
    import numpy as np

    ours = np.asarray(model.routed_experts(params, ids[None], jnp.asarray(watch, jnp.int32)))[:, 0]
    theirs = np.asarray(seen)  # (Lm, n, R) bool
    shared = np.take_along_axis(theirs, ours, axis=-1).sum()
    return {"routed_experts_shared_with_reference": (int(shared), int(theirs.sum()))}
