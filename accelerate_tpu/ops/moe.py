"""Mixture-of-experts routing — top-k gating with two dispatch back-ends.

TPU-first design (the GShard/Switch recipe rather than a torch-style gather
loop), with the implementation picked per mesh (``moe_ffn``):

- **sorted** (long sequences / drop-free capacity): claims sort by expert id
  and the expert FFNs run as ``lax.ragged_dot`` grouped matmuls over
  expert-contiguous rows — O(B·S·k) routing memory, drop-free safe at any
  sequence length (the einsum path is O(B·S·E·C) = O(S²) at Mixtral's
  drop-free capacity).
- **einsum** (ep > 1, and the measured winner at short S — see ``moe_ffn``):
  dense one-hot dispatch/combine tensors and batched einsums over a leading
  expert dim. Under GSPMD, sharding that dim on ``ep`` partitions the expert
  FFNs the way row-parallel TP partitions a matmul: dispatch stays
  device-local, and the combine contracts the sharded expert dim — one
  all-reduce over ``ep`` per layer, inserted by XLA. ragged_dot's group dim
  is opaque to the partitioner, so this remains the ep-sharded form.

Both share one routing semantics (same capacity drop rule, same Switch aux
loss) — pinned by ``tests/test_moe.py::test_sorted_and_einsum_dispatch_agree``.

**Serving** has a layer of its own, :func:`expert_share_ffn`: drop-free (no
capacity), told which experts of the router's width it holds, and returning
their part of the routed sum alone (a chip's share of a layer; the model adds
a shared expert). ``ACCELERATE_MOE_DISPATCH`` does not reach it.

Reference context: the reference has no MoE implementation of its own (only
DeepSpeed-MoE passthrough flags, ``utils/dataclasses.py``); this is a native
capability of the framework (SURVEY.md §2.4 lists EP as a note-only strategy
for the reference).

Shapes (per group = batch row): x (B, S, h); router (h, E); k choices per
token; capacity C per expert per group.

- ``dispatch`` (B, S, E, C) one-hot: token (b, s) occupies slot c of expert e.
- ``combine``  (B, S, E, C) = dispatch · gate: weights for the return trip.
- expert inputs  = einsum('bsec,bsh->ebch', dispatch, x)
- expert outputs = SwiGLU with weights (E, h, i) via 'ebch,ehi->ebci'
- token outputs  = einsum('ebch,bsec->bsh', expert_out, combine)

The auxiliary load-balancing loss is the Switch formulation:
``E · Σ_e  f_e · p̄_e`` (token fraction × mean router prob).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def router_capacity(tokens_per_group: int, num_experts: int, k: int, capacity_factor: float) -> int:
    """Slots per expert per group; multiples of 8 keep the lanes happy."""
    cap = int(np.ceil(tokens_per_group * k * capacity_factor / num_experts))
    return max(8, int(np.ceil(cap / 8)) * 8)


def _route(router_logits, k: int, capacity: int):
    """Shared routing front-end for BOTH dispatch back-ends — the single source
    of the capacity-drop semantics and the Switch aux loss.

    Returns ``(expert_idx (B,S,k), gate_vals (B,S,k) normalized, onehot
    (B,S,k,E), pos (B,S·k,E) claim rank per expert, keep (B,S·k,E) kept-claim
    one-hot, aux_loss scalar)``. Earlier tokens (and higher-priority choices)
    claim an expert's ``capacity`` slots per batch row first; the Switch aux
    loss is ``E · Σ_e f_e · p̄_e`` (≈1 at perfect balance)."""
    B, S, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)  # (B,S,E)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (B,S,k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (B,S,k,E)
    flat = onehot.reshape(B, S * k, E)
    # Position of each claim within its expert's slots (count of prior claims).
    pos = jnp.cumsum(flat, axis=1) - flat  # (B, S·k, E)
    keep = flat * (pos < capacity)

    top1 = jax.nn.one_hot(expert_idx[..., 0], E, dtype=jnp.float32)
    aux_loss = E * jnp.sum(jnp.mean(top1, axis=(0, 1)) * jnp.mean(probs, axis=(0, 1)))
    return expert_idx, gate_vals, onehot, pos, keep, aux_loss


def top_k_routing(router_logits, k: int, capacity: int, dtype=jnp.float32):
    """Build dispatch/combine tensors from router logits (the einsum back-end).

    router_logits: (B, S, E). Returns (dispatch (B,S,E,C), combine (B,S,E,C),
    aux_loss scalar), dispatch/combine in ``dtype``. Tokens beyond an expert's
    capacity are dropped (their combine weights are zero → they ride the
    residual stream only, the standard Switch behavior).

    ``dtype`` sizes the C-width one-hot intermediates — the path's dominant
    HBM traffic. Routing arithmetic (softmax, cumsum ranks, aux) stays fp32
    regardless; one-hot values are exact in any float dtype, and gate values
    were cast to the compute dtype at the combine einsum anyway, so bf16 here
    changes traffic, not semantics.

    Construction collapses the k dim BEFORE any C-width tensor exists:
    ``top_k`` returns distinct experts per token, so a token holds at most
    one claim per expert and the per-(token, expert) claim rank / kept flag /
    gate reduce over k in O(B·S·k·E) — the C-width one-hot is then built
    once at (B,S,E,C). The previous form materialized the (B,S·k,E,C) slot
    tensor (k× the traffic) plus a 5-D max and a C-width combine einsum; the
    r5 on-chip attribution measured that front-end at 5.1 ms/layer against
    9.2 ms of expert matmuls (benchmarks/moe_op_attribution.py), which is
    what paid for this rewrite.
    """
    B, S, E = router_logits.shape
    expert_idx, gate_vals, onehot, pos, keep, aux_loss = _route(router_logits, k, capacity)
    keep4 = keep.reshape(B, S, k, E)  # {0,1}: claim kept under capacity
    # Per (token, expert): rank of its (unique) claim, kept flag, gate value.
    rank = jnp.sum(pos.reshape(B, S, k, E) * keep4, axis=2)  # (B,S,E)
    claimed = jnp.max(keep4, axis=2)  # (B,S,E)
    gate_e = jnp.einsum("bske,bsk->bse", keep4, gate_vals)  # 0 when dropped

    slotoh = jax.nn.one_hot(rank.astype(jnp.int32), capacity, dtype=dtype)  # (B,S,E,C)
    dispatch = claimed.astype(dtype)[..., None] * slotoh
    combine = gate_e.astype(dtype)[..., None] * slotoh
    return dispatch, combine, aux_loss


def moe_ffn_sorted(x, router_w, w_gate, w_up, w_down, *, k: int, capacity_factor: float = 1.25):
    """Sort-by-expert MoE layer — O(S·k) dispatch memory (VERDICT r2 #4).

    Claims (token, choice) are grouped by expert id so each expert's tokens
    are contiguous and the three FFN matmuls run as ``lax.ragged_dot``
    (grouped matmul over expert-contiguous rows — the MXU-native megablocks
    shape). No (B,S,E,C) one-hot ever exists: peak routing intermediates are
    O(B·S·k·max(E,h)) versus the einsum path's O(B·S·E·C) — quadratic in S at
    Mixtral's drop-free capacity. Drop semantics match the einsum path exactly
    (same per-batch-row capacity rule; dropped claims keep gate 0).

    The grouping permutation is a COUNTING sort built from the routing
    cumsum's per-expert claim ranks — ``dest = expert_base + row_base +
    rank_within(row, expert)`` — not a comparison ``argsort``: the O(n·log²n)
    bitonic sort was the wrapper's dominant VPU cost (r5 on-chip: 25.5% →
    35.9% active-MFU at the bench shape). The inverse permutation is
    materialized with one tiny int32 scatter so token rows move with a
    GATHER, and the combine re-gathers each claim's output row at ``dest`` —
    sum over the k choices — so no scatter-add touches (T·k, h) data at all.
    Identical claim order to the old stable argsort (by (expert, batch row,
    claim index)), so numerics are unchanged.
    """
    B, S, h = x.shape
    E = router_w.shape[-1]
    capacity = router_capacity(S, E, k, capacity_factor)
    router_logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)
    expert_idx, gate_vals, onehot, pos, keep, aux = _route(router_logits, k, capacity)
    gates = gate_vals * jnp.sum(keep.reshape(B, S, k, E), axis=-1)  # dropped → 0

    Sk = S * k
    N = B * Sk
    e_claim = expert_idx.reshape(B, Sk)
    # Rank of each claim within (its batch row, its expert) — already computed
    # by the routing cumsum; the capacity clamp never applies to ranks here
    # (dropped claims still occupy a ragged row; only their gate is zero).
    rank = jnp.take_along_axis(pos, e_claim[..., None], axis=2)[..., 0].astype(jnp.int32)
    counts = jnp.sum(onehot.reshape(B, Sk, E), axis=1).astype(jnp.int32)  # (B, E)
    row_base = jnp.cumsum(counts, axis=0) - counts  # claims of e in earlier rows
    group_sizes = jnp.sum(counts, axis=0)  # (E,)
    expert_base = jnp.cumsum(group_sizes) - group_sizes
    dest = (
        jnp.take(expert_base, e_claim, axis=0)
        + jnp.take_along_axis(row_base, e_claim, axis=1)
        + rank
    ).reshape(N)
    # Inverse permutation via one (N,) int32 scatter; rows then move by gather.
    inv = jnp.zeros((N,), jnp.int32).at[dest].set(jnp.arange(N, dtype=jnp.int32))

    claim_x = jnp.broadcast_to(x[:, :, None], (B, S, k, h)).reshape(N, h)
    sorted_in = jnp.take(claim_x, inv, axis=0)  # (N, h) expert-contiguous

    # f32 inputs (tests / CPU) get exact accumulation; bf16 keeps the MXU fast path.
    prec = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    rd = lambda lhs, rhs: jax.lax.ragged_dot(
        lhs, rhs.astype(x.dtype), group_sizes, precision=prec
    )
    gated = jax.nn.silu(rd(sorted_in, w_gate)) * rd(sorted_in, w_up)
    sorted_out = rd(gated, w_down)  # (N, h)

    y = jnp.take(sorted_out, dest, axis=0).reshape(B, S, k, h)  # gather combine
    out = jnp.sum(y * gates.reshape(B, S, k, 1).astype(x.dtype), axis=2)
    return out.reshape(B, S, h), aux


def moe_ffn_indexed(x, router_w, w_gate, w_up, w_down, *, k: int, capacity_factor: float = 1.25):
    """Gather-based capacity-slot dispatch — dense expert matmuls without the
    one-hot einsums OR the sorted path's scatter-add.

    The einsum back-end pays two O(B·S·E·C·h) dispatch/combine matmuls
    (~20% extra FLOPs at the bench shape) just to move tokens; the sorted
    back-end avoids them but pays argsort + ragged_dot + a scatter-add.
    This back-end moves tokens with *indices* instead:

    1. scatter the claim ranks into a ``(B, E, C)`` slot→token index map
       (O(S·k) elements — no C-sized one-hot ever exists),
    2. gather tokens into ``(E, B, C, h)`` capacity slots and run the SAME
       dense batched expert einsums as the einsum path (full MXU tiles,
       no ragged group dim),
    3. combine by gathering each claim's output slot and summing the k
       gate-weighted rows — a pure gather, no scatter.

    Routing memory is O(B·S·k·E + B·E·C·h) — subquadratic in S at drop-free
    capacity, like sorted. Drop semantics are identical to both other paths
    (same ``_route`` front-end); unfilled slots default to token 0 and compute
    harmless padding work that the combine never reads (gate 0). Not
    ep-shardable for the same reason as sorted: the gather indices are opaque
    to the partitioner — ``moe_ffn`` keeps einsum under ep.
    """
    B, S, h = x.shape
    E = router_w.shape[-1]
    capacity = router_capacity(S, E, k, capacity_factor)
    router_logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)
    expert_idx, gate_vals, _onehot, pos, keep, aux = _route(router_logits, k, capacity)

    Sk = S * k
    e_j = expert_idx.reshape(B, Sk)  # chosen expert per claim
    # Rank of each claim within its expert's slots, and whether it was kept.
    p_j = jnp.take_along_axis(pos, e_j[..., None], axis=2)[..., 0].astype(jnp.int32)
    kept_j = jnp.sum(keep, axis=-1)  # (B, Sk) ∈ {0,1}

    # Slot→token map: claim j of row b sits at slot (e_j, p_j); dropped claims
    # aim at row C (out of bounds) and are dropped by the scatter.
    tok_j = jnp.broadcast_to((jnp.arange(Sk, dtype=jnp.int32) // k)[None], (B, Sk))
    b_idx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, Sk))
    p_sc = jnp.where(kept_j > 0, p_j, capacity)
    slot_tok = jnp.zeros((B, E, capacity), jnp.int32).at[b_idx, e_j, p_sc].set(
        tok_j, mode="drop"
    )

    expert_in = jnp.take_along_axis(
        x, slot_tok.reshape(B, E * capacity)[..., None], axis=1
    ).reshape(B, E, capacity, h).transpose(1, 0, 2, 3)  # (E, B, C, h)
    expert_in = _constrain_expert_layout(expert_in)
    gated = jax.nn.silu(jnp.einsum("ebch,ehi->ebci", expert_in, w_gate.astype(x.dtype)))
    up = jnp.einsum("ebch,ehi->ebci", expert_in, w_up.astype(x.dtype))
    expert_out = jnp.einsum("ebci,eih->ebch", gated * up, w_down.astype(x.dtype))

    # Combine: gather each claim's output slot, weight by its gate (0 when
    # dropped — the clipped gather row is then never read into the sum).
    eo = expert_out.transpose(1, 0, 2, 3).reshape(B, E * capacity, h)
    flat_ec = e_j * capacity + jnp.clip(p_j, 0, capacity - 1)
    y = jnp.take_along_axis(eo, flat_ec[..., None], axis=1)  # (B, Sk, h)
    g = (gate_vals.reshape(B, Sk) * kept_j).astype(x.dtype)
    out = jnp.sum((y * g[..., None]).reshape(B, S, k, h), axis=2)
    return out, aux


def moe_ffn_einsum(x, router_w, w_gate, w_up, w_down, *, k: int, capacity_factor: float = 1.25):
    """Dense one-hot einsum MoE layer (GShard form) — the ``ep``-sharded path.

    x: (B, S, h); router_w: (h, E); w_gate/w_up: (E, h, i); w_down: (E, i, h).
    Returns (output (B, S, h), aux_loss scalar). Sharding the leading E dim of
    the expert weights on ``ep`` keeps expert compute local; the final combine
    contracts the sharded expert dim — one all-reduce over ``ep`` per layer,
    which is what GSPMD partitions well (ragged_dot's group dim is opaque to
    the partitioner). Memory is O(B·S·E·C): prefer ``moe_ffn_sorted`` whenever
    the mesh has no ep axis.
    """
    B, S, h = x.shape
    E = router_w.shape[-1]
    capacity = router_capacity(S, E, k, capacity_factor)
    router_logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)
    dispatch, combine, aux = top_k_routing(router_logits, k, capacity, dtype=x.dtype)

    expert_in = jnp.einsum("bsec,bsh->ebch", dispatch, x)
    expert_in = _constrain_expert_layout(expert_in)
    gated = jax.nn.silu(jnp.einsum("ebch,ehi->ebci", expert_in, w_gate.astype(x.dtype)))
    up = jnp.einsum("ebch,ehi->ebci", expert_in, w_up.astype(x.dtype))
    expert_out = jnp.einsum("ebci,eih->ebch", gated * up, w_down.astype(x.dtype))
    expert_out = _constrain_expert_layout(expert_out)
    out = jnp.einsum("ebch,bsec->bsh", expert_out, combine.astype(x.dtype))
    return out, aux


def moe_ffn(x, router_w, w_gate, w_up, w_down, *, k: int, capacity_factor: float = 1.25):
    """Route → expert FFN → combine, auto-selecting the implementation.

    - ep > 1 in the mesh → **einsum** (the ep-shardable form; ragged_dot's
      group dim is opaque to the partitioner).
    - otherwise, short sequences at modest capacity → **einsum** too: the r5
      op-level attribution (PERF.md; benchmarks/moe_op_attribution.py) shows
      ``lax.ragged_dot`` runs 31% below the dense per-expert einsums at the
      bench shape (127 vs 181 TF/s fwd+bwd) and the row gathers cost more
      than einsum's dispatch matmuls — end-to-end einsum 42.6% vs sorted
      27.7% active-MFU at S=1024/cf1.0 on v5e; sorted ties einsum near
      S=4096 (30.8% vs 31.3%).
    - long sequences or drop-free capacity → **sorted** (einsum memory is
      O(S²) at Mixtral's drop-free cf = E/k).

    Override with ``ACCELERATE_MOE_DISPATCH=sorted|einsum|indexed``."""
    import os

    impl = os.environ.get("ACCELERATE_MOE_DISPATCH", "auto")
    if impl == "auto":
        from ..state import PartialState

        try:
            mesh = PartialState().mesh
            ep = mesh.shape.get("ep", 1) if mesh is not None else 1
        except Exception:
            ep = 1
        if ep > 1:
            impl = "einsum"
        else:
            S = x.shape[1]
            impl = "einsum" if (S <= 2048 and capacity_factor <= 2.0) else "sorted"
    fns = {"sorted": moe_ffn_sorted, "einsum": moe_ffn_einsum,
           "indexed": moe_ffn_indexed}
    if impl not in fns:
        raise ValueError(
            f"ACCELERATE_MOE_DISPATCH={impl!r} is not a dispatch back-end "
            f"(valid: auto|{'|'.join(sorted(fns))})"
        )
    return fns[impl](x, router_w, w_gate, w_up, w_down, k=k, capacity_factor=capacity_factor)


# ---------------------------------------------------------------------------
# The serving expert layer: drop-free, told which experts it holds.

def route_top_k(router_logits, k: int, *, norm_topk_prob: bool = True,
                scoring: str = "softmax", bias=None):
    """Every expert the router scores (``scoring``: ``"softmax"`` over all of
    them, or ``"sigmoid"`` of each logit alone), the ``k`` largest, and (with
    ``norm_topk_prob``) their weights renormalised to sum to 1: all float32.
    ``bias`` (R,), where given, is added to the scores for the choice alone:
    the chosen experts' weights are their scores without it. ``router_logits``:
    (N, R). Returns ``(weights (N, k), experts (N, k))``."""
    if scoring == "softmax":
        probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    elif scoring == "sigmoid":
        probs = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    else:
        raise ValueError(f"router scoring must be 'softmax' or 'sigmoid', got {scoring!r}")
    if bias is None:
        weights, experts = jax.lax.top_k(probs, k)
    else:
        _, experts = jax.lax.top_k(probs + bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts


def router_logits(x, router_w):
    """``x`` (..., h) against the router's whole width (h, R): float32 operands
    at the highest precision, whatever the weights' dtype or the engine's
    ``matmul_precision`` (a near tie decides which expert a token gets)."""
    return jax.lax.dot_general(
        x.astype(jnp.float32), router_w.astype(jnp.float32), (((x.ndim - 1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _grouped_matmul(rows, weights, sizes, group_of_row, layer, precision: str):
    """``rows`` (M, a), sorted by group, times its group's ``weights[layer, g]``
    (L, E, a, b), as one ``lax.ragged_dot`` over ALL the layers' experts with
    sizes zero outside ``layer``: the product skips a group that has no row,
    so it reads the experts touched and no others, and no layer's experts are
    sliced out first (a copy of all of them, 1.8 ms a layer at 32 experts of
    3072 x 1024 x 3: PERF.md, PR 36). Under ``int8`` both operands are
    quantized as ``ops/int8.int8_matmul`` quantizes them (rows by row, the
    layer's weights by expert and column) and the int8 values, exact in bf16,
    are multiplied with float32 accumulation."""
    layers, experts = weights.shape[:2]
    if precision == "int8":
        from .int8 import quantize_rowwise

        q_rows, s_rows = quantize_rowwise(rows, axis=-1)
        q_w, s_w = quantize_rowwise(
            jax.lax.dynamic_index_in_dim(weights, layer, 0, keepdims=False), axis=1)  # (E, 1, b)
        acc = jax.lax.ragged_dot(q_rows.astype(jnp.bfloat16), q_w.astype(jnp.bfloat16), sizes,
                                 preferred_element_type=jnp.float32)
        scale = jnp.take(s_w[:, 0], jnp.minimum(group_of_row, experts - 1), axis=0)
        return (acc * s_rows * scale).astype(rows.dtype)
    if precision != "default":
        raise ValueError(f"matmul precision must be 'default' or 'int8', got {precision!r}")
    group = jnp.arange(layers * experts)
    every = jnp.where(group // experts == layer, jnp.take(sizes, group % experts), 0)
    exact = jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None
    return jax.lax.ragged_dot(rows, weights.reshape(-1, *weights.shape[2:]).astype(rows.dtype),
                              every, precision=exact)


def _experts_grouped(x, weights, local, held, w_gate, w_up, w_down, layer, precision):
    """Claims sorted by the expert held (claims of absent experts last, in no
    group) into three grouped products; each claim's row comes back by the
    inverse permutation, so nothing is scatter-added. Returns ``(out (N, h)
    float32, claims (E,))``."""
    n, k = local.shape
    e = w_gate.shape[1]
    flat = jnp.where(held, local, e).reshape(n * k)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=e + 1)[:e].astype(jnp.int32)
    group = jnp.take(flat, order)
    rows = jnp.take(x, order // k, axis=0)  # (n·k, h), expert-contiguous
    mm = lambda a, w: _grouped_matmul(a, w, sizes, group, layer, precision)
    out = mm(jax.nn.silu(mm(rows, w_gate)) * mm(rows, w_up), w_down)
    inverse = jnp.zeros((n * k,), jnp.int32).at[order].set(jnp.arange(n * k, dtype=jnp.int32))
    back = jnp.take(out, inverse, axis=0).reshape(n, k, -1).astype(jnp.float32)
    # Rows outside every group are whatever the product left there: never read.
    return jnp.sum(jnp.where(held[..., None], back * weights[..., None], 0.0), axis=1), sizes


def expert_share_ffn(x, router_w, w_gate, w_up, w_down, *, first: int, k: int,
                     norm_topk_prob: bool = True, scale: float = 1.0,
                     precision: str = "default", row_mask=None, layer=None,
                     scoring: str = "softmax", bias=None):
    """The part of a routed expert layer that the experts held here give
    (``model-configs`` guide, section 4; what expert parallelism asks anyway).

    ``x``: (N, h) rows; ``router_w``: (h, R), the router at its whole width;
    ``w_gate``/``w_up``: (E, h, i) and ``w_down``: (E, i, h), the SwiGLU experts
    ``first .. first + E`` of the R; with ``layer`` (a traced index will do)
    the three are stacked over layers, (L, E, ...), and ``layer`` says which:
    a model that scans its layers hands the stacks over whole. Every row is
    routed over all R experts (float32 scores by ``scoring``, the ``k`` largest
    of score plus ``bias`` where one is given, renormalised under
    ``norm_topk_prob``, times ``scale``: :func:`route_top_k`); the result is the
    weighted sum over the claims that name an expert held here, and nothing
    for the others: **no claim is dropped, no capacity exists**, and no code
    stands in for absent experts. A row whose ``row_mask`` (N,) is false
    (bucket padding, a free slot's row) claims nothing. A shared expert is the
    caller's. Returns ``(out (N, h) in x's dtype, claims (E,) int32: the
    claims each held expert got)``.

    One form serves a decode step's handful of rows and a prefill chunk's
    thousand (:func:`_experts_grouped`): claims sorted by expert into
    ``lax.ragged_dot``, which reads the experts that got a claim and no
    others, so a decode step is bound by the bytes of the experts touched and
    a chunk by its grouped products (``PERF.md``, PR 36, has every form timed
    on the chip at both shapes). ``precision`` is ``ops/int8.matmul``'s, and
    reaches the experts' products; the router stays float32 under either."""
    if layer is None:
        layer, (w_gate, w_up, w_down) = 0, (w_gate[None], w_up[None], w_down[None])
    experts_held = w_gate.shape[1]
    weights, chosen = route_top_k(router_logits(x, router_w), k, norm_topk_prob=norm_topk_prob,
                                  scoring=scoring, bias=bias)
    weights = weights * scale
    local = chosen - first
    held = (local >= 0) & (local < experts_held)
    if row_mask is not None:
        held = held & row_mask.astype(bool)[:, None]
    out, claims = _experts_grouped(x, weights, local, held, w_gate, w_up, w_down, layer, precision)
    return out.astype(x.dtype), claims


def _constrain_expert_layout(t):
    """Pin (E, B, C, ...) intermediates to expert-major sharding: E on ``ep``,
    B on the data axes — guarantees the partitioner keeps expert compute on
    the expert's own shard instead of gathering expert weights to the tokens."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.sharding import data_batch_axes
    from ..state import PartialState

    try:
        mesh = PartialState().mesh
    except Exception:
        return t
    if mesh is None or mesh.shape.get("ep", 1) == 1:
        return t
    axes = data_batch_axes()
    spec = P("ep", axes if axes else None, *([None] * (t.ndim - 2)))
    return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, spec))
