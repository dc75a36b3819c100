"""Operations and bytes the algorithm REQUIRES, computed from shapes.

The yardstick's numerators. A decoder's training step needs, for each token:

- the matrix multiplications of every layer and of the output head, forward
  and backward: 6 floating-point operations for each weight they multiply by;
- causal attention, each (query, key) pair counted once: 4·D operations a pair
  and head forward (scores and mix), twice that backward.

Not counted, because the algorithm does not require them: the embedding
gather, the upper triangle a kernel may compute and mask, recomputation under
``remat``, norms, rope and the other elementwise work.
"""

from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def layer_matmul_params(cfg: dict) -> int:
    """Weights that one decoder layer multiplies a token by (dense SwiGLU)."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    q = h * cfg["num_attention_heads"] * d
    kv = 2 * h * cfg["num_key_value_heads"] * d
    o = cfg["num_attention_heads"] * d * h
    mlp = 3 * h * cfg["intermediate_size"]
    return q + kv + o + mlp


def head_matmul_params(cfg: dict) -> int:
    """The output head's matrix (tied or not, it multiplies every token)."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    """Every parameter of the model as the program holds it (for sizes only)."""
    h, d, layers = cfg["hidden_size"], head_dim(cfg), cfg["num_hidden_layers"]
    per_layer = layer_matmul_params(cfg) + 2 * h + (2 * d if cfg.get("qk_norm") else 0)
    embed = cfg["vocab_size"] * h
    head = 0 if cfg.get("tie_word_embeddings") else embed
    return layers * per_layer + embed + head + h


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def attention_flops_forward(batch: int, heads: int, seq: int, dim: int) -> int:
    """Causal attention forward: QK^T and PV, 2·D each for every pair and head."""
    return 4 * dim * heads * batch * causal_pairs(seq)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Required operations of one training step, per token of the batch."""
    matmul = 6 * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                  + head_matmul_params(cfg))
    attn_seq = 3 * cfg["num_hidden_layers"] * attention_flops_forward(
        1, cfg["num_attention_heads"], seq, head_dim(cfg))
    return matmul + attn_seq / seq


def flash_call_cost(batch: int, heads: int, seq: int, dim: int, *,
                    backward: bool, bytes_per_el: int = 2) -> dict:
    """Required operations and HBM bytes of one flash-attention call on
    ``(batch, heads, seq, dim)`` operands (keys and values already repeated to
    ``heads``, as the program hands them to the kernel).

    Forward reads Q, K, V and writes O. Backward (all of its kernels together)
    reads Q, K, V, O, dO and writes dQ, dK, dV; its required operations are
    twice the forward's (four matrix products against two); the recomputed
    scores are not required work. The row statistics are left out of the bytes.
    """
    tensor = batch * heads * seq * dim * bytes_per_el
    fwd = attention_flops_forward(batch, heads, seq, dim)
    if backward:
        return {"flops": 2 * fwd, "bytes": 8 * tensor}
    return {"flops": fwd, "bytes": 4 * tensor}


def roofline_seconds(cost: dict, peaks: dict) -> dict:
    """Least time the chip could take, and which of the two bounds sets it."""
    by_flops = cost["flops"] / peaks["bf16_flops"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory"}
