"""Attribute the vocab128k train step's time at the op level on the real chip.

The `BENCH_CONFIG=vocab128k` row (Llama-3.2-proportioned h2048/i8192, V=128256
tied) trails the swept-shape headline because of non-matmul overhead that an
end-to-end MFU number cannot localize. This script times fwd+bwd of each piece
at the bench shape so the tax is measured, not guessed:

- ``embed``: the (V, h) table lookup (+ scatter-add backward);
- ``block`` / ``mlp``: one decoder layer and its SwiGLU FFN in isolation
  (attention ≈ block − mlp);
- ``layers_<policy>``: the full L-layer remat'd scan per BENCH_REMAT_POLICY;
- ``head_dense``: final norm + full-logit matmul + CE (the path that cannot
  compile at b8 on a 16G chip — expect OOM there, that is the finding);
- ``head_fused_*``: the vocab-chunked streaming CE across the sweep surface —
  chunk sizes (BENCH_VOCAB_CHUNK, comma list), chunk dtype (BENCH_FUSED_DTYPE),
  backward strategy (BENCH_FUSED_BWD: custom|ad|both) and scan unroll
  (BENCH_FUSED_UNROLL).

The same envs drive bench.py's vocab128k config, so a winning knob found here
is re-checked end-to-end by exporting the identical variables. Model code runs
under ``jax.named_scope`` tags (embed/attn/mlp/lm_head), so a captured profile
(``jax.profiler.trace``) attributes to the same names these probes use.

Prints one JSON line per probe. BENCH_PROFILE_SMALL=1 shrinks every dimension
for CPU smoke runs (used by the test suite).

Usage: python benchmarks/vocab128k_profile.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

SMALL = os.environ.get("BENCH_PROFILE_SMALL", "0") == "1"
if SMALL:
    H, I, V, L, HEADS, KV, B, S = 64, 128, 1000, 2, 4, 2, 2, 32
    STEPS, WARMUP = 3, 1
    CHUNKS = [int(c) for c in os.environ.get("BENCH_VOCAB_CHUNK", "256,512").split(",")]
else:
    H, I, V, L, HEADS, KV, B, S = 2048, 8192, 128256, 8, 32, 8, 8, 1024
    STEPS, WARMUP = 20, 3
    CHUNKS = [int(c) for c in os.environ.get("BENCH_VOCAB_CHUNK", "4096,8192,16384,32768").split(",")]
T = B * S
DTYPE = jnp.bfloat16


def bench(name, fn, *args, flops=None, grad_argnums=0):
    f = jax.jit(jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(), argnums=grad_argnums))
    try:
        for _ in range(WARMUP):
            out = f(*args)
    except Exception as exc:  # OOM / compile rejection IS a datapoint
        print(json.dumps({"probe": name, "error": f"{type(exc).__name__}: {exc}"[:200]}))
        return None
    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(leaf[..., 0:1])  # fetch: ends warm-up
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = f(*args)
    np.asarray(jax.tree_util.tree_leaves(out)[0][..., 0:1])
    dt = (time.perf_counter() - t0) / STEPS
    rec = {"probe": name, "ms": round(dt * 1e3, 3)}
    if flops:
        rec["tflops_s"] = round(flops / dt / 1e12, 1)
    print(json.dumps(rec))
    return dt


def main():
    from accelerate_tpu.models import Llama, LlamaConfig
    from accelerate_tpu.ops.losses import cross_entropy_loss, fused_cross_entropy_loss

    rng = np.random.default_rng(0)
    cfg = LlamaConfig(
        vocab_size=V, hidden_size=H, intermediate_size=I,
        num_hidden_layers=L, num_attention_heads=HEADS, num_key_value_heads=KV,
        max_position_embeddings=S, tie_word_embeddings=True,
    )
    model = Llama(cfg)
    params = jax.tree_util.tree_map(
        lambda t: t.astype(DTYPE), model.init_params(jax.random.key(0))
    )
    ids = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    x = jnp.asarray(rng.standard_normal((B, S, H)), DTYPE)
    table = params["embed"]["weight"]  # (V, H) — the tied head, native layout

    # (a) embedding lookup + scatter-add backward.
    def embed_fn(table):
        h, _ = model.embed({"embed": {"weight": table}}, ids)
        return h

    bench("embed", embed_fn, table)

    # (b) one decoder block and its FFN alone (attention ≈ block − mlp).
    layer = jax.tree_util.tree_map(lambda t: t[0], params["layers"])
    _, ctx = model.embed(params, ids)
    block_flops = 3 * 2 * T * (H * (HEADS + 2 * KV) * cfg.head_dim + HEADS * cfg.head_dim * H + 3 * H * I)
    mlp_flops = 3 * 2 * T * 3 * H * I
    bench("block", lambda x: model.block(layer, x, ctx), x, flops=block_flops)
    bench("mlp", lambda x: model.mlp(layer, x), x, flops=mlp_flops)

    # (c) the full remat'd layer stack per policy (BENCH_REMAT_POLICY, comma
    # list; names_saveable exercises the checkpoint_name tags).
    policies = os.environ.get(
        "BENCH_REMAT_POLICY", "dots_with_no_batch_dims_saveable,names_saveable"
    ).split(",")
    for policy in [p.strip() for p in policies if p.strip()]:
        import dataclasses

        m2 = Llama(dataclasses.replace(cfg, remat=True, remat_policy=policy))

        def layers_fn(x, _m=m2):
            out, _ = _m._run_layers(params["layers"], x, ctx)
            return out

        bench(f"layers_{policy}", layers_fn, x, flops=L * block_flops)

    # (d) the head: dense full-logit CE vs the fused sweep. 3 matmul passes
    # (fwd + dx + dw) for dense; the fused custom backward pays 4 (fwd +
    # recompute + dx + dw), its structural overhead.
    head_flops_dense = 3 * 2 * T * H * V
    head_flops_fused = 4 * 2 * T * H * V
    shifted = jnp.asarray(labels)

    def head_dense(x, table):
        logits = jax.lax.dot_general(x, table.astype(x.dtype), (((2,), (1,)), ((), ())))
        return cross_entropy_loss(logits, shifted)

    bench("head_dense", head_dense, x, table, flops=head_flops_dense, grad_argnums=(0, 1))

    dtypes = [d for d in os.environ.get("BENCH_FUSED_DTYPE", "fp32,bf16").split(",") if d]
    bwds = os.environ.get("BENCH_FUSED_BWD", "both")
    bwds = ["custom", "ad"] if bwds == "both" else [bwds]
    unroll = int(os.environ.get("BENCH_FUSED_UNROLL", "1"))
    for chunk in CHUNKS:
        for cd in dtypes:
            for bwd in bwds:

                def head_fused(x, table, _c=chunk, _cd=cd, _b=bwd):
                    return fused_cross_entropy_loss(
                        x, table.astype(x.dtype), shifted,
                        vocab_chunk=_c, chunk_dtype=_cd, unroll=unroll,
                        head_transposed=True, custom_backward=_b == "custom",
                    )

                bench(
                    f"head_fused_c{chunk}_{cd}_{bwd}", head_fused, x, table,
                    flops=head_flops_fused, grad_argnums=(0, 1),
                )


if __name__ == "__main__":
    main()
