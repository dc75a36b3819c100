"""Big-model-inference benchmark: load-time + s/token for dispatched models.

Counterpart of the reference's ``benchmarks/big_model_inference/
big_model_inference.py`` (load a checkpoint with a device_map — possibly
CPU/disk-offloaded — and measure model load time and generation latency;
published numbers in BASELINE.md's big-model table).

Scenarios measured, each printed as one JSON line:
  1. ``on_chip``      — checkpoint → load_checkpoint_and_dispatch(device_map
     'auto') with everything HBM-resident; fused scan-decode generation.
  2. ``cpu_offload``  — layers forced to host RAM, streamed per token
     (StreamedScanModel double-buffered DMA) — the OPT-30B-style config.
  3. ``disk_offload`` — layers memmapped from disk (GPT-NeoX-fp32-style).

Usage: python benchmarks/big_model_inference.py [tiny|medium|1b|3b] [--tokens N]
Default size: 1b on TPU, tiny elsewhere.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

SIZES = {
    # name -> (hidden, inter, layers, heads, kv_heads, vocab)
    "tiny": (64, 128, 2, 4, 2, 256),
    "medium": (512, 1408, 8, 8, 4, 8192),
    "1b": (2048, 5632, 22, 16, 4, 32000),
    "3b": (3072, 8192, 26, 24, 8, 32000),
}


def build(size: str, family: str = "llama"):
    h, inter, L, nh, nkv, vocab = SIZES[size]
    if family == "llama":
        from accelerate_tpu.models import Llama, LlamaConfig

        return Llama(LlamaConfig(
            vocab_size=vocab, hidden_size=h, intermediate_size=inter,
            num_hidden_layers=L, num_attention_heads=nh, num_key_value_heads=nkv,
            max_position_embeddings=2048,
        ))
    # The baseline's own architectures (BASELINE.md tables: GPT-J / GPT-NeoX /
    # OPT) at the scaled-down SIZES shapes — same three placement regimes.
    from accelerate_tpu.models import GPTX, GPTXConfig

    rotary_dim = max(2, (h // nh) // 4 // 2 * 2)
    recipes = {
        "neox": dict(position_style="rotary_neox", rotary_dim=rotary_dim),
        "gptj": dict(position_style="rotary_gptj", rotary_dim=rotary_dim,
                     shared_layernorm=True, attention_bias=False, lm_head_bias=True),
        "opt": dict(position_style="learned", position_offset=2,
                    parallel_residual=False, hidden_act="relu",
                    tie_word_embeddings=True),
    }
    return GPTX(GPTXConfig(
        vocab_size=vocab, hidden_size=h, intermediate_size=inter,
        num_hidden_layers=L, num_attention_heads=nh,
        max_position_embeddings=2048, **recipes[family],
    ))


def run_scenario(name, size, family, checkpoint, device_map, offload_dir,
                 prompt_len, n_tokens):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu import load_checkpoint_and_dispatch
    from accelerate_tpu.big_modeling import init_empty_weights
    from accelerate_tpu.generation import generate

    with init_empty_weights():
        model = build(size, family)
        model.init_params(jax.random.key(0))
    # The dispatched model may come back wrapped (StreamedScanModel for the
    # offload regimes) — read static facts off the bare zoo model now.
    n_params, vocab = model.num_params(), model.config.vocab_size

    t0 = time.perf_counter()
    model = load_checkpoint_and_dispatch(
        model, checkpoint, device_map=device_map, offload_folder=offload_dir
    )
    load_time = time.perf_counter() - t0

    ids = np.random.default_rng(0).integers(0, vocab, (1, prompt_len)).astype(np.int32)

    # Warmup (compile) with a 2-token generation, then timed run.
    generate(model, ids, max_new_tokens=2, cache_dtype=jnp.bfloat16).block_until_ready()
    t0 = time.perf_counter()
    out = generate(model, ids, max_new_tokens=n_tokens, cache_dtype=jnp.bfloat16)
    out.block_until_ready()
    gen_time = time.perf_counter() - t0

    print(json.dumps({
        "scenario": name,
        "model": f"{family}-{size}",
        "params": n_params,
        "load_time_s": round(load_time, 3),
        "s_per_token": round(gen_time / n_tokens, 4),
        "tokens_per_s": round(n_tokens / gen_time, 2),
        "backend": jax.default_backend(),
    }))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("size", nargs="?", default=None, choices=list(SIZES))
    parser.add_argument("--family", default="llama",
                        choices=["llama", "neox", "gptj", "opt"],
                        help="architecture recipe; neox/gptj/opt mirror the "
                             "reference baseline's own model families")
    parser.add_argument("--tokens", type=int, default=32)
    parser.add_argument("--prompt-len", type=int, default=64)
    parser.add_argument("--scenarios", default="on_chip,cpu_offload,disk_offload")
    args = parser.parse_args()

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import jax

    backend = jax.default_backend()
    size = args.size or ("1b" if backend == "tpu" else "tiny")

    from accelerate_tpu.checkpointing import export_full_weights

    # Materialize a real checkpoint once so load time is measured honestly.
    model = build(size, args.family)
    model.init_params(jax.random.key(0))
    tmp = tempfile.mkdtemp(prefix="bmi_ckpt_")
    export_full_weights(model.params, tmp, max_shard_size="1GB")
    top_keys = list(model.params)
    del model

    def offload_map(where):
        # Layer stack offloaded; every other top-level group stays HBM-resident
        # (key names differ per family: final_norm/ln_f, optional lm_head/wpe).
        return {k: ("tpu:0" if k != "layers" else where) for k in top_keys}

    scenarios = {
        "on_chip": ("auto", None),
        "cpu_offload": (offload_map("cpu"), None),
        "disk_offload": (offload_map("disk"), tempfile.mkdtemp(prefix="bmi_disk_")),
    }
    for name in args.scenarios.split(","):
        device_map, offload_dir = scenarios[name]
        run_scenario(name, size, args.family, tmp, device_map, offload_dir,
                     args.prompt_len, args.tokens)


if __name__ == "__main__":
    main()
