"""Attribute the int8 KV-cache pool on the paged serving engine.

int8 KV blocks store quantized k/v with one f32 scale per token row, so the
pool holds ~2x the tokens per HBM byte (the exact ratio is
``4HD / (2HD + 8)`` per token — per-token scales amortize away as the head
dim grows). The cost is a dequant on every gather, which the Pallas paged
kernels fold into the DMA-to-VMEM step. This profile prices both sides:

- ``pool_capacity``: ``kv_cache_bytes`` for the fp32 vs int8 pool at the
  same block count — the capacity_x ratio IS the >= 1.8x acceptance gate.
- ``gather_{fp,int8}``: op-level view assembly (``gather_view``) against
  each pool layout — the dequant tax at the seam the kernel optimizes.
- ``wave_{fp,int8}``: the mixed-length wave in each pool dtype —
  tokens/s plus the token-level divergence count (quantization noise is
  allowed; the pinned tolerance lives in tests/test_speculative.py).

Prints one JSON line per probe; ``summarize()`` returns the dict bench.py
embeds as ``detail.serving.kv_quant`` under ``BENCH_KV_QUANT=1``.
``BENCH_PROFILE_SMALL=1`` shrinks everything for CPU smoke runs.

Usage: python benchmarks/kv_quant_profile.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


SMALL = os.environ.get("BENCH_PROFILE_SMALL", "0") == "1"


def _shapes():
    if SMALL:
        return dict(layers=2, heads=4, kv=2, hidden=128, inter=256, vocab=256,
                    slots=2, max_new=8, sync=2, block=4,
                    prompt_lens=(5, 14, 3, 12, 7, 4), buckets=(8, 16))
    return dict(layers=8, heads=16, kv=8, hidden=1024, inter=4096, vocab=32000,
                slots=8, max_new=64, sync=8, block=16,
                prompt_lens=(33, 180, 12, 250, 96, 40, 140, 64),
                buckets=(64, 128, 256))


def _build_model(s):
    import jax

    from accelerate_tpu.models import Llama, LlamaConfig

    cfg = LlamaConfig.tiny(
        vocab_size=s["vocab"], hidden_size=s["hidden"],
        intermediate_size=s["inter"], num_hidden_layers=s["layers"],
        num_attention_heads=s["heads"], num_key_value_heads=s["kv"],
    )
    model = Llama(cfg)
    model.init_params(jax.random.key(0))
    return model


def probe_gather(s):
    """Op-level: paged view assembly from an fp32 vs int8 pool at the same
    logical shape — the dequant tax at the DMA seam."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.ops.paged_attention import gather_view

    rng = np.random.default_rng(0)
    b, bs = s["slots"], s["block"]
    m = max(2, (max(s["prompt_lens"]) + s["max_new"]) // bs + 1)
    hkv, d = s["kv"], s["hidden"] // s["heads"]
    n = b * m + 1
    pool_f = jnp.asarray(rng.standard_normal((n, bs, hkv, d)), jnp.float32)
    scale = jnp.abs(pool_f).max(axis=(-2, -1)) / 127.0
    pool_q = jnp.round(pool_f / scale[..., None, None]).astype(jnp.int8)
    tables = jnp.asarray(1 + np.arange(b * m, dtype=np.int32).reshape(b, m))

    f_fp = jax.jit(lambda p: gather_view(p, tables))
    f_q = jax.jit(lambda p, sc: gather_view(p, tables, scales=sc,
                                            out_dtype=jnp.float32))

    def timeit(f, *args):
        out = f(*args)
        np.asarray(out[..., 0:1])
        steps = 5 if SMALL else 50
        t0 = time.perf_counter()
        for _ in range(steps):
            out = f(*args)
        np.asarray(out[..., 0:1])
        return (time.perf_counter() - t0) / steps

    t_fp = timeit(f_fp, pool_f)
    t_q = timeit(f_q, pool_q, scale)
    return {
        "gather_fp_ms": round(t_fp * 1e3, 4),
        "gather_int8_ms": round(t_q * 1e3, 4),
        "dequant_overhead_x": round(t_q / max(t_fp, 1e-9), 2),
    }


def probe_wave(model, s, quant: bool):
    import jax.numpy as jnp

    from accelerate_tpu.serving import ContinuousBatcher

    engine = ContinuousBatcher(
        model, batch_slots=s["slots"], max_new_tokens=s["max_new"],
        max_cache_len=4096 if not SMALL else 1024, cache_dtype=jnp.float32,
        bucket_sizes=s["buckets"], sync_every=s["sync"],
        block_size=s["block"], kv_quant="int8" if quant else None,
    )
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, s["vocab"], (n,)).astype(np.int32)
               for n in s["prompt_lens"]]
    rids = [engine.submit(p) for p in prompts]
    t0 = time.perf_counter()
    outs = engine.run()
    dt = time.perf_counter() - t0
    gen = sum(len(outs[r]) for r in rids)
    return {
        "mode": "int8" if quant else "fp",
        "wall_s": round(dt, 4),
        "tokens_per_sec": round(gen / dt, 1),
        "kv_cache_bytes": engine.kv_cache_bytes,
    }, [outs[r] for r in rids]


def summarize(model=None):
    """Run every probe; returns the ``detail.serving.kv_quant`` dict."""
    s = _shapes()
    if model is None:
        model = _build_model(s)
    out = {"small": SMALL, "block_size": s["block"]}
    out.update(probe_gather(s))
    wave_f, outs_f = probe_wave(model, s, quant=False)
    wave_q, outs_q = probe_wave(model, s, quant=True)
    out["wave_fp"] = wave_f
    out["wave_int8"] = wave_q
    out["pool_capacity_x"] = round(
        wave_f["kv_cache_bytes"] / max(wave_q["kv_cache_bytes"], 1), 3)
    total = sum(len(a) for a in outs_f)
    diverged = sum(
        int(np.sum(np.asarray(a)[: min(len(a), len(b))]
                   != np.asarray(b)[: min(len(a), len(b))]))
        + abs(len(a) - len(b))
        for a, b in zip(outs_f, outs_q)
    )
    out["tokens_total"] = total
    out["tokens_diverged"] = int(diverged)
    out["divergence_fraction"] = round(diverged / max(total, 1), 4)
    return out


def main():
    summary = summarize()
    for key in ("gather_fp_ms", "gather_int8_ms", "dequant_overhead_x"):
        print(json.dumps({"probe": key, "value": summary[key]}))
    for key in ("wave_fp", "wave_int8"):
        print(json.dumps({"probe": key, **summary[key]}))
    print(json.dumps({
        "probe": "headline",
        "pool_capacity_x": summary["pool_capacity_x"],
        "divergence_fraction": summary["divergence_fraction"],
    }))


if __name__ == "__main__":
    main()
