"""Attribute int8 weight-quantized serving end-to-end.

The kernel-backed int8 matmul (``ops/int8.py``, routed through every Llama
projection when ``matmul_precision="int8"``) already carries op-level
microbenches; this profile prices the precision policy where it ships — the
serving forward — against the default-precision wave:

- ``matmul_{default,int8}``: op-level decode-shaped matmul at each
  precision (activation row-quant + int8 MXU dot vs the default dot).
- ``wave_{default,int8}``: the mixed-length serving wave under each
  precision policy — tokens/s plus token-level divergence (weight
  quantization shifts logits; greedy outputs may diverge — the fraction is
  the signal, bit-identity is NOT the contract here, unlike spec decode).

Prints one JSON line per probe; ``summarize()`` returns the dict bench.py
embeds as ``detail.serving.int8_serving`` under ``BENCH_INT8_SERVING=1``.
``BENCH_PROFILE_SMALL=1`` shrinks everything for CPU smoke runs.

Usage: python benchmarks/int8_serving_profile.py
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
        return dict(layers=2, heads=4, kv=2, hidden=64, inter=128, vocab=256,
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


def probe_matmul(s):
    """Op-level: a decode-shaped projection at each precision."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.ops.int8 import matmul

    rng = np.random.default_rng(0)
    b, h, inter = s["slots"], s["hidden"], s["inter"]
    x = jnp.asarray(rng.standard_normal((b, h)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((h, inter)), jnp.float32)

    f_def = jax.jit(lambda x, w: matmul(x, w, precision="default"))
    f_q = jax.jit(lambda x, w: matmul(x, w, precision="int8"))

    def timeit(f):
        out = f(x, w)
        np.asarray(out[..., 0:1])
        steps = 5 if SMALL else 100
        t0 = time.perf_counter()
        for _ in range(steps):
            out = f(x, w)
        np.asarray(out[..., 0:1])
        return (time.perf_counter() - t0) / steps

    t_def = timeit(f_def)
    t_q = timeit(f_q)
    return {
        "matmul_default_ms": round(t_def * 1e3, 4),
        "matmul_int8_ms": round(t_q * 1e3, 4),
        "int8_speedup_x": round(t_def / max(t_q, 1e-9), 2),
    }


def probe_wave(model, s, precision: str | None):
    import jax.numpy as jnp

    from accelerate_tpu.serving import ContinuousBatcher

    engine = ContinuousBatcher(
        model, batch_slots=s["slots"], max_new_tokens=s["max_new"],
        max_cache_len=4096 if not SMALL else 1024, cache_dtype=jnp.float32,
        bucket_sizes=s["buckets"], sync_every=s["sync"],
        block_size=s["block"], matmul_precision=precision,
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
        "mode": precision or "default",
        "wall_s": round(dt, 4),
        "tokens_per_sec": round(gen / dt, 1),
    }, [outs[r] for r in rids]


def summarize(model=None):
    """Run every probe; returns the ``detail.serving.int8_serving`` dict."""
    s = _shapes()
    if model is None:
        model = _build_model(s)
    out = {"small": SMALL}
    out.update(probe_matmul(s))
    wave_d, outs_d = probe_wave(model, s, None)
    wave_q, outs_q = probe_wave(model, s, "int8")
    out["wave_default"] = wave_d
    out["wave_int8"] = wave_q
    total = sum(len(a) for a in outs_d)
    diverged = sum(
        int(np.sum(np.asarray(a)[: min(len(a), len(b))]
                   != np.asarray(b)[: min(len(a), len(b))]))
        + abs(len(a) - len(b))
        for a, b in zip(outs_d, outs_q)
    )
    out["tokens_total"] = total
    out["tokens_diverged"] = int(diverged)
    out["divergence_fraction"] = round(diverged / max(total, 1), 4)
    out["serving_speedup_x"] = round(
        wave_q["tokens_per_sec"] / max(wave_d["tokens_per_sec"], 1e-9), 3)
    return out


def main():
    summary = summarize()
    for key in ("matmul_default_ms", "matmul_int8_ms", "int8_speedup_x"):
        print(json.dumps({"probe": key, "value": summary[key]}))
    for key in ("wave_default", "wave_int8"):
        print(json.dumps({"probe": key, **summary[key]}))
    print(json.dumps({
        "probe": "headline",
        "serving_speedup_x": summary["serving_speedup_x"],
        "divergence_fraction": summary["divergence_fraction"],
    }))


if __name__ == "__main__":
    main()
