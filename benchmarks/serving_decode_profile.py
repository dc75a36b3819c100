"""Attribute a serving decode wave at the op and engine level.

The serving acceptance criteria are ratios, not absolutes — this script
measures them the way ``vocab128k_profile.py`` measures the fused-loss sweep:
probe-by-probe, so a regression (or the future Pallas paged kernel's win,
ROADMAP item 3) is attributed instead of guessed:

- ``decode_attention_{contiguous,paged}``: the op-level seam — one decode
  step's attention against a contiguous cache (``cached_attention``) vs
  block tables (``paged_attention``'s reference gather lowering) at the same
  logical shape. The gap between these two IS the gather tax the Pallas
  kernel exists to kill.
- ``wave_paged``: a mixed-length wave through ``ContinuousBatcher``, each
  request's tokens held to solo ``generate()`` — tokens/s, observed
  TTFT/TPOT, and admitted tokens per consumed KV slot.
- ``prefill_{monolithic,chunked}``: a long prompt admitted mid-wave, with
  the max gap between consecutive decode windows recorded — chunked prefill
  must bound per-step decode stall by one chunk's compute (the <= 2x
  criterion), where monolithic prefill stalls by the whole prompt.

Prints one JSON line per probe; ``summarize()`` returns the same dict that
``bench.py`` embeds as ``detail.serving`` under ``BENCH_SERVING=1``.
``BENCH_PROFILE_SMALL=1`` shrinks everything for CPU smoke runs (the test
suite's path).

Usage: python benchmarks/serving_decode_profile.py
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
                    prompt_lens=(5, 14, 3, 12, 7, 4), long_len=21,
                    chunk=8, buckets=(8, 16), mono_bucket=32)
    return dict(layers=8, heads=16, kv=8, hidden=1024, inter=4096, vocab=32000,
                slots=8, max_new=64, sync=8, block=16,
                prompt_lens=(33, 180, 12, 250, 96, 40, 140, 64), long_len=480,
                chunk=128, buckets=(64, 128, 256), mono_bucket=512)


class _TimedBatcher:
    """Wrap a ContinuousBatcher subclass-style: record the wall gap between
    consecutive decode-window completions (the report fetch blocks until the
    window's compute lands, so on a real chip the gap IS window latency plus
    whatever prefill interleaved ahead of it)."""

    def __init__(self, engine):
        self.engine = engine
        self.window_gaps = []
        self._last_t = None
        orig = engine._process_report

        def timed(report, force_stop):
            orig(report, force_stop)
            t = time.perf_counter()
            if self._last_t is not None:
                self.window_gaps.append(t - self._last_t)
            self._last_t = t

        engine._process_report = timed


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


def probe_decode_attention(s):
    """Op-level: one decode step's attention, contiguous vs paged gather."""
    import jax.numpy as jnp

    from accelerate_tpu.ops.attention import cached_attention
    from accelerate_tpu.ops.paged_attention import paged_attention

    rng = np.random.default_rng(0)
    b, bs = s["slots"], s["block"]
    m = max(2, (max(s["prompt_lens"]) + s["max_new"]) // bs + 1)
    k_len = m * bs
    hkv, d, h = s["kv"], s["hidden"] // s["heads"], s["heads"]
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    k_cache = jnp.asarray(rng.standard_normal((b, k_len, hkv, d)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((b, k_len, hkv, d)), jnp.float32)
    n = b * m + 1
    k_pool = jnp.asarray(rng.standard_normal((n, bs, hkv, d)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((n, bs, hkv, d)), jnp.float32)
    tables = jnp.asarray(1 + np.arange(b * m, dtype=np.int32).reshape(b, m))
    pool_mask = jnp.ones((n, bs), jnp.int32)
    kv_mask = jnp.ones((b, k_len), jnp.int32)
    q_pos = jnp.full((b, 1), k_len - 1, jnp.int32)

    import jax

    f_cont = jax.jit(lambda q, k, v: cached_attention(
        q, k, v, q_positions=q_pos, kv_mask=kv_mask))
    f_paged = jax.jit(lambda q, kp, vp: paged_attention(
        q, kp, vp, tables, q_positions=q_pos, pool_mask=pool_mask))

    def timeit(f, *args):
        out = f(*args)
        np.asarray(out[..., 0:1])
        steps = 5 if SMALL else 50
        t0 = time.perf_counter()
        for _ in range(steps):
            out = f(*args)
        np.asarray(out[..., 0:1])
        return (time.perf_counter() - t0) / steps

    t_cont = timeit(f_cont, q, k_cache, v_cache)
    t_paged = timeit(f_paged, q, k_pool, v_pool)
    return {
        "decode_attention_contiguous_ms": round(t_cont * 1e3, 4),
        "decode_attention_paged_ms": round(t_paged * 1e3, 4),
        "gather_overhead_x": round(t_paged / max(t_cont, 1e-9), 2),
    }


def probe_wave(model, s):
    """A mixed-length wave through the engine: throughput, latency
    accounting, and consumed-capacity; returns the prompts and outputs for
    the parity join."""
    import jax.numpy as jnp

    from accelerate_tpu.serving import ContinuousBatcher

    engine = ContinuousBatcher(
        model, batch_slots=s["slots"], max_new_tokens=s["max_new"],
        max_cache_len=4096 if not SMALL else 1024, cache_dtype=jnp.float32,
        bucket_sizes=s["buckets"], sync_every=s["sync"], block_size=s["block"])
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, s["vocab"], (n,)).astype(np.int32)
               for n in s["prompt_lens"]]
    rids = [engine.submit(p) for p in prompts]
    t0 = time.perf_counter()
    outs = engine.run()
    dt = time.perf_counter() - t0
    gen = sum(len(outs[r]) for r in rids)
    admitted = gen + sum(p.size for p in prompts)
    report = engine.slo_report()
    return {
        "wall_s": round(dt, 4),
        "tokens_per_sec": round(gen / dt, 1),
        "admitted_tokens": admitted,
        "consumed_kv_slots_peak": engine.kv_consumed_slots_peak,
        "tokens_per_kv_slot": round(admitted / engine.kv_consumed_slots_peak, 4),
        "kv_cache_bytes": engine.kv_cache_bytes,
        "ttft_s": [round(x, 5) for x in report["ttft_s"]],
        "tpot_s": [round(x, 6) for x in report["tpot_s"]],
        # Per-request lifecycle summary (telemetry/requests.py): TTFT/TPOT
        # quantiles + the slowest-request table — summarize() hoists it
        # to detail.serving.requests (schema v11).
        "requests": engine.tracer.summary() if engine.tracer is not None else None,
    }, prompts, [outs[r] for r in rids]


def probe_prefill_stall(model, s, mode: str):
    """Decode-window pacing with a long prompt admitted mid-wave ("chunked" /
    "monolithic" — both through the paged engine, so the ONLY variable is the
    chunking policy) or with no admission at all ("none" — the no-admit
    baseline the <= 2x stall criterion is measured against)."""
    import jax.numpy as jnp

    from accelerate_tpu.serving import ContinuousBatcher

    chunked = mode == "chunked"
    buckets = s["buckets"] if chunked else tuple(
        sorted(set(s["buckets"]) | {s["mono_bucket"]})
    )
    engine = ContinuousBatcher(
        model, batch_slots=s["slots"], max_new_tokens=s["max_new"],
        max_cache_len=4096 if not SMALL else 1024, cache_dtype=jnp.float32,
        bucket_sizes=buckets, sync_every=s["sync"], block_size=s["block"],
        prefill_chunk=s["chunk"] if chunked else s["mono_bucket"],
        max_tokens_per_request=s["mono_bucket"] + s["max_new"] + s["chunk"],
    )
    timer = _TimedBatcher(engine)
    rng = np.random.default_rng(9)
    short = rng.integers(1, s["vocab"], (s["prompt_lens"][0],)).astype(np.int32)
    long_p = rng.integers(1, s["vocab"], (s["long_len"],)).astype(np.int32)
    engine.submit(short)       # establishes the decode wave
    if mode != "none":
        engine.submit(long_p)  # admitted mid-wave: the stall source
    outs = engine.run()
    # Drop the first gap: it carries the one-time chunk/decode program
    # compiles, which on tiny smoke shapes dwarf the steady-state window.
    gaps = timer.window_gaps[1:] if len(timer.window_gaps) > 1 \
        else timer.window_gaps or [0.0]
    chunks = sum(1 for e in engine._dispatch_log if e.startswith("chunk"))
    return {
        "mode": mode,
        "prefill_dispatches": chunks,
        "max_window_gap_s": round(max(gaps), 5),
        "mean_window_gap_s": round(sum(gaps) / len(gaps), 5),
        "max_decode_step_stall_s": round(max(gaps) / s["sync"], 6),
    }, outs


def summarize(model=None):
    """Run every probe; returns the ``detail.serving`` dict for bench.py."""
    s = _shapes()
    if model is None:
        model = _build_model(s)
    out = {"small": SMALL, "sync_every": s["sync"], "block_size": s["block"]}
    out.update(probe_decode_attention(s))
    import jax.numpy as jnp

    from accelerate_tpu.generation import generate

    wave_p, prompts, outs_p = probe_wave(model, s)
    identical = all(
        np.array_equal(got, np.asarray(generate(
            model, prompt[None], max_new_tokens=s["max_new"], temperature=0.0,
            include_prompt=False, cache_dtype=jnp.float32))[0])
        for prompt, got in zip(prompts, outs_p))
    # The request-trace summary rides once at the top level (schema v11
    # detail.serving.requests).
    out["requests"] = wave_p.pop("requests", None)
    out["wave_paged"] = wave_p
    out["outputs_identical"] = bool(identical)
    none, _ = probe_prefill_stall(model, s, mode="none")
    mono, _ = probe_prefill_stall(model, s, mode="monolithic")
    chk, _ = probe_prefill_stall(model, s, mode="chunked")
    out["prefill_no_admit"] = none
    out["prefill_monolithic"] = mono
    out["prefill_chunked"] = chk
    out["stall_ratio_chunked_vs_monolithic"] = round(
        chk["max_window_gap_s"] / max(mono["max_window_gap_s"], 1e-9), 3
    )
    # The acceptance criterion's shape: chunked admission vs the no-admit
    # baseline (<= 2x on a compute-dominated rig; dispatch/compile-dominated
    # smoke shapes inflate it — read it from a real-chip BENCH_SERVING row).
    out["stall_ratio_chunked_vs_no_admit"] = round(
        chk["max_window_gap_s"] / max(none["max_window_gap_s"], 1e-9), 3
    )
    return out


def main():
    summary = summarize()
    for key in ("decode_attention_contiguous_ms", "decode_attention_paged_ms",
                "gather_overhead_x"):
        print(json.dumps({"probe": key, "value": summary[key]}))
    for key in ("wave_paged", "prefill_no_admit",
                "prefill_monolithic", "prefill_chunked"):
        print(json.dumps({"probe": key, **summary[key]}))
    print(json.dumps({
        "probe": "headline",
        "outputs_identical": summary["outputs_identical"],
        "stall_ratio_chunked_vs_monolithic":
            summary["stall_ratio_chunked_vs_monolithic"],
        "stall_ratio_chunked_vs_no_admit":
            summary["stall_ratio_chunked_vs_no_admit"],
    }))


if __name__ == "__main__":
    main()
