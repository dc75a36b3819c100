"""Attribute speculative decoding on the paged serving engine.

Speculative decoding's pitch is k-for-1: a tiny draft proposes k tokens per
slot and the target verifies the whole window in ONE paged forward, so the
target's per-token cost drops by the acceptance rate. This profile measures
that pitch the way ``serving_decode_profile.py`` measures the paged-capacity
pitch — probe by probe, against the non-speculative wave at IDENTICAL
outputs (greedy spec decode is bit-identical by construction; a mismatch
here is a correctness regression, not noise):

- ``wave_baseline``: the non-speculative paged wave — tokens/s and target
  decode dispatches.
- ``wave_spec_k{K}``: the same wave under speculation — tokens/s, verify
  dispatches (one per window instead of ``sync_every`` decode steps),
  proposed/accepted draft tokens, acceptance rate, accepted-tokens/s.
- ``headline``: outputs_identical verdict + the speedup and
  dispatch-reduction ratios.

The draft is the target itself in SMALL smoke runs (acceptance ~1 — probes
the machinery, not a real draft) and the zoo "tiny" preset otherwise.

Prints one JSON line per probe; ``summarize()`` returns the dict bench.py
embeds as ``detail.serving.spec`` under ``BENCH_SPEC=1``.
``BENCH_PROFILE_SMALL=1`` shrinks everything for CPU smoke runs.

Usage: python benchmarks/spec_decode_profile.py
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
                    slots=2, max_new=8, sync=2, block=4, ks=(2,),
                    prompt_lens=(5, 14, 3, 12, 7, 4), buckets=(8, 16))
    return dict(layers=8, heads=16, kv=8, hidden=1024, inter=4096, vocab=32000,
                slots=8, max_new=64, sync=8, block=16, ks=(2, 4),
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


def _build_draft(s, target):
    # SMALL: draft == target — deterministic full acceptance exercises the
    # whole verify/commit path without paying a second model's compiles on
    # the smoke rig. Full runs draft with the zoo "tiny" preset at the
    # target's vocab (the deployment shape).
    if SMALL:
        return target
    import jax

    from accelerate_tpu.models import Llama, LlamaConfig

    draft = Llama(LlamaConfig.tiny(
        vocab_size=s["vocab"],
        max_position_embeddings=target.config.max_position_embeddings,
    ))
    draft.init_params(jax.random.key(1))
    return draft


def probe_wave(model, s, k: int = 0, draft=None):
    """One paged wave; ``k > 0`` speculates with ``draft``. Returns the
    probe dict plus outputs for the bit-identity join."""
    import jax.numpy as jnp

    from accelerate_tpu.serving import ContinuousBatcher

    kw = dict(batch_slots=s["slots"], max_new_tokens=s["max_new"],
              max_cache_len=4096 if not SMALL else 1024,
              cache_dtype=jnp.float32, bucket_sizes=s["buckets"],
              sync_every=s["sync"], block_size=s["block"])
    if k:
        kw.update(speculative_k=k, draft_model=draft)
    engine = ContinuousBatcher(model, **kw)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, s["vocab"], (n,)).astype(np.int32)
               for n in s["prompt_lens"]]
    rids = [engine.submit(p) for p in prompts]
    t0 = time.perf_counter()
    outs = engine.run()
    dt = time.perf_counter() - t0
    gen = sum(len(outs[r]) for r in rids)
    windows = sum(1 for e in engine._dispatch_log
                  if e.startswith(("decode", "verify")))
    probe = {
        "mode": f"spec_k{k}" if k else "baseline",
        "wall_s": round(dt, 4),
        "tokens_per_sec": round(gen / dt, 1),
        "generated_tokens": gen,
        "target_windows": windows,
    }
    if k:
        rep = engine.spec_report()
        probe.update({
            "proposed_tokens": rep["proposed_tokens"],
            "accepted_tokens": rep["accepted_tokens"],
            "acceptance_rate": rep["acceptance_rate"],
            "accepted_tokens_per_sec": round(rep["accepted_tokens"] / dt, 1),
        })
    return probe, [outs[r] for r in rids]


def summarize(model=None):
    """Run every probe; returns the ``detail.serving.spec`` dict."""
    s = _shapes()
    if model is None:
        model = _build_model(s)
    draft = _build_draft(s, model)
    out = {"small": SMALL, "sync_every": s["sync"],
           "draft": "target" if draft is model else "tiny-preset"}
    base, base_outs = probe_wave(model, s)
    out["wave_baseline"] = base
    for k in s["ks"]:
        wave, outs = probe_wave(model, s, k=k, draft=draft)
        wave["outputs_identical"] = bool(
            all(np.array_equal(a, b) for a, b in zip(base_outs, outs)))
        wave["speedup_x"] = round(
            wave["tokens_per_sec"] / max(base["tokens_per_sec"], 1e-9), 3)
        wave["window_reduction_x"] = round(
            base["target_windows"] / max(wave["target_windows"], 1), 3)
        out[f"wave_spec_k{k}"] = wave
    out["outputs_identical"] = bool(
        all(out[f"wave_spec_k{k}"]["outputs_identical"] for k in s["ks"]))
    return out


def main():
    summary = summarize()
    s = _shapes()
    print(json.dumps({"probe": "wave_baseline", **summary["wave_baseline"]}))
    for k in s["ks"]:
        print(json.dumps({"probe": f"wave_spec_k{k}",
                          **summary[f"wave_spec_k{k}"]}))
    print(json.dumps({"probe": "headline",
                      "outputs_identical": summary["outputs_identical"],
                      "draft": summary["draft"]}))


if __name__ == "__main__":
    main()
