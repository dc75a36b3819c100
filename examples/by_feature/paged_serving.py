"""Feature: paged KV-cache serving (see docs/serving.md).

`ContinuousBatcher` end-to-end on a tiny Llama: a block pool with
per-slot block tables, refcounted cross-request prefix sharing (set_prefix is
just the degenerate case), chunked prefill interleaved with decode windows,
and SLO-aware admission with per-request TTFT/TPOT accounting. The script
verifies the engine's correctness contract live — every paged output is
bit-identical to per-request `generate()` — then prints the pool stats, the
admission ledger, and the serving metrics the registry exports.

Run:
    python examples/by_feature/paged_serving.py
    python examples/by_feature/paged_serving.py --requests 12 --ttft_slo 0.5
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp

from accelerate_tpu.generation import generate
from accelerate_tpu.models import Llama, LlamaConfig
from accelerate_tpu.serving import ContinuousBatcher, SLOTargets
from accelerate_tpu.telemetry.metrics import get_registry


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--requests", type=int, default=8)
    parser.add_argument("--slots", type=int, default=2)
    parser.add_argument("--max_new", type=int, default=8)
    parser.add_argument("--block_size", type=int, default=4)
    parser.add_argument("--prefill_chunk", type=int, default=8)
    parser.add_argument("--ttft_slo", type=float, default=None)
    args = parser.parse_args()

    model = Llama(LlamaConfig.tiny(num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2))
    model.init_params(jax.random.key(0))

    engine = ContinuousBatcher(
        model,
        batch_slots=args.slots,
        max_new_tokens=args.max_new,
        max_cache_len=1024,                      # pool tokens, not B x columns
        cache_dtype=jnp.float32,
        bucket_sizes=(8, 16),
        sync_every=2,
        block_size=args.block_size,
        prefill_chunk=args.prefill_chunk,
        max_tokens_per_request=64,
        slo=SLOTargets(ttft_s=args.ttft_slo, tpot_s=None),
    )

    rng = np.random.default_rng(0)
    # A shared system-prompt prefix: the first request prefills its blocks,
    # every later request aliases them (refcounted — watch aliased_blocks).
    prefix = rng.integers(1, 256, (12,)).astype(np.int32)
    engine.set_prefix(prefix)
    # Mixed lengths, including one prompt long enough to need chunked prefill.
    lengths = [5, 9, 21, 3, 12, 7, 4, 14][: args.requests]
    while len(lengths) < args.requests:
        lengths.append(int(rng.integers(3, 20)))
    suffixes = [rng.integers(1, 256, (n,)).astype(np.int32) for n in lengths]
    rids = [engine.submit(s) for s in suffixes]
    outputs = engine.run()

    # The correctness contract, verified live: paged == solo generate().
    exact = 0
    for rid, suffix in zip(rids, suffixes):
        ref = np.asarray(generate(
            model, np.concatenate([prefix, suffix])[None],
            max_new_tokens=args.max_new, temperature=0.0,
            cache_dtype=jnp.float32, include_prompt=False,
        ))[0]
        got = outputs[rid]
        assert np.array_equal(got, ref[: len(got)]), f"rid {rid} diverged"
        exact += 1
    print(f"{exact}/{len(rids)} outputs bit-identical to solo generate()")

    report = engine.slo_report()
    print("admission ledger:", json.dumps(report["decisions"]))
    print("pool:", json.dumps(engine.pool_stats()))
    print(f"peak consumed KV slots: {engine.kv_consumed_slots_peak} "
          f"of {engine.num_blocks * engine.block_size}")
    if report["ttft_s"]:
        print(f"TTFT p50 ~ {sorted(report['ttft_s'])[len(report['ttft_s']) // 2]:.4f}s "
              f"over {len(report['ttft_s'])} requests")
    snapshot = get_registry().snapshot()
    served = {k: v for k, v in snapshot.items() if "serving" in k}
    print("registry:", json.dumps(served, sort_keys=True))


if __name__ == "__main__":
    main()
