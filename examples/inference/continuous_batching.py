"""Continuous-batching serving with shared-prefix caching.

The reference serves through ``model.generate`` one batch at a time — short
requests wait for the longest row. ``ContinuousBatcher`` keeps a fixed set of
decode slots over a paged KV pool, refills a slot the moment its sequence
finishes, and (here) a system prompt shared by every request is stored via
``set_prefix`` — the first request prefills its blocks and the others alias
them, so its prefill compute and pool blocks are paid once, not per request.

Outputs stay exactly what solo ``generate(prefix + suffix)`` would produce,
however requests interleave (pinned by tests/test_serving.py).

Run:
    python examples/inference/continuous_batching.py
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from accelerate_tpu import ContinuousBatcher
from accelerate_tpu.models import Llama, LlamaConfig


def main():
    import jax
    import jax.numpy as jnp

    cfg = LlamaConfig.tiny(vocab_size=256, num_hidden_layers=2)
    model = Llama(cfg)
    model.init_params(jax.random.key(0))

    engine = ContinuousBatcher(
        model,
        batch_slots=2,              # decode this many requests concurrently
        max_new_tokens=8,
        max_cache_len=512,          # the pool's token capacity
        block_size=8,               # tokens per pool block
        max_tokens_per_request=64,  # prefix + turn + completion
        eos_token_id=None,
        bucket_sizes=(8, 16),       # prefill-chunk programs compile per bucket
        sync_every=4,               # decode steps per host check
        cache_dtype=jnp.float32,
    )

    rng = np.random.default_rng(0)
    system_prompt = rng.integers(1, cfg.vocab_size, 24).astype(np.int32)
    engine.set_prefix(system_prompt)  # prefilled once, its blocks shared by every request

    # Six ragged user turns; each submits only its suffix — and each may carry
    # its OWN generation controls (length / temperature / eos / stop
    # sequences), heterogeneously within the wave, with no recompiles.
    turns = [rng.integers(1, cfg.vocab_size, int(n)).astype(np.int32)
             for n in rng.integers(3, 14, 6)]
    rids = [
        engine.submit(turns[0]),                           # engine defaults
        engine.submit(turns[1], max_new_tokens=3),         # short completion
        engine.submit(turns[2], temperature=0.8),          # sampled
        engine.submit(turns[3], stop_sequences=[[7, 7]]),  # stop on a bigram
        engine.submit(turns[4]),
        engine.submit(turns[5], max_new_tokens=5),
    ]
    outputs = engine.run()

    for rid, turn in zip(rids, turns):
        print(f"request {rid}: {len(turn)}-token turn -> {outputs[rid].tolist()}")
    print(f"peak pool slots in use: {engine.kv_consumed_slots_peak} "
          f"of {engine.num_blocks * engine.block_size} "
          f"(prefix blocks aliased: {engine.slo_report()['decisions']['aliased_blocks']}); "
          f"after the wave: {engine.cache_columns_used} in use, "
          f"utilization {engine.cache_utilization:.2f}")


if __name__ == "__main__":
    main()
