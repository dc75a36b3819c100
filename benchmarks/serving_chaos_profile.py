"""Profile the serving tier's fault-tolerance tax end-to-end over real HTTP.

``serving_disagg_profile.py`` measures the no-fault routing rig; this script
measures what a mid-stream worker death COSTS. It stands up two decode
workers behind an affinity router (all in one process, each on its own
loopback ``MetricsServer``), drives the same prompt mix through twice, and
diffs the passes:

- **clean pass**: every request completes first-try; the per-request
  client-side TTFT (wall time from POST to the first streamed frame) is the
  baseline the fault tax is measured against.
- **faulted pass**: a fresh rig with ``ACCELERATE_FAULT_PLAN``-style chaos
  armed on worker A (``req:K=worker_kill`` with ``kill_mode="stream"`` — the
  stream breaks mid-delivery without a terminal frame, exactly the wire
  signature of a crashed host). The router must recover the request on
  worker B under the same rid with the already-delivered prefix trimmed.

Reported (the ``detail.serving.chaos`` dict bench.py embeds under
``BENCH_SERVING_CHAOS=1``, schema v13):

- **recovered_requests / lost_requests**: how many requests needed a retry
  leg (from each stream's ``done`` trace) and how many failed outright —
  the drill contract is recovered ≥ 1 and lost == 0.
- **added_ttft_under_fault_s / added_latency_under_fault_s**: the client-
  side TTFT and completion-time deltas the recovered request paid versus
  its own clean-pass run — the retry backoff + re-dispatch + re-prefill
  tax a fault adds to exactly the requests it touches. The TTFT delta is
  ~0 by contract (the victim streams the first frame before dying and the
  retry resumes the SAME client stream); the tax lands in completion time.
- **outputs_identical**: the faulted pass's streams are bit-identical to
  the clean pass's (greedy decode; retry is re-dispatch, never a re-roll).
- the router's ``retries``/``evictions`` rollups for the faulted pass.

Prints one JSON line per probe; ``summarize()`` returns the payload.
``BENCH_PROFILE_SMALL=1`` shrinks shapes for CPU smoke runs (the test
suite's path).

Usage: python benchmarks/serving_chaos_profile.py
"""

import json
import os
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


SMALL = os.environ.get("BENCH_PROFILE_SMALL", "0") == "1"

# Which request (0-based, sequential) dies mid-stream on worker A. Sequential
# idle-rig requests all land on A (least-loaded ties break toward the lowest
# rank), so A's admission seq tracks the request index until the kill —
# offset by one because each pass spends A's seq 0 on an untimed JIT-warmup
# request (first-dispatch compile time would otherwise swamp the fault tax).
FAULT_AT = 2


def _shapes():
    if SMALL:
        return dict(layers=2, heads=4, kv=2, hidden=64, inter=128, vocab=256,
                    slots=2, max_new=8, sync=2, block=4, chunk=8,
                    buckets=(8, 16), cache=1024, prompt_lens=(5, 7, 3, 6))
    return dict(layers=8, heads=16, kv=8, hidden=1024, inter=4096, vocab=32000,
                slots=8, max_new=64, sync=8, block=16, chunk=128,
                buckets=(64, 128, 256), cache=4096,
                prompt_lens=(33, 96, 12, 57, 80, 21))


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


def _engine(model, s):
    import jax.numpy as jnp

    from accelerate_tpu.serving import ContinuousBatcher

    return ContinuousBatcher(
        model, batch_slots=s["slots"], max_new_tokens=s["max_new"],
        max_cache_len=s["cache"], cache_dtype=jnp.float32,
        bucket_sizes=s["buckets"], sync_every=s["sync"],
        block_size=s["block"], prefill_chunk=s["chunk"],
        max_tokens_per_request=max(s["prompt_lens"]) + s["max_new"] + s["chunk"],
    )


def _start_worker(engine, role):
    from accelerate_tpu.serving_net import ServingFrontend
    from accelerate_tpu.telemetry.metrics import MetricsServer

    server = MetricsServer(0, host="127.0.0.1")
    port = server.start()
    endpoint = f"127.0.0.1:{port}"
    frontend = ServingFrontend(engine, role=role)
    frontend.install(server=server, endpoint=endpoint)
    return server, frontend, endpoint


def _generate_timed(endpoint, prompt, max_new):
    """One request through the real wire format, with the client-side TTFT
    clock: wall seconds from POST to the first streamed frame. Client-side
    on purpose — under a fault the survivor's tracer only sees the retry
    leg, so its ``ttft_s`` would hide exactly the tax being measured."""
    from accelerate_tpu.serving_net.frontend import (
        ServingStreamError,
        iter_sse,
    )

    req = urllib.request.Request(
        f"http://{endpoint}/v1/generate",
        data=json.dumps({"prompt": [int(t) for t in prompt],
                         "max_new_tokens": int(max_new)}).encode(),
        headers={"Content-Type": "application/json"},
    )
    t0 = time.perf_counter()
    ttft_s, deltas, done = None, [], None
    with urllib.request.urlopen(req, timeout=300.0) as response:
        for kind, data in iter_sse(response):
            if ttft_s is None:
                ttft_s = time.perf_counter() - t0
            payload = json.loads(data)
            if kind == "error":
                raise ServingStreamError(
                    f"serving stream error: {payload.get('error')}",
                    retryable=payload.get("retryable", True),
                )
            if kind == "tokens":
                deltas.append(payload["tokens"])
            elif kind == "done":
                done = payload
    if done is None:
        raise ServingStreamError("stream closed without a done event",
                                 retryable=True)
    return {"tokens": done["tokens"], "deltas": deltas, "done": done,
            "ttft_s": ttft_s, "wall_s": time.perf_counter() - t0}


def _rig(model, s, fault_plan=None):
    """Two decode workers + a router. ``fault_plan`` (a ``req:`` spec) arms
    worker A — the one sequential requests land on — with soft-death chaos."""
    from accelerate_tpu.resilience.faults import FaultPlan, set_active_plan
    from accelerate_tpu.serving_net import Router
    from accelerate_tpu.telemetry.metrics import MetricsServer

    servers, frontends = [], []
    server, frontend_a, ep_a = _start_worker(_engine(model, s), "decode")
    servers.append(server)
    frontends.append(frontend_a)
    server, frontend_b, ep_b = _start_worker(_engine(model, s), "decode")
    servers.append(server)
    frontends.append(frontend_b)
    if fault_plan:
        frontend_a.kill_mode = "stream"
        set_active_plan(FaultPlan.parse(fault_plan))
    router_server = MetricsServer(0, host="127.0.0.1")
    router_port = router_server.start()
    servers.append(router_server)
    router = Router(
        workers=[{"rank": 0, "role": "decode", "endpoint": ep_a},
                 {"rank": 1, "role": "decode", "endpoint": ep_b}],
        backoff_base_s=0.02, backoff_cap_s=0.1,
    )
    router.install(server=router_server, endpoint=f"127.0.0.1:{router_port}")
    return servers, frontends, router, f"127.0.0.1:{router_port}"


def _teardown(servers, frontends):
    from accelerate_tpu.resilience.faults import reset_active_plan
    from accelerate_tpu.serving_net.router import reset_serving_registry

    for frontend in frontends:
        frontend.uninstall()
    for server in servers:
        server.stop()
    reset_active_plan()
    reset_serving_registry()


def _pass(model, s, prompts, fault_plan=None):
    """One sequential pass of the prompt mix; returns per-request results
    plus the router's stats snapshot."""
    servers, frontends, router, router_ep = _rig(model, s, fault_plan)
    try:
        # Untimed warmup (spends worker A's admission seq 0): pays the
        # first-dispatch XLA compile outside the clock in BOTH passes, so
        # the clean baseline measures steady-state latency.
        _generate_timed(router_ep, prompts[0], s["max_new"])
        results = [_generate_timed(router_ep, p, s["max_new"])
                   for p in prompts]
        return results, router.stats()
    finally:
        _teardown(servers, frontends)


def summarize(model=None):
    """Run both passes; returns the ``detail.serving.chaos`` dict for
    bench.py (schema v13, BENCH_SERVING_CHAOS=1)."""
    s = _shapes()
    if model is None:
        model = _build_model(s)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, s["vocab"], (n,)).astype(np.int32)
               for n in s["prompt_lens"]]

    clean, _ = _pass(model, s, prompts)
    faulted, stats = _pass(model, s, prompts,
                           fault_plan=f"req:{FAULT_AT + 1}=worker_kill")

    retried = [i for i, r in enumerate(faulted)
               if (r["done"].get("trace") or [{}])[0].get("retries")]
    clean_ttfts = [r["ttft_s"] for r in clean]
    mean_clean_ttft = sum(clean_ttfts) / len(clean_ttfts)
    # Per-index deltas over the recovered requests. The TTFT delta is
    # typically ~0 BY CONTRACT — the victim delivers the first frame before
    # dying and retry resumes the same client stream — so the fault tax
    # shows up in completion latency (re-dispatch + backoff + re-prefill).
    added_ttft = (max(faulted[i]["ttft_s"] - clean[i]["ttft_s"]
                      for i in retried) if retried else None)
    added_wall = (max(faulted[i]["wall_s"] - clean[i]["wall_s"]
                      for i in retried) if retried else None)
    payload = {
        "small": SMALL,
        "requests": len(prompts),
        "fault_at": FAULT_AT,
        "recovered_requests": len(retried),
        "lost_requests": 0,  # _pass raises on any failed stream
        "outputs_identical": bool(
            all(a["tokens"] == b["tokens"] for a, b in zip(clean, faulted))
        ),
        "clean_ttft_mean_s": round(mean_clean_ttft, 4),
        "added_ttft_under_fault_s": (round(added_ttft, 4)
                                     if added_ttft is not None else None),
        "added_latency_under_fault_s": (round(added_wall, 4)
                                        if added_wall is not None else None),
        "retries": stats["retries"],
        "evictions": stats["evictions"],
    }
    return payload


def main():
    summary = summarize()
    print(json.dumps({"probe": "recovery",
                      "recovered_requests": summary["recovered_requests"],
                      "lost_requests": summary["lost_requests"],
                      "retries": summary["retries"],
                      "evictions": summary["evictions"]}))
    print(json.dumps({"probe": "fault_tax",
                      "clean_ttft_mean_s": summary["clean_ttft_mean_s"],
                      "added_ttft_under_fault_s":
                          summary["added_ttft_under_fault_s"],
                      "added_latency_under_fault_s":
                          summary["added_latency_under_fault_s"]}))
    print(json.dumps({"probe": "headline",
                      "requests": summary["requests"],
                      "outputs_identical": summary["outputs_identical"]}))


if __name__ == "__main__":
    main()
