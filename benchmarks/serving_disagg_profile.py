"""Profile the disaggregated serving path end-to-end over real HTTP.

``serving_decode_profile.py`` attributes the single-host engine; this script
stands up the full ``serving_net`` rig IN one process — a prefill worker, a
decode worker, and an affinity router, each behind its own loopback
``MetricsServer`` — and drives it through the actual wire format (POST
/v1/generate against the router, SSE frames back), so every number is
measured through the same code path a multi-host fleet runs:

- **routing split**: which tier each request entered (the SLO sentinel's
  arbitration — single-chunk prompts decode where they land, multi-chunk
  prompts enter the prefill tier) plus the router's affinity hit rate.
  NOTE: in a pure prefill/decode rig the hit rate measures 0 by design —
  ``export_chain`` frees the prefill host's chain and ``import_chain`` keeps
  imported blocks private, so only prefixes left resident on a decode
  worker by its OWN single-chunk requests can match.
- **handoff volume**: chains/blocks/bytes shipped prefill → decode, read
  from the prefill engine's tracer records (per-request attribution, not
  process-global counters).
- **per-tier latency**: each tier's TTFT/TPOT quantiles from its own
  tracer, so the handoff RTT shows up as the prefill-entry TTFT tax the
  arbitration policy trades against decode-tier TPOT protection.
- **parity**: the same prompts through one unified engine with identical
  kwargs — disaggregated greedy output must be bit-identical
  (``outputs_identical``), and every relayed stream's ``done`` trace must
  span router → prefill → decode (``trace_spans_tiers``).

Prints one JSON line per probe; ``summarize()`` returns the dict bench.py
embeds as ``detail.serving.routing`` under ``BENCH_SERVING_DISAGG=1``
(schema v12). ``BENCH_PROFILE_SMALL=1`` shrinks everything for CPU smoke
runs (the test suite's path).

Usage: python benchmarks/serving_disagg_profile.py
"""

import json
import os
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


SMALL = os.environ.get("BENCH_PROFILE_SMALL", "0") == "1"


def _shapes():
    if SMALL:
        # 5/3-token prompts fit one 8-token chunk (decode entry); 14/21 are
        # multi-chunk (prefill entry, chain handoff). The trailing repeat of
        # the first prompt probes affinity against whatever its first pass
        # left resident on the decode worker.
        return dict(layers=2, heads=4, kv=2, hidden=64, inter=128, vocab=256,
                    slots=2, max_new=8, sync=2, block=4, chunk=8,
                    buckets=(8, 16), cache=1024,
                    prompt_lens=(5, 14, 3, 21), repeat_first=True)
    return dict(layers=8, heads=16, kv=8, hidden=1024, inter=4096, vocab=32000,
                slots=8, max_new=64, sync=8, block=16, chunk=128,
                buckets=(64, 128, 256), cache=4096,
                prompt_lens=(33, 180, 12, 250, 96, 480), repeat_first=True)


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
    """One paged engine; the prefill tier, the decode tier, and the unified
    parity baseline all build from THESE kwargs — identical programs, so the
    only variable between rigs is where the chain lives."""
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
    """One serving worker on a loopback port: its own MetricsServer with the
    frontend attached per-server (the multi-role single-process rig)."""
    from accelerate_tpu.serving_net import ServingFrontend
    from accelerate_tpu.telemetry.metrics import MetricsServer

    server = MetricsServer(0, host="127.0.0.1")
    port = server.start()
    endpoint = f"127.0.0.1:{port}"
    frontend = ServingFrontend(engine, role=role)
    frontend.install(server=server, endpoint=endpoint)
    return server, frontend, endpoint


def _generate(endpoint, prompt, max_new):
    """One client request through the real wire format."""
    from accelerate_tpu.serving_net.frontend import read_sse_response

    req = urllib.request.Request(
        f"http://{endpoint}/v1/generate",
        data=json.dumps({"prompt": [int(t) for t in prompt],
                         "max_new_tokens": int(max_new)}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=300.0) as response:
        return read_sse_response(response)


def _tier_summary(tracer):
    """The per-tier latency slice of a tracer summary (the slowest-N table
    stays out of the bench row — it is debugging payload, not a metric)."""
    if tracer is None:
        return None
    summary = tracer.summary()
    return {key: summary.get(key)
            for key in ("total", "states", "ttft_s", "tpot_s")}


def probe_disagg(model, s):
    """Drive the 3-tier rig through the router; returns the routing payload
    plus each request's streamed tokens for the parity join."""
    from accelerate_tpu.serving_net import Router
    from accelerate_tpu.serving_net.router import reset_serving_registry
    from accelerate_tpu.telemetry.metrics import MetricsServer

    prefill_engine = _engine(model, s)
    decode_engine = _engine(model, s)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, s["vocab"], (n,)).astype(np.int32)
               for n in s["prompt_lens"]]
    if s["repeat_first"]:
        prompts.append(prompts[0].copy())

    servers, frontends = [], []
    try:
        server, frontend, prefill_ep = _start_worker(prefill_engine, "prefill")
        servers.append(server)
        frontends.append(frontend)
        server, frontend, decode_ep = _start_worker(decode_engine, "decode")
        servers.append(server)
        frontends.append(frontend)
        router_server = MetricsServer(0, host="127.0.0.1")
        router_port = router_server.start()
        servers.append(router_server)
        router = Router(workers=[
            {"rank": 0, "role": "prefill", "endpoint": prefill_ep},
            {"rank": 1, "role": "decode", "endpoint": decode_ep},
        ])
        router.install(server=router_server,
                       endpoint=f"127.0.0.1:{router_port}")
        router_ep = f"127.0.0.1:{router_port}"

        results = [None] * len(prompts)
        errors = []

        def client(i, prompt):
            try:
                results[i] = _generate(router_ep, prompt, s["max_new"])
            except Exception as exc:  # surfaced after join — not swallowed
                errors.append(f"request {i}: {exc!r}")

        # The original mix rides concurrently (continuous batching on both
        # tiers); the repeat goes AFTER the joined wave so its affinity
        # probe sees whatever pass one left resident.
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i, p))
                   for i, p in enumerate(prompts[: len(s["prompt_lens"])])]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        if s["repeat_first"]:
            client(len(prompts) - 1, prompts[-1])
        wall_s = time.perf_counter() - t0
        if errors:
            raise RuntimeError("; ".join(errors))

        handoff = {"chains": 0, "blocks": 0, "bytes": 0}
        if prefill_engine.tracer is not None:
            for record in prefill_engine.tracer.records():
                leg = record.get("handoff")
                if leg and leg.get("direction") == "out":
                    handoff["chains"] += 1
                    handoff["blocks"] += int(leg.get("blocks", 0))
                    handoff["bytes"] += int(leg.get("bytes", 0))

        spans = []
        for result in results:
            tiers = [r.get("tier") for r in result["done"].get("trace", [])]
            spans.append(tiers)
        trace_spans_tiers = all(
            t[0] == "router" and t[-1] == "decode"
            and ("prefill" in t) == (len(t) == 3)
            for t in spans
        )
        stats = router.stats()
        payload = {
            "requests": len(prompts),
            "wall_s": round(wall_s, 4),
            "routed": stats["routed"],
            "affinity_hits": stats["affinity_hits"],
            "affinity_hit_rate": stats["affinity_hit_rate"],
            "handoff": handoff,
            "trace_spans_tiers": bool(trace_spans_tiers),
            "tiers": {
                "router": _tier_summary(router.tracer),
                "prefill": _tier_summary(prefill_engine.tracer),
                "decode": _tier_summary(decode_engine.tracer),
            },
        }
        return payload, [r["tokens"] for r in results], prompts
    finally:
        for frontend in frontends:
            frontend.uninstall()
        for server in servers:
            server.stop()
        reset_serving_registry()


def probe_unified(model, s, prompts):
    """The parity baseline: the SAME prompts through one unified engine with
    identical kwargs — greedy output must be bit-identical to the routed
    path (handoff is state surgery, never a recompute)."""
    engine = _engine(model, s)
    rids = [engine.submit(p) for p in prompts]
    outs = engine.run()
    return [[int(t) for t in outs[r]] for r in rids]


def summarize(model=None):
    """Run the rig; returns the ``detail.serving.routing`` dict for bench.py
    (schema v12, BENCH_SERVING_DISAGG=1)."""
    s = _shapes()
    if model is None:
        model = _build_model(s)
    payload, disagg_tokens, prompts = probe_disagg(model, s)
    unified_tokens = probe_unified(model, s, prompts)
    payload["small"] = SMALL
    payload["prefill_chunk"] = s["chunk"]
    payload["outputs_identical"] = bool(
        len(disagg_tokens) == len(unified_tokens)
        and all(a == b for a, b in zip(disagg_tokens, unified_tokens))
    )
    return payload


def main():
    summary = summarize()
    print(json.dumps({"probe": "routing", "routed": summary["routed"],
                      "affinity_hits": summary["affinity_hits"],
                      "affinity_hit_rate": summary["affinity_hit_rate"]}))
    print(json.dumps({"probe": "handoff", **summary["handoff"]}))
    for tier, stats in summary["tiers"].items():
        print(json.dumps({"probe": f"tier_{tier}", **(stats or {})}))
    print(json.dumps({
        "probe": "headline",
        "requests": summary["requests"],
        "wall_s": summary["wall_s"],
        "outputs_identical": summary["outputs_identical"],
        "trace_spans_tiers": summary["trace_spans_tiers"],
    }))


if __name__ == "__main__":
    main()
