"""Disaggregated-serving drill, run under the real 2-process launcher::

    accelerate-tpu launch --cpu --num_processes 2 -m \
        accelerate_tpu.test_utils.disagg_script

Proves the tentpole property ``tests/test_serving_net.py`` pins: prefill and
decode run on disjoint "hosts" (rank 0 = decode, rank 1 = prefill — separate
processes, separate pools, separate metrics endpoints registered in the
coordination-service KV namespace), a router on the decode host discovers
BOTH workers through that registry, and a client driving the router over
real HTTP/SSE gets:

- token output **bit-identical** to one unified single-host paged engine
  running the same prompts (handoff is state surgery, never a recompute);
- one ``done``-event trace per request spanning router admission → prefill
  chunks → chain handoff → first decode token, with TTFT/TPOT and
  queue-wait attribution on the records;
- ``accelerate-tpu top`` (JSON and human frames, real subprocesses against
  the lead host's endpoint) showing BOTH tiers' fleet rollups.

The model is tiny and seeded identically on both ranks, so every parity
assertion is exact; the registration, discovery, routing, chunked prefill,
chain transfer, import surgery, and streaming are all real.

Chaos mode (``AT_DISAGG_CHAOS=1``, 3 processes) turns the same script into
the serving fault-tolerance drill ``tests/test_serving_faults.py`` pins::

    AT_DISAGG_CHAOS=1 accelerate-tpu launch --cpu --num_processes 3 \
        --serving_lease_ttl 2 --serving_retry_budget 3 --drain_grace_s 20 \
        -m accelerate_tpu.test_utils.disagg_script

Rank 0 runs the router, the prefill tier, and the client; ranks 1 and 2 are
decode workers. Three phases, each against the single-host baseline:

- **A (worker_kill)**: rank 1's fault plan kills its first stream after the
  first delta. The router retries on rank 2 under the same rid; the client
  sees ONE contiguous bit-identical stream, and the corpse is lease-evicted
  from discovery within its TTL.
- **B (handoff_drop)**: rank 0's first chain export is dropped on the wire.
  Free-on-ack returns every block to the prefill free list (no leaks) and
  the request still completes bit-identically through re-entry.
- **C (graceful drain)**: rank 2 gets SIGTERM mid-request. The in-flight
  stream finishes, the lease is revoked, and the next request is shed with
  a fast 503 + ``retry_after_s`` (every decode worker gone).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

from accelerate_tpu import PartialState
from accelerate_tpu.telemetry import start_default_server
from accelerate_tpu.telemetry.fleet import (
    FleetAggregator,
    install_fleet_provider,
    publish_metrics_endpoint,
)
from accelerate_tpu.utils.agreement import kv_all_gather

# chunk=8 with these prompt lengths pins the routing split: 3/5 fit one
# chunk (decode entry), 14/21 are multi-chunk (prefill entry + handoff).
PROMPT_LENS = (5, 14, 3, 21)
CHUNK = 8
MAX_NEW = 8


def _model():
    import jax

    from accelerate_tpu.models import Llama, LlamaConfig

    cfg = LlamaConfig.tiny(vocab_size=256, hidden_size=64,
                           intermediate_size=128, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2)
    model = Llama(cfg)
    model.init_params(jax.random.key(0))  # same key both ranks: exact parity
    return model


def _engine(model):
    import jax.numpy as jnp

    from accelerate_tpu.serving import ContinuousBatcher

    return ContinuousBatcher(
        model, batch_slots=2, max_new_tokens=MAX_NEW, max_cache_len=1024,
        cache_dtype=jnp.float32, bucket_sizes=(8, 16), sync_every=2,
        block_size=4, prefill_chunk=CHUNK,
        max_tokens_per_request=48,
    )


def _prompts():
    rng = np.random.default_rng(7)
    return [rng.integers(1, 256, (n,)).astype(np.int32) for n in PROMPT_LENS]


def _generate(endpoint: str, prompt) -> dict:
    from accelerate_tpu.serving_net.frontend import read_sse_response

    req = urllib.request.Request(
        f"http://{endpoint}/v1/generate",
        data=json.dumps({"prompt": [int(t) for t in prompt],
                         "max_new_tokens": MAX_NEW}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=300.0) as response:
        return read_sse_response(response)


def main():
    state = PartialState()
    assert state.num_processes >= 2, "run under `launch --num_processes 2`"
    rank = state.process_index
    role = "decode" if rank == 0 else "prefill"

    from accelerate_tpu.serving_net import Router, ServingFrontend

    model = _model()
    server = start_default_server(0)  # ephemeral: nobody knows the port
    endpoint = publish_metrics_endpoint(process_index=rank, server=server)
    assert endpoint is not None, "metrics endpoint registration failed"

    engine = _engine(model)
    frontend = ServingFrontend(engine, role=role)
    # Global-provider install: this rank's ONE metrics server now serves
    # /v1/* for its tier, and the role+endpoint lands in the serving KV
    # namespace (what the router discovers — no address list anywhere).
    frontend.install(process_index=rank, endpoint=endpoint)

    kv_all_gather("ready", state.num_processes, rank,
                  namespace="at_disagg_drill/ready")

    if rank == 0:
        # The single-host truth: one unified engine, same model, same
        # kwargs, same prompts — greedy output the routed path must match
        # bit for bit.
        prompts = _prompts()
        baseline_engine = _engine(model)
        rids = [baseline_engine.submit(p) for p in prompts]
        baseline = baseline_engine.run()
        expected = [[int(t) for t in baseline[r]] for r in rids]

        # The router rides its own loopback server (multi-role host): its
        # /v1 provider is attached per-server, so the default server keeps
        # serving the decode tier.
        from accelerate_tpu.telemetry.metrics import MetricsServer

        router_server = MetricsServer(0, host="127.0.0.1")
        router_port = router_server.start()
        router = Router(num_processes=state.num_processes)
        router_server.set_serving(router)
        router_ep = f"127.0.0.1:{router_port}"
        workers = {w["role"]: w for w in router.workers()}
        assert set(workers) == {"decode", "prefill"}, workers
        assert workers["decode"]["endpoint"] == endpoint, workers

        results = [None] * len(prompts)
        errors = []

        def client(i, prompt):
            try:
                results[i] = _generate(router_ep, prompt)
            except Exception as exc:
                errors.append(f"request {i}: {exc!r}")

        threads = [threading.Thread(target=client, args=(i, p))
                   for i, p in enumerate(prompts)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        assert not errors, errors

        # Bit-identical parity, every request.
        for i, result in enumerate(results):
            assert result["tokens"] == expected[i], (
                f"request {i}: disagg {result['tokens']} != unified {expected[i]}"
            )

        # One trace per request spanning every tier it crossed, TTFT/TPOT +
        # queue-wait attribution on the records.
        for i, result in enumerate(results):
            done = result["done"]
            trace = done["trace"]
            tiers = [r.get("tier") for r in trace]
            multi_chunk = PROMPT_LENS[i] > CHUNK
            want = (["router", "prefill", "decode"] if multi_chunk
                    else ["router", "decode"])
            assert tiers == want, (i, tiers)
            assert done["ttft_s"] is not None and done["tpot_s"] is not None, done
            router_rec, decode_rec = trace[0], trace[-1]
            assert router_rec["decision"] == (
                "route_prefill" if multi_chunk else "route_decode"
            ), router_rec
            # Queue wait is attributed on the tier the request ENTERED —
            # the prefill record for handed-off requests, the decode record
            # for requests that decoded where they landed.
            entered = trace[1] if multi_chunk else decode_rec
            assert entered["queue_wait_s"] is not None, entered
            assert decode_rec["state"] == "finished", decode_rec
            if multi_chunk:
                prefill_rec = trace[1]
                assert prefill_rec["state"] == "handed_off", prefill_rec
                leg = prefill_rec["handoff"]
                assert leg["direction"] == "out" and leg["bytes"] > 0, leg
                assert len(prefill_rec["chunks"]) >= 2, prefill_rec
                assert decode_rec["handoff"]["direction"] == "in", decode_rec
            # One rid spans every tier it crossed.
            assert len({r["rid"] for r in trace}) == 1, trace

        # The operator console: both tiers' rollups through the real
        # aggregate-and-render path.
        install_fleet_provider(FleetAggregator(state=state))
        snap = subprocess.run(
            [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
             "top", "--once", "--json", "--endpoint", endpoint],
            capture_output=True, text=True, timeout=120,
        )
        assert snap.returncode == 0, snap.stdout[-800:] + snap.stderr[-800:]
        got = json.loads(snap.stdout)
        assert got["hosts"]["0"]["serving_role"] == "decode", got["hosts"]
        assert got["hosts"]["1"]["serving_role"] == "prefill", got["hosts"]
        tiers = got["fleet"]["serving_tiers"]
        assert set(tiers) >= {"decode", "prefill"}, tiers
        assert tiers["decode"]["hosts"] == 1 and tiers["prefill"]["hosts"] == 1
        assert tiers["decode"]["requests"] >= len(prompts), tiers["decode"]
        assert tiers["prefill"]["handoff"]["out"]["chains"] == 2, tiers["prefill"]
        assert tiers["decode"]["handoff"]["in"]["chains"] == 2, tiers["decode"]
        assert tiers["decode"]["ttft_s_mean"] is not None, tiers["decode"]

        frame = subprocess.run(
            [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
             "top", "--once", "--endpoint", endpoint],
            capture_output=True, text=True, timeout=120,
        )
        assert frame.returncode == 0, frame.stderr[-800:]
        assert "serving[decode]" in frame.stdout, frame.stdout
        assert "serving[prefill]" in frame.stdout, frame.stdout

        router_server.stop()

    kv_all_gather("done", state.num_processes, rank,
                  namespace="at_disagg_drill/done")
    frontend.uninstall()
    print(f"DISAGG_OK rank={rank} role={role} endpoint={endpoint}")


def _drive_chaos(state, model, engine, endpoint: str, ttl: float):
    """Rank 0's client script for the chaos drill: baseline, router, the
    three phases (worker_kill / handoff_drop / drain), and the fleet-rollup
    asserts. ``engine`` is this rank's own prefill engine (phase B asserts
    directly on its free list)."""
    from accelerate_tpu.resilience.faults import FaultPlan, set_active_plan
    from accelerate_tpu.serving_net import Router
    from accelerate_tpu.telemetry.fleet import _kv_client
    from accelerate_tpu.telemetry.metrics import MetricsServer

    rng = np.random.default_rng(11)
    prompt_a = rng.integers(1, 256, (5,)).astype(np.int32)   # decode entry
    prompt_b = rng.integers(1, 256, (21,)).astype(np.int32)  # prefill entry
    prompt_c = rng.integers(1, 256, (6,)).astype(np.int32)   # decode entry

    baseline = _engine(model)
    rids = [baseline.submit(p) for p in (prompt_a, prompt_b, prompt_c)]
    outs = baseline.run()
    want_a, want_b, want_c = ([int(t) for t in outs[r]] for r in rids)

    router_server = MetricsServer(0, host="127.0.0.1")
    router_port = router_server.start()
    # cache_s is short so eviction polls observe fresh discovery; the retry
    # budget must be the --serving_retry_budget 3 the launcher exported.
    router = Router(num_processes=state.num_processes, cache_s=0.5)
    assert router.retry_budget == 3, router.retry_budget
    router_server.set_serving(router)
    router_ep = f"127.0.0.1:{router_port}"

    workers = router.workers()
    by_rank = {w["rank"]: w for w in workers}
    assert set(by_rank) == {0, 1, 2}, workers
    assert by_rank[0]["role"] == "prefill", workers
    assert {by_rank[1]["role"], by_rank[2]["role"]} == {"decode"}, workers
    for worker in workers:
        assert worker.get("expires"), f"lease without expiry: {worker}"
    victim_ep = by_rank[1]["endpoint"]

    # ------------------------------------------------- phase A: worker_kill
    # Least-loaded tie-break picks the lowest rank, so the first
    # decode-entry request deterministically lands on rank 1 — whose plan
    # kills the stream right after the first delta.
    res_a = _generate(router_ep, prompt_a)
    assert res_a["tokens"] == want_a, (res_a["tokens"], want_a)
    # ONE contiguous stream: the deltas across both legs concatenate to a
    # clean prefix of the final token list (the engine holds the last token
    # for the done frame) — replayed prefix trimmed, nothing lost.
    streamed = [t for d in res_a["deltas"] for t in d]
    assert streamed and streamed == want_a[:len(streamed)], res_a["deltas"]
    stats = router.stats()
    assert stats["retries"].get("stream_broken", 0) >= 1, stats["retries"]
    legs = res_a["done"]["trace"][0].get("retries")
    assert legs and legs[0]["reason"] == "stream_broken", legs

    # Lease eviction within one TTL of the corpse's last heartbeat: poll
    # discovery (bounded by TTL + one refresh slice + slack) until the
    # victim vanishes, then check the breaker opened and the reason stuck.
    deadline = time.monotonic() + ttl + 5.0
    while time.monotonic() < deadline:
        if victim_ep not in {w["endpoint"] for w in router.workers()}:
            break
        time.sleep(0.25)
    else:
        raise AssertionError(f"victim {victim_ep} never lease-evicted")
    stats = router.stats()
    assert stats["evictions"].get(victim_ep) == "lease_expired", stats
    assert stats["breakers"].get(victim_ep) == "open", stats["breakers"]

    # ------------------------------------------------ phase B: handoff_drop
    # This rank's FIRST chain export is dropped on the wire. The chain must
    # come back to the free list (free-on-ack — a dropped handoff never
    # leaks blocks) and the request must still finish bit-identically
    # through re-entry on a surviving path.
    set_active_plan(FaultPlan.parse("req:0=handoff_drop"))
    free0 = len(engine._free_blocks)
    res_b = _generate(router_ep, prompt_b)
    set_active_plan(None)
    assert res_b["tokens"] == want_b, (res_b["tokens"], want_b)
    streamed = [t for d in res_b["deltas"] for t in d]
    assert streamed == want_b[:len(streamed)], res_b["deltas"]
    deadline = time.monotonic() + 10.0
    while (len(engine._free_blocks) != free0
           and time.monotonic() < deadline):
        time.sleep(0.05)
    assert len(engine._free_blocks) == free0, (
        f"handoff_drop leaked blocks: {len(engine._free_blocks)} != {free0}"
    )
    stats = router.stats()
    assert stats["retries"].get("handoff_failed", 0) >= 1, stats["retries"]

    # ------------------------------------------------------ phase C: drain
    # SIGTERM the last decode worker while a request is in flight on it:
    # the stream must finish (drain waits), the lease must be revoked, and
    # the next request must be shed with a fast 503 + retry_after_s.
    client = _kv_client()
    result_c: dict = {}

    def run_c():
        try:
            result_c["res"] = _generate(router_ep, prompt_c)
        except Exception as exc:
            result_c["err"] = repr(exc)

    survivor_ep = next(w["endpoint"] for w in router.workers()
                       if w["role"] == "decode")
    thread = threading.Thread(target=run_c)
    thread.start()
    deadline = time.monotonic() + 60.0
    stats_c: dict = {}
    while time.monotonic() < deadline:
        with urllib.request.urlopen(
                f"http://{survivor_ep}/v1/stats", timeout=5.0) as response:
            stats_c = json.loads(response.read())
        if stats_c.get("in_flight", 0) >= 1:
            break
        if not thread.is_alive():
            raise AssertionError(
                f"phase-C request finished before the drain order — the "
                f"slow_worker fault never fired: client={result_c} "
                f"survivor_stats={stats_c}"
            )
        time.sleep(0.02)
    else:
        raise AssertionError(
            f"phase-C request never reached the survivor: client={result_c} "
            f"survivor_stats={stats_c} router={router.stats()}"
        )
    client.key_value_set("at_chaos_drill/drain", "1")
    thread.join(180.0)
    assert not thread.is_alive(), "phase-C stream never finished under drain"
    res_c = result_c.get("res")
    assert res_c is not None and res_c["tokens"] == want_c, result_c

    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        roles = {w["role"] for w in router.workers()}
        if not roles & {"decode", "unified"}:
            break
        time.sleep(0.25)
    else:
        raise AssertionError("survivor lease never revoked after drain")

    body = json.dumps({"prompt": [int(t) for t in prompt_c],
                       "max_new_tokens": MAX_NEW}).encode()
    request = urllib.request.Request(
        f"http://{router_ep}/v1/generate", data=body,
        headers={"Content-Type": "application/json"},
    )
    started = time.monotonic()
    try:
        urllib.request.urlopen(request, timeout=30.0)
        raise AssertionError("expected a 503 once every decode worker died")
    except urllib.error.HTTPError as exc:
        shed = json.loads(exc.read())
        assert exc.code == 503, exc.code
        assert shed.get("retryable") is True, shed
        assert shed.get("retry_after_s"), shed
    assert time.monotonic() - started < 15.0, "shed was not fast"

    # Fleet rollups: the retry/eviction counters live on this host (router
    # rides the prefill rank's registry), the drained-in-flight counter on
    # the decode tier (rank 2 booked its drain before revoking).
    agg = FleetAggregator(state=state)
    tiers = agg.snapshot()["fleet"]["serving_tiers"]
    assert tiers["prefill"]["evictions"].get("lease_expired", 0) >= 1, tiers
    retried = sum(tiers["prefill"].get("retries", {}).values())
    assert retried >= 2, tiers["prefill"]
    assert tiers["decode"].get("drained_in_flight", 0) >= 1, tiers["decode"]

    router_server.stop()
    print("CHAOS_PHASES_OK worker_kill handoff_drop drain")


def main_chaos():
    """Entry point for the 3-process chaos drill (module docstring)."""
    state = PartialState()
    assert state.num_processes >= 3, "run under `launch --num_processes 3`"
    rank = state.process_index
    role = "prefill" if rank == 0 else "decode"

    from accelerate_tpu.resilience.faults import FaultPlan, set_active_plan
    from accelerate_tpu.serving_net import ServingFrontend
    from accelerate_tpu.serving_net.lease import (
        drain_grace_from_env,
        lease_ttl_from_env,
        retry_budget_from_env,
    )
    from accelerate_tpu.telemetry.fleet import _kv_client

    # The launch flags must have reached every worker's env.
    ttl = lease_ttl_from_env()
    assert ttl == 2.0, f"drill expects --serving_lease_ttl 2, got {ttl}"
    assert retry_budget_from_env() == 3, retry_budget_from_env()
    assert drain_grace_from_env() == 20.0, drain_grace_from_env()

    model = _model()
    server = start_default_server(0)
    endpoint = publish_metrics_endpoint(process_index=rank, server=server)
    assert endpoint is not None, "metrics endpoint registration failed"

    engine = _engine(model)
    frontend = ServingFrontend(engine, role=role)
    if rank == 1:
        # The victim. Soft death ("stream") keeps the PROCESS alive so the
        # gang's coordination-service barriers stay sound, while the worker
        # behaves exactly like a corpse on the wire: its stream breaks with
        # no terminal frame, its heartbeat stops so the lease expires, and
        # every later handler answers 503 (probes fail). The hard
        # ``os._exit`` flavor stays the production default.
        frontend.kill_mode = "stream"
        set_active_plan(FaultPlan.parse("req:0=worker_kill"))
    elif rank == 2:
        # The survivor: stretch its third admission (phase C) so the drain
        # order always lands while that request is in flight — and exercise
        # the slow_worker grammar while at it. Admissions here: phase A's
        # retry leg (0), phase B's re-entry (1), phase C (2); seq 3 is armed
        # too in case phase B re-enters twice.
        set_active_plan(
            FaultPlan.parse("req:2=slow_worker:6x;req:3=slow_worker:6x"))
    frontend.install(process_index=rank, endpoint=endpoint)

    kv_all_gather("ready", state.num_processes, rank,
                  namespace="at_chaos_drill/ready")
    client = _kv_client()

    if rank == 0:
        _drive_chaos(state, model, engine, endpoint, ttl)
        client.key_value_set("at_chaos_drill/done", "1")
        frontend.uninstall()
    elif rank == 1:
        # Serve until rank 0 is done (the kill arrives over HTTP); no
        # all-rank barrier after the fault — the corpse must not be waited
        # on by anyone.
        client.blocking_key_value_get("at_chaos_drill/done", 480_000)
    else:
        # Serve until ordered to drain, then deliver SIGTERM to ourselves —
        # the preemption watcher (installed by frontend.install) flips the
        # flag, and the frontend's watch thread runs the drain: admission
        # stops, the in-flight stream finishes, the lease is revoked.
        client.blocking_key_value_get("at_chaos_drill/drain", 480_000)
        os.kill(os.getpid(), signal.SIGTERM)
        client.blocking_key_value_get("at_chaos_drill/done", 480_000)

    print(f"DISAGG_OK rank={rank} role={role} endpoint={endpoint}")


if __name__ == "__main__":
    if os.environ.get("AT_DISAGG_CHAOS") == "1":
        main_chaos()
    else:
        main()
