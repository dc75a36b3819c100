"""Serving-tier fault tolerance (``serving_net/lease.py`` + the router's
retry/breaker layer + the frontend's drain path): lease-based discovery,
retry/re-handoff under the SAME rid, free-on-ack chain ownership, graceful
drain, and the ``req:`` chaos grammar.

Correctness contract: a worker death mid-stream is invisible to the client
beyond latency — the router replays on a survivor (greedy decode is
deterministic), trims the already-delivered prefix, and the client sees ONE
contiguous bit-identical stream. Every stream ends in a terminal frame
(``done`` or ``error`` with a ``retryable`` verdict); a failed handoff never
leaks pool blocks; a drained worker finishes its in-flight work and revokes
its lease. The 3-process launcher drill at the bottom pins the same
properties across real process boundaries with real kills.
"""

import io
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import Llama, LlamaConfig
from accelerate_tpu.resilience.faults import (
    FaultPlan,
    reset_active_plan,
    serving_fault,
    set_active_plan,
)
from accelerate_tpu.serving import ContinuousBatcher
from accelerate_tpu.serving_net import (
    LeaseHeartbeat,
    Router,
    ServingFrontend,
    ServingStreamError,
    export_chain,
    release_chain,
    run_prefill_only,
)
from accelerate_tpu.serving_net.frontend import read_sse_response, sse_event
from accelerate_tpu.serving_net.lease import (
    DEFAULT_DRAIN_GRACE_S,
    DEFAULT_LEASE_TTL_S,
    DEFAULT_RETRY_BUDGET,
    drain_grace_from_env,
    encode_lease,
    lease_expired,
    lease_ttl_from_env,
    parse_lease,
    retry_budget_from_env,
)
from accelerate_tpu.serving_net.router import (
    _Breaker,
    discover_serving_workers,
    publish_serving_endpoint,
    reset_serving_registry,
    revoke_serving_endpoint,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def llama():
    model = Llama(LlamaConfig.tiny(num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2))
    model.init_params(jax.random.key(0))
    return model


@pytest.fixture(autouse=True)
def _clean_slate():
    yield
    reset_active_plan()
    reset_serving_registry()
    # The routed/retry/eviction counters are process-global and cumulative;
    # later files (test_serving_net) assert absolute counts from zero.
    from accelerate_tpu.telemetry.metrics import get_registry

    get_registry().reset()


def _paged(model, **overrides):
    kw = dict(batch_slots=2, max_new_tokens=8, max_cache_len=1024,
              cache_dtype=jnp.float32, bucket_sizes=(8, 16), sync_every=2,
              block_size=4, prefill_chunk=8,
              max_tokens_per_request=48)
    kw.update(overrides)
    return ContinuousBatcher(model, **kw)


def _start_worker(engine, role):
    from accelerate_tpu.telemetry.metrics import MetricsServer

    server = MetricsServer(0, host="127.0.0.1")
    port = server.start()
    frontend = ServingFrontend(engine, role=role)
    frontend.install(server=server, endpoint=f"127.0.0.1:{port}")
    return server, frontend, f"127.0.0.1:{port}"


def _generate(endpoint, prompt, max_new=8, **extra):
    body = {"prompt": [int(t) for t in prompt], "max_new_tokens": max_new}
    body.update(extra)
    req = urllib.request.Request(
        f"http://{endpoint}/v1/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120.0) as response:
        return read_sse_response(response)


# ============================================================ chaos grammar
def test_fault_plan_req_grammar():
    """``req:N=action[:arg]`` parses alongside the training ``step:`` scope,
    validates its arguments at parse time, and consumption is filtered by
    site (the admission path never eats a handoff fault) and fired-once."""
    plan = FaultPlan.parse(
        "req:0=worker_kill;req:1=handoff_drop;req:2=stall:0.5;"
        "req:3=slow_worker:4x;step:9=kill"
    )
    by_step = {(f.scope, f.step): f for f in plan.faults}
    assert by_step[("req", 2)].stall_s == 0.5
    assert by_step[("req", 3)].slow_factor == 4.0
    assert by_step[("step", 9)].action == "kill"

    # Site filtering: the handoff site only consumes handoff_drop, so an
    # armed worker_kill at the same index survives for the admission site.
    assert plan.take_serving_fault(0, ("handoff_drop",)) is None
    fault = plan.take_serving_fault(0, ("worker_kill", "stall", "slow_worker"))
    assert fault is not None and fault.action == "worker_kill"
    assert plan.take_serving_fault(0) is None  # fired once

    for bad in ("req:0=explode", "req:0=worker_kill:3", "req:0=stall:soon",
                "req:0=slow_worker:0x", "req:x=worker_kill"):
        with pytest.raises(ValueError, match="Bad fault-plan entry"):
            FaultPlan.parse(bad)

    # The module-level hook reads the process-wide plan.
    set_active_plan(FaultPlan.parse("req:1=stall:0.01"))
    assert serving_fault(0) is None
    assert serving_fault(1).stall_s == 0.01
    assert serving_fault(1) is None


# ==================================================================== lease
def test_lease_wire_format(monkeypatch):
    """Encode/parse round trip, back-compat with the pre-lease value, and
    the tri-state env accessors the launcher flags feed."""
    now = 1000.0
    value = encode_lease("decode", "10.0.0.1:9090", ttl_s=15.0, now=now)
    assert value == "decode|10.0.0.1:9090|expires=1015.000"
    lease = parse_lease(value)
    assert lease == {"role": "decode", "endpoint": "10.0.0.1:9090",
                     "expires": 1015.0}
    assert not lease_expired(lease, now=1014.9)
    assert lease_expired(lease, now=1015.1)

    # Pre-lease registrations (no expiry) stay parseable and never expire.
    bare = parse_lease("prefill|10.0.0.1:9091")
    assert bare["expires"] is None and not lease_expired(bare, now=1e18)
    assert encode_lease("prefill", "10.0.0.1:9091", ttl_s=0) == \
        "prefill|10.0.0.1:9091"
    assert parse_lease("garbage") is None

    for env in ("ACCELERATE_SERVING_LEASE_TTL", "ACCELERATE_SERVING_RETRY_BUDGET",
                "ACCELERATE_DRAIN_GRACE_S"):
        monkeypatch.delenv(env, raising=False)
    assert lease_ttl_from_env() == DEFAULT_LEASE_TTL_S
    assert retry_budget_from_env() == DEFAULT_RETRY_BUDGET
    assert drain_grace_from_env() == DEFAULT_DRAIN_GRACE_S
    monkeypatch.setenv("ACCELERATE_SERVING_LEASE_TTL", "2.5")
    monkeypatch.setenv("ACCELERATE_SERVING_RETRY_BUDGET", "3.0")
    monkeypatch.setenv("ACCELERATE_DRAIN_GRACE_S", "0")  # 0 = library default
    assert lease_ttl_from_env() == 2.5
    assert retry_budget_from_env() == 3
    assert drain_grace_from_env() == DEFAULT_DRAIN_GRACE_S
    monkeypatch.setenv("ACCELERATE_SERVING_LEASE_TTL", "soon")
    with pytest.raises(ValueError, match="must be a number"):
        lease_ttl_from_env()


def test_lease_discovery_filters_corpses():
    """Discovery only returns live leases: an expired lease is filtered (and
    a heartbeat keeps one alive past its raw TTL); a revoked lease vanishes
    immediately."""
    reset_serving_registry()
    publish_serving_endpoint("decode", process_index=0,
                             endpoint="127.0.0.1:1111", ttl_s=30.0)
    publish_serving_endpoint("prefill", process_index=1,
                             endpoint="127.0.0.1:2222", ttl_s=0.05)
    time.sleep(0.1)  # rank 1's lease expires un-refreshed
    workers = discover_serving_workers(2)
    assert [w["endpoint"] for w in workers] == ["127.0.0.1:1111"], workers
    assert workers[0]["expires"] is not None

    heartbeat = LeaseHeartbeat("decode", 2, "127.0.0.1:3333", ttl_s=0.3)
    heartbeat.start()
    try:
        time.sleep(0.5)  # > TTL: only the refresh keeps it alive
        endpoints = {w["endpoint"] for w in discover_serving_workers(3)}
        assert "127.0.0.1:3333" in endpoints
    finally:
        heartbeat.stop(revoke=True)
    endpoints = {w["endpoint"] for w in discover_serving_workers(3)}
    assert "127.0.0.1:3333" not in endpoints  # revoked: no TTL wait

    revoke_serving_endpoint(0)
    assert discover_serving_workers(1) == []


# ================================================================== breaker
def test_breaker_state_machine():
    """closed → open after N consecutive failures → half-open one-trial
    after the cooldown; trial success closes, trial failure re-opens; a
    success anywhere resets the consecutive count."""
    breaker = _Breaker(failures=3, cooldown_s=1.0)
    assert breaker.state == "closed" and breaker.allows(0.0)
    assert breaker.fail(0.0) is False
    assert breaker.fail(0.0) is False
    breaker.ok()  # a success resets the streak
    assert breaker.consecutive == 0
    assert breaker.fail(1.0) is False
    assert breaker.fail(1.0) is False
    assert breaker.fail(1.0) is True  # third consecutive failure trips it
    assert breaker.state == "open" and not breaker.allows(1.5)

    assert breaker.allows(2.1)  # cooldown over: exactly one trial
    assert breaker.state == "half_open"
    assert not breaker.allows(2.1)  # the trial is out
    breaker.ok()
    assert breaker.state == "closed" and breaker.allows(2.2)

    breaker.fail(3.0), breaker.fail(3.0), breaker.fail(3.0)
    assert breaker.state == "open"
    breaker.permit_trial()  # re-registered worker: skip the cooldown
    assert breaker.allows(3.1) and breaker.state == "half_open"
    assert breaker.fail(3.2) is True  # failed trial re-opens immediately
    assert breaker.state == "open"


# ============================================================ retry relay
def test_router_retry_recovers_worker_kill(llama):
    """The tentpole, in one process: a decode worker dies mid-stream (soft
    ``stream`` kill — same wire behavior as a corpse), the router retries on
    the survivor under the SAME rid, and the client sees one contiguous
    stream bit-identical to the unified baseline. Then consecutive failed
    probes against the corpse trip its breaker and evict it, so later
    requests never re-pick it."""
    prompt = np.asarray([7, 3, 11, 2, 9], np.int32)
    unified = _paged(llama)
    rid = unified.submit(prompt)
    expected = [int(t) for t in unified.run()[rid]]

    servers, frontends = [], []
    try:
        server, victim_fe, victim_ep = _start_worker(_paged(llama), "decode")
        servers.append(server)
        frontends.append(victim_fe)
        server, survivor_fe, survivor_ep = _start_worker(_paged(llama), "decode")
        servers.append(server)
        frontends.append(survivor_fe)
        victim_fe.kill_mode = "stream"  # stay in-process (no os._exit)
        set_active_plan(FaultPlan.parse("req:0=worker_kill"))

        from accelerate_tpu.telemetry.metrics import MetricsServer

        router_server = MetricsServer(0, host="127.0.0.1")
        router_port = router_server.start()
        servers.append(router_server)
        router = Router(workers=[
            {"rank": 0, "role": "decode", "endpoint": victim_ep},
            {"rank": 1, "role": "decode", "endpoint": survivor_ep},
        ], retry_budget=2, backoff_base_s=0.01, backoff_cap_s=0.05)
        router_server.set_serving(router)
        router_ep = f"127.0.0.1:{router_port}"

        # Least-loaded tie-break picks the victim (first listed); its plan
        # kills the stream after the first delta.
        result = _generate(router_ep, prompt)
        assert result["tokens"] == expected, (result["tokens"], expected)
        # Contiguous: the deltas across both legs concatenate to a clean
        # prefix of the final token list (the engine holds the last token
        # for the done frame) — replayed prefix trimmed, nothing repeated,
        # nothing dropped.
        streamed = [t for d in result["deltas"] for t in d]
        assert streamed and streamed == expected[:len(streamed)], (
            streamed, expected)

        stats = router.stats()
        assert stats["retries"].get("stream_broken", 0) >= 1, stats["retries"]
        legs = result["done"]["trace"][0]["retries"]
        assert legs and legs[0]["reason"] == "stream_broken", legs
        assert legs[0]["endpoint"] == victim_ep, legs

        # The corpse now 503s every probe: consecutive failures trip the
        # breaker and evict it; traffic keeps landing on the survivor.
        for _ in range(3):
            assert _generate(router_ep, prompt)["tokens"] == expected
        stats = router.stats()
        assert stats["evictions"].get(victim_ep) == "probe_failures", stats
        assert stats["breakers"][victim_ep] == "open", stats["breakers"]
        endpoints = {w["endpoint"] for w in router.workers()}
        assert victim_ep not in endpoints  # eviction purged the candidate set
    finally:
        for frontend in frontends:
            frontend.uninstall()
        for server in servers:
            server.stop()


def test_retry_budget_exhaustion_is_terminal(llama):
    """When every dispatch fails, the client gets a terminal ``error`` frame
    with ``retryable`` set — never a hang, never a silent EOF."""
    servers, frontends = [], []
    try:
        server, frontend, endpoint = _start_worker(_paged(llama), "decode")
        servers.append(server)
        frontends.append(frontend)
        frontend.kill_mode = "stream"
        # Every admission at this worker dies mid-stream.
        set_active_plan(FaultPlan.parse("req:0=worker_kill"))

        router = Router(workers=[
            {"rank": 0, "role": "decode", "endpoint": endpoint},
        ], retry_budget=1, backoff_base_s=0.01, backoff_cap_s=0.02)
        out = router.handle_post(
            "/v1/generate", {},
            json.dumps({"prompt": [5, 1, 4], "max_new_tokens": 4}).encode())
        assert out[0] == "sse"
        with pytest.raises(ServingStreamError) as excinfo:
            read_sse_response(io.BytesIO("".join(out[1]).encode()))
        # After the kill the corpse 503s the retry dispatch; with no other
        # survivor the budget exhausts and the terminal verdict is final.
        assert excinfo.value.retryable is True
        stats = router.stats()
        assert sum(stats["retries"].values()) >= 1, stats["retries"]
    finally:
        for frontend in frontends:
            frontend.uninstall()
        for server in servers:
            server.stop()


# ============================================================== free-on-ack
def test_free_on_ack_chain_ownership(llama):
    """``export_chain(free=False)`` keeps the chain resident until an ack;
    ``release_chain`` frees it exactly once (idempotent); the default
    export still frees eagerly (the bit-identical handoff contract)."""
    engine = _paged(llama)
    total_free = len(engine._free_blocks)

    rid = engine.submit(np.arange(1, 15, dtype=np.int32))  # multi-chunk
    run_prefill_only(engine, rid)
    held = len(engine._free_blocks)
    assert held < total_free  # the chain holds blocks

    payload = export_chain(engine, rid, endpoint="127.0.0.1:1", free=False)
    assert payload["rid"] == rid
    assert len(engine._free_blocks) == held  # free=False: still ours
    assert release_chain(engine, rid) is True
    assert len(engine._free_blocks) == total_free  # ack freed everything
    assert release_chain(engine, rid) is False  # idempotent second release

    rid2 = engine.submit(np.arange(1, 15, dtype=np.int32))
    run_prefill_only(engine, rid2)
    export_chain(engine, rid2, endpoint="127.0.0.1:1")  # default free=True
    assert len(engine._free_blocks) == total_free


def test_handoff_drop_releases_chain(llama):
    """A dropped handoff with no surviving alternate: the prefill tier
    surfaces a retryable error AND returns every block to the free list —
    a lost export never leaks pool blocks."""
    engine = _paged(llama)
    total_free = len(engine._free_blocks)
    servers, frontends = [], []
    try:
        server, frontend, _ = _start_worker(engine, "prefill")
        servers.append(server)
        frontends.append(frontend)
        set_active_plan(FaultPlan.parse("req:0=handoff_drop"))

        rid = engine.submit(np.arange(1, 15, dtype=np.int32))
        frames = list(frontend._relay_prefill(rid, "127.0.0.1:1"))
        assert frames, "no terminal frame"
        kind, payload = frames[-1].split("\n", 1)
        assert kind == "event: error", frames[-1]
        detail = json.loads(payload.split("data:", 1)[1].strip().split("\n")[0])
        assert detail["retryable"] is True, detail
        assert len(engine._free_blocks) == total_free, "handoff leaked blocks"
    finally:
        for frontend in frontends:
            frontend.uninstall()
        for server in servers:
            server.stop()


# ============================================================ SSE contract
def test_sse_error_frames_carry_retryable():
    """Client-side verdicts: the error frame's ``retryable`` flag reaches
    ``ServingStreamError``; a stream that dies without a terminal frame is
    retryable by definition (the worker may have died mid-write)."""
    fatal = sse_event("error", {"rid": 1, "error": "boom", "retryable": False})
    with pytest.raises(ServingStreamError) as excinfo:
        read_sse_response(io.BytesIO(fatal.encode()))
    assert excinfo.value.retryable is False

    transient = sse_event("error", {"rid": 1, "error": "boom"})
    with pytest.raises(ServingStreamError) as excinfo:
        read_sse_response(io.BytesIO(transient.encode()))
    assert excinfo.value.retryable is True  # default when unmarked

    truncated = sse_event("tokens", {"rid": 1, "tokens": [5]})
    with pytest.raises(ServingStreamError) as excinfo:
        read_sse_response(io.BytesIO(truncated.encode()))
    assert excinfo.value.retryable is True
    # ServingStreamError stays a RuntimeError (back-compat for callers).
    assert isinstance(excinfo.value, RuntimeError)


def test_deadline_dead_on_arrival(llama):
    """A request whose propagated deadline already passed is refused with a
    non-retryable 400 — retrying can't resurrect a client that stopped
    waiting."""
    servers, frontends = [], []
    try:
        server, frontend, endpoint = _start_worker(_paged(llama), "decode")
        servers.append(server)
        frontends.append(frontend)
        with pytest.raises(urllib.error.HTTPError) as excinfo:
            _generate(endpoint, [1, 2, 3], deadline_wall=time.time() - 5.0)
        assert excinfo.value.code == 400
        detail = json.loads(excinfo.value.read())
        assert detail["retryable"] is False, detail
    finally:
        for frontend in frontends:
            frontend.uninstall()
        for server in servers:
            server.stop()


# ==================================================================== drain
def test_drain_finishes_in_flight_and_revokes(llama):
    """The SIGTERM sequence, driven directly: admission stops (503 with
    ``retryable`` + ``retry_after_s``), the in-flight stream finishes, the
    drained-in-flight counter books it, and the lease is revoked."""
    from accelerate_tpu.serving_net.frontend import _drain_counter

    reset_serving_registry()
    prompt = np.asarray([5, 1, 4], np.int32)
    unified = _paged(llama)
    rid = unified.submit(prompt)
    expected = [int(t) for t in unified.run()[rid]]

    servers, frontends = [], []
    try:
        server, frontend, endpoint = _start_worker(_paged(llama), "decode")
        servers.append(server)
        frontends.append(frontend)
        assert discover_serving_workers(1), "lease never published"

        # Stretch the stream so the drain provably overlaps it.
        set_active_plan(FaultPlan.parse("req:0=slow_worker:4x"))
        result, errors = {}, []

        def client():
            try:
                result["res"] = _generate(endpoint, prompt)
            except Exception as exc:  # surfaced by the join assert
                errors.append(repr(exc))

        thread = threading.Thread(target=client)
        thread.start()
        deadline = time.monotonic() + 30.0
        while frontend.engine.in_flight() < 1:
            assert time.monotonic() < deadline, "request never admitted"
            time.sleep(0.01)

        drained_before = _drain_counter().value()
        drain_thread = threading.Thread(target=frontend.drain,
                                        kwargs={"grace_s": 30.0})
        drain_thread.start()
        while not frontend.draining:
            time.sleep(0.005)
        # Admission refused DURING the drain, while the stream still runs.
        with pytest.raises(urllib.error.HTTPError) as excinfo:
            _generate(endpoint, prompt)
        assert excinfo.value.code == 503
        refusal = json.loads(excinfo.value.read())
        assert refusal["retryable"] is True and refusal["retry_after_s"], refusal

        drain_thread.join(60.0)
        assert not drain_thread.is_alive(), "drain never finished"
        thread.join(60.0)
        assert not errors, errors
        assert result["res"]["tokens"] == expected  # in-flight work finished
        assert _drain_counter().value() == drained_before + 1
        assert frontend.stats()["draining"] is True
        assert discover_serving_workers(1) == []  # lease revoked outright
    finally:
        for frontend in frontends:
            frontend.uninstall()
        for server in servers:
            server.stop()


# ============================================================= degradation
def test_router_sheds_with_retry_after_when_no_decode(llama):
    """All decode capacity gone: admission is a FAST 503 carrying
    ``retryable`` + ``retry_after_s``, booked as an availability breach and
    a ``no_decode`` degradation — never a hang."""
    from accelerate_tpu.telemetry.slo import _breach_counter

    router = Router(workers=[
        {"rank": 0, "role": "prefill", "endpoint": "127.0.0.1:1"},
    ], retry_after_s=1.5)
    breaches_before = _breach_counter().value(target="availability")
    started = time.monotonic()
    out = router.handle_post(
        "/v1/generate", {},
        json.dumps({"prompt": [1, 2, 3]}).encode())
    assert time.monotonic() - started < 5.0, "shed was not fast"
    assert out[0] == "json" and out[1] == 503, out
    shed = out[2]
    assert shed["retryable"] is True and shed["retry_after_s"] == 1.5, shed
    assert _breach_counter().value(target="availability") == breaches_before + 1
    assert router.stats()["degraded"].get("no_decode", 0) >= 1


# ===================================================== zero-transfer pin
def test_fault_tolerance_adds_zero_blocking_transfers(llama):
    """Acceptance pin: the no-fault steady state pays ZERO added blocking
    transfers for the fault-tolerance layer. Judged comparatively through
    ``run_nonblocking_drill`` (the load-tolerant spelling): one generation
    served direct vs served through the router with leases, breakers, and
    deadline bookkeeping active — the routed arm must add no blocking
    device traffic (leases/breakers/deadlines are host-side by design)."""
    from accelerate_tpu.telemetry.metrics import MetricsServer
    from accelerate_tpu.test_utils.drills import run_nonblocking_drill
    from accelerate_tpu.utils.transfer import reset_transfer_stats, transfer_stats

    prompt = np.asarray([7, 3, 11, 2, 9], np.int32)

    def wave(routed: bool):
        servers, frontends = [], []
        try:
            server, frontend, endpoint = _start_worker(_paged(llama), "decode")
            servers.append(server)
            frontends.append(frontend)
            target = endpoint
            if routed:
                router_server = MetricsServer(0, host="127.0.0.1")
                router_port = router_server.start()
                servers.append(router_server)
                router = Router(workers=[
                    {"rank": 0, "role": "decode", "endpoint": endpoint},
                ], retry_budget=3)
                router_server.set_serving(router)
                target = f"127.0.0.1:{router_port}"
            reset_transfer_stats()
            result = _generate(target, prompt)
            stats = transfer_stats()
            return stats, result
        finally:
            for fe in frontends:
                fe.uninstall()
            for srv in servers:
                srv.stop()
            reset_serving_registry()

    wave(routed=False)  # warm the jit cache so both measured arms match

    def drill():
        base, base_result = wave(routed=False)
        routed, routed_result = wave(routed=True)
        assert routed_result["tokens"] == base_result["tokens"]
        return {
            "extra_blocking": max(0, routed["blocking"] - base["blocking"]),
            "extra_h2d_blocking": max(
                0, routed["h2d_blocking"] - base["h2d_blocking"]),
        }

    run_nonblocking_drill(drill, keys=("extra_blocking", "extra_h2d_blocking"))


# ============================================================ launcher drill
def test_serving_chaos_drill_under_launcher():
    """Acceptance: the 3-process chaos drill under the real launcher — a
    worker_kill mid-decode recovers to a bit-identical contiguous stream
    with the corpse lease-evicted within its TTL, a dropped handoff leaks
    no blocks, and a SIGTERM'd worker drains gracefully before the router
    sheds with a fast 503 (all asserted inside the script)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("ACCELERATE_")}
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["AT_DISAGG_CHAOS"] = "1"
    proc = subprocess.run(
        [
            sys.executable, "-m", "accelerate_tpu.commands.launch", "--cpu",
            "--num_processes", "3", "--serving_lease_ttl", "2",
            "--serving_retry_budget", "3", "--drain_grace_s", "20",
            "-m", "accelerate_tpu.test_utils.disagg_script",
        ],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    assert proc.stdout.count("DISAGG_OK") == 3, proc.stdout[-2000:]
    assert "CHAOS_PHASES_OK worker_kill handoff_drop drain" in proc.stdout
