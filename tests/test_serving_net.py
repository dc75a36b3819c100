"""Disaggregated serving tier (``serving_net/``): roles, tier arbitration,
KV-chain handoff, and the HTTP/SSE front end + affinity router.

Correctness contract: disaggregation is state surgery, never a recompute —
a request prefilled on one engine and decoded on another produces greedy
output bit-identical to one unified engine running it end to end, and the
router-assigned rid threads one trace through every tier the request
crosses. The 2-process launcher drill
(``accelerate_tpu/test_utils/disagg_script.py``) pins the same properties
across real process boundaries.
"""

import io
import json
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import Llama, LlamaConfig
from accelerate_tpu.serving import ContinuousBatcher
from accelerate_tpu.serving_net import (
    SERVING_ROLES,
    Router,
    ServingFrontend,
    ServingRole,
    export_chain,
    import_chain,
    resolve_serving_role,
    router_endpoint_from_env,
    run_prefill_only,
)
from accelerate_tpu.serving_net.frontend import (
    iter_sse,
    read_sse_response,
    sse_event,
)
from accelerate_tpu.serving_net.router import reset_serving_registry
from accelerate_tpu.telemetry.slo import arbitrate_serving_tier

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def llama():
    model = Llama(LlamaConfig.tiny(num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2))
    model.init_params(jax.random.key(0))
    return model


def _paged(model, **overrides):
    kw = dict(batch_slots=2, max_new_tokens=8, max_cache_len=1024,
              cache_dtype=jnp.float32, bucket_sizes=(8, 16), sync_every=2,
              block_size=4, prefill_chunk=8,
              max_tokens_per_request=48)
    kw.update(overrides)
    return ContinuousBatcher(model, **kw)


# ================================================================== roles
def test_serving_role_env_contract(monkeypatch):
    """Role resolution is the launcher env contract: unset = unified,
    ACCELERATE_SERVING_ROLE wins, explicit beats env, junk raises with the
    valid set named."""
    monkeypatch.delenv("ACCELERATE_SERVING_ROLE", raising=False)
    assert resolve_serving_role().name == "unified"
    monkeypatch.setenv("ACCELERATE_SERVING_ROLE", "prefill")
    assert resolve_serving_role().name == "prefill"
    assert resolve_serving_role("decode").name == "decode"
    with pytest.raises(ValueError, match="unknown serving role"):
        resolve_serving_role("prefilll")
    role = ServingRole("prefill")
    assert role.prefills and not role.decodes and role.runs_engine
    role = ServingRole("router")
    assert not role.runs_engine
    assert set(SERVING_ROLES) == {"unified", "prefill", "decode", "router"}

    monkeypatch.delenv("ACCELERATE_ROUTER_ENDPOINT", raising=False)
    assert router_endpoint_from_env() is None
    monkeypatch.setenv("ACCELERATE_ROUTER_ENDPOINT", "10.0.0.1:9090")
    assert router_endpoint_from_env() == "10.0.0.1:9090"
    assert router_endpoint_from_env("  ") is None


def test_tier_arbitration_policy():
    """The SLO sentinel's admission matrix: single-chunk prompts decode
    where they land; multi-chunk prompts enter the prefill tier when one
    exists — unless a TTFT-only SLO (nothing to protect on TPOT) keeps them
    on the decode host, skipping the handoff RTT."""
    from accelerate_tpu.serving import SLOTargets

    assert arbitrate_serving_tier(500, have_prefill_tier=False) == "decode"
    assert arbitrate_serving_tier(
        8, prefill_chunk=8, have_prefill_tier=True) == "decode"
    assert arbitrate_serving_tier(
        9, prefill_chunk=8, have_prefill_tier=True) == "prefill"
    assert arbitrate_serving_tier(
        9, SLOTargets(ttft_s=0.1), prefill_chunk=8,
        have_prefill_tier=True) == "decode"
    assert arbitrate_serving_tier(
        9, SLOTargets(ttft_s=0.1, tpot_s=0.01), prefill_chunk=8,
        have_prefill_tier=True) == "prefill"


# ================================================================= handoff
def test_chain_handoff_bit_identical(llama):
    """The tentpole property, in process: prefill on engine A, export the
    chain, import into engine B, decode there — greedy output bit-identical
    to one unified engine, blocks freed on the exporter, one rid across
    both tiers' tracer records with the handoff legs booked."""
    rng = np.random.default_rng(42)
    prompt = rng.integers(1, 256, (21,)).astype(np.int32)

    unified = _paged(llama)
    rid_u = unified.submit(prompt)
    expected = unified.run()[rid_u]

    prefill = _paged(llama)
    decode = _paged(llama)
    free_before = len(prefill._free_blocks)
    rid = prefill.submit(prompt, tier="prefill")
    run_prefill_only(prefill, rid)
    payload = export_chain(prefill, rid, endpoint="dec:1")
    # The exporter's pool is whole again the moment the chain is copied out.
    assert len(prefill._free_blocks) == free_before
    assert payload["rid"] == rid and payload["data_blocks"] == -(-21 // 4)

    # The payload is JSON-safe by construction — it crosses hosts as text.
    payload = json.loads(json.dumps(payload))
    assert import_chain(decode, payload, endpoint="pre:0") == rid
    outs = decode.run()
    np.testing.assert_array_equal(outs[rid], expected)

    pre_rec = {r["rid"]: r for r in prefill.tracer.records()}[rid]
    assert pre_rec["state"] == "handed_off" and pre_rec["tier"] == "prefill"
    assert pre_rec["handoff"]["direction"] == "out"
    assert pre_rec["handoff"]["bytes"] > 0
    assert len(pre_rec["chunks"]) >= 2  # 21 tokens / chunk 8
    dec_rec = {r["rid"]: r for r in decode.tracer.records()}[rid]
    assert dec_rec["state"] == "finished"
    assert dec_rec["handoff"]["direction"] == "in"
    assert dec_rec["ttft_s"] is not None and dec_rec["tpot_s"] is not None


def test_chain_import_rejects_layout_mismatch(llama):
    """A chain only splices into a pool with the exporter's exact layout —
    block size drift is a hard error naming both sides, not corruption."""
    prompt = np.arange(1, 22, dtype=np.int32)
    prefill = _paged(llama)
    rid = prefill.submit(prompt, tier="prefill")
    run_prefill_only(prefill, rid)
    payload = export_chain(prefill, rid)
    other = _paged(llama, block_size=8, bucket_sizes=(8, 16))
    with pytest.raises(ValueError, match="layout mismatch"):
        import_chain(other, payload)
    bad = dict(payload, version=99)
    with pytest.raises(ValueError, match="version"):
        import_chain(_paged(llama), bad)


def test_frontend_role_validation(llama):
    """The frontend refuses the role it cannot serve: router runs no engine."""
    with pytest.raises(ValueError, match="router role runs no engine"):
        ServingFrontend(_paged(llama), role="router")


# ================================================================ SSE wire
def test_sse_wire_format_roundtrip():
    """sse_event → iter_sse → read_sse_response is a faithful round trip,
    and an error frame raises client-side instead of silently truncating."""
    stream = (sse_event("tokens", {"rid": 1, "tokens": [5, 6]})
              + sse_event("tokens", {"rid": 1, "tokens": [7]})
              + sse_event("done", {"rid": 1, "tokens": [5, 6, 7],
                                   "ttft_s": 0.1, "tpot_s": 0.01,
                                   "trace": []}))
    frames = list(iter_sse(io.BytesIO(stream.encode())))
    assert [k for k, _ in frames] == ["tokens", "tokens", "done"]
    result = read_sse_response(io.BytesIO(stream.encode()))
    assert result["tokens"] == [5, 6, 7]
    assert result["deltas"] == [[5, 6], [7]]
    assert result["done"]["ttft_s"] == 0.1

    broken = sse_event("error", {"rid": 1, "error": "pool exhausted"})
    with pytest.raises(RuntimeError, match="pool exhausted"):
        read_sse_response(io.BytesIO(broken.encode()))
    with pytest.raises(RuntimeError, match="without a done event"):
        read_sse_response(io.BytesIO(b""))


# ============================================================== HTTP rig
def _start_worker(engine, role):
    from accelerate_tpu.telemetry.metrics import MetricsServer

    server = MetricsServer(0, host="127.0.0.1")
    port = server.start()
    frontend = ServingFrontend(engine, role=role)
    frontend.install(server=server, endpoint=f"127.0.0.1:{port}")
    return server, frontend, f"127.0.0.1:{port}"


def _generate(endpoint, prompt, max_new=8):
    req = urllib.request.Request(
        f"http://{endpoint}/v1/generate",
        data=json.dumps({"prompt": [int(t) for t in prompt],
                         "max_new_tokens": max_new}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120.0) as response:
        return read_sse_response(response)


def test_router_http_end_to_end(llama):
    """The full rig over real loopback HTTP: a router + prefill + decode
    worker, prompts on both sides of the chunk boundary, streamed output
    bit-identical to a unified engine, one rid-joined trace spanning every
    tier crossed, and the router's stats carrying the routing split."""
    prompts = [np.asarray(p, np.int32) for p in (
        [7, 3, 11, 2, 9],                                        # 1 chunk
        list(range(1, 22)),                                      # 3 chunks
        [5, 1, 4],                                               # 1 chunk
    )]
    unified = _paged(llama)
    rids = [unified.submit(p) for p in prompts]
    baseline = unified.run()
    expected = [[int(t) for t in baseline[r]] for r in rids]

    servers, frontends = [], []
    try:
        server, fe, prefill_ep = _start_worker(_paged(llama), "prefill")
        servers.append(server)
        frontends.append(fe)
        server, fe, decode_ep = _start_worker(_paged(llama), "decode")
        servers.append(server)
        frontends.append(fe)
        from accelerate_tpu.telemetry.metrics import MetricsServer

        router_server = MetricsServer(0, host="127.0.0.1")
        router_port = router_server.start()
        servers.append(router_server)
        router = Router(workers=[
            {"rank": 0, "role": "prefill", "endpoint": prefill_ep},
            {"rank": 1, "role": "decode", "endpoint": decode_ep},
        ])
        router_server.set_serving(router)
        router_ep = f"127.0.0.1:{router_port}"

        results, errors = [None] * len(prompts), []

        def client(i, prompt):
            try:
                results[i] = _generate(router_ep, prompt)
            except Exception as exc:
                errors.append(f"request {i}: {exc!r}")

        threads = [threading.Thread(target=client, args=(i, p))
                   for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors

        for i, result in enumerate(results):
            assert result["tokens"] == expected[i], i
            trace = result["done"]["trace"]
            tiers = [r.get("tier") for r in trace]
            want = (["router", "prefill", "decode"] if prompts[i].size > 8
                    else ["router", "decode"])
            assert tiers == want, (i, tiers)
            assert len({r["rid"] for r in trace}) == 1
            assert result["done"]["ttft_s"] is not None

        stats = router.stats()
        assert stats["routed"] == {"decode": 2, "prefill": 1}, stats

        # The prefixes probe is the affinity feed: a prompt whose prefix is
        # resident on the decode worker answers > 0 once shared blocks pin
        # it; a cold worker answers 0.
        probe = urllib.request.Request(
            f"http://{decode_ep}/v1/prefixes",
            data=json.dumps({"prompt": [123, 45, 67]}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(probe, timeout=30.0) as response:
            answer = json.loads(response.read())
        assert answer["role"] == "decode" and answer["match_tokens"] == 0
    finally:
        for fe in frontends:
            fe.uninstall()
        for server in servers:
            server.stop()
        reset_serving_registry()


def test_router_refuses_without_decode_worker():
    """Admission fails closed: no decode-capable worker is a 503-shaped
    RuntimeError, not a hang."""
    router = Router(workers=[
        {"rank": 0, "role": "prefill", "endpoint": "127.0.0.1:1"},
    ])
    with pytest.raises(RuntimeError, match="no decode-capable"):
        router.route({"prompt": [1, 2, 3]})
    with pytest.raises(ValueError, match="prompt"):
        router.route({"prompt": []})


# ========================================================== launcher drill
def test_serving_two_process_disagg_drill():
    """Acceptance: prefill and decode on disjoint launcher processes, a
    router discovering both through the coordination-service KV namespace,
    bit-identical greedy output vs single-host serving, one trace spanning
    router admission → prefill chunks → chain handoff → first decode token,
    and `accelerate-tpu top` rendering both tiers' rollups (all asserted
    inside the script)."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("ACCELERATE_")}
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable, "-m", "accelerate_tpu.commands.launch", "--cpu",
            "--num_processes", "2", "-m",
            "accelerate_tpu.test_utils.disagg_script",
        ],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    assert proc.stdout.count("DISAGG_OK") == 2
