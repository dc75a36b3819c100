"""Spans inside the serving path (``telemetry/spans.py`` is the one
mechanism): what a record carries (``rid``, ``attrs``), the paged loop's
``serve.*`` spans against the engine's own bookkeeping, the front end's
``frontend.submit`` / ``frontend.relay``, the one switch that turns all of it
off, and the names the engine's compiled programs carry in a profile."""

import collections
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import Llama, LlamaConfig
from accelerate_tpu.serving import ContinuousBatcher
from accelerate_tpu.serving_net import ServingFrontend
from accelerate_tpu.serving_net.frontend import read_sse_response
from accelerate_tpu.telemetry import (
    SpanRing,
    get_span_ring,
    record_span,
    reset_spans,
    span,
)
from accelerate_tpu.utils.transfer import reset_transfer_stats, transfer_stats

PROMPT_LENS = (5, 21, 9, 30)  # 21 and 30 need several chunks of 8


@pytest.fixture(scope="module")
def llama():
    model = Llama(LlamaConfig.tiny(num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2))
    model.init_params(jax.random.key(0))
    return model


def _paged(model, **overrides):
    kw = dict(batch_slots=2, max_new_tokens=12, max_cache_len=512,
              cache_dtype=jnp.float32, bucket_sizes=(8, 16), sync_every=4,
              block_size=4, prefill_chunk=8, max_tokens_per_request=64)
    kw.update(overrides)
    return ContinuousBatcher(model, **kw)


def _wave(engine):
    """One wave of mixed prompts; returns (outputs, the ring's serving records,
    transfer counts of the wave). The ``program.*`` records of the programs the
    wave builds are left out (``tests/test_compile_records.py`` reads them)."""
    rng = np.random.default_rng(1)
    for n in PROMPT_LENS:
        engine.submit(rng.integers(1, 256, (n,)).astype(np.int32))
    reset_spans()
    reset_transfer_stats()
    outs = engine.run()
    records = [r for r in get_span_ring().snapshot() if not r.name.startswith("program.")]
    return outs, records, transfer_stats()


@pytest.fixture(scope="module")
def traced_wave(llama):
    engine = _paged(llama)
    return (engine,) + _wave(engine)


# ------------------------------------------------------------ the mechanism
def test_span_records_rid_and_attrs_and_nests_as_before():
    ring = SpanRing(capacity=16)
    with span("outer", ring=ring, rid=7, slots=12) as outer:
        outer.attrs["late"] = 3  # learnt inside the block
        with span("inner", ring=ring) as inner:
            inner.rid = 8
    inner, outer = ring.snapshot()  # pushed at exit
    assert (outer.rid, outer.attrs) == (7, {"slots": 12, "late": 3})
    assert (inner.rid, inner.attrs) == (8, None)
    assert (inner.depth, inner.path) == (1, "outer/inner")
    assert (outer.depth, outer.path) == (0, "outer")
    assert outer.duration_s >= inner.duration_s >= 0.0


def test_default_ring_holds_a_benchmark_run_s_records():
    """The process ring has to keep every record a serving cell writes from
    its window's start to the read after the drain (``PERF.md`` section 7: a
    reader gives nothing once the ring has wrapped past the window's start).
    A run writes 6.9 records a turn, and before its window one record a
    phase of each program's start-up, the traces of inner jits included (over
    9,000 in the long-document cell); 65,536 slots hold those and 240 s of
    turns down to 30 ms. A push stays one slot write whatever the capacity."""
    from accelerate_tpu.telemetry import get_span_ring

    assert SpanRing().capacity == get_span_ring().capacity == 65536
    ring = SpanRing()
    for i in range(ring.capacity + 5):
        with span("turn", ring=ring, rid=i):
            pass
    kept = ring.snapshot()
    assert ring.total == ring.capacity + 5 and len(kept) == ring.capacity
    assert (kept[0].rid, kept[-1].rid) == (5, ring.capacity + 4)


def test_record_span_pushes_an_interval_read_on_two_threads():
    """The second entry point: explicit ends on ``time.perf_counter()``, no
    annotation, and the calling thread's span stack left alone."""
    ring = SpanRing(capacity=4)
    t0 = time.perf_counter()
    ended = []
    worker = threading.Thread(target=lambda: ended.append(time.perf_counter()))
    worker.start()
    worker.join(10.0)
    with span("around", ring=ring):
        record_span("frontend.relay", t0, ended[0], rid=3, ring=ring, hops=1)
    relay, around = ring.snapshot()
    assert (relay.name, relay.rid, relay.attrs) == ("frontend.relay", 3, {"hops": 1})
    assert relay.start_s == t0 and relay.duration_s == ended[0] - t0
    assert (relay.depth, relay.path) == (0, "frontend.relay")
    assert around.path == "around"


# ------------------------------------------------------------ the paged loop
def test_paged_wave_yields_one_iteration_per_turn_with_its_children(traced_wave):
    _, _, records, _ = traced_wave
    names = collections.Counter(r.name for r in records)
    assert names["serve.run"] == 1
    run = next(r for r in records if r.name == "serve.run")
    assert run.attrs == {"finished": len(PROMPT_LENS)}
    turns = [r for r in records if r.name == "serve.iteration"]
    assert names["serve.admit"] == len(turns)  # every turn admits first
    assert names["serve.report_wait"] == names["serve.process_report"]
    for r in records:
        if r.name == "serve.run":
            continue
        want = "serve.run/serve.iteration" + ("" if r.name == "serve.iteration" else "/" + r.name)
        assert r.path == want, r
    for turn in turns:
        assert set(turn.attrs) == {"chunk", "decoding", "prefilling", "queued", "free_blocks"}
        inside = [r for r in records if r.depth == 2
                  and turn.start_s <= r.start_s <= turn.start_s + turn.duration_s]
        kinds = collections.Counter(r.name for r in inside)
        assert kinds["serve.dispatch_chunk"] == (1 if turn.attrs["chunk"] else 0)
        assert kinds["serve.dispatch_decode"] == (1 if turn.attrs["decoding"] else 0)
        assert max(kinds.values()) == 1, kinds  # at most one of each a turn
    assert turns[0].attrs["chunk"] == 8 and turns[0].attrs["queued"] == 2


def test_dispatch_spans_count_what_the_dispatch_log_counts(traced_wave):
    engine, _, records, _ = traced_wave
    log = collections.Counter(e.split(":")[0] for e in engine._dispatch_log)
    chunks = [r for r in records if r.name == "serve.dispatch_chunk"]
    windows = [r for r in records if r.name == "serve.dispatch_decode"]
    assert (len(chunks), len(windows)) == (log["chunk"], log["decode"])
    assert [f"chunk:{r.attrs['p']}" for r in chunks] == [
        e for e in engine._dispatch_log if e.startswith("chunk")]
    # each request's chunks carry its rid, their real tokens add up to its
    # prompt, and exactly the last one is final
    for rid, n in enumerate(PROMPT_LENS):
        mine = [r for r in chunks if r.rid == rid]
        assert sum(r.attrs["tokens"] for r in mine) == n
        assert [r.attrs["final"] for r in mine] == [False] * (len(mine) - 1) + [True]
    full = engine.max_blocks_per_slot * engine.block_size
    for w in windows:
        assert w.attrs["slots"] == 2 and w.attrs["window"] == 4
        assert 1 <= w.attrs["decoding"] <= 2
        # the view's width: this engine's table is under 512 columns, one width
        assert w.attrs["view_cols"] == w.attrs["view_cols_full"] == full


def test_process_report_tokens_sum_to_the_tokens_returned(traced_wave):
    _, outs, records, _ = traced_wave
    reports = [r for r in records if r.name == "serve.process_report"]
    assert sum(r.attrs["tokens"] for r in reports) == sum(len(v) for v in outs.values())
    assert sum(r.attrs["finished"] for r in reports) == len(outs)


def test_trace_requests_false_records_nothing_and_moves_no_transfer(llama, traced_wave):
    """The engine's one switch turns every serving span off; on or off, the
    loop fetches the same reports (the spans add no transfer; ``blocking``
    depends on the CPU's timing and is not compared)."""
    _, outs_on, _, stats_on = traced_wave
    engine = _paged(llama, trace_requests=False)
    outs_off, records, stats_off = _wave(engine)
    assert records == []
    assert engine.tracer is None
    for rid in outs_on:
        np.testing.assert_array_equal(outs_on[rid], outs_off[rid])
    for key in ("fetches", "h2d_puts", "h2d_blocking"):
        assert stats_on[key] == stats_off[key], key


# -------------------------------------------------------------- the front end
def _post_generate(endpoint, prompt, max_new):
    req = urllib.request.Request(
        f"http://{endpoint}/v1/generate",
        data=json.dumps({"prompt": [int(t) for t in prompt],
                         "max_new_tokens": max_new}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120.0) as response:
        return read_sse_response(response)


@pytest.mark.parametrize("trace_requests", [True, False])
def test_frontend_request_yields_one_submit_and_one_relay(llama, trace_requests):
    from accelerate_tpu.telemetry.metrics import MetricsServer

    server = MetricsServer(0, host="127.0.0.1")
    endpoint = f"127.0.0.1:{server.start()}"
    frontend = ServingFrontend(_paged(llama, trace_requests=trace_requests))
    frontend.install(server=server, endpoint=endpoint)
    reset_spans()
    try:
        done = _post_generate(endpoint, np.arange(1, 12, dtype=np.int32), 12)
    finally:
        frontend.uninstall()
        server.stop()
    assert len(done["tokens"]) == 12
    front = [r for r in get_span_ring().snapshot() if r.name.startswith("frontend.")]
    if not trace_requests:
        assert front == [] and not frontend._relay_t0
        return
    assert sorted(r.name for r in front) == ["frontend.relay", "frontend.submit"]
    relay, submit = sorted(front, key=lambda r: r.name)
    assert relay.rid == submit.rid == done["done"]["rid"]
    assert submit.attrs == {"prompt_tokens": 11}
    # 12 tokens at 4 a window stream in several events: the relay is the first
    # one's alone, it starts after the submit and is shorter than the request
    assert relay.start_s >= submit.start_s and 0.0 <= relay.duration_s < 60.0
    assert not frontend._relay_t0  # nothing left behind


# ------------------------------------------------------- names of the programs
@pytest.mark.parametrize("name, lower", [
    ("serve_decode_window", lambda e: e._decode().lower(*e._decode_args())),
    ("serve_prefill_chunk_8", lambda e: e._chunk_fn(8).lower(*e._chunk_args(8))),
    ("serve_prefill_chunk_16", lambda e: e._chunk_fn(16).lower(*e._chunk_args(16))),
    ("serve_spec_verify", lambda e: e._spec_verify().lower(*e._verify_args())),
])
def test_paged_programs_carry_their_names(llama, name, lower):
    spec = dict(speculative_k=2, draft_model=llama) if name == "serve_spec_verify" else {}
    text = lower(_paged(llama, **spec)).as_text()
    assert f"module @jit_{name} " in text.split("\n", 1)[0]
