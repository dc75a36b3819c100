"""Checks of ``chipbench/program_spans.py`` and of the readers built on it,
on hand-made rings (run by hand, as ``test_chipbench.py`` is):

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q

The serving timeline used here: the measured window is [100, 110) on the
program's clock (``PROCESS_START`` 90, ``setup_s`` 10, ``--seconds`` 10). A
decode window takes 0.2 s on the device and a 256-token chunk 0.1 s more. A
turn starts 2 ms after the report of the turn before became ready, spends
3 ms dispatching, waits for its report and spends 1 ms on it.
"""

from __future__ import annotations

import collections
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from accelerate_tpu.telemetry.spans import SpanRecord, SpanRing  # noqa: E402
from chipbench import program_spans  # noqa: E402
from chipbench.run import layer_metric  # noqa: E402

WINDOW_S, CHUNK_S = 0.2, 0.1
RECORD = {"kind": "serve", "end_to_end": {"setup_s": 10.0},
          "config": {"engine": {"prefill_chunk": 256}}}
# A record as the parent's program makes it: no ``rid``, no ``attrs``.
OldRecord = collections.namedtuple("OldRecord", "name start_s duration_s depth path")


def rec(name, start, end, rid=None, **attrs):
    return SpanRecord(name=name, start_s=start, duration_s=end - start, depth=0,
                      path=name, rid=rid, attrs=attrs or None)


def serve_ring(chunks, first_ready=99.0, capacity=4096, ready_early=()):
    """A ring of the turns that dispatch ``chunks`` (0 for none) one after the
    other. Turns whose index is in ``ready_early`` find their report ready (the
    device had gone idle): they wait 0.2 ms and the next report becomes ready
    a window after THEIR dispatch, not after the last report."""
    ring = SpanRing(capacity)
    ready = first_ready  # when the report that the turn reads became ready
    start = ready - 0.1
    for k, chunk in enumerate(chunks):
        dispatched = start + 0.003
        if chunk:
            ring.push(rec("serve.dispatch_chunk", start + 0.001, start + 0.002, rid=k,
                          p=chunk, tokens=chunk, final=False))
        ring.push(rec("serve.dispatch_decode", start + 0.002, dispatched,
                      decoding=6, slots=12, window=8))
        waited_until = dispatched + 0.0002 if k in ready_early else ready
        ring.push(rec("serve.report_wait", dispatched, waited_until))
        ring.push(rec("serve.process_report", waited_until, waited_until + 0.001,
                      tokens=40, finished=0))
        ring.push(rec("serve.iteration", start, waited_until + 0.001, chunk=chunk,
                      decoding=6, prefilling=1, queued=0, free_blocks=100))
        busy_from = dispatched if k in ready_early else ready
        ready = busy_from + WINDOW_S + (CHUNK_S if chunk == 256 else 0.03 if chunk else 0.0)
        start = waited_until + 0.001 + 0.002
    return ring


@pytest.fixture
def bench(monkeypatch):
    """Point the helper at a hand-made ring, with the window at [100, 110)."""
    def use(ring, process_start=90.0, seconds=10.0):
        monkeypatch.setattr(program_spans, "ring", lambda: ring)
        monkeypatch.setattr(program_spans, "process_start", lambda: process_start)
        monkeypatch.setattr(program_spans, "window_seconds", lambda: seconds)
    return use


def read(name, record=RECORD):
    return layer_metric(name)(record)


SERVE_READERS = ["decode_window_ms", "prefill_chunk_ms", "serve_host_ms_per_iteration",
                 "decode_slot_occupancy", "dispatches_per_token", "frontend_inside_ms"]


# ---------------------------------------------------------------- the helper
def test_window_is_cut_on_the_programs_clock(bench):
    ring = serve_ring([0] * 60)  # turns from 98.9 to about 111
    bench(ring)
    assert program_spans.serve_window(RECORD) == (100.0, 110.0)
    kept = program_spans.serve_records(RECORD)
    assert kept and all(100.0 <= r.start_s < 110.0 for r in kept)
    assert kept == sorted(kept, key=lambda r: r.start_s)
    outside = [r for r in ring.snapshot() if not 100.0 <= r.start_s < 110.0]
    assert outside and len(kept) + len(outside) == len(ring.snapshot())


def test_command_line_gives_the_windows_length(monkeypatch):
    monkeypatch.setattr(sys, "argv", ["chipbench/run.py", "--workload", "w", "--seconds", "50"])
    assert program_spans.window_seconds() == 50.0
    monkeypatch.setattr(sys, "argv", ["chipbench/run.py", "--seconds=7.5", "--trace", "1"])
    assert program_spans.window_seconds() == 7.5
    monkeypatch.setattr(sys, "argv", ["chipbench/run.py", "--workload", "w"])
    assert program_spans.window_seconds() is None


@pytest.mark.parametrize("why", ["no_process_start", "no_seconds", "no_setup_s", "not_serving"])
def test_a_window_that_cannot_be_bounded_gives_none(bench, why):
    bench(serve_ring([0] * 60), process_start=None if why == "no_process_start" else 90.0,
          seconds=None if why == "no_seconds" else 10.0)
    record = dict(RECORD)
    if why == "no_setup_s":
        record["end_to_end"] = {}
    if why == "not_serving":
        record["kind"] = "train"
    assert program_spans.serve_records(record) is None
    assert [read(name, record) for name in SERVE_READERS] == [None] * len(SERVE_READERS)


def test_wrap_around_past_the_windows_start_gives_none(bench):
    turns = [0] * 60
    whole = len(serve_ring(turns).snapshot())
    lost_inside = serve_ring(turns, capacity=whole - 40)  # its oldest record ends after 100
    bench(lost_inside)
    assert lost_inside.total > lost_inside.capacity
    assert program_spans.serve_records(RECORD) is None
    assert [read(name) for name in SERVE_READERS] == [None] * len(SERVE_READERS)
    lost_before = serve_ring(turns, capacity=whole - 8)  # lost records ended before 100
    bench(lost_before)
    assert lost_before.total > lost_before.capacity
    assert program_spans.serve_records(RECORD) is not None
    assert read("decode_window_ms") == pytest.approx(1e3 * WINDOW_S)


def test_a_program_without_the_spans_gives_none_and_does_not_raise(bench):
    ring = SpanRing(64)
    for k in range(20):
        ring.push(OldRecord("train_step", 100.0 + 0.4 * k, 0.002, 0, "train_step"))
    bench(ring)
    assert program_spans.serve_records(RECORD) is not None  # the window itself is whole
    assert [read(name) for name in SERVE_READERS] == [None] * len(SERVE_READERS)


# ------------------------------------------------------------- the two paces
def test_a_pair_with_a_chunk_against_one_without(bench):
    # 256-token chunks, chunks of another bucket, and turns with none, mixed
    bench(serve_ring([0, 256, 0, 0, 64, 256, 256, 0, 0, 128, 256, 0] * 4))
    pairs = program_spans.report_pairs(program_spans.serve_records(RECORD))
    by_chunk = collections.Counter(chunk for chunk, _, _ in pairs)
    assert by_chunk[0] > 10 and by_chunk[256] > 10 and by_chunk[64] and by_chunk[128]
    for chunk, seconds, paced in pairs:
        assert paced
        assert seconds == pytest.approx(WINDOW_S + {0: 0.0, 256: CHUNK_S}.get(chunk, 0.03))
    assert read("decode_window_ms") == pytest.approx(200.0)
    assert read("prefill_chunk_ms") == pytest.approx(100.0)


def test_a_report_that_was_ready_leaves_its_pairs_out(bench):
    """The 1 ms rule: a turn that waited 0.2 ms says nothing of the device's
    pace, at either end of a pair; with more than half left out, None."""
    bench(serve_ring([0] * 40, ready_early={15, 25}))
    pairs = program_spans.report_pairs(program_spans.serve_records(RECORD))
    unpaced = [seconds for _, seconds, paced in pairs if not paced]
    assert len(unpaced) == 4  # each such turn spoils the pair before and the pair after
    assert any(abs(seconds - WINDOW_S) > 0.002 for seconds in unpaced)  # and they would be wrong
    assert read("decode_window_ms") == pytest.approx(200.0)
    bench(serve_ring([0] * 40, ready_early=set(range(0, 40, 2))))
    assert read("decode_window_ms") is None
    assert read("prefill_chunk_ms") is None


def test_no_turn_without_a_chunk_gives_no_chunk_time_either(bench):
    bench(serve_ring([256] * 30))
    assert read("decode_window_ms") is None
    assert read("prefill_chunk_ms") is None


# ------------------------------------------------------------ the other readers
def test_host_time_of_a_turn_leaves_the_wait_out(bench):
    bench(serve_ring([0, 256] * 20))
    # 3 ms of dispatching and 1 ms on the report, whatever the wait was
    assert read("serve_host_ms_per_iteration") == pytest.approx(4.0)


def test_occupancy_and_dispatches_per_token(bench):
    bench(serve_ring([0, 256, 0, 0] * 10))
    records = program_spans.serve_records(RECORD)
    assert read("decode_slot_occupancy") == pytest.approx(50.0)  # 6 of 12 slots
    windows = len(program_spans.named(records, "serve.dispatch_decode"))
    chunks = len(program_spans.named(records, "serve.dispatch_chunk"))
    reports = len(program_spans.named(records, "serve.process_report"))
    assert chunks and read("dispatches_per_token") == pytest.approx((windows + chunks) / (40 * reports))


def test_frontend_inside_adds_each_requests_submit_and_relay(bench):
    ring = serve_ring([0] * 40)
    ring.push(rec("frontend.submit", 99.0, 99.001, rid=1, prompt_tokens=5))   # before the window
    ring.push(rec("frontend.relay", 99.5, 99.502, rid=1))
    ring.push(rec("frontend.submit", 101.0, 101.0004, rid=2, prompt_tokens=5))
    ring.push(rec("frontend.relay", 101.5, 101.5002, rid=2))
    ring.push(rec("frontend.submit", 105.0, 105.0006, rid=3, prompt_tokens=5))
    ring.push(rec("frontend.relay", 110.5, 110.5002, rid=3))  # first token after the window's end
    ring.push(rec("frontend.submit", 109.0, 109.0005, rid=4, prompt_tokens=5))  # no token yet
    bench(ring)
    assert read("frontend_inside_ms") == pytest.approx(0.7)  # median of 0.6 and 0.8


def test_train_host_time_reads_the_traced_steps_alone(bench):
    ring = SpanRing(64)
    for k in range(20):  # the window's steps, then the 8 traced ones
        ring.push(rec("train_step", 100.0 + 0.4 * k, 100.0 + 0.4 * k + (0.002 if k < 12 else 0.003)))
    ring.push(rec("checkpoint_save", 120.0, 121.0))
    bench(ring)
    record = {"kind": "train", "traffic": {"trace_steps": 8}}
    assert read("train_host_ms_per_step", record) == pytest.approx(3.0)
    assert read("train_host_ms_per_step", {"kind": "train", "traffic": {"trace_steps": 30}}) is None
    assert read("train_host_ms_per_step", {**record, "kind": "serve"}) is None
    old = SpanRing(64)
    for k in range(10):
        old.push(OldRecord("train_step", 100.0 + k, 0.0015, 0, "train_step"))
    bench(old)  # the parent's program has this span too
    assert read("train_host_ms_per_step", record) == pytest.approx(1.5)
