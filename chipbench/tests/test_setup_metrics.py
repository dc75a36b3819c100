"""Checks of the ``start-up`` layer's readers (``chipbench/setup_spans.py``,
``setup_trace_lower_s``, ``setup_compile_s``) on hand-made rings (run by hand,
as ``test_chipbench.py`` is):

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q

The timeline used here: ``PROCESS_START`` 90 and ``setup_s`` 10 on the
program's clock, so the measured window opens at 100.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from accelerate_tpu.telemetry.spans import SpanRecord, SpanRing  # noqa: E402
from chipbench import program_spans  # noqa: E402
from chipbench.run import layer_metric  # noqa: E402

METRICS = ("setup_trace_lower_s", "setup_compile_s")
RECORD = {"kind": "serve", "end_to_end": {"setup_s": 10.0}}


def phase(name, start, end, program="serve_decode_window", nested=0, **attrs):
    return SpanRecord(name=name, start_s=start, duration_s=end - start, depth=0, path=name,
                      attrs={"program": program, "nested": nested, **attrs})


def ring_of(*records, capacity=64):
    ring = SpanRing(capacity)
    for r in sorted(records, key=lambda r: r.start_s + r.duration_s):  # pushed as they end
        ring.push(r)
    return ring


@pytest.fixture
def bench(monkeypatch):
    def use(ring, process_start=90.0):
        monkeypatch.setattr(program_spans, "ring", lambda: ring)
        monkeypatch.setattr(program_spans, "process_start", lambda: process_start)
    return use


def read(name, record=RECORD):
    return layer_metric(name)(record)


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_nested_and_overlapping_phases_count_once(bench, kind):
    bench(ring_of(
        phase("program.trace", 91.0, 93.0),
        phase("program.trace", 91.5, 92.0, program="inner", nested=1),
        phase("program.lower", 93.0, 93.5),
        phase("program.trace", 93.2, 94.0, program="other_thread"),
        phase("program.trace", 95.0, 95.5, program="eager_op"),
        phase("program.compile", 94.0, 96.0, cache="miss"),
        phase("program.compile", 95.0, 97.0, program="other_thread", cache="hit", retrieval_s=1.5),
        SpanRecord(name="serve.dispatch_decode", start_s=90.5, duration_s=8.0, depth=0,
                   path="serve.dispatch_decode", attrs={"trace_s": 2.0}),
    ))
    record = {**RECORD, "kind": kind}
    assert read("setup_trace_lower_s", record) == pytest.approx(3.0 + 0.5)
    assert read("setup_compile_s", record) == pytest.approx(3.0)


def test_only_phases_that_end_before_the_window_opens_count(bench):
    bench(ring_of(
        phase("program.trace", 99.0, 100.0),
        phase("program.trace", 99.5, 100.5, program="across_the_start"),
        phase("program.compile", 98.0, 99.0),
        phase("program.compile", 104.0, 104.5, program="inside_the_window"),
    ))
    assert read("setup_trace_lower_s") == pytest.approx(1.0)
    assert read("setup_compile_s") == pytest.approx(1.0)


@pytest.mark.parametrize("name", METRICS)
def test_a_wrapped_ring_reads_none(bench, name):
    records = [phase("program.trace", 91.0 + i * 0.01, 91.005 + i * 0.01) for i in range(10)]
    records += [phase("program.lower", 92.0, 92.5), phase("program.compile", 93.0, 94.0)]
    bench(ring_of(*records, capacity=8))
    assert read(name) is None
    bench(ring_of(*records, capacity=12))
    assert read(name) is not None


@pytest.mark.parametrize("name", METRICS)
def test_nothing_to_read_reads_none(bench, name):
    # a program that records no phase, as before its spans recorded start-up
    bench(ring_of(SpanRecord(name="train_step", start_s=95.0, duration_s=0.5, depth=0,
                             path="train_step")))
    assert read(name) is None
    # the window's start cannot be read
    bench(ring_of(phase("program.trace", 91.0, 92.0), phase("program.compile", 92.0, 93.0)),
          process_start=None)
    assert read(name) is None
    bench(ring_of(phase("program.trace", 91.0, 92.0), phase("program.compile", 92.0, 93.0)))
    assert read(name, {"kind": "serve", "end_to_end": {}}) is None
    assert read(name) == pytest.approx(1.0)


SIX_CELLS = {"train-mistral7b-s4096", "serve-qwen3-chat", "serve-qwen3-backlog",
             "serve-minicpm-sala-longdoc", "serve-laguna-s-codemix", "serve-glm5-longctx"}


def test_the_benchmark_reads_both_in_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"] for w in spec["workloads"]}
    for name in METRICS:
        (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
        assert SIX_CELLS <= set(entry.pop("workloads")) <= cells
        assert entry == {"name": name, "unit": "s", "better": "lower", "source": "program_span",
                         "layer": "start-up", "moves": "setup_s"}
        assert os.path.exists(os.path.join(ROOT, "chipbench", "layer_metrics", name + ".py"))
