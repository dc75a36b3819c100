"""Checks of the closed loop (the ``backlog`` law), of the window's arithmetic
and of ``BENCHMARK.json``'s own consistency, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q
"""

from __future__ import annotations

import http.server
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loadgen, run  # noqa: E402
from chipbench.runners import serve  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
NEW_CELLS = ["serve-qwen3-backlog"]


def traffic_file(name):
    with open(os.path.join(ROOT, "chipbench", "traffic", name + ".json")) as f:
        return json.load(f)


# ----------------------------------------------------------------- schedule
def test_backlog_schedule_is_600_requests_in_the_chat_laws_order():
    backlog, chat = traffic_file("backlog"), traffic_file("chat")
    assert backlog["arrivals"] == {"law": "backlog", "requests_per_s_of_window": 12.0}
    for key in ("prompt_tokens", "output_tokens", "schedule_seed"):
        assert backlog[key] == chat[key]
    requests = loadgen.build_schedule(backlog, 3_000_000_019, 50.0, 151936)
    assert len(requests) == 600 and all(r.counted for r in requests)
    assert [r.index for r in requests] == list(range(600))
    order = np.random.default_rng(backlog["schedule_seed"])
    prompts = order.permutation(loadgen.sample_lengths(backlog["prompt_tokens"], 600))
    outputs = order.permutation(loadgen.sample_lengths(backlog["output_tokens"], 600))
    assert [r.prompt_len for r in requests] == list(prompts)
    assert [r.max_new for r in requests] == list(outputs)
    # Six times what the engine finished in 50 s when the cell was defined (1.9 requests/s).
    assert sum(r.max_new for r in requests) > 6 * 1.9 * 50 * 100
    assert backlog["client_threads"] == 32


# ------------------------------------------------------------- closed loop
class StubServer:
    """Answers ``POST /v1/generate`` as the front end does (headers once the
    request is taken, then ``tokens`` and ``done`` frames), and keeps the order
    in which requests were taken and how many were open at once."""

    def __init__(self, seconds_a_request: float):
        stub = self
        self.taken, self.open, self.most_open, self.lock = [], 0, 0, threading.Lock()

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
                with stub.lock:
                    rid = len(stub.taken)
                    stub.taken.append(body["prompt"][0])
                    stub.open += 1
                    stub.most_open = max(stub.most_open, stub.open)
                self.send_response(200)
                self.send_header("Connection", "close")
                self.end_headers()
                self.wfile.flush()
                time.sleep(seconds_a_request)
                n = body["max_new_tokens"]
                frames = (f'event: tokens\ndata: {{"tokens": {list(range(n - 1))}}}\n\n'
                          f'event: done\ndata: {{"rid": {rid}, "tokens": {list(range(n))}}}\n\n')
                with stub.lock:
                    stub.open -= 1
                self.wfile.write(frames.encode())

            def log_message(self, *args):
                pass

        self.server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.thread = threading.Thread(target=self.server.serve_forever, daemon=True)

    def __enter__(self):
        self.thread.start()
        return self

    def __exit__(self, *exc):
        self.server.shutdown()
        self.thread.join(5)

    @property
    def endpoint(self):
        return f"127.0.0.1:{self.server.server_port}"


def numbered_requests(n: int, max_new: int = 5) -> list:
    """Requests all due at once whose first prompt token is their place in the schedule."""
    requests = []
    for i in range(n):
        body = json.dumps({"prompt": [i + 1, 7], "max_new_tokens": max_new}).encode()
        requests.append(loadgen.Request(i, -0.05, 2, max_new, True, body))
    return requests


def test_closed_loop_keeps_order_and_bound_and_stops_at_the_window_s_end():
    requests, threads, seconds = numbered_requests(200), 4, 0.6
    with StubServer(0.03) as stub:
        loop = loadgen.OpenLoop(stub.endpoint, requests, time.perf_counter() + 0.05, threads,
                                timeout_s=10.0, stop_sending_at=seconds)
        loop.start()
        assert loop.wait(seconds + 5.0) == 0
    sent = [r for r in requests if r.sent is not None]
    # Never more than ``client_threads`` requests out, and always that many while the loop ran.
    assert stub.most_open == threads
    # Taken by the server in the schedule's order, with none left out before the last one sent.
    assert stub.taken == [r.index + 1 for r in sent] == list(range(1, len(sent) + 1))
    assert threads < len(sent) < len(requests)
    # Nothing was sent once the window had ended; what was sent returned whole.
    assert all(r.sent < seconds for r in sent)
    assert all(r.done is not None and len(r.tokens) == r.max_new for r in sent)
    assert all(r.done is None and not r.events for r in requests if r.sent is None)
    # The runner attempts what was sent, not what was prepared.
    window = serve.window_metrics(requests, seconds, closed=True)
    assert window["counted"] == sent and window["failed"] == 0
    assert [r.done["rid"] for r in sent] == sorted(r.done["rid"] for r in sent)
    delivered = sum(n for r in sent for t, n in r.events if 0.0 <= t <= seconds)
    assert window["end_to_end"]["serve_tokens_per_s"] == delivered / seconds
    # tpot_p95_ms: only requests whose last token fell inside the window.
    inside = [r for r in sent if r.events[-1][0] <= seconds]
    assert 0 < len(inside) < len(sent)


def test_without_the_closed_loop_s_options_every_request_is_sent():
    requests = numbered_requests(12)
    with StubServer(0.01) as stub:
        loop = loadgen.OpenLoop(stub.endpoint, requests, time.perf_counter() + 0.05, 3, timeout_s=10.0)
        loop.start()
        assert loop.wait(10.0) == 0
    assert all(r.sent is not None and r.done is not None for r in requests)
    assert sorted(stub.taken) == list(range(1, 13))


# ------------------------------------------------------- window arithmetic
def recorded_requests() -> list:
    """A recorded set of events: three lead-in requests (uncounted, one still
    streaming into the window) and six due in the window, one of which failed,
    one of which came back short, one of which ends after the window."""
    def request(index, due, counted, max_new, sent_late, events, tokens=None, error=None):
        r = loadgen.Request(index, due, 10, max_new, counted)
        r.sent, r.events, r.error = due + sent_late, events, error
        if tokens is not None:
            r.tokens, r.done = list(range(tokens)), {"rid": index}
        return r

    return [
        request(0, -3.0, False, 17, 0.001, [(-2.5, 8), (-2.0, 8), (-1.9, 1)], 17),
        request(1, -2.0, False, 17, 0.001, [(-1.0, 8), (0.5, 8), (0.6, 1)], 17),
        request(2, -1.0, False, 9, 0.002, [(0.25, 8), (0.75, 1)], 9),
        request(3, 0.5, True, 17, 0.002, [(1.0, 8), (1.4, 8), (1.5, 1)], 17),
        request(4, 1.0, True, 25, 0.001, [(2.0, 8), (2.5, 8), (3.0, 8), (3.2, 1)], 25),
        request(5, 2.0, True, 9, 0.003, [(2.25, 8), (2.5, 1)], 9),
        request(6, 3.0, True, 17, 0.001, [(3.5, 8)], None, "stream closed without a done event"),
        request(7, 4.0, True, 17, 0.001, [(4.5, 8), (5.0, 1)], 9),          # short of what it asked for
        request(8, 9.0, True, 17, 0.004, [(9.5, 8), (11.5, 8), (11.6, 1)], 17),  # ends in the drain
    ]


def test_the_chat_mix_s_arithmetic_on_recorded_events():
    requests = recorded_requests()
    window = serve.window_metrics(requests, 10.0, closed=False)
    assert [r.index for r in window["counted"]] == [3, 4, 5, 6, 7, 8]
    assert [r.index for r, _ in window["ok"]] == [3, 4, 5, 8] and window["failed"] == 2
    end = window["end_to_end"]
    # TTFT from the due time, nearest rank: {0.5, 1.0, 0.25, 0.5} -> the largest.
    assert end["ttft_p95_ms"] == pytest.approx(1000.0)
    # TPOT (last - first) / (tokens - 1): 0.5/16, 1.2/24, 0.25/8, 2.1/16 -> the largest.
    assert end["tpot_p95_ms"] == pytest.approx(1e3 * 2.1 / 16)
    # Every token inside [0, 10], the lead-in's spill and the failed requests' included:
    # 9 + 9 + 17 + 25 + 9 + 8 + 9 + 8.
    assert end["serve_tokens_per_s"] == pytest.approx(94 / 10.0)


def test_the_backlog_law_s_arithmetic_on_the_same_events():
    requests = recorded_requests()
    for r in requests:
        r.counted = True
    requests.append(loadgen.Request(9, -3.0, 10, 17, True))  # prepared, never sent
    window = serve.window_metrics(requests, 10.0, closed=True)
    assert [r.index for r in window["counted"]] == list(range(9)) and window["failed"] == 2
    # Request 8's last token fell in the drain: its 2.1/16 is not in the percentile, and
    # request 1's 1.6/16 (it ended inside the window) is the largest of the rest.
    assert window["end_to_end"]["tpot_p95_ms"] == pytest.approx(1e3 * 1.6 / 16)
    assert window["end_to_end"]["serve_tokens_per_s"] == pytest.approx(94 / 10.0)


# ---------------------------------------------------------- BENCHMARK.json
def cells_of(metric: dict) -> list:
    return metric.get("workloads") or [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("metric,cell", [
    (m["name"], cell) for m in BENCHMARK["per_layer"] for cell in cells_of(m)])
def test_a_per_layer_metric_moves_a_metric_its_cell_reports(metric, cell):
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == metric)
    moved = next(m for m in BENCHMARK["end_to_end"] if m["name"] == entry["moves"])
    assert cell in cells_of(moved), f"{metric} moves {entry['moves']}, which {cell} does not report"
    assert os.path.exists(os.path.join(ROOT, "chipbench", "layer_metrics", metric + ".py"))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_reports_set_up_another_metric_and_a_layer(cell):
    loaded = run.load_cell(cell, rehearse=False)
    names = [m["name"] for m in loaded["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2 and loaded["per_layer"]
    assert len({m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]}) == \
        len(BENCHMARK["end_to_end"] + BENCHMARK["per_layer"])


def test_serve_tokens_per_s_is_the_backlog_cell_s_alone():
    moved = next(m for m in BENCHMARK["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert moved["workloads"] == ["serve-qwen3-backlog"]
    chat = [m["name"] for m in run.load_cell("serve-qwen3-chat", rehearse=False)["end_to_end"]]
    assert chat == ["ttft_p95_ms", "tpot_p95_ms", "setup_s"]
    backlog = [m["name"] for m in run.load_cell("serve-qwen3-backlog", rehearse=False)["end_to_end"]]
    assert backlog == ["tpot_p95_ms", "serve_tokens_per_s", "setup_s"]


@pytest.mark.parametrize("cell", NEW_CELLS)
def test_load_cell_finds_the_new_cells_files(cell):
    loaded = run.load_cell(cell, rehearse=False)
    assert loaded["traffic"]["kind"] in ("serve", "train")
    assert os.path.exists(os.path.join(ROOT, "chipbench", "runners", loaded["traffic"]["kind"] + ".py"))
    for metric in loaded["per_layer"]:
        assert callable(run.layer_metric(metric["name"]))
    at_most_a_quarter = sum(w["chips"] == 4 for w in BENCHMARK["workloads"])
    assert at_most_a_quarter <= max(1, len(BENCHMARK["workloads"]) // 4)


def test_the_four_chip_configuration_is_the_one_chip_one_but_for_depth_and_fsdp():
    """The data files of ``train-mistral7b-fsdp4``, kept for the PR that brings
    the cell with a check of three steps (``PERF.md``, Open questions); no cell
    of ``BENCHMARK.json`` uses them yet."""
    with open(os.path.join(ROOT, "chipbench", "configs", "mistral-7b-v0.3-L3.json")) as f:
        one = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "configs", "mistral-7b-v0.3-L6-fsdp4.json")) as f:
        four = json.load(f)
    assert "mistral-7b-v0.3-L6-fsdp4" not in [c["name"] for c in BENCHMARK["configs"]]
    differ = {k for k in set(one) | set(four) if one.get(k) != four.get(k)}
    assert differ == {"name", "stands_for", "num_hidden_layers", "reduced", "training"}
    assert four["num_hidden_layers"] == 6 and four["training"]["parallelism"] == {"fsdp_size": 4}
    assert {**four["training"], "parallelism": {}} == one["training"]
    b8, b2 = traffic_file("s4096-b8"), traffic_file("s4096")
    assert {k for k in b2 if b2[k] != b8[k]} == {"what", "batch"} and b8["batch"] == 8


# ------------------------------------------------- the control and a fault
def rehearse_in_process(capsys, *extra):
    """``run.main`` here, on the CPU: the rehearsal skips the look for a chip
    and drives the rest of a run. Returns the result line."""
    capsys.readouterr()
    assert run.main(["--rehearse", "--workload", "rehearse-backlog", "--seconds", "2",
                     "--trace", "0", *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_int8_control_comes_out_not_correct(capsys, monkeypatch):
    """The control at a size a test can hold: two layers of 128 and the couple
    of hundred served tokens of ``tiny-backlog``'s sample. The widest gap does
    not separate the two there (eight seeds read 0-0.028 as configured and
    0.038-0.137 with int8 weights); the mean gap does (0-0.00024 against
    0.00061-0.0021), and the rehearsal's limit on it lies between. ``PERF.md``
    gives the readings at the cell's own size."""
    from accelerate_tpu import serving

    seen = []
    init = serving.ContinuousBatcher.__init__

    def spy(self, *args, **kwargs):
        seen.append(kwargs.get("matmul_precision"))
        init(self, *args, **kwargs)

    monkeypatch.setattr(serving.ContinuousBatcher, "__init__", spy)
    plain = rehearse_in_process(capsys, "--seed", "5")
    control = rehearse_in_process(capsys, "--seed", "5", "--control", "int8-weights")
    assert seen == [None, "int8"]
    assert plain["correct"] is True and control["failed"] == 0
    assert control["correct"] is False
    agreement = control["checks"]["reference_agrees_within_limits"]
    assert agreement["ok"] is False and agreement["mean_logit_gap"] > agreement["mean_logit_gap_limit"]
    assert all(check["ok"] for name, check in control["checks"].items()
               if name != "reference_agrees_within_limits")


def test_a_token_altered_where_it_is_streamed_comes_out_not_correct(capsys, monkeypatch):
    from accelerate_tpu.serving_net import frontend

    on_stream = frontend.ServingFrontend._on_stream

    def altered(self, rid, tokens, final):
        tokens = np.asarray(tokens).copy()
        if final:  # the authoritative answer of the ``done`` frame
            tokens[tokens.size // 2] = (tokens[tokens.size // 2] + 1) % 512
        on_stream(self, rid, tokens, final)

    monkeypatch.setattr(frontend.ServingFrontend, "_on_stream", altered)
    line = rehearse_in_process(capsys, "--seed", "11")
    checks = line["checks"]
    assert line["correct"] is False
    assert checks["every_request_returned_what_it_asked_for"]["ok"] is True
    assert checks["reference_agrees_within_limits"]["ok"] is False
