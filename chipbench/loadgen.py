"""The load generator: one general reader of traffic files of kind ``serve``.

A mix is data: the arrival law (``poisson`` at a fixed ``rate_per_s``,
``gamma`` with a coefficient of variation for bursts, or ``backlog``: every
request due at once, ``lead_in_s`` before the window opens), and the laws of
prompt and output length (lognormal or fixed, clipped). A new mix is a new
file, never new code.

**Every seed gets the same work.** For ``n`` requests the lengths are the
law's ``n`` evenly spaced quantiles (so the clips, the mean and the tail are
the same in every run) and the gaps between arrivals are the ``n`` evenly
spaced quantiles of the gap law, scaled so that they fill the window exactly.
Their order is one sample path, drawn from the mix's own ``schedule_seed``:
in a queueing system the order IS the work (whether three long prompts arrive
together decides the tail), and with a few tens of requests in a window a
fresh order for every seed moved the 95th percentile of time to first token
by 17% between two seeds (my chip runs, PR 25). The run's ``--seed`` draws the
prompts' tokens (and the weights), so the same seed gives the same inputs and
no two seeds the same ones. Another sample path is another mix: a new file.

The open loop: one scheduler thread sleeps until each request is due and hands
it to a pool of client threads; each client posts to ``/v1/generate`` on
loopback and stamps every SSE event as it arrives. Latency is counted from the
time a request was **due**; how late it was really sent is recorded beside it.
The HTTP client and the SSE reader are copies of what ``chip_smoke.py`` and
``serving_net/frontend.py`` use, kept here as part of the yardstick.

The closed loop is the same machinery under a ``backlog``: every request is
due at once, so the pool's ``client_threads`` bound how many are with the
server, and each answer's end sends the next of the schedule. One parameter,
``stop_sending_at``, closes the loop, and makes it one sample path in two
ways. From that time on nothing more is sent (a request never sent has no
``sent`` time and is no one's failure), so the backlog can be sized never to
run out. And a request is sent only once the one before it in the schedule has
its response's headers, which the server writes after the engine's ``submit``
returned; otherwise two answers that end in one report race their successors
into the engine's queue, and the order of that queue is the work.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import http.client
import json
import math
import statistics
import threading
import time

import numpy as np


# ------------------------------------------------------------------- laws
def quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def sample_lengths(law: dict, n: int) -> np.ndarray:
    """The law's ``n`` evenly spaced quantiles, clipped and rounded."""
    if law["law"] == "fixed":
        values = np.full(n, float(law["value"]))
    elif law["law"] == "lognormal":
        normal = statistics.NormalDist()
        z = np.array([normal.inv_cdf(float(p)) for p in quantile_points(n)])
        values = np.exp(math.log(law["median"]) + law["sigma"] * z)
    else:
        raise ValueError(f"unknown length law {law['law']!r}")
    return np.clip(np.rint(values), law.get("min", 1), law.get("max", np.inf)).astype(int)


def sample_gaps(arrivals: dict, n: int, duration: float) -> np.ndarray:
    """``n`` gaps between arrivals that sum to ``duration``."""
    law = arrivals["law"]
    if law == "backlog":
        return np.zeros(n)
    p = quantile_points(n)
    if law == "poisson":
        gaps = -np.log1p(-p)  # exponential quantiles
    elif law == "gamma":
        # Gap law with coefficient of variation ``cv`` (bursty for cv > 1):
        # quantiles of a gamma by a Wilson-Hilferty transform of the normal's.
        shape = 1.0 / arrivals["cv"] ** 2
        normal = statistics.NormalDist()
        z = np.array([normal.inv_cdf(float(q)) for q in p])
        gaps = shape * np.maximum(1 - 1 / (9 * shape) + z / (3 * math.sqrt(shape)), 0.0) ** 3
    else:
        raise ValueError(f"unknown arrival law {law!r}")
    return gaps * (duration / gaps.sum())


@dataclasses.dataclass
class Request:
    index: int
    due: float            # seconds from the start of the window (< 0: lead-in)
    prompt_len: int
    max_new: int
    counted: bool         # due inside the window (a backlog's: all; the runner attempts those sent)
    body: bytes = b""     # the encoded POST body, made during set-up
    prompt: np.ndarray | None = None
    sent: float | None = None
    events: list = dataclasses.field(default_factory=list)   # (time, new tokens)
    tokens: list | None = None
    done: dict | None = None
    error: str | None = None
    retries: int = 0


def make_request(rng, vocab: int, index: int, due: float, prompt_len: int, max_new: int,
                 counted: bool) -> Request:
    """A request of ``prompt_len`` random tokens (never the pad token 0) with
    its POST body encoded, so that sending it costs the window nothing."""
    prompt = rng.integers(1, vocab, (prompt_len,)).astype(np.int32)
    body = json.dumps({"prompt": prompt.tolist(), "max_new_tokens": max_new}).encode()
    return Request(index, due, prompt_len, max_new, counted, body, prompt)


def _phase(traffic: dict, order, rng, n: int, start: float, duration: float, counted: bool,
           vocab: int, first_index: int) -> list:
    prompts = order.permutation(sample_lengths(traffic["prompt_tokens"], n))
    outputs = order.permutation(sample_lengths(traffic["output_tokens"], n))
    gaps = order.permutation(sample_gaps(traffic["arrivals"], n, duration))
    dues = start + np.cumsum(gaps) - gaps  # the first request is due at the start
    requests = []
    for i in range(n):
        requests.append(make_request(rng, vocab, first_index + i, float(dues[i]),
                                     int(prompts[i]), int(outputs[i]), counted))
    return requests


def build_schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> list:
    """The lead-in (uncounted, due before 0) and the window's requests; under
    a ``backlog`` one phase, all counted and all due ``lead_in_s`` before 0.

    The order of lengths and gaps comes from the mix's ``schedule_seed``, the
    prompts' tokens from the run's ``seed``."""
    order = np.random.default_rng(traffic["schedule_seed"])
    rng = np.random.default_rng(seed)
    arrivals = traffic["arrivals"]
    lead = traffic.get("lead_in_s", 0.0)
    if arrivals["law"] == "backlog":
        n = max(1, math.ceil(arrivals["requests_per_s_of_window"] * seconds))
        return _phase(traffic, order, rng, n, -lead, 0.0, True, vocab, 0)
    rate = arrivals["rate_per_s"]
    n_lead = round(rate * lead)
    lead_in = (_phase(traffic, order, rng, n_lead, -lead, lead, False, vocab, 0)
               if n_lead else [])
    n = max(1, round(rate * seconds))
    return lead_in + _phase(traffic, order, rng, n, 0.0, seconds, True, vocab, n_lead)


# ----------------------------------------------------------------- client
MAX_RETRIES = 1


def iter_sse(fp):
    """``(kind, data)`` frames of a Server-Sent-Events byte stream."""
    kind, data = None, []
    for raw in fp:
        line = raw.decode("utf-8", "replace").rstrip("\r\n")
        if not line:
            if data:
                yield (kind or "message", "\n".join(data))
            kind, data = None, []
        elif line.startswith("event:"):
            kind = line[len("event:"):].strip()
        elif line.startswith("data:"):
            data.append(line[len("data:"):].strip())
    if data:
        yield (kind or "message", "\n".join(data))


def generate(endpoint: str, request: Request, clock, timeout_s: float,
             accepted=lambda: None) -> None:
    """One request over HTTP; every event stamped on ``clock`` as it arrives.
    ``accepted`` is called once the first attempt has its response's headers
    (or has failed before them).

    The wire contract marks an ``error`` frame ``retryable`` where sending the
    request again may succeed. As a client of that contract would, a request
    refused so before its first token is sent once more, at once; its times
    still count from when it was first due and first sent, and the retry is
    counted (``retries``). Any other failure stands."""
    host, port = endpoint.rsplit(":", 1)
    request.sent = clock()
    for attempt in range(1 + MAX_RETRIES):
        conn = http.client.HTTPConnection(host, int(port), timeout=timeout_s)
        retry = False
        try:
            conn.request("POST", "/v1/generate", body=request.body,
                         headers={"Content-Type": "application/json"})
            response = conn.getresponse()
            accepted()
            if response.status != 200:
                request.error = f"HTTP {response.status}: {response.read()[:200]!r}"
                return
            delivered = 0
            for kind, data in iter_sse(response):
                now = clock()
                payload = json.loads(data)
                if kind == "tokens":
                    request.events.append((now, len(payload["tokens"])))
                    delivered += len(payload["tokens"])
                elif kind == "done":
                    request.tokens = payload["tokens"]
                    if len(request.tokens) > delivered:
                        request.events.append((now, len(request.tokens) - delivered))
                    request.done = payload
                elif kind == "error":
                    retry = (bool(payload.get("retryable")) and delivered == 0
                             and attempt < MAX_RETRIES)
                    request.error = None if retry else str(payload.get("error"))
            if request.done is None and request.error is None and not retry:
                request.error = "stream closed without a done event"
        except Exception as exc:  # a failed request is a result, not a crash
            request.error = repr(exc)
        finally:
            conn.close()
            accepted()
        if not retry:
            return
        request.retries += 1


class OpenLoop:
    """Sends a schedule against ``endpoint``; ``t0`` is the window's start on
    ``time.perf_counter``. ``start`` returns at once; ``wait`` joins.

    ``stop_sending_at`` (on the window's clock) closes the loop: sends go out
    one at a time in the schedule's order, and none from then on (the module's
    docstring)."""

    def __init__(self, endpoint: str, requests: list, t0: float, client_threads: int,
                 timeout_s: float = 300.0, stop_sending_at: float | None = None):
        self.endpoint, self.requests, self.t0 = endpoint, requests, t0
        self.timeout_s, self.stop_sending_at = timeout_s, stop_sending_at
        self.pool = concurrent.futures.ThreadPoolExecutor(client_threads,
                                                          thread_name_prefix="bench-client")
        self.futures: list = []
        self.thread = threading.Thread(target=self._schedule, name="bench-loadgen", daemon=True)
        self._turn, self._next = threading.Condition(), 0

    def clock(self) -> float:
        return time.perf_counter() - self.t0

    def _send(self, position: int, request: Request):
        """One client's work. The pool hands requests out in the schedule's
        order, so the holder of the lowest unsent position is always running:
        waiting for one's turn cannot deadlock."""
        if self.stop_sending_at is None:
            return generate(self.endpoint, request, self.clock, self.timeout_s)

        def pass_turn():
            with self._turn:
                self._next = max(self._next, position + 1)
                self._turn.notify_all()

        with self._turn:
            self._turn.wait_for(lambda: self._next == position)
        try:
            if self.clock() < self.stop_sending_at:
                generate(self.endpoint, request, self.clock, self.timeout_s, pass_turn)
        finally:
            pass_turn()

    def _schedule(self):
        for position, request in enumerate(sorted(self.requests, key=lambda r: r.due)):
            delay = request.due - self.clock()
            if delay > 0:
                time.sleep(delay)
            self.futures.append(self.pool.submit(self._send, position, request))

    def start(self):
        self.thread.start()

    def wait(self, until: float) -> int:
        """Wait for every request, at most until ``until`` on the window's
        clock; returns how many were still unfinished then."""
        self.thread.join(max(0.0, until - self.clock()))
        _, unfinished = concurrent.futures.wait(self.futures, timeout=max(0.0, until - self.clock()))
        self.pool.shutdown(wait=False, cancel_futures=True)
        return len(unfinished) + (1 if self.thread.is_alive() else 0)


# ---------------------------------------------------------------- metrics
def percentile(values, q: float):
    """The q-th percentile by the nearest-rank rule (the smallest value with
    at least q% of the sample at or below it); None for an empty sample."""
    ordered = sorted(values)
    if not ordered:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def request_metrics(request: Request) -> dict | None:
    """Client-side times of one finished request, in seconds."""
    if request.error or not request.events:
        return None
    first, last = request.events[0][0], request.events[-1][0]
    n = sum(count for _, count in request.events)
    return {
        "ttft_s": first - request.due,
        "ttft_from_send_s": first - request.sent,
        "tpot_s": (last - first) / (n - 1) if n > 1 else None,
        "late_s": request.sent - request.due,
        "tokens": n,
    }
