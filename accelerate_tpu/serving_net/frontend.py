"""Streaming HTTP front end over one serving engine — the /v1/* worker API.

One :class:`ServingFrontend` wraps one ``ContinuousBatcher`` and installs
itself as the serving provider on the SAME HTTP server the process already
runs for ``/metrics`` (telemetry/metrics.py routes ``/v1/*`` here), so a
serving worker exposes generation, prefix-affinity answers, and load stats
on the one port the fleet registry already publishes:

- ``POST /v1/generate`` — submit a prompt, stream its tokens back as SSE
  events (``tokens`` deltas at the engine's sync cadence, then ONE ``done``
  event carrying the authoritative output plus the request's tracer record —
  TTFT/TPOT ride every stream's final event). On a ``prefill`` worker this
  instead runs prefill to completion, ships the chain to the request's
  decode host (:mod:`.handoff`), and RELAYS that host's stream, prepending
  its own tier record to the final event's trace.
- ``POST /v1/import`` — decode tier: splice a shipped chain in and stream
  the request's decode exactly as if it had prefilled locally.
- ``POST /v1/prefixes`` / ``GET /v1/stats`` — the router's affinity and
  least-loaded routing feeds (both pure host lookups; a routing decision
  never touches a device).

Threading: HTTP handler threads only QUEUE work (``submit`` appends to the
engine's deque; imports land in a staging queue) and then block on per-rid
subscriber queues; one background loop thread owns every engine dispatch —
it drains staged imports between waves and calls ``engine.run()`` whenever
work is in flight. The engine's one-window-lookahead loop keeps its
zero-blocking-transfer discipline; streaming rides the report it already
fetches (serving.py ``_process_report``).

Failure semantics (docs/serving.md "Failure semantics"):

- Every ``error`` frame carries a ``retryable`` flag (can the router/client
  re-dispatch this request and expect a different outcome?), and a terminal
  frame (``done`` or ``error``) is guaranteed on every path — a mid-stream
  engine exception, a timed-out subscriber, and a dead downstream tier all
  close the stream explicitly, never silently.
- The worker's registration is a heartbeat-refreshed TTL lease
  (:mod:`.lease`); SIGTERM rides the preemption watcher into
  :meth:`ServingFrontend.drain` — stop admission (503 ``retryable`` with a
  retry hint), finish in-flight requests inside the grace window, revoke the
  lease, then shut down.
- A failed prefill→decode handoff re-enters on the next surviving decode
  endpoint WITHOUT re-prefilling: the export keeps the chain
  (``free=False``) until the importer acks (first non-error frame), then
  frees it — free-on-ack, so a dropped handoff never leaks pool blocks.
- The serving chaos grammar (``resilience/faults.py`` ``req:N=...``) is
  consumed here: ``worker_kill`` dies after the request's first streamed
  delta (``kill_mode`` picks a real ``os._exit`` for launcher drills or a
  soft in-process death for tests/bench), ``stall`` sleeps before admission,
  ``slow_worker`` stretches every stream event, ``handoff_drop`` loses the
  first export POST.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
import urllib.request

import numpy as np

from ..logging import get_logger
from ..telemetry.spans import no_span, record_span, span
from ..utils.transfer import host_view
from .handoff import export_chain, import_chain, release_chain, run_prefill_only
from .lease import LeaseHeartbeat, drain_grace_from_env
from .roles import ServingRole, resolve_serving_role

logger = get_logger(__name__)

# How long a subscriber waits for the next stream event before the stream
# closes with an error event — a wedged engine must not hold client
# connections (and their handler threads) forever.
STREAM_TIMEOUT_S = 300.0

# How long the drain-admission 503 tells clients/routers to back off before
# retrying AGAINST THE FLEET (the router re-routes immediately; this hint is
# for direct clients).
DRAIN_RETRY_AFTER_S = 1.0

# Per-event delay unit for the slow_worker chaos action: the injected delay
# is <mult> × this per stream event.
SLOW_WORKER_UNIT_S = 0.05

_DRAIN_COUNTER = None  # telemetry.metrics.cached_handles accessor


def _drain_counter():
    global _DRAIN_COUNTER
    if _DRAIN_COUNTER is None:
        from ..telemetry.metrics import cached_handles

        _DRAIN_COUNTER = cached_handles(lambda registry: registry.counter(
            "accelerate_serving_drained_inflight_total",
            "In-flight requests finished inside a graceful-drain grace window",
        ))
    return _DRAIN_COUNTER()


class ServingStreamError(RuntimeError):
    """An ``error`` SSE frame surfaced client-side (``read_sse_response``).
    ``retryable`` mirrors the frame's flag: True means re-submitting the
    request may succeed (worker died, stream broke, fleet draining); False
    means the request itself is unservable (bad input, deadline exceeded)."""

    def __init__(self, message: str, retryable: bool = True):
        super().__init__(message)
        self.retryable = bool(retryable)


def sse_event(kind: str, data: dict) -> str:
    """One Server-Sent Event frame (the wire contract docs/serving.md pins):
    ``event:`` names the kind, ``data:`` carries one JSON object."""
    return f"event: {kind}\ndata: {json.dumps(data)}\n\n"


def iter_sse(fp):
    """Parse an SSE byte stream into ``(kind, data_str)`` frames — the relay
    tiers' client side (router ← worker, prefill ← decode)."""
    kind, data_lines = None, []
    for raw in fp:
        line = raw.decode("utf-8", "replace").rstrip("\r\n")
        if not line:
            if data_lines:
                yield (kind or "message", "\n".join(data_lines))
            kind, data_lines = None, []
        elif line.startswith("event:"):
            kind = line[len("event:"):].strip()
        elif line.startswith("data:"):
            data_lines.append(line[len("data:"):].strip())
    if data_lines:
        yield (kind or "message", "\n".join(data_lines))


class ServingFrontend:
    """The /v1/* provider for one engine + role; see module docstring.

    ``engine`` is a ``ContinuousBatcher``; ``role`` defaults to the launcher
    env contract (:func:`~.roles.resolve_serving_role`)."""

    # How a worker_kill chaos fault dies: "process" is the real thing
    # (os._exit mid-stream — launcher drills; exit code 0 so the gang
    # launcher doesn't take the survivors down), "stream" is the in-process
    # soft death (tests, the bench chaos lever): the stream breaks without a
    # terminal frame, the heartbeat stops so the lease expires, and every
    # subsequent handler answers 503 so health probes fail like a corpse's.
    kill_mode = "process"

    def __init__(self, engine, role: str | ServingRole | None = None,
                 stream_timeout_s: float = STREAM_TIMEOUT_S):
        if isinstance(role, ServingRole):
            self.role = role
        else:
            self.role = resolve_serving_role(role)
        if not self.role.runs_engine:
            raise ValueError(
                "the router role runs no engine; use serving_net.Router"
            )
        self.engine = engine
        self.stream_timeout_s = float(stream_timeout_s)
        self._lock = threading.Lock()          # engine submission/surgery
        self._streams: dict[int, queue.Queue] = {}
        self._deadlines: dict[int, float] = {}  # rid -> deadline (wall clock)
        self._imports: queue.Queue = queue.Queue()
        self._wake = threading.Condition()
        self._shutdown = threading.Event()
        self._draining = threading.Event()
        self._thread: threading.Thread | None = None
        self._watch_thread: threading.Thread | None = None
        self._heartbeat: LeaseHeartbeat | None = None
        self._watcher = None
        self._server = None
        self._process_index = 0
        # Serving chaos state (resilience/faults.py req: grammar): the
        # frontend counts ITS OWN admission events (/v1/generate +
        # /v1/import, in arrival order) and handoff exports, so req:N
        # indexes are deterministic per worker.
        self._req_seq = 0
        self._handoff_seq = 0
        self._kill_rids: set[int] = set()
        self._slow: dict[int, float] = {}  # rid -> injected per-event delay
        self._killed = False
        # The engine's one tracing switch (``trace_requests``) arms the front
        # end's spans too: ``frontend.submit`` on the handler's thread, and
        # ``frontend.relay`` for each request's FIRST stream event, from the
        # loop thread's push to the handler thread's yield of that frame
        # (rid -> time of the push; None until the push happens).
        self._span = span if engine.tracer is not None else no_span
        self._relay_t0: dict[int, float | None] = {}
        engine.stream = self._on_stream

    # ------------------------------------------------------------ lifecycle
    def install(self, process_index: int = 0, start_loop: bool | None = None,
                server=None, endpoint: str | None = None):
        """Become the process's serving provider: route ``/v1/*`` here,
        publish the role gauge (``accelerate_serving_role{role=}`` — what
        /fleet tier rollups group hosts by), start the lease heartbeat that
        keeps the worker's role+endpoint registration alive in the serving
        KV namespace (what the router discovers — :mod:`.lease`), arm the
        preemption watcher so SIGTERM drains instead of dropping streams,
        and start the engine loop thread (decoding roles; a prefill worker
        dispatches synchronously per request, so it needs no loop).
        ``server`` attaches to one specific
        :class:`~..telemetry.metrics.MetricsServer` instead of the
        process-global route (multi-role single-process rigs)."""
        from ..telemetry.metrics import get_registry, set_serving_provider

        self._process_index = int(process_index)
        self._server = server
        if server is not None:
            server.set_serving(self)
            if endpoint is None and server.port is not None:
                endpoint = f"127.0.0.1:{server.port}"
        else:
            set_serving_provider(self)
        get_registry().gauge(
            "accelerate_serving_role",
            "Serving tier this process runs (1 = the labeled role)",
            labelnames=("role",),
        ).set(1, role=self.role.name)
        from ..telemetry.fleet import metrics_endpoint

        lease_endpoint = endpoint or metrics_endpoint()
        if lease_endpoint is not None:
            self._heartbeat = LeaseHeartbeat(
                self.role.name, process_index, lease_endpoint
            ).start()
        try:
            # Signal handlers are main-thread-only; a frontend installed off
            # the main thread still drains when something else (PartialState)
            # installed the watcher, or when drain() is called directly.
            from ..resilience.preemption import get_default_watcher

            self._watcher = get_default_watcher(install=True)
        except Exception:
            self._watcher = None
        if start_loop is None:
            start_loop = self.role.decodes
        if start_loop and self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="at-serving-loop", daemon=True
            )
            self._thread.start()
        if self._watcher is not None and self._watch_thread is None:
            self._watch_thread = threading.Thread(
                target=self._watch_preemption, name="at-serving-drain",
                daemon=True,
            )
            self._watch_thread.start()
        return self

    def uninstall(self):
        if self._heartbeat is not None:
            self._heartbeat.stop(revoke=True)
            self._heartbeat = None
        if self._server is not None:
            self._server.set_serving(None)
            self._server = None
        else:
            from ..telemetry.metrics import set_serving_provider

            set_serving_provider(None)
        self._shutdown.set()
        with self._wake:
            self._wake.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    # ----------------------------------------------------------------- drain
    def _watch_preemption(self):
        """Poll the preemption watcher's sticky flag; SIGTERM → drain. Runs
        on its own daemon thread so prefill workers (no engine loop) drain
        too."""
        while not self._shutdown.is_set():
            try:
                if self._watcher.poll():
                    self.drain()
                    return
            except Exception:
                return
            self._shutdown.wait(timeout=0.2)

    def drain(self, grace_s: float | None = None):
        """Graceful shutdown, in contract order (docs/serving.md "Failure
        semantics"): (1) stop admission — new ``/v1/*`` work answers 503
        ``retryable`` with a retry hint while in-flight streams keep
        flowing; (2) wait up to ``grace_s`` (default
        ``ACCELERATE_DRAIN_GRACE_S``) for in-flight requests to finish,
        booking how many did into
        ``accelerate_serving_drained_inflight_total``; (3) revoke the lease
        (the router sees the worker gone on its next discovery, not a TTL
        later) and shut the loop down. Idempotent; callable from any
        thread."""
        if self._draining.is_set():
            return
        self._draining.set()
        grace = float(grace_s if grace_s is not None else drain_grace_from_env())
        in_flight_at_start = self.in_flight()
        logger.warning(
            f"serving worker draining ({self.role.name}): admission stopped, "
            f"{in_flight_at_start} in flight, grace {grace:.1f}s"
        )
        deadline = time.monotonic() + grace
        while time.monotonic() < deadline and self.in_flight() > 0:
            self._notify()
            time.sleep(0.05)
        still_in_flight = self.in_flight()
        drained = max(0, in_flight_at_start - still_in_flight)
        if drained:
            _drain_counter().inc(drained)
        from ..telemetry.flight import get_flight_recorder

        get_flight_recorder().record(
            "serving_drain", role=self.role.name,
            in_flight_at_sigterm=int(in_flight_at_start),
            drained=int(drained), abandoned=int(still_in_flight),
        )
        self.uninstall()

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    # ----------------------------------------------------------------- chaos
    def _next_req_seq(self) -> int:
        with self._lock:
            seq = self._req_seq
            self._req_seq += 1
        return seq

    def _take_admission_fault(self):
        """Consume an admission-indexed serving fault for this worker's next
        request; ``stall`` sleeps here (pre-admission, before any lock), the
        other actions are applied per-rid by :meth:`_arm_request_fault`."""
        from ..resilience.faults import serving_fault

        fault = serving_fault(self._next_req_seq(),
                              "worker_kill", "stall", "slow_worker")
        if fault is not None and fault.action == "stall":
            time.sleep(fault.stall_s)
        return fault

    def _arm_request_fault(self, fault, rid: int):
        """``worker_kill`` arms death after the rid's first streamed delta;
        ``slow_worker`` stretches its stream events."""
        if fault is None:
            return
        if fault.action == "worker_kill":
            self._kill_rids.add(rid)
        elif fault.action == "slow_worker":
            self._slow[rid] = fault.slow_factor * SLOW_WORKER_UNIT_S

    def _die(self):
        """The worker_kill chaos action fires: a hard ``os._exit(0)`` under
        the real launcher (exit 0 so the gang supervisor leaves the
        survivors up — the point is proving THEIR recovery), or the soft
        in-process death (see ``kill_mode``)."""
        logger.warning(f"chaos worker_kill firing ({self.kill_mode} mode)")
        if self.kill_mode == "process":
            os._exit(0)
        self._killed = True
        if self._heartbeat is not None:
            self._heartbeat.stop(revoke=False)  # a crash revokes nothing
            self._heartbeat = None

    def _refuse(self, why: str, retry_after_s: float | None = None):
        detail = {"error": why, "retryable": True}
        if retry_after_s is not None:
            detail["retry_after_s"] = retry_after_s
        return ("json", 503, detail)

    # ---------------------------------------------------------- engine loop
    def _loop(self):
        """The one thread that dispatches engine work: drain staged imports
        (chain surgery must not race a live wave's donated state tuple),
        then run the wave whenever anything is in flight."""
        while not self._shutdown.is_set():
            did_work = False
            while True:
                try:
                    payload, endpoint = self._imports.get_nowait()
                except queue.Empty:
                    break
                did_work = True
                try:
                    import_chain(self.engine, payload, endpoint=endpoint)
                except Exception as exc:
                    logger.warning(f"chain import failed: {exc!r}")
                    self._push(int(payload.get("rid", -1)),
                               ("error", f"import failed: {exc}"))
            if self.engine.in_flight() > 0:
                did_work = True
                try:
                    self.engine.run()
                except Exception as exc:
                    logger.warning(f"serving engine wave failed: {exc!r}")
                    for rid in list(self._streams):
                        self._push(rid, ("error", f"engine error: {exc}"))
            if not did_work:
                with self._wake:
                    self._wake.wait(timeout=0.05)

    def _notify(self):
        with self._wake:
            self._wake.notify_all()

    # ------------------------------------------------------------- streaming
    def _on_stream(self, rid: int, tokens: np.ndarray, final: bool):
        """The engine's streaming sink (runs on the loop thread, fed from
        the report the loop already fetches)."""
        kind = "final" if final else "tokens"
        if self._relay_t0.get(rid, 0.0) is None:
            self._relay_t0[rid] = time.perf_counter()
        self._push(rid, (kind, [int(t) for t in host_view(tokens).reshape(-1)]))

    def _push(self, rid: int, item):
        subscriber = self._streams.get(rid)
        if subscriber is not None:
            subscriber.put(item)

    def _trace_record(self, rid: int) -> dict | None:
        """This tier's tracer record for ``rid`` — what rides the final SSE
        event so the client (and each relay tier) assembles the cross-tier
        trace without scraping anything."""
        tracer = self.engine.tracer
        if tracer is None:
            return None
        for record in tracer.records():
            if record["rid"] == rid:
                return record
        return None

    def _stream_response(self, rid: int):
        """The SSE generator behind a local (non-relayed) request: token
        deltas as they land, then the ``done`` frame with the authoritative
        output + this tier's trace record (TTFT/TPOT inside). A terminal
        frame is GUARANTEED on every path — timeout, engine error, deadline,
        and unexpected exception all close with an ``error`` frame carrying
        ``retryable``."""
        subscriber = self._streams[rid]
        slow_s = self._slow.get(rid)
        streamed_any = False

        def relayed(frame: str) -> str:
            """Close ``frontend.relay`` at the first frame this stream hands
            to the handler; later frames pass through."""
            pushed_at = self._relay_t0.pop(rid, None)
            if pushed_at is not None:
                record_span("frontend.relay", pushed_at, time.perf_counter(), rid=rid)
            return frame

        try:
            while True:
                deadline_wall = self._deadlines.get(rid)
                wait_s = self.stream_timeout_s
                if deadline_wall is not None:
                    wait_s = min(wait_s, max(0.01, deadline_wall - time.time()))
                try:
                    kind, payload = subscriber.get(timeout=wait_s)
                except queue.Empty:
                    if deadline_wall is not None and time.time() >= deadline_wall:
                        yield sse_event("error", {
                            "rid": rid, "retryable": False,
                            "error": "request deadline exceeded",
                        })
                    else:
                        yield sse_event("error", {
                            "rid": rid, "retryable": True,
                            "error": f"stream timed out after "
                                     f"{self.stream_timeout_s}s",
                        })
                    return
                if slow_s:
                    time.sleep(slow_s)
                if kind == "error":
                    yield sse_event("error", {"rid": rid, "error": payload,
                                              "retryable": True})
                    return
                if kind == "final":
                    record = self._trace_record(rid)
                    yield relayed(sse_event("done", {
                        "rid": rid,
                        "tokens": payload,
                        "ttft_s": (record or {}).get("ttft_s"),
                        "tpot_s": (record or {}).get("tpot_s"),
                        "trace": [record] if record else [],
                    }))
                    return
                yield relayed(sse_event("tokens", {"rid": rid, "tokens": payload}))
                streamed_any = True
                if rid in self._kill_rids and streamed_any:
                    # worker_kill: die AFTER the client saw a delta, so the
                    # drill proves retry de-duplication, not just re-dispatch.
                    self._kill_rids.discard(rid)
                    self._die()
                    return  # soft mode: stream breaks, no terminal frame
        except GeneratorExit:
            raise
        except Exception as exc:  # the terminal-frame guarantee
            logger.warning(f"serving stream for rid {rid} failed: {exc!r}")
            yield sse_event("error", {"rid": rid, "retryable": True,
                                      "error": f"stream failed: {exc}"})
        finally:
            self._streams.pop(rid, None)
            self._deadlines.pop(rid, None)
            self._slow.pop(rid, None)
            self._relay_t0.pop(rid, None)

    # ------------------------------------------------------------- handlers
    def handle_get(self, path: str, query: dict):
        if self._killed:
            return (503, "application/json",
                    json.dumps({"error": "worker killed (chaos)"}).encode())
        if path == "/v1/stats":
            body = json.dumps(self.stats()).encode()
            return (200, "application/json", body)
        return None

    def handle_post(self, path: str, query: dict, body: bytes):
        if self._killed:
            return self._refuse("worker killed (chaos)")
        if path == "/v1/prefixes":
            if self._draining.is_set():
                # A draining worker must drop out of routing decisions too.
                return self._refuse("worker draining",
                                    retry_after_s=DRAIN_RETRY_AFTER_S)
            request = json.loads(body or b"{}")
            prompt = np.asarray(request.get("prompt", []), np.int32)
            return ("json", 200, {
                "match_tokens": self.engine.prefix_match_tokens(prompt),
                "in_flight": self.in_flight(),
                "role": self.role.name,
            })
        if path == "/v1/generate":
            if self._draining.is_set():
                return self._refuse("worker draining: admission stopped",
                                    retry_after_s=DRAIN_RETRY_AFTER_S)
            return self._handle_generate(json.loads(body or b"{}"))
        if path == "/v1/import":
            if not self.role.decodes:
                return ("json", 409, {
                    "error": f"role {self.role.name!r} does not decode",
                    "retryable": False,
                })
            if self._draining.is_set():
                return self._refuse("worker draining: admission stopped",
                                    retry_after_s=DRAIN_RETRY_AFTER_S)
            payload = json.loads(body or b"{}")
            rid = int(payload["rid"])
            self._arm_request_fault(self._take_admission_fault(), rid)
            self._streams[rid] = queue.Queue()
            deadline_wall = payload.get("deadline_wall")
            if deadline_wall is not None:
                self._deadlines[rid] = float(deadline_wall)
            self._imports.put((payload, None))
            self._notify()
            return ("sse", self._stream_response(rid))
        return None

    def in_flight(self) -> int:
        """Client-visible in-flight count: requests admitted whose stream
        has not yet delivered its terminal frame. Strictly ≥ the engine's
        own count — a slow subscriber keeps a request in flight after the
        engine freed its slot, and drain must wait for delivery, not just
        for compute."""
        return max(self.engine.in_flight(), len(self._streams))

    def stats(self) -> dict:
        """The least-loaded routing feed (host bookkeeping only)."""
        return {
            "role": self.role.name,
            "in_flight": self.in_flight(),
            "prefill_chunk": getattr(self.engine, "prefill_chunk", None),
            "pool": self.engine.pool_stats(),
            "draining": self._draining.is_set(),
        }

    def _handle_generate(self, request: dict):
        with self._span("frontend.submit") as rec:
            reply = self._submit_generate(request, rec)
        if reply[0] == "sse":
            self._notify()
        return reply

    def _submit_generate(self, request: dict, rec):
        """``_handle_generate`` from the parsed body to the return of
        ``engine.submit``: the ``frontend.submit`` span (``rec``)."""
        prompt = np.asarray(request.get("prompt", []), np.int32).reshape(-1)
        rec.attrs["prompt_tokens"] = int(prompt.size)
        if prompt.size == 0:
            return ("json", 400, {"error": "empty or missing 'prompt'",
                                  "retryable": False})
        deadline_wall = request.get("deadline_wall")
        if deadline_wall is not None and time.time() >= float(deadline_wall):
            # Deadlines propagate end-to-end; admitting dead-on-arrival work
            # would only burn decode slots the survivors need.
            return ("json", 400, {"error": "request deadline exceeded",
                                  "retryable": False})
        kwargs = {}
        for key in ("max_new_tokens", "eos_token_id"):
            if request.get(key) is not None:
                kwargs[key] = int(request[key])
        if request.get("temperature") is not None:
            kwargs["temperature"] = float(request["temperature"])
        if request.get("stop_sequences"):
            kwargs["stop_sequences"] = [
                np.asarray(s, np.int32) for s in request["stop_sequences"]
            ]
        fault = self._take_admission_fault()
        with self._lock:
            # The rid is reserved BEFORE submit so the subscriber queue
            # exists when the loop thread emits the first delta — a
            # router-assigned request_id threads through unchanged (one rid
            # across every tier it crosses).
            rid = (int(request["request_id"])
                   if request.get("request_id") is not None
                   else self.engine._next_rid)
            rec.rid = rid
            self._arm_request_fault(fault, rid)
            if self.role.name == "prefill":
                decode_endpoint = request.get("decode_endpoint")
                if not decode_endpoint:
                    return ("json", 400, {
                        "error": "prefill tier needs 'decode_endpoint' "
                                 "(where the finished chain ships)",
                        "retryable": False,
                    })
                self.engine.submit(prompt, request_id=rid,
                                   tier=self.role.name, **kwargs)
                return ("sse", self._relay_prefill(
                    rid, decode_endpoint,
                    alternates=request.get("decode_endpoints") or (),
                    deadline_wall=deadline_wall,
                ))
            self._streams[rid] = queue.Queue()
            if deadline_wall is not None:
                self._deadlines[rid] = float(deadline_wall)
            if self.engine.tracer is not None:
                self._relay_t0[rid] = None
            self.engine.submit(prompt, request_id=rid, tier=self.role.name,
                               **kwargs)
        return ("sse", self._stream_response(rid))

    # ---------------------------------------------------------------- relay
    def _relay_prefill(self, rid: int, decode_endpoint: str,
                       alternates=(), deadline_wall: float | None = None):
        """The prefill tier's generate path: run this request's chunked
        prefill to completion (no decode window ever dispatches here), ship
        the chain, then relay the decode host's stream — prepending this
        tier's record to the final event's trace, so the client's one trace
        spans prefill chunks AND the handoff leg.

        Free-on-ack re-handoff: the export keeps the chain resident
        (``free=False``); the first non-error frame from a decode import is
        the ack that frees it. A failed import (dead host, dropped POST —
        the ``handoff_drop`` chaos action) moves to the next surviving
        decode endpoint in ``alternates`` WITHOUT re-prefilling; exhausting
        them surfaces a retryable error (the router's retry re-enters
        prefill), and the chain is released on every exit path — a failed
        handoff never leaks pool blocks."""
        try:
            with self._lock:
                run_prefill_only(self.engine, rid)
                payload = export_chain(self.engine, rid,
                                       endpoint=decode_endpoint, free=False)
        except Exception as exc:
            logger.warning(f"prefill for request {rid} failed: {exc!r}")
            yield sse_event("error", {"rid": rid, "error": str(exc),
                                      "retryable": True})
            return
        if deadline_wall is not None:
            payload["deadline_wall"] = float(deadline_wall)

        def finalize(done: dict) -> dict:
            record = self._trace_record(rid)
            if record is not None:
                done["trace"] = [record] + done.get("trace", [])
            return done

        from ..resilience.faults import serving_fault

        with self._lock:
            handoff_seq = self._handoff_seq
            self._handoff_seq += 1
        dropped = serving_fault(handoff_seq, "handoff_drop")
        targets = [decode_endpoint] + [ep for ep in alternates
                                       if ep != decode_endpoint]
        acked = False
        try:
            for attempt, endpoint in enumerate(targets):
                if dropped is not None and attempt == 0:
                    # The chaos action: this POST never happens — exactly a
                    # payload lost on the wire before the importer saw it.
                    logger.warning(
                        f"chaos handoff_drop: dropping export of rid {rid} "
                        f"to {endpoint}"
                    )
                    self._book_handoff_retry(rid, attempt + 1, endpoint)
                    continue
                url = f"http://{endpoint}/v1/import"
                try:
                    req = urllib.request.Request(
                        url, data=json.dumps(payload).encode(),
                        headers={"Content-Type": "application/json"},
                    )
                    response = urllib.request.urlopen(
                        req, timeout=self.stream_timeout_s)
                except Exception as exc:
                    logger.warning(
                        f"handoff of rid {rid} to {endpoint} failed: {exc!r}"
                    )
                    self._book_handoff_retry(rid, attempt + 1, endpoint)
                    continue
                leg_failed = False
                with response:
                    for kind, data in iter_sse(response):
                        if not acked:
                            if kind == "error":
                                detail = json.loads(data)
                                if detail.get("retryable") is False:
                                    # Unservable anywhere: surface as-is.
                                    with self._lock:
                                        release_chain(self.engine, rid)
                                    acked = True  # chain handled
                                    detail.setdefault("rid", rid)
                                    yield sse_event("error", detail)
                                    return
                                logger.warning(
                                    f"decode import of rid {rid} on "
                                    f"{endpoint} refused: {detail.get('error')}"
                                )
                                self._book_handoff_retry(rid, attempt + 1,
                                                         endpoint)
                                leg_failed = True
                                break
                            # First non-error frame: the importer owns the
                            # chain now — free our copy (free-on-ack).
                            acked = True
                            with self._lock:
                                release_chain(self.engine, rid)
                        if kind == "done" and finalize is not None:
                            try:
                                done = finalize(json.loads(data))
                                yield sse_event("done", done)
                                continue
                            except (ValueError, TypeError):
                                pass
                        yield f"event: {kind}\ndata: {data}\n\n"
                if acked:
                    return
                if not leg_failed:
                    # Stream ended before any frame: the importer died
                    # between accepting the POST and streaming.
                    self._book_handoff_retry(rid, attempt + 1, endpoint)
            yield sse_event("error", {
                "rid": rid, "retryable": True,
                "error": f"handoff failed on all {len(targets)} decode "
                         "endpoint(s)",
            })
        finally:
            if not acked:
                with self._lock:
                    release_chain(self.engine, rid)

    def _book_handoff_retry(self, rid: int, attempt: int, endpoint: str):
        """One failed handoff leg: the shared retries counter (reason
        ``handoff_failed``), this tier's tracer retry leg, and the flight
        recorder (via the tracer)."""
        from .router import _fault_counters

        retries, _, _, _ = _fault_counters()
        retries.inc(reason="handoff_failed")
        if self.engine.tracer is not None:
            self.engine.tracer.retry(rid, attempt, "handoff_failed",
                                     endpoint=endpoint)


def relay_generate(url: str, request: dict, finalize=None,
                   timeout_s: float = STREAM_TIMEOUT_S):
    """POST ``request`` to a downstream tier's SSE endpoint and re-yield its
    stream. ``finalize(done_payload) -> done_payload`` rewrites the final
    event as it passes through — each relay tier prepends its own tracer
    record to the ``trace`` list there, which is how the client's one trace
    comes to span router admission → prefill chunks → chain handoff → decode
    — the one relay primitive the prefill tier and the router share."""
    req = urllib.request.Request(
        url, data=json.dumps(request).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        response = urllib.request.urlopen(req, timeout=timeout_s)
    except Exception as exc:
        yield sse_event("error", {
            "error": f"downstream tier {url} unreachable: {exc}",
            "retryable": True,
        })
        return
    with response:
        for kind, data in iter_sse(response):
            if kind == "done" and finalize is not None:
                try:
                    payload = finalize(json.loads(data))
                    yield sse_event("done", payload)
                    continue
                except (ValueError, TypeError):
                    pass
            yield f"event: {kind}\ndata: {data}\n\n"


def read_sse_response(fp) -> dict:
    """Drain one generate stream client-side: returns ``{"tokens": [...],
    "deltas": [...], "done": {...}}`` — the drill's and the tests' client
    helper, so they consume the REAL wire format, not a shortcut. An
    ``error`` frame (or a stream that dies without a terminal frame) raises
    :class:`ServingStreamError`, whose ``retryable`` mirrors the frame's
    flag so callers know whether re-submitting can help."""
    deltas, done = [], None
    for kind, data in iter_sse(fp):
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
        raise ServingStreamError("serving stream closed without a done event",
                                 retryable=True)
    return {"tokens": done["tokens"], "deltas": deltas, "done": done}
