"""Nestable host-side spans with XLA-trace name parity.

``span("data_load")`` times a block of host code into a lock-free ring buffer
AND enters a ``jax.profiler.TraceAnnotation`` of the same name, so the label a
user (or the framework — prepare/train_step/checkpoint/gather are
pre-instrumented) sees in the step timeline is the label they find in a
captured XLA/perfetto trace. Spans nest; each record carries its depth and its
``outer/inner`` path (the parent), an optional ``rid`` shared by the spans of
one request, and ``attrs``: small counts taken where the work happens.
:func:`record_span` pushes an interval whose two ends were read on different
threads (``time.perf_counter()`` both); it enters no annotation.

The ring is a fixed-size slot array indexed by an ``itertools.count`` — the
one CPython-atomic primitive that makes concurrent pushes (orbax background
writers, the serving loop, the train thread) safe without a lock on the hot
path. A full ring overwrites the oldest records; ``total`` keeps counting so
wraparound is observable.

Span durations also land in the shared metrics registry as the
``accelerate_span_seconds{name=...}`` histogram, so the Prometheus endpoint
answers "where does the wall-clock go" without a trace capture.

A program's start-up is recorded too. JAX times each phase of it (tracing the
Python function, lowering it to StableHLO, compiling it or loading it from the
persistent cache) and reports both ends on the calling thread through
``jax.monitoring``; one listener, registered when this module is first
imported, pushes each phase as a ``program.trace``, ``program.lower`` or
``program.compile`` record, adds the seconds of the outermost phases to the
innermost open span of that thread (``trace_s``, ``lower_s``, ``compile_s``)
and books them in the goodput ledger's ``compile`` bucket. Once every program
has been built JAX reports nothing, and the listener is not called.
"""

from __future__ import annotations

import itertools
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass

import jax

from .journal import journal_event

try:  # host-side runtime trace annotation; absent on exotic builds
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # pragma: no cover
    _TraceAnnotation = None


@dataclass
class SpanRecord:
    name: str
    start_s: float  # time.perf_counter() at entry
    duration_s: float
    depth: int  # 0 = top-level
    path: str  # "outer/inner"
    rid: int | None = None  # request id: spans of one request share it
    attrs: dict | None = None  # small host-side counts, taken where the work happens


class SpanRing:
    """Fixed-capacity overwrite-oldest span store; push is lock-free.

    Each slot stores ``(index, record)`` where the index comes from one
    ``itertools.count`` draw — the CPython-atomic primitive — and ordering /
    ``total`` are DERIVED from the stored indices at read time. There is no
    separate length bookkeeping a concurrent pusher could regress (the
    read-modify-write that a plain ``self._n = i + 1`` hides)."""

    def __init__(self, capacity: int = 65536):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = capacity
        self._slots = [None] * capacity
        self._ctr = itertools.count()

    def push(self, record: SpanRecord):
        i = next(self._ctr)  # atomic under the GIL: unique slot per push
        self._slots[i % self.capacity] = (i, record)

    @property
    def total(self) -> int:
        """Spans ever pushed (keeps growing after wraparound)."""
        return max((s[0] for s in self._slots if s is not None), default=-1) + 1

    def snapshot(self) -> list[SpanRecord]:
        """The retained records, oldest first."""
        kept = sorted((s for s in self._slots if s is not None), key=lambda s: s[0])
        return [record for _, record in kept]

    def clear(self):
        self._slots = [None] * self.capacity
        self._ctr = itertools.count()


_RING = SpanRing()
_tls = threading.local()
_SPAN_HIST = None


def get_span_ring() -> SpanRing:
    return _RING


def reset_spans():
    _RING.clear()


def _open_spans() -> list[SpanRecord]:
    """This thread's open ``span`` records, outermost first."""
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def _span_hist():
    global _SPAN_HIST
    if _SPAN_HIST is None:
        from .metrics import cached_handles

        _SPAN_HIST = cached_handles(lambda registry: registry.histogram(
            "accelerate_span_seconds",
            "Host wall-clock of instrumented spans",
            labelnames=("name",),
        ))
    return _SPAN_HIST()


def _publish(record: SpanRecord):
    """Histogram + durable journal tee of one finished span; never raises."""
    try:
        _span_hist().observe(record.duration_s, name=record.name)
    except Exception:  # pragma: no cover - instrumentation never raises
        pass
    # Durable tee (telemetry/journal.py): no-op when journaling is off; pure
    # host bookkeeping (the record itself) when on.
    try:
        journal_event("span", name=record.name, path=record.path, depth=record.depth,
                      duration_s=round(record.duration_s, 6))
    except Exception:  # pragma: no cover - instrumentation never raises
        pass


@contextmanager
def span(name: str, ring: SpanRing | None = None, record_metric: bool = True,
         rid: int | None = None, **attrs):
    """Time a block into the span ring (and the XLA trace). Nestable; safe on
    any thread; never raises from instrumentation.

    ``attrs`` known at entry also ride the ``TraceAnnotation``, so the event
    in a captured profile carries them. The block receives the record
    (``with span(...) as rec``) and may set ``rec.rid`` or add to
    ``rec.attrs`` what it only learns inside; those reach the ring alone."""
    ring = _RING if ring is None else ring
    stack = _open_spans()
    record = SpanRecord(name=name, start_s=0.0, duration_s=0.0, depth=len(stack),
                        path=stack[-1].path + "/" + name if stack else name,
                        rid=rid, attrs=attrs)
    stack.append(record)
    ann = _TraceAnnotation(name, **attrs) if _TraceAnnotation is not None else None
    if ann is not None:
        ann.__enter__()
    record.start_s = time.perf_counter()
    try:
        yield record
    finally:
        record.duration_s = time.perf_counter() - record.start_s
        if ann is not None:
            ann.__exit__(None, None, None)
        stack.pop()
        if not record.attrs:
            record.attrs = None
        ring.push(record)
        if record_metric:
            _publish(record)


def record_span(name: str, start_s: float, end_s: float, rid: int | None = None,
                ring: SpanRing | None = None, **attrs):
    """Push a span whose two ends were read with ``time.perf_counter()`` by
    the caller: an interval that begins on one thread and ends on another
    (the front end's relay). Top-level, and no ``TraceAnnotation``: an
    annotation must be entered and left on one thread."""
    record = SpanRecord(name=name, start_s=start_s, duration_s=end_s - start_s,
                        depth=0, path=name, rid=rid, attrs=attrs or None)
    (_RING if ring is None else ring).push(record)
    _publish(record)


@contextmanager
def no_span(name: str, rid: int | None = None, **attrs):
    """``span``'s shape with nothing recorded: what a caller whose
    instrumentation is switched off binds in its place."""
    yield SpanRecord(name=name, start_s=0.0, duration_s=0.0, depth=0, path=name,
                     rid=rid, attrs=attrs)


# ------------------------------------------------------ the program's start-up
# JAX's three phases of a program's start-up (jax/_src/dispatch.py): the name of
# the record each becomes, and the key under which an open span sums them.
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": ("program.trace", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("program.lower", "lower_s"),
    "/jax/core/compile/backend_compile_duration": ("program.compile", "compile_s"),
}
_COMPILE = "/jax/core/compile/backend_compile_duration"
# Reported inside a compile phase, on its thread (jax/_src/compiler.py).
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


def _open_phases() -> list[dict]:
    """This thread's open phases, outermost first."""
    phases = getattr(_tls, "phases", None)
    if phases is None:
        phases = _tls.phases = []
    return phases


def _open_compile():
    phases = getattr(_tls, "phases", None)
    return phases[-1] if phases and phases[-1]["event"] == _COMPILE else None


def _program_name(fun_name) -> str:
    name = str(fun_name)
    return name[4:-1] if name.startswith("jit(") and name.endswith(")") else name


def _on_phase_entry(event, value, **kwargs):
    """A phase begins: JAX reports its start time as a scalar."""
    try:
        if event in _PHASES:
            _open_phases().append({"event": event})
    except Exception:  # pragma: no cover - instrumentation never raises
        pass


def _on_cache_event(event, **kwargs):
    try:
        frame = _open_compile()
        if frame is not None and event == _CACHE_HIT:
            frame["cache"] = "hit"
        elif frame is not None and event == _CACHE_ASKED:
            frame["asked"] = True
    except Exception:  # pragma: no cover - instrumentation never raises
        pass


def _on_duration(event, seconds, **kwargs):
    """A phase ends, timed by JAX: push its record, and for an outermost phase
    add its seconds to the innermost open span and to the goodput ledger."""
    try:
        if event == _CACHE_RETRIEVAL:
            frame = _open_compile()
            if frame is not None:
                frame["retrieval_s"] = float(seconds)
            return
        if event not in _PHASES:
            return
        end = time.perf_counter()
        phases = _open_phases()
        frame = phases.pop() if phases and phases[-1]["event"] == event else {}
        name, key = _PHASES[event]
        attrs = {"program": _program_name(kwargs.get("fun_name")), "nested": len(phases)}
        if event == _COMPILE:
            # JAX asks its cache on every compile; it only has one where a
            # directory is set.
            asked = frame.get("asked") and jax.config.jax_compilation_cache_dir
            attrs["cache"] = frame.get("cache") or ("miss" if asked else "off")
            if "retrieval_s" in frame:
                attrs["retrieval_s"] = frame["retrieval_s"]
        stack = _open_spans()
        _RING.push(SpanRecord(name=name, start_s=end - seconds, duration_s=seconds,
                              depth=len(stack), attrs=attrs,
                              path=stack[-1].path + "/" + name if stack else name))
        if phases:
            return
        if stack:
            stack[-1].attrs[key] = stack[-1].attrs.get(key, 0.0) + seconds
        from ..resilience.goodput import get_ledger

        get_ledger().add("compile", seconds, count=int(event == _COMPILE))
    except Exception:  # pragma: no cover - instrumentation never raises
        pass


jax.monitoring.register_scalar_listener(_on_phase_entry)
jax.monitoring.register_event_listener(_on_cache_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
