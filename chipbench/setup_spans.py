"""The program's start-up, as its span ring records it: what the metrics of
the ``start-up`` layer read.

``accelerate_tpu/telemetry/spans.py`` pushes one record for each phase of a
program's start-up that JAX times: ``program.trace`` (the Python function
traced to a jaxpr), ``program.lower`` (the jaxpr lowered to StableHLO) and
``program.compile`` (an XLA compile, or on a persistent-cache hit the
executable read back). Each is an interval on ``time.perf_counter()`` whose
``attrs`` name the ``program``, how many phases of its thread were open when
it began (``nested``) and, for a compile, its ``cache``.

A reader counts the records that end before the measured window opens
(``PROCESS_START`` of ``chipbench/run.py`` plus ``setup_s``, for every kind of
runner), and takes the union of their intervals, so that a trace nested in
another and phases on threads that overlap count once. It gives None where the
program records no such phase, where the window's start cannot be read, and
where the ring has wrapped: start-up records are its oldest.
"""

from __future__ import annotations

from chipbench import program_spans


def union_s(record: dict, names) -> float | None:
    """Seconds covered by the start-up records named ``names``, or None."""
    spans, start = program_spans.ring(), program_spans.process_start()
    setup_s = (record.get("end_to_end") or {}).get("setup_s")
    if spans is None or start is None or setup_s is None or spans.total > spans.capacity:
        return None
    opens = start + setup_s
    intervals = sorted((r.start_s, program_spans.end_s(r)) for r in spans.snapshot()
                       if r.name in names and program_spans.end_s(r) <= opens)
    if not intervals:
        return None
    total, (lo, hi) = 0.0, intervals[0]
    for a, b in intervals[1:]:
        if a > hi:
            total, lo = total + hi - lo, a
        hi = max(hi, b)
    return total + hi - lo
