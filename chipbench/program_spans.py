"""The program's own spans, cut to the measured window.

A per-layer metric whose ``source`` is ``program_span`` is read from the ring
that ``accelerate_tpu/telemetry/spans.py`` keeps in the program's process. The
benchmark runs the program in its own process, so a reader takes the ring
with ``get_span_ring()`` after the run. Every record is one span: ``name``,
``start_s`` and ``duration_s`` on ``time.perf_counter()``, ``path`` (the
spans around it, ``outer/inner``), and, from the PR that put spans into the
serving loop on, ``rid`` and ``attrs``. On a program without those spans the
window simply holds no record of the name asked for, and a reader gives None.

**The window, serving.** Its start is in the record: ``end_to_end.setup_s`` is
the window's start less ``PROCESS_START`` of ``chipbench/run.py``, which runs
as ``__main__`` (``process_start``). Its end is the start plus ``--seconds``
of the command line that this process was given (``window_seconds``): the
record does not carry the window's length, the serve runner sleeps until
exactly then, and what follows in the ring (the drain, the reference check) is
not the load the cell states. Where either cannot be read, or the ring has
wrapped past the window's start, ``serve_records`` returns None: no reader
guesses. A record belongs to the window by its start.

**The window, training.** The traced steps are the last
``traffic["trace_steps"]`` records named ``train_step``: the runner traces
those steps last of all, so nothing has to be bounded on the clock.
"""

from __future__ import annotations

import bisect
import statistics
import sys

# A report that the host waited for less than this was ready when asked for:
# the device had gone idle, and the report's end says nothing of its pace.
MIN_WAIT_S = 1e-3


def ring():
    """The program's span ring, or None where the program has none."""
    try:
        from accelerate_tpu.telemetry import get_span_ring
    except ImportError:
        return None
    return get_span_ring()


def process_start():
    """``PROCESS_START`` of ``chipbench/run.py`` in this process, or None."""
    for name in ("__main__", "chipbench.run"):
        start = getattr(sys.modules.get(name), "PROCESS_START", None)
        if isinstance(start, float):
            return start
    return None


def window_seconds():
    """``--seconds`` as this process's command line gives it, or None."""
    argv = sys.argv
    for i, word in enumerate(argv):
        value = None
        if word == "--seconds" and i + 1 < len(argv):
            value = argv[i + 1]
        elif word.startswith("--seconds="):
            value = word.split("=", 1)[1]
        if value is not None:
            try:
                return float(value)
            except ValueError:
                return None
    return None


def end_s(rec) -> float:
    return rec.start_s + rec.duration_s


def attr(rec, key, default=None):
    return (getattr(rec, "attrs", None) or {}).get(key, default)


def serve_window(record: dict):
    """``(lo, hi)`` of the measured window on ``time.perf_counter()``, or None."""
    start, seconds = process_start(), window_seconds()
    setup_s = (record.get("end_to_end") or {}).get("setup_s")
    if start is None or seconds is None or setup_s is None:
        return None
    return start + setup_s, start + setup_s + seconds


def serve_records(record: dict):
    """The ring's records that start inside the measured window, by start; None
    where the window cannot be bounded or the ring may have lost part of it.

    Records are pushed as they end, so what a wrapped ring has lost ended
    before its oldest record did: the window is whole if that one ended
    before the window began."""
    spans, window = ring(), serve_window(record)
    if record.get("kind") != "serve" or spans is None or window is None:
        return None
    kept = spans.snapshot()
    if spans.total > spans.capacity and (not kept or end_s(kept[0]) > window[0]):
        return None
    lo, hi = window
    return sorted((r for r in kept if lo <= r.start_s < hi), key=lambda r: r.start_s)


def named(records, name: str) -> list:
    return [r for r in records if r.name == name]


def turns(records) -> list:
    """Each ``serve.iteration`` of the window with the spans that started
    inside it, by name: ``(iteration, {name: [records]})``, by start."""
    iterations = named(records, "serve.iteration")
    starts = [r.start_s for r in iterations]
    children = [{} for _ in iterations]
    for rec in records:
        if rec.name == "serve.iteration" or not rec.name.startswith("serve."):
            continue
        i = bisect.bisect_right(starts, rec.start_s) - 1
        if i >= 0 and rec.start_s < end_s(iterations[i]):
            children[i].setdefault(rec.name, []).append(rec)
    return list(zip(iterations, children))


def report_pairs(records) -> list:
    """Two consecutive turns that each read a report: ``(chunk, seconds,
    paced)``. With the loop's one-window lookahead, turn k dispatches chunk k
    (if any) and window k and then waits for report k-1, so between the ends
    of two consecutive ``serve.report_wait`` the device ran the chunk of the
    EARLIER turn and one decode window. ``chunk`` is that turn's ``chunk``
    (its bucket, 0 for none); ``paced`` says that the host waited at least
    ``MIN_WAIT_S`` at both ends, so both ends are the device's."""
    pairs = []
    listed = turns(records)
    for (turn, kids), (_, next_kids) in zip(listed, listed[1:]):
        first, second = kids.get("serve.report_wait"), next_kids.get("serve.report_wait")
        if not first or not second:
            continue
        pairs.append((attr(turn, "chunk"), end_s(second[0]) - end_s(first[0]),
                      min(first[0].duration_s, second[0].duration_s) >= MIN_WAIT_S))
    return pairs


def paced_median_s(pairs, chunk):
    """Median seconds of the device-paced pairs with that chunk between them;
    None where there is none or more than half of them were not paced."""
    mine = [(seconds, paced) for c, seconds, paced in pairs if c == chunk]
    paced = [seconds for seconds, ok in mine if ok]
    if not paced or 2 * len(paced) < len(mine):
        return None
    return statistics.median(paced)


def train_step_records(record: dict):
    """The ``train_step`` spans of the traced steps (the last
    ``trace_steps`` of the ring), or None."""
    spans = ring()
    steps = (record.get("traffic") or {}).get("trace_steps")
    if record.get("kind") != "train" or spans is None or not steps:
        return None
    found = named(spans.snapshot(), "train_step")
    return found[-steps:] if len(found) >= steps else None
