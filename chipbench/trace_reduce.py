"""From a profiler trace to device busy and idle time and the top operations.

The smallest reduction that is sound: which planes are devices, the union of
the intervals in which an operation ran on each, the gaps between them, and
what the host was doing in the longest gaps (by the benchmark's own
``TraceAnnotation`` spans, which the profiler writes on the same clock).

``load`` reads the profiler's ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain lists; ``reduce`` is pure arithmetic on those lists, so that it can
be checked on a small recorded trace (``chipbench/tests/``). The interval
arithmetic is a copy of ``accelerate_tpu/telemetry/traceview.py`` (``_merge``,
``_total``, ``_clip``), kept here so that no later PR can change the yardstick.

Device planes are named ``/device:TPU:<n>``. On such a plane the line
``XLA Ops`` holds one event for every operation that ran, and the line
``XLA Modules`` one for every compiled program; ``Steps`` and the others
repeat the same time at another grain and are not read for busy time.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE_PREFIX = "/host:"
WINDOW_SPAN = "bench.trace_window"
SPAN_PREFIX = "bench."
NS = 1e-9


# ---------------------------------------------------------------- intervals
def merge(intervals: list) -> list:
    """Overlapping or adjacent [start, end) intervals -> disjoint, sorted."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def total(merged: list) -> float:
    return sum(end - start for start, end in merged)


def clip(merged: list, lo: float, hi: float) -> list:
    return [[max(start, lo), min(end, hi)] for start, end in merged
            if min(end, hi) > max(start, lo)]


def gaps(merged: list, lo: float, hi: float) -> list:
    """The idle intervals of [lo, hi) that ``merged`` (clipped to it) leaves."""
    out, cursor = [], lo
    for start, end in merged:
        if start > cursor:
            out.append([cursor, start])
        cursor = max(cursor, end)
    if hi > cursor:
        out.append([cursor, hi])
    return out


# ------------------------------------------------------------------ capture
@contextlib.contextmanager
def capture(directory: str):
    """Trace what runs inside to ``directory``: device and host spans, no
    Python call tracing (it slows the host and fills the file)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


_OP = re.compile(r"%?(\S+) = \(?([a-z0-9]+\[[0-9,]*\])?")


def short_name(name: str) -> str:
    """``%fusion.3 = bf16[2,4096]{...} fusion(...)`` -> ``fusion.3 bf16[2,4096]``:
    the profiler names a device operation by its whole HLO line."""
    found = _OP.match(name)
    if not found:
        return name[:96]
    return found.group(1) + (" " + found.group(2) if found.group(2) else "")


def load(path: str) -> dict:
    """The trace as plain data: for each device plane its operation events,
    and the host's ``bench.*`` spans; times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    lines[line.name] = [
                        (short_name(ev.name), ev.start_ns * NS,
                         (ev.start_ns + ev.duration_ns) * NS)
                        for ev in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns * NS,
                                      (ev.start_ns + ev.duration_ns) * NS))
    return {"devices": devices, "spans": spans}


# ------------------------------------------------------------------- reduce
def _label(gap, spans) -> str:
    """The benchmark span that covers most of the gap, else ``host``."""
    best, best_cover = "host", 0.0
    for name, start, end in spans:
        if name == WINDOW_SPAN:
            continue
        cover = min(end, gap[1]) - max(start, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def self_seconds(events: list, lo: float, hi: float) -> dict:
    """Time of each operation inside [lo, hi) without the time of the
    operations nested in it (a ``while`` holds its body's operations on the
    same line), summed by name. The self times of a line add up to its union."""
    out: dict = {}
    stack: list = []  # [name, end, self seconds]

    def close(entry):
        out[entry[0]] = out.get(entry[0], 0.0) + max(entry[2], 0.0)

    for name, start, end in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    while stack:
        close(stack.pop())
    return out


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and idle seconds inside the traced window, averaged over the
    device planes that ran anything; the operations with most self time; the
    idle time by what the host was doing (the ``bench.*`` span that covers
    most of each gap, else ``host``).

    The window is the host's ``bench.trace_window`` span where the trace has
    it and it holds the device's operations (host spans and device operations
    are written on one clock); otherwise it runs from the first operation's
    start to the last one's end.
    """
    planes = {name: lines for name, lines in trace["devices"].items()
              if lines.get(OPS_LINE)}
    if not planes:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0, "device_ops": [],
                "idle_gaps": [], "op_seconds": {}, "op_counts": {}, "window_from": "none"}
    first = min(ev[1] for lines in planes.values() for ev in lines[OPS_LINE])
    last = max(ev[2] for lines in planes.values() for ev in lines[OPS_LINE])
    (lo, hi), window_from = (first, last), "device_ops"
    slack = 0.05 * (last - first)
    for name, start, end in trace["spans"]:
        if name == WINDOW_SPAN and start <= first + slack and end >= last - slack:
            (lo, hi), window_from = (start, end), "host_span"
    busy, op_seconds, op_counts, gap_seconds = [], {}, {}, {}
    for lines in planes.values():
        merged = clip(merge([[s, e] for _, s, e in lines[OPS_LINE]]), lo, hi)
        busy.append(total(merged))
        for name, seconds in self_seconds(lines[OPS_LINE], lo, hi).items():
            op_seconds[name] = op_seconds.get(name, 0.0) + seconds
        for name, start, end in lines[OPS_LINE]:
            if lo <= start and end <= hi:
                op_counts[name] = op_counts.get(name, 0) + 1
        for gap in gaps(merged, lo, hi):
            label = _label(gap, trace["spans"])
            gap_seconds[label] = gap_seconds.get(label, 0.0) + (gap[1] - gap[0])
    n = len(planes)
    ranked = sorted(op_seconds.items(), key=lambda kv: -kv[1])
    ranked_gaps = sorted(gap_seconds.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / n,
        "window_s": hi - lo,
        "devices": n,
        "window_from": window_from,
        "device_ops": [[name, seconds / n] for name, seconds in ranked[:top]],
        "idle_gaps": [[name, seconds / n] for name, seconds in ranked_gaps[:top]],
        "op_seconds": {name: seconds / n for name, seconds in ranked},
        "op_counts": {name: count / n for name, count in op_counts.items()},
    }


def idle_share_percent(reduced: dict | None):
    """1 - busy / window of a reduced trace, in %; None where no device ran."""
    if not reduced or not reduced["window_s"] or not reduced["devices"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def describe(path: str, events: int = 12) -> None:
    """Print a trace's planes, lines and the longest events of each line: the
    look by hand that comes before any code is written against a trace."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = [(ev.duration_ns, ev.start_ns, ev.name) for ev in line.events]
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for duration, start, name in sorted(evs, reverse=True)[:events]:
                print(f"      {duration * NS:12.6f} s  at {start * NS:12.6f}  {name[:140]}")


if __name__ == "__main__":
    import sys

    describe(sys.argv[1], *(int(a) for a in sys.argv[2:3]))
    print(reduce(load(sys.argv[1])))
