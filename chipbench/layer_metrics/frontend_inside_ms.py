"""What the front end's own code takes of a request's time to first token,
in ms (layer: entry point): the median, over the requests submitted in the
window, of ``frontend.submit`` (parsed body to the return of the engine's
``submit``) plus that request's ``frontend.relay`` (the first stream event
from the loop thread's push to the handler thread's yield of its frame).
``frontend_overhead_ms`` times the same layer from outside
(``chipbench/program_spans.py``)."""

import statistics

from chipbench import program_spans


def compute(record: dict):
    records = program_spans.serve_records(record)
    if records is None:
        return None
    relays = {r.rid: r.duration_s
              for r in program_spans.named(program_spans.ring().snapshot(), "frontend.relay")}
    inside = [s.duration_s + relays[s.rid]
              for s in program_spans.named(records, "frontend.submit") if s.rid in relays]
    return 1e3 * statistics.median(inside) if inside else None
