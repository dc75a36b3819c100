"""Host time of one turn of the paged serving loop, in ms (layer: serving
loop): the median over the window's ``serve.iteration`` spans of the turn's
duration less its ``serve.report_wait`` child, the part in which the host is
blocked on the device (``chipbench/program_spans.py``)."""

import statistics

from chipbench import program_spans


def compute(record: dict):
    records = program_spans.serve_records(record)
    if records is None:
        return None
    own = [turn.duration_s - sum(w.duration_s for w in kids.get("serve.report_wait", ()))
           for turn, kids in program_spans.turns(records)]
    return 1e3 * statistics.median(own) if own else None
