"""Device time of one paged decode window, in ms (layer: compiled programs),
read on the host's clock: the median time between two consecutive decode
reports becoming ready (the ends of ``serve.report_wait``), over pairs of
turns with no prefill chunk dispatched between the two windows and with the
host waiting at both ends (``chipbench/program_spans.py``)."""

from chipbench import program_spans


def compute(record: dict):
    records = program_spans.serve_records(record)
    if records is None:
        return None
    seconds = program_spans.paced_median_s(program_spans.report_pairs(records), 0)
    return None if seconds is None else 1e3 * seconds
