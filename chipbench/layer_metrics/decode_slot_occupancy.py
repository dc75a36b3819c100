"""Share of computed decode rows that carry a request, in % (layer: serving
loop): the sum of ``decoding`` over the sum of ``slots`` of the window's
``serve.dispatch_decode`` spans. Every window computes all slots
(``chipbench/program_spans.py``)."""

from chipbench import program_spans


def compute(record: dict):
    records = program_spans.serve_records(record)
    if records is None:
        return None
    windows = program_spans.named(records, "serve.dispatch_decode")
    slots = sum(program_spans.attr(w, "slots", 0) for w in windows)
    if not slots:
        return None
    return 100.0 * sum(program_spans.attr(w, "decoding", 0) for w in windows) / slots
