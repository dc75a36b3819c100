"""Engine programs dispatched for each output token (layer: serving loop):
the window's ``serve.dispatch_chunk`` and ``serve.dispatch_decode`` spans over
the sum of ``tokens`` of its ``serve.process_report`` spans
(``chipbench/program_spans.py``)."""

from chipbench import program_spans


def compute(record: dict):
    records = program_spans.serve_records(record)
    if records is None:
        return None
    tokens = sum(program_spans.attr(r, "tokens", 0)
                 for r in program_spans.named(records, "serve.process_report"))
    dispatches = sum(r.name in ("serve.dispatch_chunk", "serve.dispatch_decode") for r in records)
    return dispatches / tokens if tokens > 0 else None
