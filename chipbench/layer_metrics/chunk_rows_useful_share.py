"""Share of the rows a prefill chunk program computed that were tokens of the
prompt it prefilled, in % (layer: serving loop): the sum of ``tokens`` over
the sum of ``rows_computed`` (slots x bucket) of the window's
``serve.dispatch_chunk`` spans. The chunk program computes every slot for one,
so at 4 slots it cannot pass 25. None on a program whose spans lack the count."""

from chipbench import program_spans


def compute(record: dict):
    records = program_spans.serve_records(record)
    if records is None:
        return None
    chunks = program_spans.named(records, "serve.dispatch_chunk")
    rows = sum(program_spans.attr(c, "rows_computed", 0) for c in chunks)
    if not rows:
        return None
    return 100.0 * sum(program_spans.attr(c, "tokens", 0) for c in chunks) / rows
