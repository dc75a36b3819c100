"""Share of the keys a prefill chunk scored in its main attention that its
queries had selected, in % (layer: compiled programs): the sum of
``keys_selected`` over the sum of ``keys_scored`` of the window's
``serve.dispatch_chunk`` spans (each summed by the chunk program over its real
queries and its layers: the keys a query's indexer selected, and the keys whose
attention scores the program computed for it, masked or not; read once a later
report has been read). Masked tiles over the whole view read near ``index_topk``
over the view's columns; gathered rows would read 100. None on a program whose
spans lack the counts."""

from chipbench import program_spans


def compute(record: dict):
    records = program_spans.serve_records(record)
    if records is None:
        return None
    chunks = program_spans.named(records, "serve.dispatch_chunk")
    scored = sum(program_spans.attr(c, "keys_scored", 0) for c in chunks)
    if not scored:
        return None
    return 100.0 * sum(program_spans.attr(c, "keys_selected", 0) for c in chunks) / scored
