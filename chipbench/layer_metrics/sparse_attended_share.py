"""Share of its causal context that a decode token of a block-sparse layer
attended, in % (layer: compiled programs): the sum of ``attended_keys`` over
the sum of ``context_keys`` of the window's ``serve.dispatch_decode`` spans
(both summed by the decode program over decoding rows, steps, sparse layers
and key-value heads, and read with its report). 100 would mean that the
selection discards nothing. None on a program whose spans lack the counts."""

from chipbench import program_spans


def compute(record: dict):
    records = program_spans.serve_records(record)
    if records is None:
        return None
    windows = program_spans.named(records, "serve.dispatch_decode")
    context = sum(program_spans.attr(w, "context_keys", 0) for w in windows)
    if not context:
        return None
    return 100.0 * sum(program_spans.attr(w, "attended_keys", 0) for w in windows) / context
