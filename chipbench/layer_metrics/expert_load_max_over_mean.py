"""The most claims an expert held here got from a prefill chunk over the mean
(layer: compiled programs): the sum of ``expert_claims_max`` over the sum of
``expert_claims_mean`` of the window's ``serve.dispatch_chunk`` spans (each
summed by the chunk program over its expert layers, taken over the experts
held; read once a later report has been read). It is the straggler a chunk's
grouped product waits for; 1 would be a level load. None on a program whose
spans lack the counts."""

from chipbench import program_spans


def compute(record: dict):
    records = program_spans.serve_records(record)
    if records is None:
        return None
    chunks = program_spans.named(records, "serve.dispatch_chunk")
    mean = sum(program_spans.attr(c, "expert_claims_mean", 0) for c in chunks)
    if not mean:
        return None
    return sum(program_spans.attr(c, "expert_claims_max", 0) for c in chunks) / mean
