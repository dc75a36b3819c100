"""Share of the experts held here that a decode step touched, in % (layer:
compiled programs): the sum of ``experts_touched`` over the sum of
``experts_held`` of the window's ``serve.dispatch_decode`` spans (held experts
times expert layers that got at least one claim, and held experts times expert
layers, both summed by the decode program over its steps and read with its
report). It is how much of the experts' bytes a decode step has to read; 100
would mean that the rows' claims reach every expert held. None on a program
whose spans lack the counts."""

from chipbench import program_spans


def compute(record: dict):
    records = program_spans.serve_records(record)
    if records is None:
        return None
    windows = program_spans.named(records, "serve.dispatch_decode")
    held = sum(program_spans.attr(w, "experts_held", 0) for w in windows)
    if not held:
        return None
    return 100.0 * sum(program_spans.attr(w, "experts_touched", 0) for w in windows) / held
