"""Host time of one training step, in ms (layer: train loop): the median of
the program's ``train_step`` span (placing the batch and dispatching the
fused step) over the traced steps (``chipbench/program_spans.py``)."""

import statistics

from chipbench import program_spans


def compute(record: dict):
    steps = program_spans.train_step_records(record)
    return None if not steps else 1e3 * statistics.median(s.duration_s for s in steps)
