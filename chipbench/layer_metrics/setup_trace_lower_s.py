"""Seconds of set-up spent tracing the program's Python and lowering it to
StableHLO (layer: start-up): the union of the ``program.trace`` and
``program.lower`` records that end before the measured window opens
(``chipbench/setup_spans.py``). Every process pays it; a persistent
compilation cache saves none of it."""

from chipbench import setup_spans


def compute(record: dict):
    return setup_spans.union_s(record, ("program.trace", "program.lower"))
