"""Seconds of set-up spent compiling programs with XLA, or on a persistent-cache
hit reading the executable back (layer: start-up): the union of the
``program.compile`` records that end before the measured window opens
(``chipbench/setup_spans.py``)."""

from chipbench import setup_spans


def compute(record: dict):
    return setup_spans.union_s(record, ("program.compile",))
