"""Device time of one full prefill chunk, in ms (layer: compiled programs):
the median time between two consecutive decode reports becoming ready, over
pairs of turns with one chunk of the engine's ``prefill_chunk`` size between
them, less ``decode_window_ms`` (``chipbench/program_spans.py``)."""

from chipbench import program_spans


def compute(record: dict):
    records = program_spans.serve_records(record)
    chunk = (record.get("config", {}).get("engine") or {}).get("prefill_chunk")
    if records is None or not chunk:
        return None
    pairs = program_spans.report_pairs(records)
    window = program_spans.paced_median_s(pairs, 0)
    both = program_spans.paced_median_s(pairs, chunk)
    return None if window is None or both is None else 1e3 * (both - window)
