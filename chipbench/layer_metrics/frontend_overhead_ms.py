"""Median over requests, in ms, of the client's time to first token counted
from the send, minus the engine tracer's ``ttft_s`` counted from ``submit``
(layer: entry point): what HTTP, the front end's queues and the SSE relay add
around the engine."""

import statistics


def compute(record: dict):
    extra = [r["ttft_from_send_s"] - r["engine_ttft_s"] for r in record.get("requests", ())
             if r.get("engine_ttft_s") is not None]
    return 1e3 * statistics.median(extra) if extra else None
