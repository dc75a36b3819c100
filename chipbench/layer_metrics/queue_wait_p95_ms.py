"""95th percentile, in ms, of the time a request waited in the engine's queue
for a slot (layer: serving loop). Read from ``queue_wait_s`` of each request's
tracer record, which the front end sends in the stream's ``done`` event."""

from chipbench import loadgen


def compute(record: dict):
    waits = [r["queue_wait_s"] for r in record.get("requests", ())
             if r.get("queue_wait_s") is not None]
    value = loadgen.percentile(waits, 95)
    return None if value is None else 1e3 * value
