"""95th percentile, in ms, of how late the load generator sent a request
(layer: load generator): the send time minus the time it was due. A starved
generator must not be read as a fast server."""

from chipbench import loadgen


def compute(record: dict):
    late = [r["late_s"] for r in record.get("requests", ())]
    value = loadgen.percentile(late, 95)
    return None if value is None else 1e3 * value
