"""Share of the traced window in which no operation ran on the device, in %
(layer: device), while the serving engine's programs run. 1 - union of the device's operation
intervals / window, from the profiler trace, averaged over the chips used."""

from chipbench import trace_reduce


def compute(record: dict):
    return trace_reduce.idle_share_percent(record.get("trace"))
