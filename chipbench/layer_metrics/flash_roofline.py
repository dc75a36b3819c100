"""Share of its roofline that the library flash-attention kernel reaches in
training, in % (layer: kernels).

Time: the device time of every flash event in the trace, forward
(``flash_attention*``) and backward (``flash_mha_bwd_dkv*``,
``flash_mha_bwd_dq*``), the forward's second run under ``remat`` included.
Required work: one forward and one backward for every ``dkv`` call (the
recomputed forward is not required work), each
``max(FLOP / peak, bytes / HBM bandwidth)`` from ``chipbench/flops.py`` on the
``(batch on this chip, heads, seq, head_dim)`` operands the kernel gets. At
sequence 4096 both are bound by compute (about 1024 FLOP a byte).

The events are told apart by the kernels' own names in the compiled step. A
trace without them gives nothing, and the metric is left out of the line.
"""

from chipbench import flops, peaks


def compute(record: dict):
    trace = record.get("trace")
    if not trace or record.get("kind") != "train":
        return None
    names = trace["op_seconds"]
    seconds = sum(s for name, s in names.items()
                  if name.startswith(("flash_attention", "flash_mha_bwd")))
    calls = sum(n for name, n in trace["op_counts"].items()
                if name.startswith("flash_mha_bwd_dkv"))
    if seconds <= 0 or not calls:
        return None
    peak = peaks.peaks_for(record["device"]["kind"])
    dims, traffic = record["dims"], record["traffic"]
    shape = (traffic["batch"] // record["chips"], dims["num_attention_heads"],
             traffic["seq"], dims["head_dim"])
    least = sum(flops.roofline_seconds(flops.flash_call_cost(*shape, backward=b), peak)["seconds"]
                for b in (False, True))
    return 100.0 * calls * least / seconds
