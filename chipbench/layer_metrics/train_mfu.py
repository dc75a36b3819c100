"""Model-FLOP/s utilisation of training, in % (layer: compiled programs).

``train_tokens_per_s`` of this run, times the operations a token requires
(``chipbench/flops.py``: the layers' and the head's matrix multiplications,
causal attention counted once; no embedding gather, no recomputation), over
the chips used times the chip's published bf16 peak (``chipbench/peaks.py``).
An unknown device kind is an error."""

from chipbench import flops, peaks


def compute(record: dict):
    if record.get("kind") != "train":
        return None
    peak = peaks.peaks_for(record["device"]["kind"])["bf16_flops"] * record["chips"]
    per_token = flops.train_flops_per_token(record["dims"], record["traffic"]["seq"])
    return 100.0 * record["tokens_per_s"] * per_token / peak
