"""Model FLOPs utilisation of the training step, in % of the chips' peak.

Training FLOPs per token (``counts.train_flops_per_token``) times the
window's tokens per second on the host clock, over chips times the bf16
peak.  Recomputation is not counted.
"""

from benchmarks.chip import counts


def read(rec):
    flops = counts.train_flops_per_token(rec.model, rec.traffic["seq_len"])
    return 100.0 * flops * rec.window["tokens_per_s"] / (rec.chips * rec.peak["bf16_flops_per_s"])
