"""Model FLOPs utilisation of a held-expert training step, in % of the
chips' peak.

Training FLOPs per token (``moe_counts.train_flops_per_token``: the
parameters a token reaches on this chip and attention over each layer's
window or sequence) times the window's tokens per second on the host clock,
over chips times the bf16 peak.  Recomputation is not counted.
"""

from benchmarks.chip import moe_counts


def read(rec):
    flops = moe_counts.train_flops_per_token(rec.model, rec.traffic["seq_len"])
    return 100.0 * flops * rec.window["tokens_per_s"] / (rec.chips * rec.peak["bf16_flops_per_s"])
