"""The whole decode step's share of the chip's bf16 peak, in %.

The FLOPs of one decode step (``counts.decode_flops``: the batch's tokens
through the layers and the LM head, attending over the mean context of the
mix) over the mean gap between consecutive tokens on the host clock, which
holds everything a step costs: the decode program, the argmax and the token
fetch.  Unlike ``decode_hbm_roofline.serve`` it needs no program name in the
trace, so it still reads where the decode program is renamed or split.
"""

from benchmarks.chip import counts


def read(rec):
    t = rec.traffic
    ctx = counts.mean_decode_context(t["prompt_len"], t["gen_tokens"])
    flops = counts.decode_flops(rec.model, t["batch"], ctx)
    return 100.0 * flops / rec.window["itl_mean_s"] / rec.peak["bf16_flops_per_s"]
