"""Prefill's share of the chip's bf16 peak, in %.

The prefill FLOPs of one batch (``counts.prefill_flops``: every prompt
position through the layers, the LM head on the last) over the mean device
time of the prefill program in the trace.
"""

from benchmarks.chip import counts
from benchmarks.chip import trace as tr


def read(rec):
    if rec.trace is None:
        return None
    lo, hi = rec.window_ns
    runs = tr.runs_within(rec.trace.devices[0], "serve_prefill", lo, hi)
    if not runs:
        return None
    mean_s = sum(e - s for s, e in runs) / len(runs) / 1e9
    t = rec.traffic
    flops = counts.prefill_flops(rec.model, t["batch"], t["prompt_len"])
    return 100.0 * flops / mean_s / rec.peak["bf16_flops_per_s"]
