"""The decode step's share of its HBM roofline, in %.

The least bytes one decode step must read (``counts.decode_bytes``: the
weights at the compute dtype and the keys and values of the positions so
far, at the mean context of the mix) over the mean device time of the
decode program in the trace, against the chip's HBM bandwidth.
"""

from benchmarks.chip import counts
from benchmarks.chip import trace as tr


def read(rec):
    if rec.trace is None:
        return None
    lo, hi = rec.window_ns
    runs = tr.runs_within(rec.trace.devices[0], "serve_decode", lo, hi)
    if not runs:
        return None
    mean_s = sum(e - s for s, e in runs) / len(runs) / 1e9
    t = rec.traffic
    ctx = counts.mean_decode_context(t["prompt_len"], t["gen_tokens"])
    return 100.0 * counts.decode_bytes(rec.model, t["batch"], ctx) / mean_s / rec.peak["hbm_bytes_per_s"]
