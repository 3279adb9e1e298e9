"""Share of the traced window in which no operation ran on the device, in %,
averaged over the chips (1 - union of operation intervals / window)."""

from benchmarks.chip import trace as tr


def read(rec):
    if rec.trace is None:
        return None
    lo, hi = rec.window_ns
    return 100.0 * tr.mean_idle_share(rec.trace, lo, hi)
