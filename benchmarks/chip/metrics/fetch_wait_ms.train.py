"""Device-idle time between train steps while the runtime loop fetches the
step's loss and gradient norm, in ms per step.

The idle time between consecutive runs of the step program that the loop's
``repro.train.fetch`` span covers on the host: the transfers to the host
after the step has ended.  Averaged over the chips (``spans.idle_under``).
"""

from benchmarks.chip import spans


def read(rec):
    return spans.idle_under(rec, "repro.train.fetch")
