"""Device-idle time between train steps while the runtime loop feeds the
next batch, in ms per step.

The idle time between consecutive runs of the step program (as
``step_gap_ms.train`` finds it) that the loop's ``repro.train.feed`` span
covers on the host: the loader and the batch ``device_put``.  Averaged over
the chips (``spans.idle_under``).
"""

from benchmarks.chip import spans


def read(rec):
    return spans.idle_under(rec, "repro.train.feed")
