"""Device-idle time between train steps while the runtime loop dispatches
the next step, in ms per step.

The idle time between consecutive runs of the step program that the loop's
``repro.train.dispatch`` span covers on the host: the step call, from its
arguments' flattening to the launch, until it returns.  Averaged over the
chips (``spans.idle_under``).
"""

from benchmarks.chip import spans


def read(rec):
    return spans.idle_under(rec, "repro.train.dispatch")
