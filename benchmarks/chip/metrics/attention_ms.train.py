"""Device time of attention in a train step, in ms.

The self time of the operations of the step program's runs whose named-scope
path holds ``attention`` (norm1, the projections, the attention scan and
their gradients), per run, averaged over the chips (``spans.scope_ms``).
"""

from benchmarks.chip import spans


def read(rec):
    return spans.scope_ms(rec, ["attention"])
