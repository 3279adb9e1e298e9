"""Device time of the optimizer in a train step, in ms.

The self time of the operations of the step program's runs whose named-scope
path holds ``adamw`` (clipping by the global norm and the AdamW update), per
run, averaged over the chips (``spans.scope_ms``).
"""

from benchmarks.chip import spans


def read(rec):
    return spans.scope_ms(rec, ["adamw"])
