"""Device time of the held experts' grouped matmuls in a train step, in ms.

The self time of the operations of the step program's runs whose named-scope
path holds ``moe_experts`` (the gate, up and down matmuls over the held
experts and the activation between them, forward, recomputed and backward),
per run, averaged over the chips (``spans.scope_ms``).
"""

from benchmarks.chip import spans


def read(rec):
    return spans.scope_ms(rec, ["moe_experts"])
