"""Device time of routing tokens to the held experts and back in a train
step, in ms.

The self time of the operations of the step program's runs whose named-scope
path holds ``moe_router`` (router logits, softmax, top-k, renormalisation,
balance loss), ``moe_dispatch`` (sorting the (token, expert) pairs and
gathering their rows) or ``moe_combine`` (the weighted gather back to the
tokens), forward, recomputed and backward, per run, averaged over the chips
(``spans.scope_ms``).
"""

from benchmarks.chip import spans


def read(rec):
    return spans.scope_ms(rec, ["moe_router", "moe_dispatch", "moe_combine"])
