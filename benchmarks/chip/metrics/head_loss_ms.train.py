"""Device time of the LM head and the loss in a train step, in ms.

The self time of the operations of the step program's runs whose named-scope
path holds ``head`` (final norm and unembedding) or ``loss`` (log-softmax
and cross-entropy), forward and backward, per run, averaged over the chips
(``spans.scope_ms``).
"""

from benchmarks.chip import spans


def read(rec):
    return spans.scope_ms(rec, ["head", "loss"])
