"""Exposed cross-pod exchange of a train step, in ms.

The device time of the step program's runs in which an operation under the
``sync`` scope (each ``sync_*`` of ``distributed/sync.py``) or ``wan_int8``
(the int8 compression of ``distributed/compression.py``) runs and no other
operation of the step does, per run, averaged over the chips
(``spans.exposed_ms``): the part of the exchange that no compute hides.
"""

from benchmarks.chip import spans


def read(rec):
    return spans.exposed_ms(rec, ["sync", "wan_int8"])
