"""Mean device-idle time between one train-step program and the next, in ms.

Read from the device trace: the step program is the module that took most
of the device's time in the window; between consecutive runs of it, the
gap less any operation in it.  Averaged over the chips.  It holds the
runtime loop's host work between steps: the loss fetch, the batch
``device_put`` and the trainer's bookkeeping.
"""

from benchmarks.chip import trace as tr


def read(rec):
    if rec.trace is None:
        return None
    lo, hi = rec.window_ns
    per = []
    for dev in rec.trace.devices:
        step = tr.main_program(dev, lo, hi)
        idle = tr.idle_between_runs(tr.runs_within(dev, step, lo, hi), dev.busy()) if step else []
        if idle:
            per.append(sum(idle) / len(idle))
    return sum(per) / len(per) / 1e6 if per else None
