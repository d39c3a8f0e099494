"""engine.pop_ns_per_event: device time of the drive loop's ops in the
``pop`` phase of the step, the pop of the earliest event
(``queue.pop_min``), in ns per event (``_phases.py``)."""

from benchmark.metrics._phases import ns_per_event


def read(ctx):
    return ns_per_event(ctx, "pop")
