"""engine.push_ns_per_event: device time of the drive loop's ops in the
``push`` phase of the step, the push of the emitted events
(``queue.push_many``), in ns per event (``_phases.py``)."""

from benchmark.metrics._phases import ns_per_event


def read(ctx):
    return ns_per_event(ctx, "push")
