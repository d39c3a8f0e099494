"""engine.handler_ns_per_event: device time of the drive loop's ops in the
``handler`` phase of the step, the workload's handler
(``Workload.handle``), in ns per event (``_phases.py``)."""

from benchmark.metrics._phases import ns_per_event


def read(ctx):
    return ns_per_event(ctx, "handler")
