"""engine.commit_ns_per_event: device time of the drive loop's ops in the
``commit`` phase of the step, the clock and jitter, the cover, history
and event-mix planes, and the select tree with the state's assembly, in
ns per event (``_phases.py``)."""

from benchmark.metrics._phases import ns_per_event


def read(ctx):
    return ns_per_event(ctx, "commit")
