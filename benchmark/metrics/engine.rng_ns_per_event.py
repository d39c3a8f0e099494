"""engine.rng_ns_per_event: device time of the drive loop's ops in the
``rng`` phase of the step, the event's counter-based draws
(``rng.event_bits``), in ns per event (``_phases.py``)."""

from benchmark.metrics._phases import ns_per_event


def read(ctx):
    return ns_per_event(ctx, "rng")
