"""engine.device_ns_per_event: device time of the sweep programs (the
engine's init and drive loop, by the XLA module names of
``engine.core._init`` and ``_drive`` on one chip) divided by the events
the traced batches dispatched (the summaries' ``events_total``), in
ns/event."""

PROGRAMS = ("jit__init", "jit__drive")


def read(ctx):
    t = sum(v for k, v in ctx["trace"]["modules"].items() if k in PROGRAMS)
    events = sum(r["events_total"] for r in ctx["reports"])
    if t <= 0 or events <= 0:
        return None
    return t * 1e9 / events
