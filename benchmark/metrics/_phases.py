"""Device time of one step phase per event, shared by the
``engine.<phase>_ns_per_event`` readers.

The phase of each XLA op comes from the program:
``engine.core.drive_phase_map()`` reads the ``jax.named_scope`` of the
step's phases (rng, pop, handler, push, commit) back out of every drive
program the run dispatched, naming each instruction as the trace's "XLA
Ops" line does. A name the drive shares with its ``_init`` program is
not in the map, so it counts for no phase. The ops' device seconds are
summed from the trace over the traced batches and divided by their
summaries' ``events_total``, the denominator of
``engine.device_ns_per_event``."""


def phase_map():
    """The program's map, or None where the program has none."""
    try:
        from madsim_tpu.engine.core import drive_phase_map
    except ImportError:
        return None
    return drive_phase_map() or None


def ns_per_event(ctx, phase: str):
    phases = phase_map()
    events = sum(r["events_total"] for r in ctx["reports"])
    if phases is None or events <= 0:
        return None
    t = sum(v for k, v in ctx["trace"]["ops"].items() if phases.get(k) == phase)
    return t * 1e9 / events
