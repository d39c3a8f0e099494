"""raft.snapshot_ns_per_event: device time of the drive loop's ops in
the ``snapshot`` scope of raft's handler, in ns per event over the
traced summaries' ``events_total`` (``_phases.py``). The scope holds the
state machine's apply on every commit (each committed entry's step of
the digest), compaction, building InstallSnapshot and installing it.
None where the program maps no op to that scope."""

from benchmark.metrics._phases import ns_per_event, phase_map


def read(ctx):
    phases = phase_map()
    if phases is None or "snapshot" not in phases.values():
        return None
    return ns_per_event(ctx, "snapshot")
