"""engine.lane_occupancy: events dispatched over lane-steps stepped by
the lockstep drive loop, a fraction: the share of its steps that did
work, where a chunk runs until its slowest lane is done. Read from the
process registry's ``engine_events_total`` and
``engine_lane_steps_total`` (fed by ``engine.core.run_drive``), which
count every drive of the run, warm-up batch included."""


def read(ctx):
    from madsim_tpu import obs

    reg = obs.default_registry()
    steps = reg.get("engine_lane_steps_total")
    if not steps:
        return None
    return reg.get("engine_events_total") / steps
