"""The engine's phase and lane-occupancy readers on synthetic contexts:
the phase map and the process registry stand in for what a run of the
program leaves behind."""

import pytest

from benchmark import harness

PHASES = ("rng", "pop", "handler", "push", "commit")

# "%shared" is in both the drive and its _init program, so the program's
# map leaves it out; "%while.3" and "%copy.9" are in no phase scope
PHASE_MAP = {"%fusion.1": "rng", "%fusion.2": "pop", "%fusion.3": "handler",
             "%reduce.4": "handler", "%fusion.5": "push",
             "%select.6": "commit", "%while.3": None, "%copy.9": None}
OPS = {"%fusion.1": 1.0, "%fusion.2": 2.0, "%fusion.3": 3.0,
       "%reduce.4": 0.5, "%fusion.5": 4.0, "%select.6": 5.0,
       "%while.3": 20.0, "%copy.9": 0.25, "%shared": 7.0,
       "%fusion.77": 9.0}
WANT = {"rng": 1.0, "pop": 2.0, "handler": 3.5, "push": 4.0, "commit": 5.0}


def _ctx(events=1000, ops=OPS):
    return {"trace": {"ops": dict(ops)}, "reports": [{"events_total": events}]}


@pytest.fixture
def phase_map(monkeypatch):
    from madsim_tpu.engine import core

    current = dict(PHASE_MAP)
    monkeypatch.setattr(core, "drive_phase_map", lambda: dict(current))
    return current


@pytest.mark.parametrize("phase", PHASES)
def test_phase_reader_sums_its_phase(phase, phase_map):
    read = harness._reader(f"engine.{phase}_ns_per_event")
    assert read(_ctx()) == pytest.approx(WANT[phase] * 1e9 / 1000)


@pytest.mark.parametrize("phase", PHASES)
def test_name_shared_with_init_counts_for_no_phase(phase, phase_map):
    read = harness._reader(f"engine.{phase}_ns_per_event")
    without = read(_ctx(ops={k: v for k, v in OPS.items() if k != "%shared"}))
    assert read(_ctx()) == without


@pytest.mark.parametrize("phase", PHASES)
def test_empty_map_or_no_events_reads_none(phase, phase_map):
    read = harness._reader(f"engine.{phase}_ns_per_event")
    assert read(_ctx(events=0)) is None
    phase_map.clear()
    assert read(_ctx()) is None


def test_phases_never_exceed_the_mapped_ops(phase_map):
    total = sum(harness._reader(f"engine.{p}_ns_per_event")(_ctx())
                for p in PHASES)
    assert total == pytest.approx(sum(WANT.values()) * 1e9 / 1000)


@pytest.fixture
def registry(monkeypatch):
    from madsim_tpu import obs

    reg = obs.Registry()
    monkeypatch.setattr(obs, "default_registry", lambda: reg)
    return reg


def test_lane_occupancy_reader(registry):
    read = harness._reader("engine.lane_occupancy")
    assert read({}) is None  # a program that feeds no counters
    registry.counter("engine_lane_steps_total").inc(0)
    registry.counter("engine_events_total").inc(0)
    assert read({}) is None  # zero lane-steps, zero events
    registry.counter("engine_lane_steps_total").inc(16384 * 612)
    registry.counter("engine_events_total").inc(16384 * 575)
    assert read({}) == pytest.approx(575 / 612)
