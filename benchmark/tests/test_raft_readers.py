"""The raft3snap readers on synthetic contexts: the phase map and the
summaries stand in for what a run of the program leaves behind, and a
program without log compaction reads nothing."""

import pytest

from benchmark import harness

OPS = {"%fusion.1": 2.0, "%fusion.2": 3.0, "%while.3": 20.0}


@pytest.fixture
def phase_map(monkeypatch):
    from madsim_tpu.engine import core

    current = {"%fusion.1": "snapshot", "%fusion.2": "handler", "%while.3": None}
    monkeypatch.setattr(core, "drive_phase_map", lambda: dict(current))
    return current


def test_snapshot_reader_sums_the_snapshot_phase(phase_map):
    read = harness._reader("raft.snapshot_ns_per_event")
    ctx = {"trace": {"ops": dict(OPS)}, "reports": [{"events_total": 1000}]}
    assert read(ctx) == pytest.approx(2.0 * 1e9 / 1000)
    phase_map["%fusion.1"] = "handler"  # a program with no snapshot scope
    assert read(ctx) is None


def test_installs_reader_reads_the_traced_summaries():
    read = harness._reader("raft.installs_per_seed")
    reports = [{"seeds": 100, "installs": 3000}, {"seeds": 100, "installs": 3200}]
    assert read({"reports": reports}) == pytest.approx(31.0)
    assert read({"reports": [{"seeds": 100}]}) is None  # no such counter
