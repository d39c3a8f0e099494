"""The trace reduction on a small trace recorded on a v5e
(``record_trace.py``): one bench.batch span, two programs, a 50 ms host
pause under bench.summary."""

import os

import pytest

from benchmark import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_file(DATA, 1)


def test_window_and_busy(reduced):
    assert reduced["window_s"] > 0.05
    assert 0 < reduced["busy_s"] < reduced["window_s"] - 0.04
    assert reduced["busy_per_chip"] == [reduced["busy_s"]]


def test_programs_by_module_name(reduced):
    assert {"jit__drive", "jit__screen"} <= set(reduced["modules"])
    assert reduced["modules"]["jit__drive"] > reduced["modules"]["jit__screen"]
    assert sum(reduced["modules"].values()) <= reduced["window_s"]


def test_idle_gap_goes_to_the_host_span(reduced):
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert gaps["bench.summary"] >= 0.045
    assert len(reduced["breakdown"]["device_ops"]) <= 10


def test_union_clips_and_merges():
    total, gaps = tr._union([(0, 10), (5, 20), (30, 40)], 2, 35)
    assert total == 18 + 5
    assert gaps == [(20, 30)]


def test_no_device_plane_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.reduce(str(tmp_path), 1)


def test_engine_ns_per_event_reader(reduced):
    from benchmark import harness

    read = harness._reader("engine.device_ns_per_event")
    ctx = {"trace": reduced, "reports": [{"events_total": 1000}]}
    assert read(ctx) == reduced["modules"]["jit__drive"] * 1e9 / 1000
    assert read(dict(ctx, reports=[{"events_total": 0}])) is None
    assert read(dict(ctx, trace={"modules": {"jit__screen": 1.0}})) is None


def test_idle_share_reader(reduced):
    from benchmark import harness

    read = harness._reader("device.idle_share")
    assert 0.04 / reduced["window_s"] < read({"trace": reduced}) < 1
