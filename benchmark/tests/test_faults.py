"""The timed path broken underneath, each way the cell can break: the
comparison has to come out not correct."""

import jax
import jax.numpy as jnp
import pytest

from helpers import rehearse

CELL = "raft5.sweep"


@pytest.fixture
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_step_returns_state_unchanged(monkeypatch, fresh_programs):
    from madsim_tpu.engine import core

    monkeypatch.setattr(core, "step_one", lambda wl, cfg, s: s)
    line = rehearse(CELL)
    assert not line["correct"]
    assert line["checks"]["seeds_differing"]["value"] > 0


def test_half_the_batch_left_out(monkeypatch, fresh_programs):
    """Each chunk sweeps only its first half of seeds, twice over."""
    from madsim_tpu.engine import core

    real = core.run_sweep

    def half(workload, cfg, seeds, params=None):
        h = seeds.shape[0] // 2
        return real(workload, cfg, jnp.concatenate([seeds[:h], seeds[:h]]), params)

    monkeypatch.setattr(core, "run_sweep", half)
    line = rehearse(CELL)
    assert not line["correct"]


def test_summary_drops_lanes(monkeypatch, fresh_programs):
    """The summary counts half of the batch."""
    from madsim_tpu.models import raft

    real = raft.sweep_summary
    monkeypatch.setattr(
        raft, "sweep_summary",
        lambda final: real(jax.tree.map(lambda a: a[: a.shape[0] // 2], final)),
    )
    line = rehearse(CELL)
    assert not line["correct"]
    assert line["checks"]["seeds_unaccounted"]["value"] > 0


def test_answer_altered_where_produced(monkeypatch, fresh_programs):
    """Every step's clock lands 1 ns late."""
    from madsim_tpu.engine import core

    real = core.step_one
    monkeypatch.setattr(
        core, "step_one",
        lambda wl, cfg, s: real(wl, cfg, s)._replace(now_ns=real(wl, cfg, s).now_ns + 1),
    )
    line = rehearse(CELL)
    assert not line["correct"]
