"""Raft with log compaction and InstallSnapshot, and the command/crash
rounds that drive it (``RaftConfig.snapshot_every``, ``rounds``; the
benchmark's ``raft3snap``), at a tiny size: a 16-entry window, a
snapshot every 4 applied entries, 4 rounds.

The device sweep must equal the plain reference
(``benchmark/reference/raft_snap.py``) on every compared field, replay
bit-exactly on the CPU, and stream to `run_sweep_chunked`'s summary; the
state-machine checker must latch on a planted fault, and a node that
loses its durable state must diverge from the reference."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu import replay
from madsim_tpu.engine import core
from madsim_tpu.engine.checkpoint import run_sweep_pipelined
from madsim_tpu.engine.stream import stream_sweep
from madsim_tpu.models import raft

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import raft_snap  # noqa: E402
from benchmark.reference.engine import run_seed  # noqa: E402

ROUNDS = 4
PARAMS = dict(
    num_nodes=3, election_lo_ns=150_000_000, election_hi_ns=300_000_000,
    heartbeat_ns=50_000_000, commands=1, cmd_window_ns=1_000_000_000,
    cmd_retry_ns=50_000_000, cmd_max_retries=64, log_cap=16, crashes=0,
    crash_window_ns=5_000_000_000, restart_lo_ns=300_000_000,
    restart_hi_ns=800_000_000, loss_q32=429_496_730, lat_lo_ns=1_000_000,
    lat_hi_ns=27_000_000, buggify_q32=0, history=16, volatile_state=False,
    hist_slots=0, event_mix=False, snapshot_every=4, rounds=ROUNDS,
)
ENGINE = dict(queue_capacity=40, time_limit_ns=(ROUNDS + 2) * 1_000_000_000,
              max_steps=20_000, jitter_lo_ns=50, jitter_hi_ns=100)
CFG = raft.RaftConfig(**PARAMS)
ECFG = raft.engine_config(CFG, **ENGINE)
SEEDS = np.arange(64, dtype=np.int64) + (2_400_000_017 << 24)


def _leaf(final, path):
    for p in path.split("."):
        final = getattr(final, p)
    return np.asarray(final)


def _differing(final, params, seeds):
    """Seeds whose compared fields differ from the reference's."""
    out = []
    for j, seed in enumerate(seeds):
        ref = run_seed(raft_snap.Model(params), int(seed), ENGINE)
        if not all(
            np.array_equal(np.asarray(ref[f], np.int64),
                           _leaf(final, f)[j].astype(np.int64))
            for f in raft_snap.FIELDS
        ):
            out.append(int(seed))
    return out


@pytest.fixture(scope="module")
def final():
    return core.run_sweep(raft.workload(CFG), ECFG, jnp.asarray(SEEDS))


def test_device_matches_reference_on_every_field(final):
    summary = raft.sweep_summary(final)
    assert summary["snapshots"] > 0 and summary["installs"] > 0
    assert summary["installs_sent"] >= summary["installs"]
    assert summary["violations"] == 0 and summary["overflow_seeds"] == 0
    assert _differing(final, PARAMS, SEEDS) == []


def test_overflowing_seeds_match_the_reference():
    """A 3-entry window overflows in most seeds: the seed latches
    ``log_overflow``, the summary counts it, and the device still equals
    the reference."""
    params = dict(PARAMS, log_cap=3, snapshot_every=2)
    cfg = raft.RaftConfig(**params)
    seeds = SEEDS[:16]
    final = core.run_sweep(raft.workload(cfg), raft.engine_config(cfg, **ENGINE),
                           jnp.asarray(seeds))
    overflowed = np.asarray(final.wstate.log_overflow)
    assert overflowed.sum() >= 8
    assert raft.sweep_summary(final)["log_overflow_seeds"] == overflowed.sum()
    assert _differing(final, params, seeds) == []


def _leader_with_log(cfg, length):
    """Node 0 leads term 1 with ``length`` entries of term 1; nodes 1 and
    2 follow, node 1 matched through index 2 (next index 3)."""
    w, _ = raft._init(cfg, jax.random.key(0))
    n = cfg.num_nodes
    log = (jnp.arange(cfg.log_cap) <= length).astype(jnp.int32).at[0].set(0)
    return w._replace(
        role=jnp.array([raft.LEADER] + [raft.FOLLOWER] * (n - 1), jnp.int32),
        term=jnp.ones((n,), jnp.int32),
        log_term=w.log_term.at[0].set(log),
        log_len=w.log_len.at[0].set(length),
        next_idx=w.next_idx.at[0].set(jnp.array([length + 1, 3, length + 1], jnp.int32)),
        match_idx=w.match_idx.at[0].set(jnp.array([0, 2, 0], jnp.int32)),
    )


def test_a_reply_that_moves_a_follower_sends_its_next_entry():
    """Replication is pipelined: the leader answers an append reply that
    moved the follower's next index with the follower's next append, in
    the reply's slot; a duplicate reply, and a reply from a follower that
    has caught up, send nothing."""
    cfg = CFG._replace(loss_q32=0)
    n = cfg.num_nodes
    rand = jnp.full((2 * n + 3,), 12345, jnp.uint32)

    def reply(w, ok, match):
        pay = raft._pay(cfg, 0, raft.M_APPEND_RSP, 1, 1, ok, match)
        w2, emits = raft._on_msg(cfg, w, jnp.int64(10**9), pay, rand)
        sent = bool(emits.enables[n]) and int(emits.kinds[n]) == raft.K_MSG
        return w2, sent, np.asarray(emits.pays[n])

    w = _leader_with_log(cfg, 6)
    w2, sent, pay = reply(w, 1, 3)  # node 1 took index 3: send it index 4
    assert sent and pay[0] == 1 and pay[1] == raft.M_APPEND and pay[4] == 3
    assert int(w2.msgs_sent) == int(w.msgs_sent) + 1
    _, sent, _ = reply(w2, 1, 3)  # the same reply again moves nothing
    assert not sent
    _, sent, pay = reply(w, 0, 1)  # a rejection backs up to index 2
    assert sent and pay[1] == raft.M_APPEND and pay[4] == 1
    _, sent, _ = reply(w, 1, 6)  # caught up: nothing to send
    assert not sent


def test_traced_replay_is_bit_exact(final):
    seed = int(SEEDS[np.argmax(np.asarray(final.wstate.snap.installs))])
    parity = core.cpu_parity(raft.workload(CFG), ECFG, [seed, seed + 1])
    assert parity["leaves_equal"] and parity["traced_replay_equal"]
    traced, _ = core.run_traced(raft.workload(CFG), ECFG, seed)
    assert int(traced.wstate.snap.installs) > 0


def test_rounds_crash_one_server_at_a_time():
    _, trace = core.run_traced(raft.workload(CFG), ECFG, int(SEEDS[0]))
    plan = replay.extract_fault_schedule(trace, raft.K_FAULT)
    assert [a for _, a, _ in plan] == ["crash", "restart"] * ROUNDS
    for r, ((t0, _, v0), (t1, _, v1)) in enumerate(zip(plan[::2], plan[1::2])):
        assert v0 == v1 and 300_000_000 <= t1 - t0 < 800_000_000
        assert (r + 1) * 1_000_000_000 <= t0 < t1 < (r + 2) * 1_000_000_000


def test_stream_sweep_agrees_with_run_sweep_chunked():
    wl, seeds = raft.workload(CFG), jnp.asarray(SEEDS[:24])
    summary = raft.sweep_summary(core.run_sweep_chunked(wl, ECFG, seeds, chunk_size=8))
    assert stream_sweep(wl, ECFG, seeds, raft.sweep_summary, chunk_size=8,
                        pool_size=8, round_steps=128) == summary
    assert run_sweep_pipelined(wl, ECFG, seeds, raft.sweep_summary,
                               chunk_size=8) == summary


def test_state_machine_checker_latches_a_stale_install(final):
    base = raft.workload(CFG)

    def handle(w, now, kind, pay, rand):
        w2, emits = base.handle(w, now, kind, pay, rand)
        # the planted fault: an install keeps the follower's stale digest
        stale = w2.snap.installs > w.snap.installs
        digest = jnp.where(stale, w.snap.digest, w2.snap.digest)
        return w2._replace(snap=w2.snap._replace(digest=digest)), emits

    bad = core.run_sweep(base._replace(handle=handle), ECFG, jnp.asarray(SEEDS))
    kinds = np.asarray(bad.wstate.viol_kind)
    assert np.any(kinds & raft.V_APPLY)
    assert not np.any(np.asarray(final.wstate.viol_kind) & raft.V_APPLY)


def test_volatile_state_diverges_from_the_reference():
    cfg = CFG._replace(volatile_state=True)
    seeds = SEEDS[:16]
    amnesia = core.run_sweep(raft.workload(cfg), raft.engine_config(cfg, **ENGINE),
                             jnp.asarray(seeds))
    assert len(_differing(amnesia, PARAMS, seeds)) > 0
    with pytest.raises(ValueError):
        raft_snap.Model(dict(PARAMS, volatile_state=True))


def test_opt_in_fields_show_in_repr_only_when_set():
    assert "snapshot_every" not in repr(raft.RaftConfig())
    assert "rounds" not in repr(raft.RaftConfig())
    assert "snapshot_every=4, rounds=4)" in repr(CFG)
