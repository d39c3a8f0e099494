"""TPU engine: queue ops, determinism, raft sweep behavior, CPU parity.

The determinism contract under test is SURVEY.md §7's invariant: one seed =
one bit-exact execution, independent of batch size or batch position —
the property that lets a TPU sweep find a failure and a CPU replay
reproduce it byte-identically.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu.engine import core as ecore
from madsim_tpu.engine import net as enet
from madsim_tpu.engine import queue as equeue
from madsim_tpu.engine.core import EngineConfig
from madsim_tpu.engine.rng import bounded, coin, event_bits, prob_to_q32, seed_key
from madsim_tpu.models import raft


# -- queue -----------------------------------------------------------------


def _push_one(q, time, kind, pay, enable=True):
    """``push_many`` with a single emit."""
    return equeue.push_many(
        q,
        jnp.array([time], jnp.int64),
        jnp.array([kind], jnp.int32),
        jnp.array([pay], jnp.int32),
        jnp.array([enable]),
    )


def test_queue_push_pop_min_order():
    q = equeue.make(8, 2)
    for t in [50, 10, 30]:
        q, ov = _push_one(q, t, t, [t, 0])
        assert not bool(ov)
    times = []
    for _ in range(4):
        q, t, kind, pay, found = equeue.pop_min(q)
        if bool(found):
            times.append(int(t))
            assert int(kind) == int(t)
    assert times == [10, 30, 50]
    assert int(equeue.size(q)) == 0


def test_queue_overflow_flag():
    q = equeue.make(2, 1)
    for i in range(3):
        q, ov = _push_one(q, i, i, [i])
    assert bool(ov)


def test_queue_disabled_push_is_noop():
    q = equeue.make(2, 1)
    q, ov = _push_one(q, 1, 1, [1], enable=False)
    assert not bool(ov)
    assert int(equeue.size(q)) == 0


def _first_free_push(time, kind, pay, times, kinds, pays, enables):
    """One lane of ``push_many`` as a plain loop: emit ``e`` goes to the
    e-th free slot whether or not earlier emits are enabled (the rule of
    ``benchmark/reference/engine.py``'s ``push``)."""
    time, kind, pay = list(time), list(kind), [list(p) for p in pay]
    free = [i for i, t in enumerate(time) if t == equeue.INVALID_TIME]
    overflow = False
    for e, on in enumerate(enables):
        if not on:
            continue
        if e >= len(free):
            overflow = True
            continue
        s = free[e]
        time[s], kind[s], pay[s] = times[e], kinds[e], list(pays[e])
    return time, kind, pay, overflow


def _planes(pay):
    """A stacked ``[..., Q, P]`` payload as the queue carries it: one
    ``[..., Q]`` plane per payload word."""
    return tuple(jnp.asarray(pay[..., p]) for p in range(pay.shape[-1]))


def _stacked(planes):
    """The queue's payload planes stacked back to ``[..., Q, P]``."""
    assert len({np.shape(p) for p in planes}) == 1
    return np.stack([np.asarray(p) for p in planes], axis=-1)


_PUSH_CASES = [(c, e) for c in (7, 60, 64, 129, 256) for e in ("wide", 1, 7, 15, "off")]


@pytest.mark.parametrize("slots", [3, 8], ids=["P3", "P8"])
@pytest.mark.parametrize(
    "capacity,emits",
    _PUSH_CASES,
    ids=[str(c) if e == "wide" else f"{c}-e{e}" for c, e in _PUSH_CASES],
)
def test_push_many_matches_first_free_loop(capacity, emits, slots):
    """``push_many`` under vmap against the plain first-free loop: same
    planes, same overflow flag, on random free masks (lane 0 full, lane 1
    empty) and random enables (lane 2 all on). E is one emit, raft's step
    (7) and init (15) widths, or "wide", ``capacity // 4 + 3``, reaching
    past the free count; "off" is the wide E with every emit disabled.
    P is 3 or raft's 8 payload slots."""
    n_emit = emits if isinstance(emits, int) else capacity // 4 + 3
    lanes = 64
    rng = np.random.default_rng((capacity, n_emit, slots))
    occupied = rng.random((lanes, capacity)) < rng.random((lanes, 1))
    occupied[0], occupied[1] = True, False
    time = np.where(occupied, rng.integers(0, 1 << 40, (lanes, capacity)), equeue.INVALID_TIME)
    kind = rng.integers(-99, 99, (lanes, capacity)).astype(np.int32)
    pay = rng.integers(-99, 99, (lanes, capacity, slots)).astype(np.int32)
    times = rng.integers(0, 1 << 40, (lanes, n_emit))
    kinds = rng.integers(-99, 99, (lanes, n_emit)).astype(np.int32)
    pays = rng.integers(-99, 99, (lanes, n_emit, slots)).astype(np.int32)
    enables = rng.random((lanes, n_emit)) < 0.7
    enables[0, 0] = enables[2] = True
    if emits == "off":
        enables[:] = False

    q = equeue.EventQueue(jnp.asarray(time), jnp.asarray(kind), _planes(pay))
    got, overflow = jax.jit(jax.vmap(equeue.push_many))(
        q, jnp.asarray(times), jnp.asarray(kinds), jnp.asarray(pays), jnp.asarray(enables)
    )
    assert len(got.pay) == slots and got.pay[0].shape == (lanes, capacity)
    got_time, got_kind, overflow = map(np.asarray, (got.time, got.kind, overflow))
    got_pay = _stacked(got.pay)
    for i in range(lanes):
        want = _first_free_push(time[i], kind[i], pay[i], times[i], kinds[i], pays[i], enables[i])
        assert got_time[i].tolist() == want[0], i
        assert got_kind[i].tolist() == want[1], i
        assert got_pay[i].tolist() == want[2], i
        assert bool(overflow[i]) == want[3], i
    # the cases at both ends were drawn: a full lane, an empty one
    assert overflow[0] == (emits != "off")
    assert overflow[1] == enables[1, capacity:].any()


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)
                elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    yield from _eqns(sub.jaxpr)


def _push_many_jaxpr(capacity=60, n_emit=7, slots=8, lanes=16):
    q = equeue.make(capacity, slots)
    emits = (
        jnp.zeros((lanes, n_emit), jnp.int64),
        jnp.zeros((lanes, n_emit), jnp.int32),
        jnp.zeros((lanes, n_emit, slots), jnp.int32),
        jnp.ones((lanes, n_emit), bool),
    )
    planes = jax.tree.map(lambda a: jnp.broadcast_to(a, (lanes, *a.shape)), q)
    return jax.make_jaxpr(jax.vmap(equeue.push_many))(planes, *emits).jaxpr


def test_push_many_rank_has_no_cumsum():
    """The free-slot rank is a matmul: a ``cumsum`` lowers on TPU to a
    whole-axis reduce_window, an O(Q²) sum on the vector unit."""
    names = {eqn.primitive.name for eqn in _eqns(_push_many_jaxpr())}
    assert "dot_general" in names
    assert not {n for n in names if n.startswith("cum") or "reduce_window" in n}, names


def test_push_many_picks_values_without_slot_by_emit_plane():
    """Each slot takes at most one emit, so the values are written with one
    masked select per emit: no array carries both the slot (Q = 60) and
    emit (E = 7) axes — the one-hot pick and its layout copy — and no
    int64 sum over E, which the TPU emulates with carries."""
    eqns = list(_eqns(_push_many_jaxpr(capacity=60, n_emit=7)))
    both = [
        e for e in eqns for v in e.outvars
        if {60, 7} <= set(getattr(v.aval, "shape", ()))
    ]
    assert not both, both
    sums = [
        e for e in eqns
        if e.primitive.name == "reduce_sum" and e.invars[0].aval.dtype == jnp.int64
    ]
    assert not sums, sums


def _murmur_prio(slot, tie):
    """``pop_min``'s per-slot tie-break priority in plain Python ints."""
    m = 0xFFFFFFFF
    x = ((slot * 2654435761) & m) ^ tie
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & m
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & m
    return x ^ (x >> 16)


def _pop_min_loop(time, kind, pay, tie, enable):
    """One lane of ``pop_min`` as a plain loop: the minimum time, the
    candidate holding it with the least murmur priority, and the slot
    vacated only if found and enabled. An empty queue reports the
    sentinel time and kind 0, and the payload row of the slot the
    tie-break picks among all slots."""
    time = [int(t) for t in time]
    t = min(time)
    found = t != equeue.INVALID_TIME
    slot = min((i for i, x in enumerate(time) if x == t), key=lambda i: _murmur_prio(i, tie))
    if found and enable:
        time[slot] = equeue.INVALID_TIME
    return time, t, int(kind[slot]) if found else 0, [int(p) for p in pay[slot]], found


_POP_PATTERNS = ("random", "ties", "empty")


@pytest.mark.parametrize("pattern", _POP_PATTERNS)
@pytest.mark.parametrize("capacity", [7, 60, 64, 129, 256])
def test_pop_min_matches_plain_reference(capacity, pattern):
    """``pop_min`` under vmap against the plain loop, bit for bit: the
    popped time, kind, payload and ``found``, and the queue afterwards —
    the slot vacated only where ``enable``, every plane untouched where
    not. "random" draws times from a wide range, "ties" from two values
    so most lanes hold several slots at the minimum, "empty" holds none.
    Lane 0 is full, lane 1 holds one event, and the tie draws include
    0 and 0xFFFFFFFF."""
    lanes, slots = 64, 8
    rng = np.random.default_rng((capacity, _POP_PATTERNS.index(pattern)))
    occupied = rng.random((lanes, capacity)) < rng.random((lanes, 1))
    occupied[0], occupied[1] = True, np.arange(capacity) == capacity // 2
    if pattern == "empty":
        occupied[:] = False
    values = rng.integers(0, 1 << 40, (lanes, capacity))
    if pattern == "ties":
        values = rng.choice(np.array([1 << 33, (1 << 33) + 1]), (lanes, capacity))
    time = np.where(occupied, values, equeue.INVALID_TIME)
    kind = rng.integers(-99, 99, (lanes, capacity)).astype(np.int32)
    pay = rng.integers(-99, 99, (lanes, capacity, slots)).astype(np.int32)
    tie = rng.integers(0, 1 << 32, lanes, dtype=np.uint32)
    tie[:4] = [0, 0xFFFFFFFF, 0, 0xFFFFFFFF]
    enable = rng.random(lanes) < 0.7
    enable[:2] = True

    q = equeue.EventQueue(jnp.asarray(time), jnp.asarray(kind), _planes(pay))
    got = jax.jit(jax.vmap(equeue.pop_min))(q, jnp.asarray(enable), jnp.asarray(tie))
    got_q, got_t, got_kind, got_pay, got_found = jax.tree.map(np.asarray, got)
    assert (got_q.kind == kind).all() and (_stacked(got_q.pay) == pay).all()
    assert got_pay.shape == (lanes, slots)
    for i in range(lanes):
        want = _pop_min_loop(time[i], kind[i], pay[i], int(tie[i]), bool(enable[i]))
        assert got_q.time[i].tolist() == want[0], i
        assert int(got_t[i]) == want[1], i
        assert int(got_kind[i]) == want[2], i
        assert got_pay[i].tolist() == want[3], i
        assert bool(got_found[i]) == want[4], i
    assert got_found[:2].all() == (pattern != "empty")


# -- rng -------------------------------------------------------------------


def test_event_bits_counter_based():
    k = seed_key(jnp.int64(42))
    a = event_bits(k, jnp.int32(7), 4)
    b = event_bits(k, jnp.int32(7), 4)
    c = event_bits(k, jnp.int32(8), 4)
    assert jnp.array_equal(a, b)
    assert not jnp.array_equal(a, c)


def test_bounded_range():
    k = seed_key(jnp.int64(1))
    draws = event_bits(k, jnp.int32(0), 256)
    vals = bounded(draws, 10, 20)
    assert int(vals.min()) >= 10 and int(vals.max()) < 20


def test_bounded_wide_spans_do_not_sign_wrap():
    """spans above 2**31 (5 s fault windows, day-scale spans) used to
    overflow the int64 product and wrap times negative; the half-width
    multiply must stay in range AND match exact integer arithmetic."""
    k = seed_key(jnp.int64(2))
    draws = event_bits(k, jnp.int32(0), 256)
    for lo, hi in ((0, 5_000_000_000), (0, 1 << 47), (-3, 4_000_000_000)):
        vals = bounded(draws, lo, hi)
        assert int(vals.min()) >= lo and int(vals.max()) < hi
        expect = [lo + ((int(d) * (hi - lo)) >> 32) for d in draws]
        assert [int(v) for v in vals] == expect


def test_coin_fixed_point():
    assert not bool(coin(jnp.uint32(0xFFFFFFFF), jnp.uint32(prob_to_q32(0.5))))
    assert bool(coin(jnp.uint32(0), jnp.uint32(prob_to_q32(0.001))))


# -- net model -------------------------------------------------------------


def test_route_latency_within_bounds():
    links = enet.make(3, loss_q32=0, lat_lo_ns=100, lat_hi_ns=200)
    k = seed_key(jnp.int64(5))
    u = event_bits(k, jnp.int32(0), 2)
    t, deliver = enet.route(links, jnp.int64(1000), jnp.int32(0), jnp.int32(1), u[0], u[1])
    assert bool(deliver)
    assert 1100 <= int(t) <= 1200


def test_clog_drops_messages():
    links = enet.make(3)
    links = enet.clog_link(links, jnp.int32(0), jnp.int32(1))
    k = seed_key(jnp.int64(5))
    u = event_bits(k, jnp.int32(0), 2)
    _, deliver = enet.route(links, jnp.int64(0), jnp.int32(0), jnp.int32(1), u[0], u[1])
    assert not bool(deliver)
    # reverse direction unaffected
    _, deliver_rev = enet.route(links, jnp.int64(0), jnp.int32(1), jnp.int32(0), u[0], u[1])
    assert bool(deliver_rev)
    links = enet.unclog_link(links, jnp.int32(0), jnp.int32(1))
    _, deliver2 = enet.route(links, jnp.int64(0), jnp.int32(0), jnp.int32(1), u[0], u[1])
    assert bool(deliver2)


def test_clog_node_blocks_both_directions():
    links = enet.clog_node(enet.make(4), jnp.int32(2))
    assert bool(links.clog[2, 0]) and bool(links.clog[0, 2])
    assert not bool(links.clog[0, 1])
    links = enet.unclog_node(links, jnp.int32(2))
    assert not bool(links.clog.any())


# -- raft sweep ------------------------------------------------------------


SMALL = raft.RaftConfig(crashes=1, loss_q32=prob_to_q32(0.01))
ECFG = raft.engine_config(SMALL, time_limit_ns=3_000_000_000, max_steps=20_000)


@pytest.fixture(scope="module")
def raft_final():
    wl = raft.workload(SMALL)
    seeds = jnp.arange(32, dtype=jnp.int64)
    return ecore.run_sweep(wl, ECFG, seeds)


def test_raft_sweep_elects_leaders(raft_final):
    s = raft.sweep_summary(raft_final)
    assert s["seeds"] == 32
    assert s["overflow_seeds"] == 0
    assert s["violations"] == 0
    # within 3 virtual seconds nearly every 150-300ms-timeout cluster elects
    assert s["no_leader_seeds"] == 0
    assert s["events_total"] > 32 * 50
    # sent counts attempts, delivered counts link-test passes
    assert s["msgs_sent"] >= s["msgs_delivered"] > 0


def test_workload_memoized_per_config():
    """Equal configs must yield the SAME Workload object: _drive's jit
    cache keys on the Workload's partials by identity, so an equal-but-
    distinct Workload silently recompiles the whole sweep (~16 s)."""
    from madsim_tpu.models import etcd, kafka, s3

    assert raft.workload(SMALL) is raft.workload(
        raft.RaftConfig(**SMALL._asdict())
    )
    for mod, cfg_cls in (
        (kafka, kafka.KafkaConfig),
        (etcd, etcd.EtcdConfig),
        (s3, s3.S3Config),
    ):
        assert mod.workload(cfg_cls()) is mod.workload(cfg_cls())
        # default-arg call normalizes to the same cache key
        assert mod.workload() is mod.workload(cfg_cls())
    # a different config still gets its own workload
    assert raft.workload(SMALL) is not raft.workload(
        raft.RaftConfig(**{**SMALL._asdict(), "crashes": SMALL.crashes + 1})
    )


def test_raft_all_seeds_terminate(raft_final):
    assert bool(jnp.all(raft_final.done))
    # terminated by time limit, not queue starvation: clock near the limit
    assert int(raft_final.now_ns.min()) > ECFG.time_limit_ns // 2


def test_raft_seeds_diverge(raft_final):
    # different seeds must explore different schedules (ref: 10 seeds ⇒ 10
    # distinct interleavings, task/mod.rs:964-988)
    assert len(np.unique(np.asarray(raft_final.ctr))) > 8
    assert len(np.unique(np.asarray(raft_final.wstate.elections))) > 1


def test_raft_same_seed_bit_exact(raft_final):
    wl = raft.workload(SMALL)
    again = ecore.run_sweep(wl, ECFG, jnp.arange(32, dtype=jnp.int64))
    for a, b in zip(jax.tree.leaves(raft_final), jax.tree.leaves(again)):
        if jnp.issubdtype(a.dtype, jnp.integer) or a.dtype == bool:
            assert jnp.array_equal(a, b)


def test_raft_batch_position_invariant():
    """Seed 7's outcome is identical whether run in a batch of 32 or alone —
    the property that makes CPU replay of a TPU-found failure valid."""
    wl = raft.workload(SMALL)
    batch = ecore.run_sweep(wl, ECFG, jnp.arange(32, dtype=jnp.int64))
    solo = ecore.run_sweep(wl, ECFG, jnp.array([7], dtype=jnp.int64))
    assert int(batch.ctr[7]) == int(solo.ctr[0])
    assert int(batch.now_ns[7]) == int(solo.now_ns[0])
    assert int(batch.wstate.elections[7]) == int(solo.wstate.elections[0])
    assert int(batch.wstate.msgs_delivered[7]) == int(solo.wstate.msgs_delivered[0])


def test_raft_traced_replay_matches_sweep():
    wl = raft.workload(SMALL)
    sweep = ecore.run_sweep(wl, ECFG, jnp.array([3], dtype=jnp.int64))
    final, trace = ecore.run_traced(wl, ECFG, 3)
    assert int(final.ctr) == int(sweep.ctr[0])
    assert int(final.now_ns) == int(sweep.now_ns[0])
    fired = np.asarray(trace["fired"])
    assert fired.sum() == int(final.ctr)
    # trace times are monotonically non-decreasing over fired events
    t = np.asarray(trace["time_ns"])[fired]
    assert (np.diff(t) >= 0).all()


def test_raft_crash_restart_in_plan():
    # with an aggressive fault plan the sweep still holds election safety
    cfg = raft.RaftConfig(crashes=4, crash_window_ns=2_000_000_000)
    wl = raft.workload(cfg)
    final = ecore.run_sweep(
        wl, raft.engine_config(cfg, time_limit_ns=3_000_000_000), jnp.arange(16, dtype=jnp.int64)
    )
    s = raft.sweep_summary(final)
    assert s["violations"] == 0
    assert s["overflow_seeds"] == 0


def test_raft_log_replication_commits():
    """With client commands in the plan, entries get replicated and
    committed on a majority, and the log-matching checker stays quiet."""
    cfg = raft.RaftConfig(num_nodes=3, crashes=1, commands=6,
                          cmd_window_ns=2_000_000_000)
    wl = raft.workload(cfg)
    final = ecore.run_sweep(
        wl,
        raft.engine_config(cfg, time_limit_ns=4_000_000_000, max_steps=40_000),
        jnp.arange(16, dtype=jnp.int64),
    )
    s = raft.sweep_summary(final)
    assert s["violations"] == 0
    assert s["overflow_seeds"] == 0
    assert s["log_overflow_seeds"] == 0
    # nearly all commands find a leader within 4 virtual seconds, and
    # committed entries replicate
    assert s["accepted_cmds"] >= 16 * 4
    assert s["commits_total"] >= s["accepted_cmds"]  # leader + follower commits
    w = final.wstate
    # every seed: all alive nodes' committed prefixes agree with the
    # recorded commit history (end-state cross-check of the online checker)
    import numpy as np

    log_term = np.asarray(w.log_term)
    commit = np.asarray(w.commit)
    chist_term = np.asarray(w.chist_term)
    chist_set = np.asarray(w.chist_set)
    for sd in range(log_term.shape[0]):
        for node in range(cfg.num_nodes):
            for idx in range(1, commit[sd, node] + 1):
                if chist_set[sd, idx]:
                    assert log_term[sd, node, idx] == chist_term[sd, idx], (sd, node, idx)


def test_raft_total_partition_no_leader():
    """Sanity-check the checker can see *absence* too: with 100% packet
    loss no election can ever complete."""
    cfg = raft.RaftConfig(crashes=0, loss_q32=prob_to_q32(1.0))
    wl = raft.workload(cfg)
    final = ecore.run_sweep(
        wl,
        raft.engine_config(cfg, time_limit_ns=1_000_000_000, max_steps=5_000),
        jnp.arange(4, dtype=jnp.int64),
    )
    s = raft.sweep_summary(final)
    assert s["no_leader_seeds"] == 4


# -- random tie-breaking (ref mpsc.rs:71-84 random-pop semantics) ----------


def test_pop_tie_break_varies_with_draw():
    """Equal-time events pop in different orders for different tie draws,
    and identically for the same draw (deterministic per seed+event)."""
    def fill():
        q = equeue.make(8, 1)
        for k in range(4):
            q, _ = _push_one(q, 100, k, [k])
        return q

    def pop_order(tie_seq):
        q = fill()
        order = []
        for u in tie_seq:
            q, t, kind, pay, found = equeue.pop_min(q, tie_u32=jnp.uint32(u))
            assert bool(found) and int(t) == 100
            order.append(int(kind))
        return order

    a = pop_order([0x12345678, 0x9E3779B9, 0xDEADBEEF, 7])
    b = pop_order([0x12345678, 0x9E3779B9, 0xDEADBEEF, 7])
    assert a == b, "same draws must give the same order"
    assert sorted(a) == [0, 1, 2, 3], "all tied events must pop exactly once"
    orders = {tuple(pop_order([u, u + 1, u + 2, u + 3])) for u in range(12)}
    assert len(orders) > 1, "tie order must vary across draws"


def test_pop_tie_break_prefers_earlier_time():
    """The tie-break only applies within the minimum time bucket."""
    q = equeue.make(4, 1)
    for t, k in [(200, 0), (100, 1), (200, 2)]:
        q, _ = _push_one(q, t, k, [k])
    for u in (0, 1, 0xFFFFFFFF, 0x13572468):
        _, t, kind, _, found = equeue.pop_min(q, tie_u32=jnp.uint32(u))
        assert bool(found) and int(t) == 100 and int(kind) == 1


def test_same_timestamp_events_interleave_across_seeds():
    """Two events scheduled at the identical timestamp are dispatched in
    seed-dependent order — the device analogue of the reference's random
    ready-queue pop (schedule amplification across a sweep)."""
    from madsim_tpu.engine.core import Emits, Workload

    def init(key):
        w = jnp.zeros((2,), jnp.int32)  # dispatch log: order of kinds
        emits = Emits(
            times=jnp.array([1000, 1000], jnp.int64),
            kinds=jnp.array([1, 2], jnp.int32),
            pays=jnp.zeros((2, 1), jnp.int32),
            enables=jnp.ones((2,), bool),
        )
        return w, emits

    def handle(w, now, kind, pay, rand):
        slot = jnp.where(w[0] == 0, 0, 1)
        w = jnp.where(jnp.arange(2) == slot, kind, w)
        return w, Emits(
            times=jnp.zeros((1,), jnp.int64),
            kinds=jnp.zeros((1,), jnp.int32),
            pays=jnp.zeros((1, 1), jnp.int32),
            enables=jnp.zeros((1,), bool),
        )

    wl = Workload(init=init, handle=handle, num_rand=1, payload_slots=1, max_emits=1)
    cfg = EngineConfig(queue_capacity=4, time_limit_ns=10_000, max_steps=8)
    final = ecore.run_sweep(wl, cfg, jnp.arange(64, dtype=jnp.int64))
    first = np.asarray(final.wstate)[:, 0]
    assert set(first.tolist()) == {1, 2}, (
        "across seeds both orders of the tied pair must occur"
    )


# -- queue-capacity bound (exact boundary) ----------------------------------


def _spawner_workload():
    """Synthetic growth workload: every handled event spawns two future
    events, so queue occupancy grows by exactly one per step — a ruler for
    the capacity boundary."""
    from madsim_tpu.engine.core import Emits, Workload

    def init(key):
        emits = Emits(
            times=jnp.array([100, 0], jnp.int64),
            kinds=jnp.zeros((2,), jnp.int32),
            pays=jnp.zeros((2, 1), jnp.int32),
            enables=jnp.array([True, False]),
        )
        return jnp.zeros(()), emits

    def handle(w, now, kind, pay, rand):
        emits = Emits(
            times=jnp.stack([now + 100, now + 200]),
            kinds=jnp.zeros((2,), jnp.int32),
            pays=jnp.zeros((2, 1), jnp.int32),
            enables=jnp.ones((2,), bool),
        )
        return w, emits

    return Workload(init=init, handle=handle, num_rand=1, payload_slots=1, max_emits=2)


def test_queue_fills_to_exact_capacity_without_overflow():
    """Occupancy can reach exactly queue_capacity with the overflow flag
    still clear: the bound is tight, not conservative."""
    cap = 8
    wl = _spawner_workload()
    cfg = EngineConfig(queue_capacity=cap, time_limit_ns=1 << 40,
                       max_steps=cap - 1)
    final = ecore.run_sweep(wl, cfg, jnp.arange(4, dtype=jnp.int64))
    assert (np.asarray(final.qmax) == cap).all()
    assert not np.asarray(final.overflow).any()


def test_queue_overflow_latches_exactly_past_capacity():
    """One step beyond the fill point the push exceeds capacity and the
    sticky overflow flag latches — at capacity+1 demand, not before."""
    cap = 8
    wl = _spawner_workload()
    cfg = EngineConfig(queue_capacity=cap, time_limit_ns=1 << 40,
                       max_steps=cap)
    final = ecore.run_sweep(wl, cfg, jnp.arange(4, dtype=jnp.int64))
    assert (np.asarray(final.qmax) == cap).all()  # never exceeds capacity
    assert np.asarray(final.overflow).all()


# -- raft client-command retry cap ------------------------------------------


def test_cmd_retry_cap_and_giveups_surfaced():
    """With a fully lossy network no leader ever emerges: every command
    retries to the cap, gives up (bounded K_CMD chains — no spinning until
    the time limit), and the give-ups are surfaced in the summary."""
    cfg = raft.RaftConfig(
        num_nodes=3, crashes=0, commands=4, loss_q32=prob_to_q32(1.0),
        cmd_max_retries=5, cmd_retry_ns=10_000_000,
        # every command must fire AND exhaust its retries inside the
        # 2 s time limit, or it can neither accept nor give up
        cmd_window_ns=1_000_000_000,
    )
    ecfg = raft.engine_config(cfg, time_limit_ns=2_000_000_000, max_steps=50_000)
    final = ecore.run_sweep(raft.workload(cfg), ecfg, jnp.arange(8, dtype=jnp.int64))
    s = raft.sweep_summary(final)
    assert s["accepted_cmds"] == 0
    assert s["cmd_giveups"] == 8 * cfg.commands  # every command capped out


def test_chunked_sweep_matches_unchunked_with_ragged_tail():
    """run_sweep_chunked splits a sweep into fixed-size program calls
    (padding + trimming a ragged final chunk) and must be bit-identical
    per seed to one big run_sweep."""
    cfg = raft.RaftConfig(num_nodes=3, crashes=1)
    ecfg = raft.engine_config(cfg, time_limit_ns=500_000_000, max_steps=4_000)
    wl = raft.workload(cfg)
    seeds = jnp.arange(22, dtype=jnp.int64)  # 8+8+6: ragged tail
    whole = ecore.run_sweep(wl, ecfg, seeds)
    chunked = ecore.run_sweep_chunked(wl, ecfg, seeds, chunk_size=8)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(chunked)):
        if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        assert jnp.array_equal(jax.device_get(a), jax.device_get(b))


def test_buggify_latency_spikes_amplify_and_stay_deterministic():
    """The device-tier buggify spike path (engine/net.py: loss-draw remix
    gates a 1-5 s latency spike, ref net/mod.rs:287-295): enabling it
    changes schedules for most seeds, amplifies elections (delayed
    heartbeats), keeps checkers quiet, and preserves traced-replay
    parity."""
    base = raft.RaftConfig(num_nodes=3, crashes=0)
    # 50%: rare enough to keep clusters mostly healthy, frequent enough
    # that consecutive delayed heartbeats open election-timeout gaps (a
    # lone 10% spike rarely does — heartbeats keep resetting the timer)
    spiky = base._replace(buggify_q32=prob_to_q32(0.50))
    # spiked (1-5 s) messages accumulate undelivered far beyond the
    # normal-latency queue sizing — give explicit headroom so the
    # assertions measure the spike model, not dropped-event artifacts
    ecfg = raft.engine_config(
        base, queue_capacity=128, time_limit_ns=2_000_000_000, max_steps=20_000
    )
    seeds = jnp.arange(64, dtype=jnp.int64)
    fb = ecore.run_sweep(raft.workload(base), ecfg, seeds)
    fs = ecore.run_sweep(raft.workload(spiky), ecfg, seeds)
    sb, ss = raft.sweep_summary(fb), raft.sweep_summary(fs)
    assert ss["violations"] == 0, ss
    assert ss["overflow_seeds"] == 0 and sb["overflow_seeds"] == 0
    # spikes perturb most seeds' schedules
    frac_changed = np.mean(np.asarray(fb.ctr) != np.asarray(fs.ctr))
    assert frac_changed > 0.5, frac_changed
    # 1-5 s heartbeat spikes against ~150-300 ms election timeouts force
    # re-elections across the batch
    assert ss["elections_total"] > sb["elections_total"], (sb, ss)
    # replay parity holds on the buggified config
    single, _ = ecore.run_traced(raft.workload(spiky), ecfg, int(seeds[3]))
    assert int(single.ctr) == int(fs.ctr[3])
    assert bool(single.wstate.violation) == bool(fs.wstate.violation[3])
