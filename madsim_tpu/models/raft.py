"""Raft (election + log replication) as a device workload — the MadRaft
sweep.

This is the flagship model for the engine: an N-node Raft cluster — leader
election with the §5.4.1 vote restriction, single-entry AppendEntries
replication with consistency checks and next/match-index bookkeeping, and
commit advancement under the §5.4.2 current-term rule (Ongaro & Ousterhout)
— with crash/restart fault injection and per-message loss/latency,
expressed as pure array handlers so thousands of seeds run in lockstep on
TPU. It plays the role the MadRaft test suite plays for the reference
(BASELINE.md configs #3/#5): randomized schedules + faults hunting for
safety violations, with every found seed replayable bit-exactly on CPU via
``engine.run_traced``.

Two safety invariants are checked online, any breach latches ``violation``:
- **election safety**: at most one leader per term (a (term, winner) ring
  compared on every won election);
- **log matching at commit**: the first node to commit index i records
  the entry term; every later commit of i must agree.

Mechanics mirrored from the reference simulator rather than any Raft
implementation: message delivery = link test + latency draw
(madsim/src/sim/net/network.rs:261-269), node crash/restart semantics =
kill/restart with durable (term, vote, log) vs volatile (role, votes,
commit) state (madsim/src/sim/task/mod.rs:347-394), randomized timers =
the virtual-clock timer queue (madsim/src/sim/time/mod.rs:142-153).

Design notes:
- Timer staleness uses generation counters (``tgen`` per node for election
  timers, ``lepoch`` for heartbeat timers) instead of cancellation — the
  queue is append-only, cancellation is a pay-mismatch drop.
- Replication ships ONE entry per AppendEntries (the follower's
  next-index entry), so message payloads stay fixed-width; heartbeats are
  empty appends. Leaders retry/decrement on rejection — the classic loop.
- Logs are bounded arrays (``log_cap`` entries); a seed whose log would
  overflow latches ``log_overflow`` and stops appending (surfaced in the
  sweep summary, never silent).
- All node/log indexing is one-hot masked (engine/ops.py): under vmap,
  dynamic scatter/gather lower to TPU ops ~6-10x slower than the dense
  masked equivalents, and the handlers run for every seed every step.

Two opt-in mechanisms (static ``RaftConfig`` fields, off by default; off,
they add no state leaf, payload word or event kind, so the compiled
program of a config that leaves them off is the one it always was):

- **Log compaction** (``snapshot_every`` > 0; MIT 6.824 Lab 2D, the Raft
  paper §7 and Figure 13). Each entry carries a client command value in
  a 9th payload word. ``log_cap`` becomes a window over absolute
  indices: a node holds ``snap_idx+1 .. log_len``, index ``i`` in ring
  slot ``i % log_cap``, so compaction moves no data. The state machine
  applies each entry in the event that commits it (the applied index IS
  ``commit``): its digest through index ``i`` is the wrapping sum of
  ``_mix(j, cmd_j)`` over ``j <= i``. Every ``snapshot_every`` applied
  entries the node snapshots (``snap_idx``, ``snap_term``,
  ``snap_digest``, durable) and trims its log through the snapshot. A
  leader sends InstallSnapshot (``M_SNAP``) in place of an append to a
  follower whose next index is at or below its ``snap_idx``; a follower
  that is behind installs it, keeping the suffix of its log after a
  matching ``(idx, term)``. A crashed node restarts from its snapshot.
  Follower commit follows Figure 2 (the index of the last new entry);
  a rejection backs the leader's next index up to the follower's log
  length at once (Lab 2C's XLen); a reply that moves a follower's next
  index is answered at once with its next entry while it is behind
  (replication pipelined at one entry per round trip, where Lab 2D's
  Raft ships every entry from the next index in one AppendEntries); and
  a third checker latches ``V_APPLY``: the first digest recorded
  at each snapshot point (over a ring of the last ``log_cap`` points)
  must be every later node's digest there, whether it compacted to it
  or installed it. It cannot see a divergence in the entries after a
  node's last snapshot point, nor points older than the ring.
- **Command/crash rounds** (``rounds`` > 0, with compaction; Lab 2D's
  ``snapcommon`` with ``crash``): round ``r = 1 .. rounds`` starts at
  ``r * ROUND_NS`` (event ``K_ROUND``), crashes one uniformly drawn node
  for ``[restart_lo_ns, restart_hi_ns)``, and sends two command chains:
  one of ``1 + burst`` commands right away, ``burst`` drawn as the
  source's ``snapshot_every / 2 + rand % snapshot_every``, and one
  command after the restart. A chain sends its next command
  ``CMD_GAP_NS`` after a leader accepts (or the retry cap drops) the
  last, so commands arrive as the rounds go and the queue never holds
  the whole plan.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from ..engine import faults as efaults
from ..engine import net as enet
from ..engine.core import Emits, EngineConfig, Workload
from ..engine.ops import get1, get2, geti, set1, set2
from ..engine.rng import bounded, prob_to_q32
from ..oracle.history import OP_ELECT, PH_INVOKE
from . import _common

# event kinds
K_ELECTION = 0  # pay = (node, tgen)
K_HEARTBEAT = 1  # pay = (node, lepoch)
K_MSG = 2  # pay = (dst, mtype, src, term, a, b, c, d[, e])
K_FAULT = 3  # pay = (action, victim, t_lo, t_hi) — engine/faults.py stream
K_CMD = 4  # pay = (target, retries[, more]) — a client command seeking the leader
K_ROUND = 5  # pay = (round,) — one round of the command/crash plan (``rounds``)

# message types
M_REQ_VOTE = 0  # a=last_log_idx, b=last_log_term
M_VOTE_GRANT = 1
M_APPEND = 2  # a=prev_idx, b=prev_term, c=entry_term (0 = heartbeat), d=commit,
# e=entry's command (window logs)
M_APPEND_RSP = 3  # a=success, b=match_idx
# (window logs) a=snap_idx, b=snap_term, c=snap_digest; answered by M_APPEND_RSP
M_SNAP = 4

# roles
FOLLOWER = 0
CANDIDATE = 1
LEADER = 2

PAYLOAD_SLOTS = 8

# violation flavors (bitmask latched in ``viol_kind``; ``violation`` stays
# the any-flavor bool). The explore subsystem's triage keys on these.
V_ELECTION = 1  # two leaders elected in one term
V_COMMIT = 2  # log-matching breach at commit
V_APPLY = 4  # state-machine breach: two digests at one snapshot point

N_KINDS = 5  # event kinds above, without K_ROUND
N_ROLE_TRANS = 9  # role_before * 3 + role_after


class RaftConfig(NamedTuple):
    """Static sweep parameters (hashable — part of the jit key)."""

    num_nodes: int = 5
    election_lo_ns: int = 150_000_000
    election_hi_ns: int = 300_000_000
    heartbeat_ns: int = 50_000_000
    # client command plan: `commands` K_CMD events in the first
    # `cmd_window_ns`, retrying every retry_ns until a leader accepts
    commands: int = 8
    cmd_window_ns: int = 4_000_000_000
    cmd_retry_ns: int = 50_000_000
    # a command that can't find a leader stops retrying after this many
    # attempts (surfaced as cmd_giveups) instead of spinning K_CMD events
    # until the time limit in partitioned seeds
    cmd_max_retries: int = 64
    log_cap: int = 32
    # legacy crash-storm shorthand, compiled through engine/faults.py;
    # `faults` (below) overrides all four when set
    crashes: int = 2
    crash_window_ns: int = 5_000_000_000
    restart_lo_ns: int = 100_000_000
    restart_hi_ns: int = 1_000_000_000
    # network model (reference defaults: 1-10 ms latency, lossless)
    loss_q32: int = prob_to_q32(0.01)
    lat_lo_ns: int = 1_000_000
    lat_hi_ns: int = 10_000_000
    # buggified latency spikes (ref net/mod.rs:287-295: 10% → 1-5 s when
    # buggify is enabled); 0 disables
    buggify_q32: int = 0
    history: int = 16  # election-safety ring size
    # model the host-tier example's amnesia bug: crash wipes DURABLE state
    # too (term/voted/log), so a restarted node can re-vote in a term it
    # already voted in — the election-safety checker catches the double
    # vote. Used by the cross-tier replay pipeline (madsim_tpu/replay.py)
    # to find device seeds whose fault schedule breaks host-tier user code.
    volatile_state: bool = False
    # operation-history buffer rows per seed (madsim_tpu/oracle); 0 =
    # recording off. Raft records one OP_ELECT invoke row per won
    # election (client = winner node, key = term) — the history the
    # differential harness checks against oracle.specs.ElectionSpec on
    # both tiers (explore/differential.py).
    hist_slots: int = 0
    # full declarative fault campaign (engine/faults.FaultSpec), a
    # literal schedule, or a FaultEnvelope — the spec-as-data path, where
    # the jit key is the envelope SHAPE and the concrete candidate rides
    # in as per-lane FaultParams (run_sweep's ``params=``); None =
    # derive a crash-storm spec from the legacy fields above
    faults: Optional[
        Union[efaults.FaultSpec, efaults.FixedFaults, efaults.FaultEnvelope]
    ] = None
    # opt-in device-side event-mix telemetry plane (madsim_tpu/obs):
    # per-seed uint32 counters, one per event kind (N_KINDS), summarized
    # into the chunk's ``event_mix`` histogram. Changes the summary
    # schema and the checkpoint fingerprint — off by default so stock
    # sweeps stay byte-identical.
    event_mix: bool = False
    # -- opt-in mechanisms (module docstring), appended: at their
    # defaults they add nothing, and ``repr`` leaves them out, so a
    # config's checkpoint fingerprint is the one it had before them --
    # log compaction: snapshot every this many applied entries; 0 = off
    # (``log_cap`` is then the absolute log, slot 0 the sentinel)
    snapshot_every: int = 0
    # command/crash rounds (need compaction): 0 = off, only the
    # ``commands`` plan
    rounds: int = 0

    def __repr__(self):
        shown = (
            f"{k}={v!r}"
            for k, v in zip(self._fields, self)
            if k not in _OPT_IN or v != self._field_defaults[k]
        )
        return f"RaftConfig({', '.join(shown)})"


_OPT_IN = frozenset(("snapshot_every", "rounds"))
ROUND_NS = 1_000_000_000  # a round of the command/crash plan: 1 virtual s
CMD_GAP_NS = 1_000_000  # a chain's next command, after the last was taken


def payload_slots(cfg: RaftConfig) -> int:
    """Payload words per event: M_APPEND's 8, and a 9th, the entry's
    command value, where entries carry one (``snapshot_every``)."""
    return PAYLOAD_SLOTS + (1 if cfg.snapshot_every else 0)


def n_kinds(cfg: RaftConfig) -> int:
    """Event kinds the config dispatches (K_ROUND only with ``rounds``)."""
    return N_KINDS + (1 if cfg.rounds else 0)


def fault_spec(cfg: RaftConfig) -> efaults.FaultSpec:
    """The campaign this config compiles: ``cfg.faults`` verbatim, or the
    legacy crash-storm fields lifted into a FaultSpec."""
    if cfg.faults is not None:
        return cfg.faults
    return efaults.FaultSpec(
        crashes=cfg.crashes,
        crash_window_ns=cfg.crash_window_ns,
        restart_lo_ns=cfg.restart_lo_ns,
        restart_hi_ns=cfg.restart_hi_ns,
    )


def _rt(cfg: RaftConfig, w: "RaftState"):
    """Runtime spec view for the in-loop interpreter: the static spec on
    the legacy path, this lane's traced ``FaultRt`` on the envelope path."""
    return efaults.runtime_spec(fault_spec(cfg), w.frt)


def _shadow_nodes(cfg: RaftConfig) -> int:
    """Width of the durability-shadow planes: ``num_nodes`` iff the
    (static, jit-cache-key) spec can open a slow-disk window. Without
    fsync stalls the shadow provably equals the live durable state after
    every event, so the planes go width-0 and every shadow write and
    crash rollback is gated off at trace time — the no-stall common case
    (all pre-gray configs, the headline benchmarks) pays nothing. A
    ``FaultEnvelope`` decides this once per CAMPAIGN (any candidate it
    covers could draw a stall window), not per candidate."""
    return cfg.num_nodes if efaults.can_stall(fault_spec(cfg)) else 0


class RaftState(NamedTuple):
    # per-node Raft state [N] (term/voted/log are durable across crashes)
    role: jnp.ndarray  # int32
    term: jnp.ndarray  # int32
    voted: jnp.ndarray  # int32, -1 = none
    votes: jnp.ndarray  # uint32 bitmask of granted votes
    fstate: efaults.FaultState  # shared liveness/pause/partition/burst state
    last_hb: jnp.ndarray  # int64, last time a valid leader signal arrived
    tgen: jnp.ndarray  # int32 election-timer generation
    lepoch: jnp.ndarray  # int32 leadership epoch (heartbeat-timer guard)
    # replicated log [N, L]: term of each entry; slot 0 is the sentinel
    log_term: jnp.ndarray  # int32[N, L]
    log_len: jnp.ndarray  # int32[N] (== last used index; entries 1..len)
    # durability plane (gray failures, docs/faults.md): the SYNCED shadow
    # of the durable state. Outside slow-disk windows the shadow tracks
    # the live values event by event (fsync-on-mutate, the correct-raft
    # discipline); inside a window it freezes, and a crash/power_fail
    # rolls the live values back to it — crash-without-sync as a
    # schedulable fault. The model acks before fsync completes (the
    # realistic bug class), so stall+power_fail campaigns CAN surface
    # genuine election/commit-safety violations. All four planes are
    # width-0 (and the writes statically gated off) when the spec draws
    # no fsync-stall windows — see ``_shadow_nodes``.
    dur_term: jnp.ndarray  # int32[SN]  (SN = num_nodes or 0)
    dur_voted: jnp.ndarray  # int32[SN]
    dur_log_term: jnp.ndarray  # int32[SN, L]
    dur_log_len: jnp.ndarray  # int32[SN]
    commit: jnp.ndarray  # int32[N] (volatile)
    next_idx: jnp.ndarray  # int32[N, N] (leader bookkeeping, volatile)
    match_idx: jnp.ndarray  # int32[N, N]
    # network
    links: enet.LinkState
    # election-safety ring [H]
    hist_term: jnp.ndarray  # int32
    hist_node: jnp.ndarray  # int32
    hist_valid: jnp.ndarray  # bool
    hist_pos: jnp.ndarray  # int32
    # log-matching-at-commit checker [L]
    chist_term: jnp.ndarray  # int32
    chist_set: jnp.ndarray  # bool
    # sweep outputs
    violation: jnp.ndarray  # bool (any flavor)
    viol_kind: jnp.ndarray  # int32 flavor bitmask (V_ELECTION | V_COMMIT)
    log_overflow: jnp.ndarray  # bool
    elections: jnp.ndarray  # int32
    commits: jnp.ndarray  # int32 (total commit-index advancement)
    accepted_cmds: jnp.ndarray  # int32
    cmd_giveups: jnp.ndarray  # int32 commands that hit the retry cap
    msgs_sent: jnp.ndarray  # int32
    msgs_delivered: jnp.ndarray  # int32
    # spec-as-data (engine/faults.py): this lane's runtime override
    # scalars (FaultRt) on the envelope path; an empty, leafless () on
    # the legacy path — zero loop-carry cost there
    frt: object
    # log compaction (``snapshot_every``): a SnapState; an empty,
    # leafless () when compaction is off
    snap: object


class SnapState(NamedTuple):
    """Per-seed state of log compaction. ``log_term`` (and the
    log-matching ring ``chist_term``/``chist_set``) keep their names and
    widths; on a window log they are rings indexed by ``i % log_cap``."""

    log_cmd: jnp.ndarray  # int32[N, L] command value of each entry (ring)
    snap_idx: jnp.ndarray  # int32[N] last index the snapshot covers (durable)
    snap_term: jnp.ndarray  # int32[N] its term (durable)
    snap_digest: jnp.ndarray  # uint32[N] digest through snap_idx (durable)
    digest: jnp.ndarray  # uint32[N] digest through commit (volatile)
    chist_idx: jnp.ndarray  # int32[L] index each log-matching slot records
    sdig_idx: jnp.ndarray  # int32[L] snapshot point each digest slot records
    sdig_val: jnp.ndarray  # uint32[L] the first digest recorded there
    snapshots: jnp.ndarray  # int32 compactions, over nodes
    installs_sent: jnp.ndarray  # int32 M_SNAP messages sent
    installs: jnp.ndarray  # int32 M_SNAP messages installed


def _pay(cfg: RaftConfig, *vals) -> jnp.ndarray:
    return _common.pay(*vals, slots=payload_slots(cfg))


_DISABLED_EXTRA = _common.DISABLED  # sentinel: an unused extra slot


def _emits(cfg: RaftConfig, bcast, *extras) -> Emits:
    return _common.pack_emits(payload_slots(cfg), bcast, *extras)


def _no_bcast(cfg: RaftConfig):
    return _common.no_bcast(cfg.num_nodes, payload_slots(cfg), K_MSG)


def _pays(
    cfg: RaftConfig, mtype, src, term, a=0, b=0, c=0, d=0, e=0
) -> jnp.ndarray:
    """[N, P] message payloads addressed to every node; each field is a
    scalar (broadcast) or an [N] vector (per-destination). ``e``, the
    entry's command, rides only where entries carry one."""
    n = cfg.num_nodes
    dst = jnp.arange(n, dtype=jnp.int32)

    def col(v):
        return jnp.broadcast_to(jnp.asarray(v, jnp.int32), (n,))

    cols = [dst, col(mtype), col(src), col(term), col(a), col(b), col(c), col(d)]
    if payload_slots(cfg) > PAYLOAD_SLOTS:
        cols.append(col(e))
    return jnp.stack(cols, axis=1)


# -- window logs (``snapshot_every`` > 0) -------------------------------------


def _as_u32(x):
    return jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.int32), jnp.uint32)


def _as_i32(x):
    return jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.uint32), jnp.int32)


def _mix(idx, cmd):
    """The state machine's step: applying command ``cmd`` (int32) at log
    index ``idx``, as a uint32 (murmur3's finalizer over both). The
    digest through ``i`` is the wrapping sum of the steps of ``1 .. i``:
    it depends on every command and its position, and a range of entries
    applies in one masked sum."""
    x = (_as_u32(idx) * jnp.uint32(0x9E3779B1)) ^ _as_u32(cmd)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _window(cfg: RaftConfig, snap):
    """int32[L]: the absolute index each ring slot of a window log holds,
    ``snap + 1 .. snap + log_cap`` (slot ``i % log_cap`` holds ``i``)."""
    s = jnp.arange(cfg.log_cap, dtype=jnp.int32)
    return snap + 1 + jnp.remainder(s - snap - 1, cfg.log_cap)


def _ring_at(cfg: RaftConfig, row, idx):
    """``row[idx % log_cap]`` for a vector (or scalar) of indices."""
    return geti(row, jnp.remainder(jnp.atleast_1d(idx), cfg.log_cap)).reshape(
        jnp.shape(idx)
    )


def _term_at(cfg: RaftConfig, row, snap, snap_term, idx):
    """Term at absolute index ``idx`` of a window log, for ``snap <= idx
    <= log_len``: the snapshot's term at ``snap``, the ring's above."""
    return jnp.where(idx == snap, snap_term, _ring_at(cfg, row, idx))


def _node_term_at(cfg: RaftConfig, w: RaftState, node, idx):
    """``_term_at`` on ``node``'s window log."""
    return _term_at(
        cfg, get1(w.log_term, node), get1(w.snap.snap_idx, node),
        get1(w.snap.snap_term, node), idx,
    )


def _check_point(cfg: RaftConfig, w: RaftState, sn: SnapState, point, digest, on):
    """State-machine safety at snapshot point ``point``: the first digest
    recorded there (ring slot ``point / snapshot_every % log_cap``) must
    be every later one; a mismatch latches ``V_APPLY``."""
    slot = point // cfg.snapshot_every % cfg.log_cap
    hit = (jnp.arange(cfg.log_cap, dtype=jnp.int32) == slot) & on
    seen = hit & (sn.sdig_idx == point)
    bad = jnp.any(seen & (sn.sdig_val != digest))
    new = hit & ~seen
    sn = sn._replace(
        sdig_idx=jnp.where(new, point, sn.sdig_idx),
        sdig_val=jnp.where(new, digest, sn.sdig_val),
    )
    return w._replace(
        violation=w.violation | bad,
        viol_kind=w.viol_kind | jnp.where(bad, jnp.int32(V_APPLY), jnp.int32(0)),
    ), sn


def _install(cfg: RaftConfig, w: RaftState, node, idx, term, digest_bits, on):
    """InstallSnapshot at a follower (Raft Figure 13), for an ``M_SNAP``
    of the node's current term: a snapshot past the node's commit
    replaces its state machine and its snapshot; the log keeps its
    suffix after ``idx`` if it holds ``(idx, term)``, else is dropped."""
    sn = w.snap
    commit = get1(w.commit, node)
    do = on & (idx > commit)
    my_len = get1(w.log_len, node)
    keep = (idx <= my_len) & (_node_term_at(cfg, w, node, idx) == term)
    digest = _as_u32(digest_bits)
    sn = sn._replace(
        snap_idx=set1(sn.snap_idx, node, idx, do),
        snap_term=set1(sn.snap_term, node, term, do),
        snap_digest=set1(sn.snap_digest, node, digest, do),
        digest=set1(sn.digest, node, digest, do),
        installs=sn.installs + jnp.where(do, 1, 0),
    )
    w, sn = _check_point(cfg, w, sn, idx, digest, do)
    return w._replace(
        log_len=set1(w.log_len, node, jnp.where(keep, my_len, idx), do),
        commit=set1(w.commit, node, idx, do),
        commits=w.commits + jnp.where(do, idx - commit, 0),
        snap=sn,
    )


def _snaps_sent(cfg: RaftConfig, enable, src, pays):
    """M_SNAP messages a broadcast of ``pays`` attempts (self excluded)."""
    others = jnp.arange(cfg.num_nodes, dtype=jnp.int32) != src
    return jnp.sum(enable & others & (pays[:, 1] == M_SNAP), dtype=jnp.int32)


def _broadcast(cfg: RaftConfig, w: RaftState, now, src, rand, enable, pays):
    """Emit slots 0..N-1: one message per destination (self slot disabled),
    each individually link-tested — all vectorized, no per-node loop."""
    n = cfg.num_nodes
    u = rand[: 2 * n].reshape(n, 2)
    times, deliver = enet.route_from(w.links, now, src, u[:, 0], u[:, 1])
    enables = enable & (jnp.arange(n, dtype=jnp.int32) != src) & deliver
    kinds = jnp.full((n,), K_MSG, jnp.int32)
    sent = jnp.where(enable, jnp.int32(cfg.num_nodes - 1), 0)
    delivered = jnp.sum(enables, dtype=jnp.int32)
    return (times, kinds, pays, enables), sent, delivered


def _record_election(cfg: RaftConfig, w: RaftState, term, node, won):
    """Online election-safety check: a term may elect at most one leader."""
    dup = jnp.any(w.hist_valid & (w.hist_term == term) & (w.hist_node != node))
    slot = w.hist_pos % cfg.history
    return w._replace(
        violation=w.violation | (won & dup),
        viol_kind=w.viol_kind
        | jnp.where(won & dup, jnp.int32(V_ELECTION), jnp.int32(0)),
        hist_term=set1(w.hist_term, slot, term, won),
        hist_node=set1(w.hist_node, slot, node, won),
        hist_valid=set1(w.hist_valid, slot, True, won),
        hist_pos=jnp.where(won, w.hist_pos + 1, w.hist_pos),
        elections=jnp.where(won, w.elections + 1, w.elections),
    )


def _advance_commit(cfg: RaftConfig, w: RaftState, node, new_commit, enable):
    """Move ``commit[node]`` to ``new_commit`` and run the log-matching
    checker over the newly committed range."""
    if cfg.snapshot_every:
        return _advance_commit_window(cfg, w, node, new_commit, enable)
    old = get1(w.commit, node)
    new = jnp.where(enable, jnp.maximum(old, new_commit.astype(jnp.int32)), old)
    idx = jnp.arange(cfg.log_cap, dtype=jnp.int32)
    fresh = (idx > old) & (idx <= new)
    my_terms = get1(w.log_term, node)
    mismatch = jnp.any(fresh & w.chist_set & (w.chist_term != my_terms))
    return w._replace(
        commit=set1(w.commit, node, new),
        chist_term=jnp.where(fresh & ~w.chist_set, my_terms, w.chist_term),
        chist_set=w.chist_set | fresh,
        violation=w.violation | mismatch,
        viol_kind=w.viol_kind
        | jnp.where(mismatch, jnp.int32(V_COMMIT), jnp.int32(0)),
        commits=w.commits + (new - old).astype(jnp.int32),
    )


def _advance_commit_window(cfg: RaftConfig, w: RaftState, node, new_commit, enable):
    """``_advance_commit`` on a window log: the log-matching check over a
    ring of the last ``log_cap`` committed indices, then the state
    machine applies the newly committed entries, and the node snapshots
    at the last multiple of ``snapshot_every`` it reached (its snapshot
    is always there: ``snap_idx == commit // snapshot_every *
    snapshot_every``)."""
    sn = w.snap
    old = get1(w.commit, node)
    new = jnp.where(enable, jnp.maximum(old, new_commit.astype(jnp.int32)), old)
    snap = get1(sn.snap_idx, node)
    idx = _window(cfg, snap)
    fresh = (idx > old) & (idx <= new)
    my_terms = get1(w.log_term, node)
    held = w.chist_set & (sn.chist_idx == idx)
    mismatch = jnp.any(fresh & held & (w.chist_term != my_terms))
    put = fresh & ~held
    w = w._replace(
        commit=set1(w.commit, node, new),
        chist_term=jnp.where(put, my_terms, w.chist_term),
        chist_set=w.chist_set | put,
        violation=w.violation | mismatch,
        viol_kind=w.viol_kind
        | jnp.where(mismatch, jnp.int32(V_COMMIT), jnp.int32(0)),
        commits=w.commits + (new - old).astype(jnp.int32),
    )
    sn = sn._replace(chist_idx=jnp.where(put, idx, sn.chist_idx))
    with jax.named_scope("snapshot"):
        steps = _mix(idx, get1(sn.log_cmd, node))
        before = get1(sn.digest, node)
        zero = jnp.uint32(0)
        point = new // cfg.snapshot_every * cfg.snapshot_every
        compact = point > snap
        at_point = before + jnp.sum(
            jnp.where(fresh & (idx <= point), steps, zero), dtype=jnp.uint32
        )
        sn = sn._replace(
            digest=set1(
                sn.digest, node,
                before + jnp.sum(jnp.where(fresh, steps, zero), dtype=jnp.uint32),
            ),
            snap_idx=set1(sn.snap_idx, node, point, compact),
            snap_term=set1(sn.snap_term, node, _ring_at(cfg, my_terms, point), compact),
            snap_digest=set1(sn.snap_digest, node, at_point, compact),
            snapshots=sn.snapshots + jnp.where(compact, 1, 0),
        )
        w, sn = _check_point(cfg, w, sn, point, at_point, compact)
    return w._replace(snap=sn)


def _append_pays(cfg: RaftConfig, w: RaftState, leader, term) -> jnp.ndarray:
    """AppendEntries payloads [N, P]: each follower gets the entry at its
    next-index (or a pure heartbeat when the log has nothing newer); on a
    window log, a follower whose next-index is at or below the leader's
    snapshot gets the snapshot (M_SNAP) instead."""
    if cfg.snapshot_every:
        return _append_pays_window(cfg, w, leader, term)
    nxt = get1(w.next_idx, leader)  # [N]
    log_row = get1(w.log_term, leader)  # [L]
    prev_idx = nxt - 1
    prev_term = geti(log_row, prev_idx)  # [N]
    has_entry = nxt <= get1(w.log_len, leader)
    safe_nxt = jnp.minimum(nxt, cfg.log_cap - 1)
    ent_term = jnp.where(has_entry, geti(log_row, safe_nxt), 0)
    return _pays(
        cfg, M_APPEND, leader, term, prev_idx, prev_term, ent_term,
        get1(w.commit, leader),
    )


def _append_pays_window(cfg: RaftConfig, w: RaftState, leader, term):
    sn = w.snap
    nxt = get1(w.next_idx, leader)  # [N]
    log_row = get1(w.log_term, leader)
    snap = get1(sn.snap_idx, leader)
    snap_term = get1(sn.snap_term, leader)
    prev_idx = nxt - 1
    has_entry = nxt <= get1(w.log_len, leader)
    app = _pays(
        cfg, M_APPEND, leader, term, prev_idx,
        _term_at(cfg, log_row, snap, snap_term, prev_idx),
        jnp.where(has_entry, _ring_at(cfg, log_row, nxt), 0),
        get1(w.commit, leader),
        jnp.where(has_entry, _ring_at(cfg, get1(sn.log_cmd, leader), nxt), 0),
    )
    with jax.named_scope("snapshot"):
        install = _pays(
            cfg, M_SNAP, leader, term, snap, snap_term,
            _as_i32(get1(sn.snap_digest, leader)),
        )
        return jnp.where((nxt <= snap)[:, None], install, app)


# -- event handlers (each: (w, now, pay, rand) -> (w, Emits)) ---------------


def _on_election_timer(cfg: RaftConfig, w: RaftState, now, pay, rand):
    node, gen = pay[0], pay[1]
    valid = get1(efaults.up(w.fstate), node) & (gen == get1(w.tgen, node)) & (
        get1(w.role, node) != LEADER
    )
    # a live leader/candidate signal arrived since this timer was armed?
    recent = (get1(w.last_hb, node) + cfg.election_lo_ns) > now
    starting = valid & ~recent

    new_term = get1(w.term, node) + 1
    self_bit = jnp.left_shift(jnp.uint32(1), node.astype(jnp.uint32))
    w2 = w._replace(
        term=set1(w.term, node, new_term, starting),
        role=set1(w.role, node, CANDIDATE, starting),
        voted=set1(w.voted, node, node, starting),
        votes=set1(w.votes, node, self_bit, starting),
        last_hb=set1(w.last_hb, node, now, starting),
    )
    last_idx = get1(w.log_len, node)
    if cfg.snapshot_every:
        last_term = _node_term_at(cfg, w, node, last_idx)
    else:
        last_term = get2(w.log_term, node, last_idx)
    bcast, sent, delivered = _broadcast(
        cfg, w2, now, node, rand, starting,
        _pays(cfg, M_REQ_VOTE, node, new_term, last_idx, last_term),
    )
    # timer arming runs on the node's own (possibly skewed) clock
    timeout = efaults.skewed_delay(
        fault_spec(cfg), w.fstate, node,
        bounded(rand[2 * cfg.num_nodes], cfg.election_lo_ns, cfg.election_hi_ns),
        rt=_rt(cfg, w),
    )
    emits = _emits(
        cfg,
        bcast,
        # one live election timer per node, always re-armed while valid
        (now + timeout, K_ELECTION, _pay(cfg, node, get1(w.tgen, node)), valid),
        _DISABLED_EXTRA,
    )
    w2 = w2._replace(msgs_sent=w2.msgs_sent + sent, msgs_delivered=w2.msgs_delivered + delivered)
    return w2, emits


def _on_heartbeat_timer(cfg: RaftConfig, w: RaftState, now, pay, rand):
    node, epoch = pay[0], pay[1]
    valid = get1(efaults.up(w.fstate), node) & (get1(w.role, node) == LEADER) & (
        epoch == get1(w.lepoch, node)
    )
    term = get1(w.term, node)
    pays = _append_pays(cfg, w, node, term)
    bcast, sent, delivered = _broadcast(cfg, w, now, node, rand, valid, pays)
    hb = efaults.skewed_delay(
        fault_spec(cfg), w.fstate, node, cfg.heartbeat_ns, rt=_rt(cfg, w)
    )
    emits = _emits(
        cfg,
        bcast,
        (now + hb, K_HEARTBEAT, _pay(cfg, node, epoch), valid),
        _DISABLED_EXTRA,
    )
    w2 = w._replace(msgs_sent=w.msgs_sent + sent, msgs_delivered=w.msgs_delivered + delivered)
    if cfg.snapshot_every:
        w2 = w2._replace(snap=w2.snap._replace(
            installs_sent=w2.snap.installs_sent + _snaps_sent(cfg, valid, node, pays)
        ))
    return w2, emits


def _on_msg(cfg: RaftConfig, w: RaftState, now, pay, rand):
    dst, mtype, src, mterm = pay[0], pay[1], pay[2], pay[3]
    a, b, c, d = pay[4], pay[5], pay[6], pay[7]
    live = get1(efaults.up(w.fstate), dst)
    role_dst = get1(w.role, dst)
    was_leader = live & (role_dst == LEADER)

    # term catch-up (Raft §5.1): any message with a higher term demotes
    higher = live & (mterm > get1(w.term, dst))
    term_d = jnp.where(higher, mterm, get1(w.term, dst))
    role_d = jnp.where(higher, FOLLOWER, role_dst)
    voted_d = jnp.where(higher, -1, get1(w.voted, dst))

    is_rv = live & (mtype == M_REQ_VOTE)
    is_vg = live & (mtype == M_VOTE_GRANT)
    is_ap = live & (mtype == M_APPEND)
    is_ar = live & (mtype == M_APPEND_RSP)

    log_row = get1(w.log_term, dst)  # [L] this node's log terms
    my_len = get1(w.log_len, dst)
    window = bool(cfg.snapshot_every)
    if window:
        my_snap = get1(w.snap.snap_idx, dst)
        term_at = partial(_node_term_at, cfg, w, dst)

    # -- RequestVote (§5.4.1 up-to-date restriction): grant iff same term,
    # not voted for anyone else, and candidate log >= ours
    if window:
        my_last_term = term_at(my_len)
    else:
        my_last_term = geti(log_row, my_len[None])[0]
    log_ok = (b > my_last_term) | ((b == my_last_term) & (a >= my_len))
    grant = (
        is_rv
        & (mterm == term_d)
        & ((voted_d == -1) | (voted_d == src))
        & log_ok
    )
    voted_d = jnp.where(grant, src, voted_d)

    # -- VoteGrant: count iff still candidate in that term
    counted = is_vg & (role_d == CANDIDATE) & (mterm == term_d)
    src_bit = jnp.left_shift(jnp.uint32(1), src.astype(jnp.uint32))
    votes_d = jnp.where(counted, get1(w.votes, dst) | src_bit, get1(w.votes, dst))
    majority = cfg.num_nodes // 2 + 1
    won = counted & (jax.lax.population_count(votes_d).astype(jnp.int32) >= majority)
    role_d = jnp.where(won, LEADER, role_d)

    # -- AppendEntries: same-term leader signal; consistency-check and
    # append the carried entry; follow the leader's commit
    heard = is_ap & (mterm == term_d)
    signal = heard  # a same-term leader's message
    if window:
        # InstallSnapshot from this term's leader (installed in _install);
        # an append below the node's snapshot is answered with the
        # snapshot's index, which is committed and so matches the leader
        installing = live & (mtype == M_SNAP) & (mterm == term_d)
        signal = heard | installing
        below = heard & (a < my_snap)
        heard = heard & ~below
    role_d = jnp.where(signal & (role_d == CANDIDATE), FOLLOWER, role_d)
    prev_idx, prev_term, ent_term, leader_commit = a, b, c, d
    if window:
        consistent = heard & (prev_idx <= my_len) & (term_at(prev_idx) == prev_term)
    else:
        consistent = heard & (prev_idx <= my_len) & (
            geti(log_row, prev_idx[None])[0] == prev_term
        )
    has_entry = ent_term > 0
    slot_idx = prev_idx + 1
    if window:
        can_store = slot_idx - my_snap <= cfg.log_cap
    else:
        can_store = slot_idx < cfg.log_cap
    store = consistent & has_entry & can_store
    overflow = consistent & has_entry & ~can_store
    # Raft §5.3 append rule: if the slot already holds this entry (same
    # term) keep the existing suffix; a conflicting entry truncates the
    # log at the new entry's index
    if window:
        existing_same = (slot_idx <= my_len) & (term_at(slot_idx) == ent_term)
        ring_slot = jnp.remainder(slot_idx, cfg.log_cap)
    else:
        existing_same = (slot_idx <= my_len) & (
            geti(log_row, jnp.minimum(slot_idx, cfg.log_cap - 1)[None])[0] == ent_term
        )
        ring_slot = slot_idx
    new_len = jnp.where(
        store,
        jnp.where(existing_same, my_len, slot_idx),
        my_len,
    )

    lepoch_dst = get1(w.lepoch, dst)
    w2 = w._replace(
        term=set1(w.term, dst, term_d),
        role=set1(w.role, dst, role_d),
        voted=set1(w.voted, dst, voted_d),
        votes=set1(w.votes, dst, votes_d),
        lepoch=set1(w.lepoch, dst, lepoch_dst + 1, won),
        last_hb=set1(w.last_hb, dst, now, signal | grant | won),
        log_term=set2(w.log_term, dst, ring_slot, ent_term, store),
        log_len=set1(w.log_len, dst, new_len),
        log_overflow=w.log_overflow | overflow,
    )
    w2 = _record_election(cfg, w2, term_d, dst, won)
    if window:
        w2 = w2._replace(snap=w2.snap._replace(
            log_cmd=set2(w2.snap.log_cmd, dst, ring_slot, pay[8], store)
        ))
        # follower commit (Figure 2): min(leader_commit, last new entry)
        last_new = jnp.where(store, slot_idx, prev_idx)
        w2 = _advance_commit(
            cfg, w2, dst, jnp.minimum(leader_commit, last_new), consistent
        )
        with jax.named_scope("snapshot"):
            w2 = _install(cfg, w2, dst, a, b, c, installing)
    else:
        # follower commit: min(leader_commit, own len) once consistent
        w2 = _advance_commit(
            cfg, w2, dst, jnp.minimum(leader_commit, get1(w2.log_len, dst)),
            consistent,
        )

    # -- AppendEntries response (leader side): update next/match, advance
    # commit under the §5.4.2 current-term rule
    rsp_ok = is_ar & (mterm == term_d) & (role_d == LEADER)
    success = a == 1
    old_match = get2(w2.match_idx, dst, src)
    old_next = get2(w2.next_idx, dst, src)
    new_match = jnp.where(rsp_ok & success, jnp.maximum(old_match, b), old_match)
    if window:
        # a rejection carries min(prev_idx, the follower's log length): a
        # short follower is backed up to its length at once (Lab 2C's
        # XLen), so a new leader's catch-up needs no walk down its log
        backup = jnp.maximum(jnp.minimum(old_next - 1, b + 1), 1)
        new_next = jnp.where(
            rsp_ok, jnp.where(success, new_match + 1, backup), old_next
        )
    else:
        new_next = jnp.where(
            rsp_ok,
            jnp.where(success, new_match + 1, jnp.maximum(old_next - 1, 1)),
            old_next,
        )
    w2 = w2._replace(
        match_idx=set2(w2.match_idx, dst, src, new_match),
        next_idx=set2(w2.next_idx, dst, src, new_next),
    )
    # commit: highest idx replicated on a majority with an entry of the
    # leader's current term
    if window:
        idxs = _window(cfg, get1(w2.snap.snap_idx, dst))
    else:
        idxs = jnp.arange(cfg.log_cap, dtype=jnp.int32)
    self_mask = jnp.arange(cfg.num_nodes, dtype=jnp.int32) == dst
    match_row = get1(w2.match_idx, dst)  # [N]
    # replicas[i] = 1 + #followers with match_idx >= i
    reps = 1 + jnp.sum(
        (match_row[None, :] >= idxs[:, None]) & ~self_mask[None, :],
        axis=1, dtype=jnp.int32,
    )
    my_len2 = get1(w2.log_len, dst)
    log_row2 = get1(w2.log_term, dst)
    committable = (
        (idxs <= my_len2)
        & (idxs > get1(w2.commit, dst))
        & (reps >= majority)
        & (log_row2 == term_d)
    )
    best = jnp.max(jnp.where(committable, idxs, 0))
    w2 = _advance_commit(cfg, w2, dst, best, rsp_ok & (best > 0))

    # a leader demoted by a higher term must re-enter the election-timer
    # chain (its own timer chain ended when it fired during leadership)
    demoted = was_leader & (role_d != LEADER)
    tgen_dst = get1(w.tgen, dst)
    tgen_d = jnp.where(demoted, tgen_dst + 1, tgen_dst)
    w2 = w2._replace(tgen=set1(w2.tgen, dst, tgen_d))

    # on win: reset leader bookkeeping and broadcast immediate heartbeats
    init_next = get1(w2.log_len, dst) + 1
    w2 = w2._replace(
        next_idx=set1(w2.next_idx, dst, init_next, won),
        match_idx=set1(w2.match_idx, dst, 0, won),
    )
    out_pays = _append_pays(cfg, w2, dst, term_d)
    bcast, sent, delivered = _broadcast(cfg, w2, now, dst, rand, won, out_pays)
    if window:
        # pipelined replication: a reply that moved the follower's next
        # index is answered at once with the follower's next entry (or
        # the snapshot) while it is behind, so a backlog drains at one
        # entry per round trip, not one per heartbeat; a duplicate reply
        # moves nothing and sends nothing
        moved = jnp.where(success, new_match > old_match, new_next != old_next)
        pipe = rsp_ok & moved & (new_next <= get1(w2.log_len, dst))
    # extra slot 1: heartbeat timer (won) | vote reply (grant) | append rsp
    # | the pipelined append (pipe)
    rt, rdeliver = enet.route(
        w.links, now, dst, src, rand[2 * cfg.num_nodes], rand[2 * cfg.num_nodes + 1]
    )
    ap_success = jnp.where(consistent, 1, 0)
    ap_match = jnp.where(store, slot_idx, jnp.minimum(prev_idx, get1(w2.log_len, dst)))
    if window:
        ap_success = jnp.where(consistent | below | installing, 1, 0)
        ap_match = jnp.where(installing, a, jnp.where(below, my_snap, ap_match))
    reply_pay = jnp.where(
        grant,
        _pay(cfg, src, M_VOTE_GRANT, dst, mterm),
        _pay(cfg, src, M_APPEND_RSP, dst, term_d, ap_success, ap_match),
    )
    attempt_reply = (grant | is_ap) & live
    if window:
        attempt_reply = attempt_reply | (live & (mtype == M_SNAP)) | pipe
        reply_pay = jnp.where(pipe, get1(out_pays, src), reply_pay)
    send_reply = attempt_reply & rdeliver
    hb = efaults.skewed_delay(
        fault_spec(cfg), w.fstate, dst, cfg.heartbeat_ns, rt=_rt(cfg, w)
    )
    extra_time = jnp.where(won, now + hb, rt)
    extra_kind = jnp.where(won, jnp.int32(K_HEARTBEAT), jnp.int32(K_MSG))
    extra_pay = jnp.where(won, _pay(cfg, dst, get1(w2.lepoch, dst)), reply_pay)
    extra_on = won | (send_reply & ~won)
    # extra slot 2: the demoted ex-leader's fresh election timer
    retimeout = efaults.skewed_delay(
        fault_spec(cfg), w.fstate, dst,
        bounded(
            rand[2 * cfg.num_nodes + 2], cfg.election_lo_ns, cfg.election_hi_ns
        ),
        rt=_rt(cfg, w),
    )
    emits = _emits(
        cfg,
        bcast,
        (extra_time, extra_kind, extra_pay, extra_on),
        (now + retimeout, K_ELECTION, _pay(cfg, dst, tgen_d), demoted),
    )
    # sent counts every attempted reply (like the broadcast path, which
    # counts all N-1 regardless of the link test); delivered only those
    # that passed the link test
    w2 = w2._replace(
        msgs_sent=w2.msgs_sent + sent + jnp.where(attempt_reply, 1, 0),
        msgs_delivered=w2.msgs_delivered + delivered + jnp.where(send_reply, 1, 0),
    )
    if window:
        piped_snap = pipe & (get2(out_pays, src, 1) == M_SNAP)
        w2 = w2._replace(snap=w2.snap._replace(
            installs_sent=w2.snap.installs_sent
            + _snaps_sent(cfg, won, dst, out_pays)
            + jnp.where(piped_snap, 1, 0)
        ))
    return w2, emits


def _on_fault(cfg: RaftConfig, w: RaftState, now, pay, rand):
    """One event of the compiled fault campaign (engine/faults.py). The
    shared interpreter updates liveness/pause masks and the LinkState;
    this handler adds the Raft-specific consequences:

    - crash: volatile state resets (role, votes, commit) while durable
      state (term, voted, log) survives — ref kill semantics
      task/mod.rs:347-364 — plus the amnesia wipe in ``volatile_state``
      mode; timer chains are invalidated by generation bumps.
    - pause: timer chains are invalidated the same way (the paused node's
      clock stops), but no state is lost.
    - restart/resume: a restarted (or resumed non-leader) node re-enters
      the election-timer chain; a resumed LEADER keeps its role, so it
      re-enters the heartbeat chain instead — as on the host tier, where
      ``Handle.resume`` lets the leader's tasks heartbeat on (a deposed
      leader's election timer comes from the demotion path in _on_msg).
    """
    action, victim = pay[0], pay[1]
    base = efaults.NetBase(cfg.lat_lo_ns, cfg.lat_hi_ns, cfg.loss_q32)
    links2, f2, e = efaults.on_event(
        _rt(cfg, w), base, w.links, w.fstate, action, victim
    )
    crashed, restarted, resumed = e.crashed, e.restarted, e.resumed
    stopped = crashed | e.paused  # the node's event chains must die
    revived = restarted | resumed  # the node needs a fresh timer chain

    rollback = {}
    if _shadow_nodes(cfg):
        # durability rollback (crash OR power_fail edge): the "durable"
        # state reverts to its synced shadow — an identity outside
        # slow-disk windows, where every mutation synced immediately.
        # Statically absent when the spec draws no stall windows (the
        # shadow planes are width-0 then).
        rollback = dict(
            term=set1(w.term, victim, get1(w.dur_term, victim), crashed),
            voted=set1(w.voted, victim, get1(w.dur_voted, victim), crashed),
            log_len=set1(
                w.log_len, victim, get1(w.dur_log_len, victim), crashed
            ),
            log_term=set1(
                w.log_term, victim, get1(w.dur_log_term, victim), crashed
            ),
        )
    w2 = w._replace(
        links=links2,
        fstate=f2,
        role=set1(w.role, victim, FOLLOWER, crashed | restarted),
        votes=set1(w.votes, victim, jnp.uint32(0), crashed),
        commit=set1(w.commit, victim, 0, crashed),
        tgen=set1(w.tgen, victim, get1(w.tgen, victim) + 1, stopped),
        lepoch=set1(w.lepoch, victim, get1(w.lepoch, victim) + 1, stopped),
        last_hb=set1(w.last_hb, victim, now, revived),
        **rollback,
    )
    if cfg.volatile_state:
        # amnesia mode: the "durable" state dies with the process too
        # (what host-tier code that keeps everything in memory does) —
        # the shadows are wiped as well, else the NEXT crash would
        # resurrect pre-amnesia state out of them
        zlog = jnp.zeros((cfg.log_cap,), jnp.int32)
        w2 = w2._replace(
            term=set1(w2.term, victim, 0, crashed),
            voted=set1(w2.voted, victim, -1, crashed),
            log_len=set1(w2.log_len, victim, 0, crashed),
            log_term=set1(w2.log_term, victim, zlog, crashed),
        )
        if _shadow_nodes(cfg):
            w2 = w2._replace(
                dur_term=set1(w2.dur_term, victim, 0, crashed),
                dur_voted=set1(w2.dur_voted, victim, -1, crashed),
                dur_log_len=set1(w2.dur_log_len, victim, 0, crashed),
                dur_log_term=set1(w2.dur_log_term, victim, zlog, crashed),
            )
    if cfg.snapshot_every:
        # the snapshot is durable (wiped only in amnesia mode); a crashed
        # node's commit and state machine restart from it
        sn = w2.snap
        if cfg.volatile_state:
            sn = sn._replace(
                log_cmd=set1(
                    sn.log_cmd, victim, jnp.zeros((cfg.log_cap,), jnp.int32),
                    crashed,
                ),
                snap_idx=set1(sn.snap_idx, victim, 0, crashed),
                snap_term=set1(sn.snap_term, victim, 0, crashed),
                snap_digest=set1(sn.snap_digest, victim, jnp.uint32(0), crashed),
            )
        w2 = w2._replace(
            commit=set1(w2.commit, victim, get1(sn.snap_idx, victim), crashed),
            snap=sn._replace(
                digest=set1(
                    sn.digest, victim, get1(sn.snap_digest, victim), crashed
                )
            ),
        )
    timeout = efaults.skewed_delay(
        fault_spec(cfg), f2, victim,
        bounded(rand[0], cfg.election_lo_ns, cfg.election_hi_ns),
        rt=_rt(cfg, w),
    )
    still_leader = get1(w2.role, victim) == LEADER  # only a resumed leader
    hb = efaults.skewed_delay(
        fault_spec(cfg), f2, victim, cfg.heartbeat_ns, rt=_rt(cfg, w)
    )
    emits = _emits(
        cfg,
        _no_bcast(cfg),
        (
            now + timeout,
            K_ELECTION,
            _pay(cfg, victim, get1(w2.tgen, victim)),
            revived & ~still_leader,
        ),
        (
            now + hb,
            K_HEARTBEAT,
            _pay(cfg, victim, get1(w2.lepoch, victim)),
            resumed & still_leader,
        ),
    )
    return w2, emits


def _on_cmd(cfg: RaftConfig, w: RaftState, now, pay, rand):
    """A client command looking for the leader: if the target node is a
    live leader with log room, append an entry of its term; otherwise
    retry against the next node after cmd_retry_ns. On a window log the
    entry carries the command's value (this event's first draw). With
    ``rounds`` the command is one of a chain: ``more`` commands follow
    it, the next ``CMD_GAP_NS`` after a leader accepted this one (sent
    to that leader) or it hit the retry cap."""
    target, retries = pay[0], pay[1]
    is_leader = get1(efaults.up(w.fstate), target) & (
        get1(w.role, target) == LEADER
    )
    slot = get1(w.log_len, target) + 1
    if cfg.snapshot_every:
        room = slot - get1(w.snap.snap_idx, target) <= cfg.log_cap
        ring_slot = jnp.remainder(slot, cfg.log_cap)
    else:
        room = slot < cfg.log_cap
        ring_slot = slot
    accept = is_leader & room
    w2 = w._replace(
        log_term=set2(w.log_term, target, ring_slot, get1(w.term, target), accept),
        log_len=set1(w.log_len, target, slot, accept),
        log_overflow=w.log_overflow | (is_leader & ~room),
        accepted_cmds=w.accepted_cmds + jnp.where(accept, 1, 0),
    )
    if cfg.snapshot_every:
        w2 = w2._replace(snap=w2.snap._replace(
            log_cmd=set2(w2.snap.log_cmd, target, ring_slot, _as_i32(rand[0]), accept)
        ))
    next_target = (target + 1) % cfg.num_nodes
    give_up = ~accept & (retries + 1 >= cfg.cmd_max_retries)
    w2 = w2._replace(cmd_giveups=w2.cmd_giveups + jnp.where(give_up, 1, 0))
    follow = (
        now + cfg.cmd_retry_ns,
        K_CMD,
        _pay(cfg, next_target, retries + 1),
        ~accept & ~give_up,
    )
    if cfg.rounds:
        more = pay[2]
        done = accept | give_up
        follow = (
            jnp.where(done, now + CMD_GAP_NS, now + cfg.cmd_retry_ns),
            K_CMD,
            jnp.where(
                done,
                _pay(cfg, jnp.where(accept, target, next_target), 0, more - 1),
                _pay(cfg, next_target, retries + 1, more),
            ),
            ~done | (more > 0),
        )
    emits = _emits(cfg, _no_bcast(cfg), follow, _DISABLED_EXTRA)
    return w2, emits


def _on_round(cfg: RaftConfig, w: RaftState, now, pay, rand):
    """Round ``r`` of the command/crash plan (Lab 2D's ``snapcommon``
    with ``crash``): crash a uniformly drawn node now and restart it
    after a down-time in ``[restart_lo_ns, restart_hi_ns)``; send a chain
    of ``1 + burst`` commands now (the command committed without the
    victim, then the burst) and one command after the restart; arm
    round ``r + 1`` at ``(r + 1) * ROUND_NS``."""
    r = pay[0]
    n = cfg.num_nodes
    victim = bounded(rand[0], 0, n).astype(jnp.int32)
    up_at = now + bounded(rand[1], cfg.restart_lo_ns, cfg.restart_hi_ns)
    half = cfg.snapshot_every // 2
    burst = bounded(rand[2], half, half + cfg.snapshot_every)

    def fault(action, t):
        # the fault stream's payload layout (engine/faults.compile_device)
        return (
            t, K_FAULT,
            _pay(cfg, action, victim, t & 0x7FFF_FFFF, t >> 31),
            True,
        )

    def cmd(t, u, more):
        return (t, K_CMD, _pay(cfg, bounded(u, 0, n), 0, more), True)

    slots = [
        fault(efaults.F_CRASH, now),
        fault(efaults.F_RESTART, up_at),
        cmd(now + CMD_GAP_NS, rand[3], burst),
        cmd(up_at + CMD_GAP_NS, rand[4], 0),
        (
            (r.astype(jnp.int64) + 1) * ROUND_NS, K_ROUND,
            _pay(cfg, r + 1), r < cfg.rounds,
        ),
    ]
    slots += [_DISABLED_EXTRA] * (n + 2 - len(slots))
    return w, _common.pack_extras(payload_slots(cfg), *slots)


def cover_bits(cfg: RaftConfig) -> int:
    """Size of the coverage bitmap: one bit per (event kind, node, role
    transition) plus one bit per violation flavor."""
    flavors = 3 if cfg.snapshot_every else 2
    return n_kinds(cfg) * cfg.num_nodes * N_ROLE_TRANS + flavors


def _cover(cfg: RaftConfig, wb: RaftState, wa: RaftState, now, kind, pay):
    """Map one dispatched event to its coverage bit (engine contract:
    ``Workload.cover``). The bit is (kind x node x role-transition) — the
    swarm-testing signal: a campaign that makes a node take a role
    transition under an event kind no earlier spec reached lights a new
    bit. A newly latched violation flavor claims the event's bit instead
    (flavor bits are the rarest, most valuable coverage)."""
    node = jnp.where(kind == K_FAULT, pay[1], pay[0])
    node = jnp.clip(node, 0, cfg.num_nodes - 1)
    trans = get1(wb.role, node) * 3 + get1(wa.role, node)
    bit = (kind * cfg.num_nodes + node) * N_ROLE_TRANS + trans
    base = n_kinds(cfg) * cfg.num_nodes * N_ROLE_TRANS
    new_viol = wa.viol_kind & ~wb.viol_kind
    flavor = jnp.where((new_viol & V_ELECTION) != 0, 0, 1)
    if cfg.snapshot_every:
        flavor = jnp.where((new_viol & (V_ELECTION | V_COMMIT)) != 0, flavor, 2)
    return jnp.where(new_viol != 0, base + flavor, bit)


def _probe(w: RaftState):
    """Violation-flavor bitmask (engine contract: ``Workload.probe``) —
    recorded per step by ``run_traced`` so triage can locate the first
    violating event."""
    return w.viol_kind


def _record(cfg: RaftConfig, wb: RaftState, wa: RaftState, now, kind, pay):
    """Map one dispatched event to its op-history record (engine
    contract: ``Workload.record`` — at most ONE row per event).

    Raft records leadership: each won election appends one OP_ELECT
    *invoke* row (client = winner node, key = the won term, inp = the
    node again; there is no client-observed completion, so the op stays
    open — ``oracle.specs.ElectionSpec`` is a structural check over
    invoke rows). The host tier records the same rows through
    ``HostRecorder`` in ``examples/raft_host.py``, so one sequential
    spec checks both tiers (explore/differential.py)."""
    won = wa.elections > wb.elections
    # the only win sites are K_MSG handlers, where pay[0] is the winner
    node = jnp.clip(pay[0], 0, cfg.num_nodes - 1)
    term = get1(wa.term, node)
    rec = jnp.stack(
        [
            node,
            jnp.full((), OP_ELECT * 2 + PH_INVOKE, jnp.int32),
            term,
            node,
            wb.elections,  # opid: the global election counter
        ]
    )
    return rec, won


def _handle(cfg: RaftConfig, w: RaftState, now, kind, pay, rand):
    branches = [
        partial(_on_election_timer, cfg),
        partial(_on_heartbeat_timer, cfg),
        partial(_on_msg, cfg),
        partial(_on_fault, cfg),
        partial(_on_cmd, cfg),
    ]
    if cfg.rounds:
        branches.append(partial(_on_round, cfg))
    w2, emits = jax.lax.switch(kind, branches, w, now, pay, rand)
    # durability plane: fsync-on-mutate — after every event each node's
    # synced shadow catches up to the live durable state UNLESS a
    # slow-disk window holds its fsync (engine/faults.stalled), in which
    # case the shadow freezes and a crash/power_fail rolls back to it.
    # One vectorized masked write per event, statically gated off (with
    # width-0 planes) for specs that draw no stall windows.
    if _shadow_nodes(cfg):
        sync = ~efaults.stalled(w2.fstate)
        w2 = w2._replace(
            dur_term=jnp.where(sync, w2.term, w2.dur_term),
            dur_voted=jnp.where(sync, w2.voted, w2.dur_voted),
            dur_log_len=jnp.where(sync, w2.log_len, w2.dur_log_len),
            dur_log_term=jnp.where(sync[:, None], w2.log_term, w2.dur_log_term),
        )
    return w2, emits


def _init(cfg: RaftConfig, key, params=None):
    n = cfg.num_nodes
    ninit = n + cfg.commands
    # init draws live in their own counter namespace, disjoint from the
    # per-event stream (event counters stay far below 2**31) and from the
    # fault-schedule namespace (engine/faults.FAULT_STREAM)
    rand = jax.random.bits(
        jax.random.fold_in(key, 0x7FFF_FFFF),
        (n + 2 * cfg.commands,),
        dtype=jnp.uint32,
    )
    w = RaftState(
        role=jnp.zeros((n,), jnp.int32),
        term=jnp.zeros((n,), jnp.int32),
        voted=jnp.full((n,), -1, jnp.int32),
        votes=jnp.zeros((n,), jnp.uint32),
        fstate=efaults.init_state(n),
        last_hb=jnp.zeros((n,), jnp.int64),
        tgen=jnp.zeros((n,), jnp.int32),
        lepoch=jnp.zeros((n,), jnp.int32),
        log_term=jnp.zeros((n, cfg.log_cap), jnp.int32),
        log_len=jnp.zeros((n,), jnp.int32),
        dur_term=jnp.zeros((_shadow_nodes(cfg),), jnp.int32),
        dur_voted=jnp.full((_shadow_nodes(cfg),), -1, jnp.int32),
        dur_log_term=jnp.zeros((_shadow_nodes(cfg), cfg.log_cap), jnp.int32),
        dur_log_len=jnp.zeros((_shadow_nodes(cfg),), jnp.int32),
        commit=jnp.zeros((n,), jnp.int32),
        next_idx=jnp.ones((n, n), jnp.int32),
        match_idx=jnp.zeros((n, n), jnp.int32),
        links=enet.make(
            n, cfg.loss_q32, cfg.lat_lo_ns, cfg.lat_hi_ns, cfg.buggify_q32
        ),
        hist_term=jnp.zeros((cfg.history,), jnp.int32),
        hist_node=jnp.zeros((cfg.history,), jnp.int32),
        hist_valid=jnp.zeros((cfg.history,), bool),
        hist_pos=jnp.zeros((), jnp.int32),
        chist_term=jnp.zeros((cfg.log_cap,), jnp.int32),
        chist_set=jnp.zeros((cfg.log_cap,), bool),
        violation=jnp.zeros((), bool),
        viol_kind=jnp.zeros((), jnp.int32),
        log_overflow=jnp.zeros((), bool),
        elections=jnp.zeros((), jnp.int32),
        commits=jnp.zeros((), jnp.int32),
        accepted_cmds=jnp.zeros((), jnp.int32),
        cmd_giveups=jnp.zeros((), jnp.int32),
        msgs_sent=jnp.zeros((), jnp.int32),
        msgs_delivered=jnp.zeros((), jnp.int32),
        frt=efaults.make_rt(fault_spec(cfg), params),
        snap=_snap_init(cfg),
    )
    times = jnp.zeros((ninit,), jnp.int64)
    kinds = jnp.zeros((ninit,), jnp.int32)
    pays = jnp.zeros((ninit, payload_slots(cfg)), jnp.int32)
    enables = jnp.ones((ninit,), bool)
    # one election timer per node
    for i in range(n):
        times = times.at[i].set(bounded(rand[i], cfg.election_lo_ns, cfg.election_hi_ns))
        kinds = kinds.at[i].set(K_ELECTION)
        pays = pays.at[i].set(_pay(cfg, i, 0))
    # client command plan
    for k in range(cfg.commands):
        t_cmd = bounded(rand[n + 2 * k], 0, cfg.cmd_window_ns)
        target = bounded(rand[n + 2 * k + 1], 0, n).astype(jnp.int32)
        times = times.at[n + k].set(t_cmd)
        kinds = kinds.at[n + k].set(K_CMD)
        pays = pays.at[n + k].set(_pay(cfg, target, 0))
    # fault campaign: the shared compiler's event stream, spliced in
    fe = efaults.compile_device(
        fault_spec(cfg), n, key, K_FAULT, payload_slots(cfg), params=params
    )
    parts = [(times, kinds, pays, enables), tuple(fe)]
    if cfg.rounds:
        # the first round; each round arms the next
        parts.append(tuple(_common.pack_extras(
            payload_slots(cfg), (ROUND_NS, K_ROUND, _pay(cfg, 1), True)
        )))
    return w, Emits(*(jnp.concatenate(cols) for cols in zip(*parts)))


def _snap_init(cfg: RaftConfig):
    if not cfg.snapshot_every:
        return ()
    n, cap = cfg.num_nodes, cfg.log_cap
    i32 = partial(jnp.zeros, dtype=jnp.int32)
    u32 = partial(jnp.zeros, dtype=jnp.uint32)
    return SnapState(
        log_cmd=i32((n, cap)), snap_idx=i32((n,)), snap_term=i32((n,)),
        snap_digest=u32((n,)), digest=u32((n,)), chist_idx=i32((cap,)),
        sdig_idx=i32((cap,)), sdig_val=u32((cap,)), snapshots=i32(()),
        installs_sent=i32(()), installs=i32(()),
    )


def history_spec():
    """The sequential spec this model's recorded histories check
    against (oracle/specs.ElectionSpec) — also the key the device
    screen dispatches on (oracle/screen.screen_for), so a checked sweep
    needs no per-call-site spec plumbing."""
    from ..oracle.specs import ElectionSpec

    return ElectionSpec()


@_common.memoized_workload(RaftConfig)
def workload(cfg: RaftConfig = None) -> Workload:
    """Build the engine Workload for a Raft sweep configuration
    (memoized per config — see _common.memoized_workload)."""
    if cfg.rounds and (cfg.num_nodes < 3 or not cfg.snapshot_every):
        raise ValueError("rounds need snapshot_every and num_nodes >= 3 "
                         "(a round emits 5 events)")
    if cfg.snapshot_every and not 0 < cfg.snapshot_every < cfg.log_cap:
        raise ValueError("snapshot_every must lie in (0, log_cap)")
    if cfg.snapshot_every and _shadow_nodes(cfg):
        raise ValueError("log compaction has no durability shadow: no fsync stalls")
    return Workload(
        init=partial(_init, cfg),
        handle=partial(_handle, cfg),
        num_rand=2 * cfg.num_nodes + 3,
        payload_slots=payload_slots(cfg),
        max_emits=cfg.num_nodes + 2,
        cover=partial(_cover, cfg),
        cover_bits=cover_bits(cfg),
        probe=_probe,
        record=partial(_record, cfg) if cfg.hist_slots > 0 else None,
        hist_slots=cfg.hist_slots,
        event_mix_kinds=n_kinds(cfg) if cfg.event_mix else 0,
    )


def engine_config(cfg: RaftConfig = RaftConfig(), **overrides) -> EngineConfig:
    """Engine parameters sized for this workload.

    Queue sizing: steady state holds ≤1 election timer + ≤1 heartbeat
    timer per node, ≤1 in-flight broadcast (N-1 messages) per node plus
    replies, and the pending fault/command plan. 2N² + plans covers that
    with ~2x headroom (measured high-water at N=5 is ~30; overflow is a
    sticky per-seed flag and ``qmax`` reports the real high-water mark,
    so an undersized queue is observable, never silent)."""
    defaults = dict(
        queue_capacity=max(
            48,
            2 * cfg.num_nodes * cfg.num_nodes
            + cfg.commands
            + efaults.num_events(fault_spec(cfg))
            + (5 if cfg.rounds else 0),  # a round's pending events
        ),
        time_limit_ns=10_000_000_000,
        max_steps=200_000,
    )
    defaults.update(overrides)
    return EngineConfig(**defaults)


# one jitted device program for the whole summary (one transfer) — see
# _common.make_sweep_summary
_SUMMARY_FIELDS = (
    ("violations", lambda f: f.wstate.violation),
    ("elections_total", lambda f: f.wstate.elections),
    ("no_leader_seeds", lambda f: f.wstate.elections == 0),
    ("commits_total", lambda f: f.wstate.commits),
    ("accepted_cmds", lambda f: f.wstate.accepted_cmds),
    ("cmd_giveups", lambda f: f.wstate.cmd_giveups),
    ("log_overflow_seeds", lambda f: f.wstate.log_overflow),
    ("msgs_sent", lambda f: f.wstate.msgs_sent),
    ("msgs_delivered", lambda f: f.wstate.msgs_delivered),
)
_summary = _common.make_sweep_summary(_SUMMARY_FIELDS)
_summary_snap = _common.make_sweep_summary(
    _SUMMARY_FIELDS
    + (
        ("snapshots", lambda f: f.wstate.snap.snapshots),
        ("installs_sent", lambda f: f.wstate.snap.installs_sent),
        ("installs", lambda f: f.wstate.snap.installs),
    )
)


def sweep_summary(final, limit=None) -> dict:
    """``_common.make_sweep_summary``'s reduction of a finished sweep;
    a config with log compaction adds ``snapshots``, ``installs_sent``
    and ``installs`` (summed over nodes and seeds)."""
    fn = _summary_snap if isinstance(final.wstate.snap, SnapState) else _summary
    return fn(final, limit)


sweep_summary.supports_limit = True
