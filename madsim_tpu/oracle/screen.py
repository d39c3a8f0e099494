"""Vectorized on-device history screening — the oracle's first pass.

The WGL checker (oracle/check.py) is per-seed host Python: decode ~a
hundred rows, search linearizations. At 100k+ seeds the checker, not the
engine, is the wall-clock bound of a checked sweep. This module moves a
conservative first pass onto the device: per-key quick-checks computed
as masked reductions over the SoA history plane (``EngineState.hist_*``)
of a finished chunk, yielding one bool per seed — *suspect* or
*provably boring*. Full decoding + WGL search then runs only on the
suspect lanes.

The contract is CONSERVATISM: the suspect set must be a superset of the
seeds the full checker would reject, so skipping the clean lanes never
hides a violation. Each screen is therefore built from conditions of
the form "flag unless this observation is provably explainable":

- ``kv`` (etcd register spec): EXACT within a contention window — the
  screen decides single-key register linearizability outright
  (``kv_window_suspect``: value clusters, a writes-before-reads
  2-cycle test, and an absent-read pass — see its docstring for the
  argument) and falls back to "suspect" only when some key's op
  contention exceeds ``KV_WINDOW`` concurrent ops, so a flagged lane
  is either a real violation or an over-budget window. Duplicate
  written values, re-invoked opids and DEL rows defeat the
  value-identity reasoning, so their mere presence flags the seed (the
  bundled etcd model records none of them).
- ``log`` (kafka ordered-log spec): a completed FETCH at offset ``o``
  serving ``n`` records is flagged when fewer than ``o + n`` PRODUCE
  invocations preceded its completion, or when it breaks per-consumer
  offset contiguity (the exact structural pre-check of
  ``specs.LogSpec``, which appends OK rows in completion order).
- ``election`` (raft): two ELECT rows naming different winners for one
  term — exactly ``specs.ElectionSpec.structural``, so this screen is
  precise (no false positives, no misses).

Unknown op kinds, DEL rows, and OK rows with no recorded invoke flag
the seed wholesale: a row the screen cannot reason about must not be
silently trusted. Overflowed histories screen their valid prefix — the
same prefix the checker checks (the buffer never wraps).

What the screen can NOT do is *prove* a violation: a flagged seed is a
candidate, and only the WGL search's verdict counts. The false-positive
rate on clean sweeps is bounded by construction (most conditions are
exact necessary-condition checks; tests/test_screen.py pins it <5%),
which is what makes screening a throughput win rather than a shortcut.

Everything here is jittable JAX over int32/int64 planes — [H, H]
pairwise masks reduced per seed, vmapped over lanes in blocks — so the
screen of a 16k-seed chunk is one device program, enqueued right behind
the chunk's sweep (engine/checkpoint.run_sweep_pipelined overlaps the
host-side checking of chunk N with the device sweep of chunk N+1).
"""

from __future__ import annotations

from functools import lru_cache
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .history import (
    OP_ELECT,
    OP_FETCH,
    OP_GET,
    OP_PRODUCE,
    OP_PUT,
    PH_INVOKE,
    PH_OK,
)
from .specs import ABSENT

# int64 sentinels: "no such time" below/above any virtual timestamp
_T_NEG = jnp.int64(-(1 << 62))
_T_INF = jnp.int64(1 << 62)
_I32_MIN = jnp.int32(-(1 << 31))


def _cols(rec, t, n):
    """Split one seed's raw rows into masked columns."""
    H = rec.shape[0]
    idx = jnp.arange(H, dtype=jnp.int32)
    valid = idx < jnp.asarray(n, jnp.int32)
    client, code, key, val, opid = (rec[:, i] for i in range(5))
    op, ph = code // 2, code % 2
    return idx, valid, client, op, ph, key, val, opid, jnp.asarray(t)


def _invoke_join(idx, valid, client, op, ph, opid, t):
    """For every OK row, the time of its invoke row (and the pair mask).

    The decoder pairs an OK row with the LATEST earlier matching invoke
    (kafka produce retries re-invoke one opid), so the join takes the
    max time over candidates. Rows with no match get ``_T_NEG`` —
    callers flag those (an OK without an invoke is a contract breach the
    decoder would raise on)."""
    pair = (
        (valid & (ph == PH_OK))[:, None]
        & (valid & (ph == PH_INVOKE))[None, :]
        & (client[:, None] == client[None, :])
        & (op[:, None] == op[None, :])
        & (opid[:, None] == opid[None, :])
        & (idx[None, :] < idx[:, None])
    )
    inv_t = jnp.max(jnp.where(pair, t[None, :], _T_NEG), axis=1)
    return inv_t, pair


def kv_suspect(rec, t, n) -> jnp.ndarray:
    """One seed's suspect bit under the KV register spec (etcd) — the
    ORIGINAL necessary-condition screen, superseded as the registered
    ``kv`` screen by the exact ``kv_window_suspect`` (kept for
    comparison: tests pin that the new screen's suspect set is a
    subset of this one's on clean sweeps and still ⊇ the checker's
    rejections)."""
    idx, valid, client, op, ph, key, val, opid, t = _cols(rec, t, n)
    inv_t, _ = _invoke_join(idx, valid, client, op, ph, opid, t)

    put_inv = valid & (op == OP_PUT) & (ph == PH_INVOKE)
    put_ok = valid & (op == OP_PUT) & (ph == PH_OK)
    get_ok = valid & (op == OP_GET) & (ph == PH_OK)
    obs_ok = put_ok | get_ok

    # rows the value-identity reasoning cannot cover flag the seed
    unscreenable = jnp.any(valid & ~((op == OP_PUT) | (op == OP_GET)))
    orphan = jnp.any((valid & (ph == PH_OK)) & (inv_t == _T_NEG))

    same_key = key[:, None] == key[None, :]

    # two distinct PUT invokes of one (key, value): value identity no
    # longer names a unique write — flag (values are random 31-bit
    # draws in the bundled model, so this is vanishingly rare)
    dup = jnp.any(
        put_inv[:, None]
        & put_inv[None, :]
        & same_key
        & (val[:, None] == val[None, :])
        & (idx[:, None] < idx[None, :])
    )

    # commit time of the unique PUT that wrote (key_i, out_i); an
    # unacked (open) write commits "never" — nothing can be proven to
    # follow it, so the freshness conditions below stay quiet
    wrote = put_ok[None, :] & same_key & (val[:, None] == val[None, :])
    cmp_v = jnp.where(
        jnp.any(wrote, axis=1),
        jnp.max(jnp.where(wrote, t[None, :], _T_NEG), axis=1),
        _T_INF,
    )

    ti = inv_t  # a GET-OK row's invoke time
    tc = t  # ... and its completion time (the row's own stamp)

    # ABSENT read after some PUT on the key definitely committed (the
    # recorded keys are never deleted — DEL rows flag above)
    bad_absent = (val == ABSENT) & jnp.any(
        put_ok[None, :] & same_key & (t[None, :] < ti[:, None]), axis=1
    )
    # observed value that no PUT even invoked before the read returned
    no_writer = (val != ABSENT) & ~jnp.any(
        put_inv[None, :]
        & same_key
        & (val[:, None] == val[None, :])
        & (t[None, :] <= tc[:, None]),
        axis=1,
    )
    # a fresher observation: some completed op on the key observed or
    # wrote a DIFFERENT value, began after this read's value committed,
    # and finished before this read began — in every linearization that
    # op sits between the read's write and the read, so the read is
    # provably stale (unique values; duplicates flag above)
    fresher = (val != ABSENT) & jnp.any(
        obs_ok[None, :]
        & same_key
        & (val[:, None] != val[None, :])
        & (t[None, :] < ti[:, None])
        & (inv_t[None, :] > cmp_v[:, None]),
        axis=1,
    )
    bad = get_ok & (bad_absent | no_writer | fresher)
    return jnp.any(bad) | dup | unscreenable | orphan


# contention budget of the exact kv screen: a key whose concurrent-op
# depth ever exceeds this many ops falls back to "suspect" (the [H, H]
# mask cost is paid regardless — the budget bounds the CLAIM, keeping
# the exactness argument checkable, not the compute)
KV_WINDOW = 32


def kv_window_suspect(rec, t, n, window: int = KV_WINDOW) -> jnp.ndarray:
    """One seed's suspect bit under the KV register spec — EXACT within
    a per-key contention window (the device-side linearizability
    decision; docs/oracle.md "Device-side checking").

    For a unique-value register history (duplicates/re-invokes flag
    wholesale below), group ops into value clusters ``C_v = {the PUT
    writing v} ∪ {completed GETs reading v}`` with ``m_v`` = earliest
    completion among completed cluster ops (∞ if none) and ``s_v`` =
    latest invoke in the cluster. The history is linearizable iff

    (A) every completed non-ABSENT read's value has a PUT invoked no
        later than the read completes (else no linearization can place
        the write before the read);
    (B) no completed non-ABSENT observation on a key completes strictly
        before an ABSENT read of that key invokes (else some write is
        forced before the read);
    (C) no two clusters on one key 2-cycle: ``¬∃ u ≠ v: m_u < s_v ∧
        m_v < s_u`` (an op of u completing before an op of v invokes
        forces u's write before v's in EVERY linearization — edge
        u→v; any cycle in that threshold digraph contains a 2-cycle,
        and acyclicity yields a valid linearization by topological
        order: ABSENT reads first, then each cluster's write followed
        by its reads).

    Necessity of each condition is immediate; sufficiency is the
    threshold-digraph construction, with open writes that have readers
    placed at their block's start and open ops without observers
    omitted (the checker's optional-op semantics). Ties use strict
    ``<`` exactly where the WGL search does (a pending op may
    linearize before a completion at the same instant). This closes
    the old ``kv_suspect`` conservatism gap (concurrent-write
    flip-flops whose 2-cycle no single fresher-observation witnesses)
    AND eliminates its false positives: a clean lane under budget is
    *proven* clean, a flagged lane is a violation — unless the per-key
    concurrent-op depth exceeded ``window``, the wholesale budget
    fallback that keeps the claim honest without unbounded reasoning."""
    idx, valid, client, op, ph, key, val, opid, t = _cols(rec, t, n)
    inv_t, pair = _invoke_join(idx, valid, client, op, ph, opid, t)

    put_inv = valid & (op == OP_PUT) & (ph == PH_INVOKE)
    put_ok = valid & (op == OP_PUT) & (ph == PH_OK)
    get_ok = valid & (op == OP_GET) & (ph == PH_OK)
    obs_ok = put_ok | get_ok
    ok_row = valid & (ph == PH_OK)
    inv_row = valid & (ph == PH_INVOKE)

    # wholesale flags: rows the value-identity argument cannot cover
    unscreenable = jnp.any(valid & ~((op == OP_PUT) | (op == OP_GET)))
    orphan = jnp.any(ok_row & (inv_t == _T_NEG))
    same_client = client[:, None] == client[None, :]
    same_opid = opid[:, None] == opid[None, :]
    reinvoke = jnp.any(
        inv_row[:, None]
        & inv_row[None, :]
        & same_client
        & same_opid
        & (idx[:, None] < idx[None, :])
    )

    same_key = key[:, None] == key[None, :]
    same_val = val[:, None] == val[None, :]
    dup = jnp.any(
        put_inv[:, None]
        & put_inv[None, :]
        & same_key
        & same_val
        & (idx[:, None] < idx[None, :])
    )

    # an invoke row with a later matching OK row completed (re-invokes
    # flag above, so "any match" is exact here)
    claimed = jnp.any(pair, axis=0)
    open_inv = inv_row & ~claimed
    open_put = put_inv & ~claimed

    # (A) — also catches a read completing before its write invokes
    no_writer = (val != ABSENT) & ~jnp.any(
        put_inv[None, :] & same_key & same_val & (t[None, :] <= t[:, None]),
        axis=1,
    )
    bad_a = get_ok & no_writer

    # (B) — GET-OK evidence included (the old screen's bad_absent only
    # saw PUT-OK rows and missed read-witnessed writes)
    bad_b = (
        get_ok
        & (val == ABSENT)
        & jnp.any(
            obs_ok[None, :]
            & same_key
            & (val[None, :] != ABSENT)
            & (t[None, :] < inv_t[:, None]),
            axis=1,
        )
    )

    # (C) — cluster rows: completed observations of v plus open PUT
    # invokes of v. m is per-CLUSTER (min completed-observation time,
    # shared by every member row); s is per-ROW (its own invoke) — the
    # pairwise ∃ decouples, so ∃ rows (r, q): m_r < s_q ∧ m_q < s_r
    # iff the cluster-level 2-cycle ∃ u, v: m_u < s_v ∧ m_v < s_u
    rep = (obs_ok | open_put) & (val != ABSENT)
    memb = obs_ok[None, :] & same_key & same_val
    m = jnp.min(jnp.where(memb, t[None, :], _T_INF), axis=1)
    start = jnp.where(obs_ok, inv_t, t)
    cyc = jnp.any(
        rep[:, None]
        & rep[None, :]
        & same_key
        & ~same_val
        & (m[:, None] < start[None, :])
        & (m[None, :] < start[:, None])
    )

    # window budget: per-key concurrent-op depth at each op's invoke
    # (completed ops span [invoke, completion]; open ops never end)
    o_mask = ok_row | open_inv
    o_start = jnp.where(ok_row, inv_t, t)
    o_end = jnp.where(ok_row, t, _T_INF)
    depth = jnp.sum(
        (
            o_mask[:, None]
            & o_mask[None, :]
            & same_key
            & (o_start[None, :] <= o_start[:, None])
            & (o_start[:, None] <= o_end[None, :])
        ).astype(jnp.int32),
        axis=1,
    )
    over_budget = jnp.any(o_mask & (depth > jnp.int32(window)))

    return (
        jnp.any(bad_a | bad_b)
        | cyc
        | over_budget
        | dup
        | reinvoke
        | unscreenable
        | orphan
    )


def log_suspect(rec, t, n) -> jnp.ndarray:
    """One seed's suspect bit under the ordered-log spec (kafka)."""
    idx, valid, client, op, ph, key, val, opid, t = _cols(rec, t, n)
    inv_t, pair = _invoke_join(idx, valid, client, op, ph, opid, t)

    prod_inv = valid & (op == OP_PRODUCE) & (ph == PH_INVOKE)
    fetch_ok = valid & (op == OP_FETCH) & (ph == PH_OK)

    unscreenable = jnp.any(valid & ~((op == OP_PRODUCE) | (op == OP_FETCH)))
    orphan = jnp.any(fetch_ok & (inv_t == _T_NEG))

    same_key = key[:, None] == key[None, :]

    # each fetch's offset rides on its (latest matching) invoke row
    jlast = jnp.max(jnp.where(pair, idx[None, :], jnp.int32(-1)), axis=1)
    onehot = pair & (idx[None, :] == jlast[:, None])
    off = jnp.max(jnp.where(onehot, val[None, :], _I32_MIN), axis=1)
    served = val  # a FETCH-OK row's val column is the records served

    # overread: serving past every append that could precede it — each
    # PRODUCE op (retries included: the spec counts them as separate
    # appends) invoked before this fetch completed may linearize first
    navail = jnp.sum(
        (prod_inv[None, :] & same_key & (t[None, :] <= t[:, None])).astype(
            jnp.int32
        ),
        axis=1,
    )
    overread = fetch_ok & (off + served > navail)

    # per-consumer committed-offset contiguity, in completion order (OK
    # rows append at completion, so row order IS completion order) —
    # exactly specs.LogSpec.structural
    prevm = (
        fetch_ok[:, None]
        & fetch_ok[None, :]
        & same_key
        & (client[:, None] == client[None, :])
        & (idx[None, :] < idx[:, None])
    )
    jprev = jnp.max(jnp.where(prevm, idx[None, :], jnp.int32(-1)), axis=1)
    sel_prev = prevm & (idx[None, :] == jprev[:, None])
    prev_off = jnp.max(jnp.where(sel_prev, off[None, :], _I32_MIN), axis=1)
    prev_served = jnp.max(jnp.where(sel_prev, val[None, :], _I32_MIN), axis=1)
    expect = jnp.where(jprev >= 0, prev_off + prev_served, jnp.int32(0))
    gap = fetch_ok & (off != expect)

    return jnp.any(overread | gap) | unscreenable | orphan


def election_suspect(rec, t, n) -> jnp.ndarray:
    """One seed's suspect bit under the election spec (raft) — precise:
    two ELECT rows naming different winners for one term, exactly
    ``specs.ElectionSpec.structural``."""
    idx, valid, client, op, ph, key, val, opid, t = _cols(rec, t, n)
    elect = valid & (op == OP_ELECT) & (ph == PH_INVOKE)
    unscreenable = jnp.any(valid & ~(op == OP_ELECT))
    split = jnp.any(
        elect[:, None]
        & elect[None, :]
        & (key[:, None] == key[None, :])
        & (val[:, None] != val[None, :])
    )
    return split | unscreenable


_SCREENS = {
    "kv": kv_window_suspect,
    "log": log_suspect,
    "election": election_suspect,
}


def screen_for(spec) -> Optional[Callable]:
    """The per-seed screen function for a sequential spec, by its
    ``name`` — or None when no screen exists (callers must then treat
    every seed as suspect)."""
    return _SCREENS.get(getattr(spec, "name", None))


@lru_cache(maxsize=None)
def _batched(name: str):
    return jax.jit(jax.vmap(_SCREENS[name]))


@lru_cache(maxsize=None)
def _batched_sharded(name: str, mesh, block: int):
    """The per-seed screen shard_map'd over the mesh's seed axis: each
    device screens its LOCAL lanes (in ``block``-lane sub-batches, same
    [block, H, H] working-set bound as the unsharded path), so a chunk's
    screen program runs distributed right behind its sharded sweep with
    no cross-device traffic at all — the suspect mask stays sharded
    like the history planes it reduces. Cached per (spec, mesh, block):
    a fresh shard_map wrapper per chunk would retrace every call."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import SEED_AXIS

    f = jax.vmap(_SCREENS[name])

    def local(rec, t, n):
        s = rec.shape[0]
        if s <= block:
            return f(rec, t, n)
        return jnp.concatenate(
            [
                f(rec[lo : lo + block], t[lo : lo + block], n[lo : lo + block])
                for lo in range(0, s, block)
            ]
        )

    return jax.jit(
        jax.shard_map(
            local, mesh=mesh, in_specs=P(SEED_AXIS), out_specs=P(SEED_AXIS),
            check_vma=False,
        )
    )


def screen_history(rec, t, n, spec) -> bool:
    """Screen ONE seed's raw history rows (tests and replay tooling)."""
    fn = screen_for(spec)
    if fn is None:
        raise ValueError(f"no device screen for spec {spec.name!r}")
    return bool(
        fn(jnp.asarray(rec, jnp.int32), jnp.asarray(t, jnp.int64), int(n))
    )


def screen_sweep(final, spec, block: int = 1024, mesh=None) -> jnp.ndarray:
    """Suspect mask (bool[S], device array) for a finished batched sweep.

    ``block`` bounds the [block, H, H] pairwise-mask working set per
    launched program (H = hist_slots; 1024 lanes x 256 rows is ~67 MB of
    bool mask per term). The mask is NOT materialized to host — callers
    enqueue this right after the chunk's sweep and ``np.asarray`` it
    later, from the overlapped host phase.

    ``mesh`` runs the screen shard_map'd over the mesh's seed axis
    (``final`` sharded by ``parallel.run_sweep_sharded``; the batch must
    divide the mesh) — same bits per seed, distributed like the sweep
    that produced the planes."""
    fn = screen_for(spec)
    if fn is None:
        raise ValueError(
            f"no device screen for spec {getattr(spec, 'name', spec)!r}; "
            "pass screen=False (check every lane) instead"
        )
    S = int(final.seed.shape[0])
    if final.hist_rec.shape[1] == 0:
        # no recording plane: nothing to screen, nothing to check —
        # consistent with the checker accepting every empty history
        return jnp.zeros((S,), bool)
    if mesh is not None:
        return _batched_sharded(spec.name, mesh, block)(
            final.hist_rec, final.hist_t, final.hist_len
        )
    f = _batched(spec.name)
    if S <= block:
        return f(final.hist_rec, final.hist_t, final.hist_len)
    outs = [
        f(
            final.hist_rec[lo : lo + block],
            final.hist_t[lo : lo + block],
            final.hist_len[lo : lo + block],
        )
        for lo in range(0, S, block)
    ]
    return jnp.concatenate(outs)


class _HostWork:
    """The host phase of a screened checked sweep: decode the suspect
    lanes, dedup on canonical bytes, fan the WGL checker over a process
    pool, and fold the verdicts into per-chunk report dicts.

    Two consumption protocols over ONE pipeline:

    - **Sync** (``host_work(final, lo=..., ...)`` — the legacy callable
      shape every driver already speaks): submit + drain, returning the
      chunk's report dict.
    - **Incremental** (``submit`` / ``poll`` / ``drain`` — drivers that
      see ``incremental = True`` may use it, e.g.
      ``engine.stream.stream_sweep``): ``submit`` runs the cheap decode
      + dedup immediately and queues the WGL work; ``poll(seconds=...)``
      burns at most roughly that budget of checking (always making
      progress when work is pending) and returns the reports of chunks
      that FINISHED, as ``(lo, dict)`` in submission order; ``drain``
      finishes everything. The device thereby never stalls on the
      checker: unfinished verdict work carries across rounds and the
      driver merges reports strictly in submission order.

    Suspect lanes are deduplicated before checking: identical histories
    across seeds are common under coarse faults, and the WGL verdict
    depends only on the seed-free, time-rank canonical encoding
    (``history.history_canonical_bytes`` — an order-isomorphism on the
    timestamps the checker reads only through comparisons). One
    representative per equivalence class (first occurrence, lane order)
    is checked; its verdict fans back to every member, and the report
    carries the class count as ``hist_unique``.

    ``device_decode=True`` sources the canonical rows from the on-device
    decode kernel (``history.canon_sweep``) instead of per-row host
    Python: one fixed-shape jitted program derives every lane's paired +
    rank-encoded rows, the host gathers just the suspect rows and hashes
    them, and only dedup REPRESENTATIVES are materialized as ``History``
    objects (from the canonical rows themselves — rank times, same WGL
    verdict). The two paths produce bit-identical canonical bytes (the
    kernel's contract, gated by scripts/check_determinism.sh) and hence
    bit-identical reports; lanes whose rows breach the record-hook
    contract fall back to the host decoder, which raises the diagnostic.

    Determinism contract: every report dict is a pure function of its
    chunk's history planes — worker count, poll cadence and decode path
    change wall-clock only, never a byte (results are ordered by lane,
    dedup keys on content, each verdict is a pure function of one
    history, and checking is sliced in submission order).
    ``telemetry`` (``obs.Telemetry`` or None) records the suspect rate,
    the canonical-dedup ratio, WGL pool utilization, check wall time
    and budget exhaustion per chunk — out-of-band, never a report
    byte."""

    incremental = True

    def __init__(
        self, spec, max_states, workers, max_recorded, telemetry,
        device_decode,
    ):
        from collections import deque

        self._spec = spec
        self._max_states = max_states
        self._workers = workers
        self._max_recorded = max_recorded
        self._telemetry = telemetry
        self._device_decode = device_decode
        # WGL slice granularity: big enough to keep a pool's workers
        # busy per slice, small enough that a poll budget is respected
        # within ~one slice. Scheduling-only — never affects a report
        self._step = max(8, 4 * max(1, workers))
        self._q: deque = deque()

    def __call__(self, final, *, lo, n, seeds, suspect, summary):
        self.submit(
            final, lo=lo, n=n, seeds=seeds, suspect=suspect,
            summary=summary,
        )
        return self.drain()[-1][1]

    def submit(self, final, *, lo, n, seeds, suspect, summary) -> None:
        """Decode + dedup one chunk now; queue its WGL work."""
        import hashlib
        import time as _time

        from .history import (
            canon_sweep,
            canonical_bytes_from_rows,
            decode_lanes,
            history_canonical_bytes,
            history_from_canon,
        )

        del seeds, summary
        t0 = _time.perf_counter()
        n = int(n)
        if suspect is None:
            lanes = np.arange(n)
        else:
            lanes = np.nonzero(np.asarray(suspect)[:n])[0]
        rep: dict = {}  # canonical hash -> index into reps
        reps: list = []
        keys: list = []
        lane_seeds: list = []
        if self._device_decode and int(final.hist_rec.shape[1]) > 0:
            canon, n_ops, breach = canon_sweep(final)
            total = int(final.seed.shape[0])
            if lanes.size and lanes.size * 4 <= total:
                # sparse selection: gather device-side (decode_lanes'
                # transfer-sizing rule), positions then index the gather
                planes = (
                    canon[lanes], n_ops[lanes], breach[lanes],
                    final.hist_len[lanes], final.hist_overflow[lanes],
                    final.seed[lanes],
                )
                pos = np.arange(lanes.size)
            else:
                planes = (
                    canon, n_ops, breach, final.hist_len,
                    final.hist_overflow, final.seed,
                )
                pos = lanes
            rows_c, nops_h, br_h, len_h, ov_h, seed_h = (
                np.asarray(p) for p in planes
            )
            for j, p in enumerate(pos):
                if br_h[p]:
                    # record-hook contract breach: the host decoder
                    # raises the real diagnostic for this lane
                    decode_lanes(final, [int(lanes[j])])
                    raise RuntimeError(
                        f"device canonical decode flagged lane "
                        f"{int(lanes[j])} but the host decoder "
                        "accepted it"
                    )
                keys.append(
                    hashlib.sha256(
                        canonical_bytes_from_rows(
                            rows_c[p], nops_h[p], len_h[p], ov_h[p]
                        )
                    ).digest()
                )
                lane_seeds.append(int(seed_h[p]))
                if keys[-1] not in rep:
                    rep[keys[-1]] = len(reps)
                    reps.append(
                        history_from_canon(
                            rows_c[p], nops_h[p], ov_h[p], len_h[p],
                            seed=lane_seeds[-1],
                        )
                    )
        else:
            hists = decode_lanes(final, lanes)
            for h in hists:
                k = hashlib.sha256(history_canonical_bytes(h)).digest()
                keys.append(k)
                lane_seeds.append(int(h.seed))
                if k not in rep:
                    rep[k] = len(reps)
                    reps.append(h)
        if self._telemetry is not None:
            self._telemetry.count("oracle_screened_total", n)
            self._telemetry.count("oracle_suspects_total", int(lanes.size))
            self._telemetry.count("oracle_unique_total", len(reps))
            self._telemetry.gauge(
                "oracle_suspect_rate", lanes.size / max(n, 1),
                help="suspect lanes / screened lanes, last chunk",
            )
            if lanes.size:
                self._telemetry.gauge(
                    "oracle_dedup_ratio", len(reps) / lanes.size,
                    help="unique canonical histories / suspects "
                    "(lower = more dedup wins)",
                )
        self._q.append(
            {
                "lo": lo, "n": n, "suspects": int(lanes.size),
                "keys": keys, "seeds": lane_seeds, "rep": rep,
                "reps": reps, "results": [], "next": 0,
                "host_s": _time.perf_counter() - t0,
            }
        )

    def poll(self, seconds: Optional[float] = None) -> list:
        """Run queued WGL work for roughly ``seconds`` (None = until
        empty); returns ``(lo, report_dict)`` for every chunk that
        finished, in submission order. Always makes progress when work
        is pending (at least one slice per call), so a starved budget
        degrades to trickling, never to deadlock. The budget shapes
        SCHEDULING only: verdicts are computed in submission order
        regardless, so the stream of returned reports — and every byte
        in them — is invariant to the poll cadence."""
        import time as _time

        from .check import check_histories

        out = []
        deadline = (
            None if seconds is None else _time.perf_counter() + seconds
        )
        sliced = False
        while self._q:
            e = self._q[0]
            reps = e["reps"]
            while e["next"] < len(reps):
                if (
                    deadline is not None
                    and sliced
                    and _time.perf_counter() >= deadline
                ):
                    return out
                j = min(len(reps), e["next"] + self._step)
                tc = _time.perf_counter()
                e["results"].extend(
                    check_histories(
                        reps[e["next"]: j], self._spec,
                        max_states=self._max_states,
                        workers=self._workers,
                    )
                )
                e["host_s"] += _time.perf_counter() - tc
                e["next"] = j
                sliced = True
            out.append((e["lo"], self._finalize(e)))
            self._q.popleft()
        return out

    def drain(self) -> list:
        """Finish ALL queued work; ``(lo, report_dict)`` in submission
        order."""
        return self.poll(None)

    def _finalize(self, e: dict) -> dict:
        rep_results = e["results"]
        results = [rep_results[e["rep"][k]] for k in e["keys"]]
        bad = [s for s, r in zip(e["seeds"], results) if not r.ok]
        undecided = sum(1 for r in results if not r.decided)
        # distinct WGL searches that hit max_states (vs hist_undecided,
        # which counts the lanes those verdicts fanned out to)
        exhausted = sum(1 for r in rep_results if not r.decided)
        reps, workers = e["reps"], self._workers
        if self._telemetry is not None:
            if bad:
                self._telemetry.count("oracle_violations_total", len(bad))
            if exhausted:
                self._telemetry.count(
                    "oracle_budget_exceeded_total", exhausted,
                    help="WGL searches that exhausted max_states "
                    "(verdict undecided, fails clean)",
                )
            if workers > 0 and reps:
                # load-balance proxy: busy slots / pool slots over the
                # batch's -(-len // workers) waves
                waves = -(-len(reps) // workers)
                self._telemetry.gauge(
                    "oracle_pool_utilization",
                    len(reps) / (workers * waves),
                    help="checked histories / (workers x waves), "
                    "last chunk",
                )
            self._telemetry.observe(
                "oracle_check_seconds", e["host_s"],
                help="decode+dedup+WGL check per chunk",
            )
        return {
            "hist_screened": e["n"],
            "hist_suspects": e["suspects"],
            "hist_unique": len(reps),
            "hist_violations": len(bad),
            "hist_undecided": int(undecided),
            "budget_exceeded": int(exhausted),
            "hist_violating_seeds": bad[: self._max_recorded],
        }


def history_host_work(
    spec,
    max_states: int = 200_000,
    workers: int = 0,
    max_recorded: int = 32,
    telemetry=None,
    device_decode: bool = False,
) -> Callable:
    """Build the ``host_work`` for a screened checked sweep — a
    ``_HostWork``: callable with the legacy per-chunk signature (every
    driver's sync path), and exposing ``submit``/``poll``/``drain`` for
    drivers that interleave checking with device rounds (see the class
    docstring for both protocols and the determinism contract)."""
    return _HostWork(
        spec, max_states, workers, max_recorded, telemetry, device_decode
    )


# Per-chunk counts: a suspect lane is deduplicated only against its own
# chunk, so the number of distinct histories checked (and of WGL searches
# that ran out of budget) depends on where chunk boundaries fall — on the
# chunk size, and through it on the mesh size. Every other report field is
# a pure function of (config, seeds).
CHUNK_DEPENDENT = ("hist_unique", "budget_exceeded")


def chunk_invariant(report: dict) -> dict:
    """``report`` without its chunk-dependent counters: the part that is
    byte-identical across chunk sizes, mesh sizes and drivers."""
    return {k: v for k, v in report.items() if k not in CHUNK_DEPENDENT}


def checked_sweep(
    workload,
    cfg,
    seeds,
    spec,
    summarize,
    chunk_size: Optional[int] = None,
    workers: int = 0,
    max_states: int = 200_000,
    screen: bool = True,
    ckpt_dir: Optional[str] = None,
    stop_after: Optional[int] = None,
    resume_from=None,
    mesh=None,
    chunk_per_device: Optional[int] = None,
    max_recorded: int = 32,
    on_chunk=None,
    driver: str = "chunked",
    telemetry=None,
    device_decode: bool = False,
) -> dict:
    """End-to-end checked sweep: pipelined chunked sweep + on-device
    screening + process-pool WGL checking, merged into one summary dict.

    This is the optimized quantity BENCH reports as ``checked_sweep``:
    seeds/s through simulation AND history validation. ``screen=False``
    degrades to decode-and-check-every-seed (the naive baseline).
    Results are bit-identical across ``screen`` settings whenever the
    screen is conservative, and across ``workers`` always.
    ``chunk_size=None`` (the default) auto-picks the occupancy knee
    from the workload's measured loop-carry footprint, matching
    ``engine.core.run_sweep_chunked``.

    ``mesh`` routes the whole pipeline through the sharded driver
    (``parallel.run_sweep_sharded_pipelined``): sweep, screen and
    summary run sharded over the mesh, per-device chunks sized
    ``chunk_per_device`` (``core.pick_chunk_size`` when omitted; an
    explicit ``chunk_size`` stays GLOBAL and overrides). The summary
    dict is byte-identical across mesh sizes: every count is an exact
    integer reduction merged in seed order, and the
    ``hist_violating_seeds`` sample composes chunking-invariantly —
    each chunk records at most ``max_recorded`` violators (lane order)
    and the merged list is capped to the same bound, so a prefix kept
    per chunk can never change the global first-``max_recorded`` set.

    ``driver="stream"`` routes the sweep through the persistent lane
    pool (``engine.stream.stream_sweep``, docs/streaming.md): the screen
    runs once per retirement cohort on the whole pool, and the flushed
    reports are byte-identical to this function's chunked output —
    same virtual chunk boundaries, same merge order. The stream driver
    keeps its own checkpoint semantics (``stream_sweep(ckpt_path=...)``),
    so the chunk-granule ``ckpt_dir``/``stop_after``/``resume_from``
    arguments are rejected here.

    ``device_decode=True`` sources canonical history rows from the
    on-device decode kernel instead of per-row host Python
    (``history_host_work``) — bit-identical reports either way, gated
    by the determinism suite's decode leg."""
    from ..engine.checkpoint import run_sweep_pipelined

    if driver not in ("chunked", "stream"):
        raise ValueError(f"unknown driver {driver!r}")
    screen_fn = None
    if screen:
        if screen_for(spec) is None:
            raise ValueError(
                f"spec {spec.name!r} has no device screen; pass "
                "screen=False to check every lane"
            )
        screen_fn = lambda final: screen_sweep(final, spec, mesh=mesh)  # noqa: E731
    host_work = history_host_work(
        spec, max_states=max_states, workers=workers,
        max_recorded=max_recorded, telemetry=telemetry,
        device_decode=device_decode,
    )
    if driver == "stream":
        from ..engine.core import pick_chunk_size
        from ..engine.stream import stream_sweep

        if ckpt_dir is not None or stop_after is not None or resume_from:
            raise ValueError(
                "driver='stream' manages its own snapshots — use "
                "engine.stream.stream_sweep(ckpt_path=...) directly for "
                "interrupt/resume"
            )
        if chunk_size is None:
            if mesh is not None:
                n_dev = int(mesh.devices.size)
                cpd = (
                    pick_chunk_size(workload, cfg)
                    if chunk_per_device is None
                    else chunk_per_device
                )
                chunk_size = cpd * n_dev
            else:
                chunk_size = pick_chunk_size(workload, cfg)
        if mesh is not None:
            n_dev = int(mesh.devices.size)
            chunk_size = -(-chunk_size // n_dev) * n_dev
        totals = stream_sweep(
            workload, cfg, seeds, summarize,
            chunk_size=chunk_size, host_work=host_work,
            screen=screen_fn, mesh=mesh, on_chunk=on_chunk,
            telemetry=telemetry,
        )
    elif mesh is not None:
        from ..parallel.mesh import run_sweep_sharded_pipelined

        totals = run_sweep_sharded_pipelined(
            workload, cfg, seeds, summarize,
            mesh=mesh, host_work=host_work, screen=screen_fn,
            chunk_per_device=chunk_per_device, chunk_size=chunk_size,
            ckpt_dir=ckpt_dir, stop_after=stop_after,
            resume_from=resume_from, on_chunk=on_chunk,
            telemetry=telemetry,
        )
    else:
        if chunk_size is None:
            from ..engine.core import pick_chunk_size

            chunk_size = pick_chunk_size(workload, cfg)
        totals = run_sweep_pipelined(
            workload,
            cfg,
            seeds,
            summarize,
            host_work=host_work,
            screen=screen_fn,
            chunk_size=chunk_size,
            ckpt_dir=ckpt_dir,
            stop_after=stop_after,
            resume_from=resume_from,
            on_chunk=on_chunk,
            telemetry=telemetry,
        )
    if "hist_violating_seeds" in totals:
        totals["hist_violating_seeds"] = totals["hist_violating_seeds"][
            :max_recorded
        ]
    return totals
