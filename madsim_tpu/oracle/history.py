"""Operation histories: decode device ring buffers, record host-tier runs.

The device engine appends one fixed-width record per dispatched event
that the workload's ``record`` hook elects (engine/core.py): five int32
columns ``(client, code, key, val, opid)`` plus an engine-stamped int64
virtual time. ``code`` packs an op kind and a phase —
``code = op * 2 + phase`` — so one client-visible operation is TWO rows
(its invoke at send time, its completion at response-delivery time),
matched by ``(client, opid)``. One row per event is exactly what the
engine's one-masked-write-per-step discipline can afford, and the
invoke/ok pairing is the Jepsen history shape the checker wants anyway.

``decode_seed`` turns a finished ``EngineState`` lane back into ``Op``
records; ``history_bytes`` is the canonical byte encoding the
determinism gate diffs (same ``(spec, seed)`` on the sweep path and on
the bit-exact CPU ``run_traced`` replay path must produce identical
bytes). ``HostRecorder`` is the thin client-shim for the host tier: wrap
each client call in ``invoke``/``complete`` and the host run yields the
same ``History`` structure, checkable by the same specs.
"""

from __future__ import annotations

from typing import Callable, List, NamedTuple, Optional, Tuple

import numpy as np

# op kinds (the row's code column is ``op * 2 + phase``)
OP_PUT = 0  # key := inp; out echoes inp
OP_GET = 1  # read key; out = value or -1 (absent)
OP_DEL = 2  # delete key (internal ops record invoke == complete)
OP_PRODUCE = 3  # append inp (seq) to log/partition key; out = ack frontier
OP_FETCH = 4  # read from offset inp of partition key; out = records served
OP_ELECT = 5  # node inp won leadership of term key (invoke-only: no
#               client observes a completion — ElectionSpec is structural)

OP_NAMES = ("put", "get", "del", "produce", "fetch", "elect")

PH_INVOKE = 0
PH_OK = 1


def code_of(op: int, phase: int) -> int:
    """The row code the record hooks write: ``op * 2 + phase``."""
    return op * 2 + phase


class Op(NamedTuple):
    """One client-observed operation, paired from its invoke/ok rows."""

    client: int
    op: int  # OP_*
    key: int  # key (KV) or partition (log)
    inp: int  # invoke argument: PUT value / produce seq / fetch offset
    out: int  # completion result (meaningless while ``complete_ns < 0``)
    invoke_ns: int
    complete_ns: int  # -1 = never completed (open op — may have happened)
    opid: int

    @property
    def complete(self) -> bool:
        return self.complete_ns >= 0

    def describe(self) -> str:
        done = f"-> {self.out} @{self.complete_ns}" if self.complete else "-> ?"
        return (
            f"c{self.client} {OP_NAMES[self.op]}(k={self.key}, {self.inp}) "
            f"@{self.invoke_ns} {done}"
        )


class History(NamedTuple):
    """A decoded per-seed operation history."""

    seed: int
    ops: Tuple[Op, ...]  # invoke order (== record-append order)
    overflow: bool  # buffer filled up: ops is a valid strict prefix
    rows: int  # raw rows consumed


def _pair_rows(rec: np.ndarray, t: np.ndarray, n: int) -> Tuple[Op, ...]:
    """Pair invoke/ok rows by (client, opid) into ``Op`` records.

    Rows are appended in dispatch order, so an op's invoke row always
    precedes its ok row; an ok row with no recorded invoke means the
    decoder and the workload's record hook disagree — that is a bug, not
    a data condition, so it raises."""
    ops: List[List] = []
    open_ops = {}  # (client, opid) -> index into ops
    for i in range(n):
        client, code, key, val, opid = (int(v) for v in rec[i])
        op, phase = code // 2, code % 2
        when = int(t[i])
        if phase == PH_INVOKE:
            open_ops[(client, opid)] = len(ops)
            ops.append([client, op, key, val, 0, when, -1, opid])
        else:
            j = open_ops.pop((client, opid), None)
            if j is None:
                raise ValueError(
                    f"history row {i} completes op (client={client}, "
                    f"opid={opid}) with no recorded invoke — record-hook "
                    "contract breach"
                )
            if ops[j][1] != op or ops[j][2] != key:
                raise ValueError(
                    f"history row {i} completes (client={client}, "
                    f"opid={opid}) with mismatched op/key "
                    f"({op}/{key} vs {ops[j][1]}/{ops[j][2]})"
                )
            ops[j][4] = val
            ops[j][6] = when
    return tuple(Op(*o) for o in ops)


def decode_rows(
    rec, t, length, overflow, seed: int = -1
) -> History:
    """Decode one seed's raw history arrays (any source) into a History."""
    rec = np.asarray(rec)
    t = np.asarray(t)
    n = int(length)
    return History(
        seed=int(seed),
        ops=_pair_rows(rec, t, n),
        overflow=bool(overflow),
        rows=n,
    )


def decode_seed(final, lane: Optional[int] = None) -> History:
    """Decode the history buffer of a finished ``EngineState``.

    ``final`` is unbatched (``run_traced``'s final state) when ``lane``
    is None, else a batched sweep state indexed by ``lane``."""
    if lane is None:
        return decode_rows(
            final.hist_rec, final.hist_t, final.hist_len,
            final.hist_overflow, seed=int(final.seed),
        )
    return decode_rows(
        np.asarray(final.hist_rec)[lane],
        np.asarray(final.hist_t)[lane],
        np.asarray(final.hist_len)[lane],
        np.asarray(final.hist_overflow)[lane],
        seed=int(np.asarray(final.seed)[lane]),
    )


def decode_lanes(final, lanes) -> List[History]:
    """Decode SELECTED lanes of a batched sweep state — the screened
    path's batch decoder (oracle/screen.py): the device planes come off
    the device once, then only the suspect lanes pay the per-row Python
    decode. ``lanes`` is any integer sequence; order is preserved.

    The device->host transfer is sized to the selection, not the chunk:
    an empty selection never touches the device (the clean-sweep common
    case), and a sparse one (< a quarter of the lanes — the screened
    case) gathers the suspect rows device-side first, so a 16k-lane
    chunk with a handful of suspects moves kilobytes, not the whole
    ~100 MB plane."""
    lanes = [int(lane) for lane in lanes]
    if not lanes:
        return []
    n_total = int(final.seed.shape[0])
    if len(lanes) * 4 <= n_total:
        idx = np.asarray(lanes)
        planes = (
            final.hist_rec[idx], final.hist_t[idx],
            final.hist_len[idx], final.hist_overflow[idx],
            final.seed[idx],
        )
        sel = range(len(lanes))
    else:
        planes = (
            final.hist_rec, final.hist_t, final.hist_len,
            final.hist_overflow, final.seed,
        )
        sel = lanes
    rec, t, length, ov, seeds = (np.asarray(p) for p in planes)
    return [
        decode_rows(rec[i], t[i], length[i], ov[i], seed=int(seeds[i]))
        for i in sel
    ]


def decode_sweep(final) -> List[History]:
    """Decode every lane of a batched sweep state (host-side loop; pull
    the arrays off the device once, not per lane)."""
    return decode_lanes(final, range(int(final.seed.shape[0])))


def history_bytes(hist: History) -> bytes:
    """Canonical byte encoding of a decoded history.

    The determinism contract (docs/oracle.md): the same ``(spec, seed)``
    decoded from a device sweep lane and from a bit-exact CPU
    ``run_traced`` replay — or from two separate processes — must
    produce identical bytes. No wall times, no paths, no float repr."""
    lines = [f"seed={hist.seed} rows={hist.rows} overflow={int(hist.overflow)}"]
    lines += [
        f"c={o.client} op={OP_NAMES[o.op]} key={o.key} in={o.inp} "
        f"out={o.out if o.complete else '?'} "
        f"t=[{o.invoke_ns},{o.complete_ns}] id={o.opid}"
        for o in hist.ops
    ]
    return ("\n".join(lines) + "\n").encode()


_CANON_MAGIC = b"MTHC1\n"  # canonical-row format tag (docs/oracle.md)
_CANON_COLS = 8  # (client, op, key, inp, out, rank_inv, rank_comp, opid)


def canonical_bytes_from_rows(rows, n_ops, raw_rows, overflow) -> bytes:
    """Assemble the canonical byte encoding from fixed-width rows.

    ``rows`` is ``int32[*, 8]`` in invoke order — columns ``(client,
    op, key, inp, out-or-0-while-open, invoke rank, complete rank or
    -1, opid)`` — of which the first ``n_ops`` are encoded after a
    magic tag and an ``(raw_rows, overflow)`` int32 header, all
    little-endian. Both producers — the host path
    (``history_canonical_bytes``) and the device kernel
    (``canon_sweep``) — funnel through here, so their byte-identity
    contract reduces to row-array equality."""
    n = int(n_ops)
    head = np.asarray([int(raw_rows), int(bool(overflow))], dtype="<i4")
    body = np.ascontiguousarray(
        np.asarray(rows, dtype=np.int32)[:n], dtype="<i4"
    )
    return _CANON_MAGIC + head.tobytes() + body.tobytes()


def canonical_rows(hist: History) -> np.ndarray:
    """Host-side canonical rows (``int32[n_ops, 8]``) of a decoded
    history: each op's fields with its times replaced by their dense
    rank over the history's distinct valid times (open completions stay
    ``-1``; an open op's ``out`` is pinned to 0, which is what
    ``_pair_rows`` stores for a never-completed op anyway)."""
    ts = sorted(
        {
            t
            for o in hist.ops
            for t in (o.invoke_ns, o.complete_ns)
            if t >= 0
        }
    )
    rank = {t: i for i, t in enumerate(ts)}
    return np.asarray(
        [
            (
                o.client, o.op, o.key, o.inp,
                o.out if o.complete else 0,
                rank[o.invoke_ns],
                rank[o.complete_ns] if o.complete else -1,
                o.opid,
            )
            for o in hist.ops
        ],
        dtype=np.int32,
    ).reshape(len(hist.ops), _CANON_COLS)


def history_canonical_bytes(hist: History) -> bytes:
    """Seed-free, time-rank canonical encoding — the dedup key for WGL
    checking (oracle/screen.history_host_work).

    Two histories that differ only in seed and in the absolute values of
    their timestamps (but agree on every op field and on the relative
    order of all invoke/complete times) get identical bytes. The WGL
    search and every structural pre-pass read timestamps only through
    comparisons, so replacing each distinct time by its dense rank is an
    order-isomorphism that preserves the checker's verdict exactly —
    one representative verdict is valid for the whole equivalence class.
    Open ops keep their ``-1`` completion sentinel. The encoding is the
    fixed-width binary of ``canonical_bytes_from_rows`` so the on-device
    decode kernel (``canon_sweep``) can produce it without any host-side
    re-derivation. Unlike ``history_bytes`` this is NOT the
    determinism-gate encoding: it deliberately erases the seed and the
    absolute clock."""
    return canonical_bytes_from_rows(
        canonical_rows(hist), len(hist.ops), hist.rows, hist.overflow
    )


def history_from_canon(
    rows, n_ops, overflow, raw_rows, seed: int = -1
) -> History:
    """Rebuild a checkable ``History`` from canonical fixed-width rows,
    using each op's dense time ranks AS its times. Ranks are an
    order-isomorphism of the original clock, and the WGL checker and
    every structural pre-pass read times only through comparisons, so
    the verdict on the rebuilt history equals the verdict on the
    host-decoded one — the device-decode path checks THIS history and
    no report byte can tell the difference."""
    n = int(n_ops)
    r = np.asarray(rows)
    ops = tuple(
        Op(
            client=int(r[i, 0]), op=int(r[i, 1]), key=int(r[i, 2]),
            inp=int(r[i, 3]), out=int(r[i, 4]),
            invoke_ns=int(r[i, 5]), complete_ns=int(r[i, 6]),
            opid=int(r[i, 7]),
        )
        for i in range(n)
    )
    return History(
        seed=int(seed), ops=ops, overflow=bool(overflow),
        rows=int(raw_rows),
    )


_CANON_KERNEL = None


def _canon_kernel():
    """Build (once) the jitted, vmapped per-lane canonical-decode
    kernel. jax is imported lazily so the checker's pool workers —
    clean interpreters that import this module (oracle/check.py) —
    stay numpy-only."""
    global _CANON_KERNEL
    if _CANON_KERNEL is None:
        import jax
        import jax.numpy as jnp

        def lane(rec, t, n):
            H = rec.shape[0]
            idx = jnp.arange(H, dtype=jnp.int32)
            valid = idx < n
            client, code, key, val, opid = (rec[:, c] for c in range(5))
            op, ph = code // 2, code % 2
            inv = valid & (ph == PH_INVOKE)
            okm = valid & (ph == PH_OK)
            # pairing: an ok row k matches the LATEST invoke row i of
            # the same (client, opid) with prev_ok(k) < i < k, where
            # prev_ok(k) is k's latest earlier ok sibling — exactly the
            # overwrite-on-reinvoke / pop-on-ok dict semantics of
            # ``_pair_rows``. No match (or an op/key mismatch against
            # the matched invoke) is the record-hook contract breach
            # ``_pair_rows`` raises on; the kernel can't raise, so it
            # flags the lane and the caller falls back to the host
            # decoder for the real error
            same = (client[:, None] == client[None, :]) & (
                opid[:, None] == opid[None, :]
            )
            earlier = idx[None, :] < idx[:, None]
            neg = jnp.int32(-1)
            prev_ok = jnp.max(
                jnp.where(same & earlier & okm[None, :], idx[None, :], neg),
                axis=1,
            )
            cand = (
                same
                & earlier
                & inv[None, :]
                & (idx[None, :] > prev_ok[:, None])
            )
            match = jnp.max(jnp.where(cand, idx[None, :], neg), axis=1)
            m = jnp.clip(match, 0, H - 1)
            mism = (op[m] != op) | (key[m] != key)
            breach = jnp.any(okm & ((match < 0) | mism))
            # dense time rank: a row's rank = number of distinct valid
            # times strictly below its own. Exact under ties (only the
            # first row of a tie group counts as distinct); device
            # lanes have strictly increasing t so rank == row index,
            # but host-recorded planes may tie
            first = valid & ~jnp.any(
                (t[None, :] == t[:, None]) & earlier & valid[None, :],
                axis=1,
            )
            rank = jnp.sum(
                first[None, :] & (t[None, :] < t[:, None]), axis=1
            ).astype(jnp.int32)
            # assembly: invoke k is op number slot[k]; scatter invoke
            # rows whole, then patch (out, rank_comp) at the matched
            # slots — targets are disjoint (two ok rows can't match one
            # invoke: the second's prev_ok bound excludes it). Masked
            # rows scatter into the extra row H, sliced off
            slot = jnp.cumsum(inv.astype(jnp.int32)) - 1
            n_ops = jnp.sum(inv.astype(jnp.int32))
            dump = jnp.int32(H)
            inv_rows = jnp.stack(
                [
                    client, op, key, val,
                    jnp.zeros_like(val), rank,
                    jnp.full_like(val, -1), opid,
                ],
                axis=1,
            ).astype(jnp.int32)
            canon = jnp.zeros((H + 1, _CANON_COLS), dtype=jnp.int32)
            canon = canon.at[jnp.where(inv, slot, dump)].set(inv_rows)
            ok_tgt = jnp.where(okm & (match >= 0), slot[m], dump)
            canon = canon.at[ok_tgt, 4].set(val)
            canon = canon.at[ok_tgt, 6].set(rank)
            return canon[:H], n_ops, breach

        _CANON_KERNEL = jax.jit(jax.vmap(lane))
    return _CANON_KERNEL


def canon_sweep(final):
    """On-device canonical decode of EVERY lane of a finished sweep
    state: ``(canon int32[S, H, 8], n_ops int32[S], breach bool[S])``.

    ``canon[s, :n_ops[s]]`` are lane ``s``'s canonical rows — the same
    rows ``canonical_rows(decode_seed(final, s))`` derives on the host,
    by the pairing/rank arguments in ``_canon_kernel`` — so
    ``canonical_bytes_from_rows`` over a device row block equals
    ``history_canonical_bytes`` over the host-decoded lane bit-exactly.
    One fixed-shape jitted call covers the whole chunk (no per-lane
    recompiles); callers gather just the lanes they need off the device
    afterwards. ``breach[s]`` marks a record-hook contract breach
    (orphan ok / op-key mismatch) on lane ``s``: those rows are
    unusable and the caller must route that lane through the host
    decoder, which raises the diagnostic."""
    return _canon_kernel()(final.hist_rec, final.hist_t, final.hist_len)


class HostRecorder:
    """Thin client-shim recording host-tier operation histories.

    The host tier runs arbitrary async Python under the same virtual
    clock; wrapping each client call in ``invoke``/``complete`` yields
    the same ``History`` structure the device decoder produces, so one
    checker serves both tiers::

        rec = HostRecorder()
        opid = rec.invoke(client=0, op=OP_PUT, key=3, inp=42)
        resp = await kv.put(b"k3", b"42")
        rec.complete(client=0, opid=opid, out=42)
        check_history(rec.history(), KVSpec())

    Times default to the running simulation's virtual clock
    (``madsim_tpu.time``); pass ``clock`` (a ``() -> int`` of
    nanoseconds) to record outside a sim context. NOTE: two engines
    cannot share one RNG stream, so a host history for a ``(spec,
    seed)`` is *not* byte-comparable to the device history — byte
    identity is the contract between the device sweep and its CPU
    ``run_traced`` replay; host histories share only the format and the
    checker (docs/oracle.md).
    """

    def __init__(self, clock: Optional[Callable[[], int]] = None):
        if clock is None:
            def clock() -> int:
                from ..context import current_handle

                return int(current_handle().time.now_ns)

        self._clock = clock
        self._rows: List[Tuple[int, int, int, int, int, int]] = []
        self._next_opid = {}
        self._open = {}  # (client, opid) -> (invoke code, key)

    def invoke(self, client: int, op: int, key: int, inp: int) -> int:
        """Record an op's invocation; returns the opid to complete with."""
        opid = self._next_opid.get(client, 0)
        self._next_opid[client] = opid + 1
        code = code_of(op, PH_INVOKE)
        self._open[(client, opid)] = (code, key)
        self._rows.append((client, code, key, inp, opid, self._clock()))
        return opid

    def complete(self, client: int, opid: int, out: int) -> None:
        """Record an op's completion (skip for ops that never returned).
        Completing an unknown or already-completed op raises HERE, at
        the offending call, not later from the decoder."""
        # the op/key columns are reconstructed from the invoke entry, so
        # completion needs only the identity and the result
        entry = self._open.pop((client, opid), None)
        if entry is None:
            raise ValueError(
                f"complete() for unknown or already-completed "
                f"(client={client}, opid={opid})"
            )
        code, key = entry
        self._rows.append((client, code + 1, key, out, opid, self._clock()))

    def history(self, seed: int = -1) -> History:
        rec = np.asarray(
            [r[:5] for r in self._rows], dtype=np.int32
        ).reshape(len(self._rows), 5)
        t = np.asarray([r[5] for r in self._rows], dtype=np.int64)
        return decode_rows(rec, t, len(self._rows), False, seed=seed)
