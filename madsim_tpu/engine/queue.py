"""Bounded per-seed event queue as fixed-shape arrays.

The reference's timer queue is a binary heap of boxed callbacks
(madsim/src/sim/time/mod.rs:21-230, naive-timer). Heaps don't vectorize:
pointer chasing and data-dependent shapes defeat XLA. The device engine uses
the classic SoA alternative (SURVEY.md §7 "hard parts" #2): a fixed-capacity
slot table per seed —

    time  : int64[Q]        absolute deadline, ns (INVALID_TIME when free)
    kind  : int32[Q]        event discriminant (workload-defined)
    pay   : P x int32[Q]    payload slots, one plane per payload word

``pop_min`` = min + one-hot invalidate; ``push_many`` = one masked select
per emit, each slot taking the emit whose index is its rank among free
slots, with nothing summed. Everything is dense vector code — **no
dynamic scatter or gather**, which on TPU run ~6-10x slower than the
masked equivalents (see engine/ops.py). For Q ≲ 256 each op is a handful of VPU lanes, far cheaper
than the host round-trip it replaces.

The one prefix sum, ``push_many``'s rank among free slots, runs on the MXU
as a matmul with a constant upper-triangular 0/1 matrix. ``jnp.cumsum``
lowers on TPU to a whole-axis ``reduce_window`` that XLA expands into an
O(Q²) sum on the VPU; the matmul does the same O(Q²) work on the unit
built for it, and is exact (see ``_free_count``).

The payload is P separate ``[Q]`` planes, not one ``int32[Q, P]`` array,
for the TPU's tiling. Batched over lanes, a ``[lanes, Q]`` plane is tiled
``T(8,128)`` with lanes on the 128 vector lanes and Q on the 8 sublanes,
and so are the ``[lanes, Q]`` masks that push's writes and pop's read
select with: mask and plane line up tile for tile. A stacked
``[lanes, Q, P]`` array is tiled with P on the sublanes, so every masked
write would pull row q out of a packed mask tile and broadcast it across
the P sublanes, for every tile and every emit, and a P below 8 would pad
to 8. The drive loop's carry sits in on-chip VMEM, so these passes are
bound by vector work per tile, not by memory bandwidth.

Occupancy is encoded in the time plane itself: a slot is free iff its time
is ``INVALID_TIME`` (every constructor and removal maintains this), so no
separate validity plane travels in the loop carry (a layout with a
``bool valid[Q]`` plane measured 4.1% slower, docs/pallas_finding.md §5).

Equal-time pops break ties *randomly* via a caller-supplied counter-RNG
draw (``tie_u32``), mirroring the reference's uniformly-random ready-queue
pop (madsim/src/sim/utils/mpsc.rs:71-84) — the stated source of schedule
amplification — while staying bit-reproducible per (seed, event index).

Overflow sets a sticky flag instead of corrupting state; the sweep driver
surfaces it per seed so the run can be retried with a larger Q.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np

from .ops import onehot

INVALID_TIME = jnp.iinfo(jnp.int64).max

_HASH_MULT = 2654435761  # Knuth multiplicative hash constant


class EventQueue(NamedTuple):
    time: jnp.ndarray  # int64[Q]; INVALID_TIME == free slot
    kind: jnp.ndarray  # int32[Q]
    pay: Tuple[jnp.ndarray, ...]  # P planes of int32[Q], one per payload word


def make(capacity: int, payload_slots: int) -> EventQueue:
    return EventQueue(
        jnp.full((capacity,), INVALID_TIME, jnp.int64),
        jnp.zeros((capacity,), jnp.int32),
        tuple(jnp.zeros((capacity,), jnp.int32) for _ in range(payload_slots)),
    )


def _free(q: EventQueue) -> jnp.ndarray:
    return q.time == INVALID_TIME


def _free_count(free: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix count of a bool[Q] mask: ``count[j]`` is the
    number of free slots at index <= j, as int32.

    ``free (0/1) @ tri`` with ``tri[i, j] = 1`` for ``i <= j`` — a matmul
    of ``[lanes, Q] x [Q, Q]`` once vmapped, with the triangle a
    compile-time constant. Exact: both factors are 0/1, so bf16 holds
    them exactly, every product is 0 or 1, and no sum exceeds Q, which
    the float32 accumulator holds exactly for any Q below 2**24.
    """
    Q = free.shape[0]
    tri = np.triu(np.ones((Q, Q), dtype=jnp.bfloat16))
    count = jnp.dot(free.astype(jnp.bfloat16), tri, preferred_element_type=jnp.float32)
    return count.astype(jnp.int32)


def push_many(
    q: EventQueue,
    times: jnp.ndarray,  # int64[E]
    kinds: jnp.ndarray,  # int32[E]
    pays: jnp.ndarray,  # int32[E, P]
    enables: jnp.ndarray,  # bool[E]
) -> Tuple[EventQueue, jnp.ndarray]:
    """Insert up to E events in ONE dense pass: emit ``e`` maps to the
    e-th free slot (ascending index — the same assignment a sequential
    first-free scan would make), whether or not earlier emits are enabled.
    The slot's rank among free slots is a prefix count of the free mask,
    done as one matmul on the MXU (``_free_count``).

    Slot ``q`` takes at most one emit, the one whose index is its rank
    (``er``, set to E on an occupied slot so that it matches no emit).
    So each emit is written with one masked select per plane — an
    unrolled loop over the static E, which XLA fuses into one pass per
    plane — straight into the queue planes: no ``[Q, E]`` one-hot and
    no sum over E. No sort, no top_k, no scatter.

    Emit ``e``'s payload is the row ``pays[e]``; word ``p`` of it goes
    into plane ``pay[p]`` under the same mask ``m`` as the time and kind,
    so every write lines up with its mask tile for tile (module
    docstring). Slicing ``pays`` flattened instead makes push's slice
    cheaper on a v5e but changes the layout in which the handler builds
    ``pays``, which costs the handler more than push saves (raft, P = 8).
    """
    E = times.shape[0]
    free = _free(q)
    count = _free_count(free)
    er = jnp.where(free, count - 1, E)
    time, kind, pay = q.time, q.kind, list(q.pay)
    for e in range(E):
        m = (er == e) & enables[e]
        time = jnp.where(m, times[e], time)
        kind = jnp.where(m, kinds[e], kind)
        for p in range(len(pay)):
            pay[p] = jnp.where(m, pays[e, p], pay[p])
    overflow = jnp.any(enables & (jnp.arange(E) >= count[-1]))
    return EventQueue(time, kind, tuple(pay)), overflow


def pop_min(
    q: EventQueue, enable=True, tie_u32=0
) -> Tuple[EventQueue, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Remove and return the earliest event; equal-time ties break
    uniformly-at-random by ``tie_u32`` (a counter-RNG draw — deterministic
    per seed+event, different across seeds: the reference's random ready-
    queue pop semantics).

    Returns ``(queue', time, kind, pay, found)``, ``pay`` an ``int32[P]``
    vector read with one masked sum over Q per payload plane, each under
    the slot mask's own tiling (module docstring); when the queue is empty
    ``found`` is False and time is INVALID_TIME. With ``enable=False`` the
    queue is left untouched (lets a masked-out seed skip its pop without a
    whole-array select).

    Invariant used: free slots always hold ``time == INVALID_TIME`` (make
    + removal maintain it), so no validity masking is needed before min.
    """
    capacity = q.time.shape[0]
    t = jnp.min(q.time)
    found = t != INVALID_TIME
    # pseudo-random per-slot priority; argmin over candidates = random tie
    # pick. murmur3-finalizer avalanche so any bit of the draw reshuffles
    # the order (a plain xor would leave clustered draws order-preserving).
    iota = jnp.arange(capacity, dtype=jnp.uint32)
    x = iota * jnp.uint32(_HASH_MULT) ^ jnp.asarray(tie_u32, jnp.uint32)
    x ^= x >> 16
    x *= jnp.uint32(0x85EBCA6B)
    x ^= x >> 13
    x *= jnp.uint32(0xC2B2AE35)
    prio = x ^ (x >> 16)
    cand = q.time == t
    # int64 sentinel strictly above any uint32 prio, so a candidate always
    # wins even when its hash happens to be 0xFFFFFFFF
    slot = jnp.argmin(jnp.where(cand, prio.astype(jnp.int64), jnp.int64(1) << 33))
    mask = onehot(slot, capacity)
    rm = mask & found & jnp.asarray(enable, bool)
    kind = jnp.sum(jnp.where(mask & found, q.kind, 0), dtype=jnp.int32)
    pay = jnp.array(
        [jnp.sum(jnp.where(mask, plane, 0), dtype=jnp.int32) for plane in q.pay],
        jnp.int32,
    )
    return (
        EventQueue(jnp.where(rm, INVALID_TIME, q.time), q.kind, q.pay),
        t,
        kind,
        pay,
        found,
    )


def size(q: EventQueue) -> jnp.ndarray:
    return jnp.sum((~_free(q)).astype(jnp.int32))
