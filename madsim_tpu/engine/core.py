"""The batched simulation loop: pop-min / advance-clock / draw / dispatch.

This is the reference's hot loop (``Executor::block_on`` →
``advance_to_next_event``, SURVEY.md §3.1) restructured for lockstep
execution over a seed batch:

- ``step_one`` advances ONE seed by ONE event: pop the minimum-time event,
  jump the virtual clock to it plus a random 50-100 ns jitter (the
  amplification analogue of the reference's per-poll advance,
  task/mod.rs:312-315 and +50 ns epsilon, time/mod.rs:45-60), draw
  counter-based randomness, dispatch to the workload's pure handler, and
  push the events it emits.
- ``step_batch`` is ``vmap(step_one)``; finished seeds are masked (their
  state passes through unchanged and their RNG counter freezes), so
  divergent seeds never break lockstep.
- ``run_sweep`` drives ``step_batch`` under ``lax.while_loop`` until every
  seed is done (queue empty = the reference's deadlock condition,
  task/mod.rs:250; or virtual time limit, task/mod.rs:253-258) — one XLA
  program, no host round-trips.
- ``run_traced`` replays a single seed recording every dispatched event —
  the bit-exact CPU replay artifact (run it with JAX's CPU backend; the
  engine is integer-only so the trace matches the TPU batch bit for bit).

The workload is a pair of pure functions over arrays (actors as state
machines), not coroutines: user futures can't run on TPU (SURVEY.md §7
"hard parts" #1), so the device tier targets table-driven workloads
(models/), while arbitrary user code runs on the host tier with the same
simulation semantics.
"""

from __future__ import annotations

import re
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import queue as equeue
from .queue import EventQueue
from .rng import bounded, event_bits, seed_key

# Columns of one fixed-width operation-history record (madsim_tpu/oracle):
# (client, code, key, val, opid) as int32; the engine stamps the record's
# int64 virtual time itself. The oracle decoder owns the field semantics —
# the engine only owns the width and the append discipline.
HIST_COLS = 5


class Emits(NamedTuple):
    """Fixed-size batch of events emitted by one handler invocation."""

    times: jnp.ndarray  # int64[E] absolute deadlines
    kinds: jnp.ndarray  # int32[E]
    pays: jnp.ndarray  # int32[E, P]
    enables: jnp.ndarray  # bool[E]


def no_emits(max_emits: int, payload_slots: int) -> Emits:
    return Emits(
        times=jnp.zeros((max_emits,), jnp.int64),
        kinds=jnp.zeros((max_emits,), jnp.int32),
        pays=jnp.zeros((max_emits, payload_slots), jnp.int32),
        enables=jnp.zeros((max_emits,), bool),
    )


class Workload(NamedTuple):
    """A device-expressible workload: two pure functions + static sizes.

    ``init(key) -> (wstate, Emits)`` builds the per-seed actor state and the
    initial event set (timers, fault plan). ``handle(wstate, now_ns, kind,
    pay, rand_u32) -> (wstate, Emits)`` processes one event; ``rand_u32``
    is ``num_rand`` uint32 draws unique to this (seed, event) pair.
    """

    init: Callable[[jax.Array], Tuple[Any, Emits]]
    handle: Callable[..., Tuple[Any, Emits]]
    num_rand: int
    payload_slots: int
    max_emits: int
    # Optional coverage signal (madsim_tpu/explore): ``cover(wstate_before,
    # wstate_after, now_ns, kind, pay) -> int32`` maps each dispatched
    # event to one bit index in ``[0, cover_bits)`` — typically
    # (event kind x node x state transition). The engine ORs the bit into
    # the per-seed bitmap inside the same step (one extra masked write,
    # no second pass); ``cover_bits == 0`` disables the plane entirely.
    cover: Optional[Callable[..., jnp.ndarray]] = None
    cover_bits: int = 0
    # Optional violation probe: ``probe(wstate) -> int32`` flavor bitmask
    # (0 = no violation). ``run_traced`` records it per step so triage
    # (explore/triage.py) can locate the FIRST violating event.
    probe: Optional[Callable[[Any], jnp.ndarray]] = None
    # Optional operation-history recording (madsim_tpu/oracle):
    # ``record(wstate_before, wstate_after, now_ns, kind, pay) ->
    # (slot_op, enable)`` maps each dispatched event to at most one
    # fixed-width op record — ``slot_op`` is int32[HIST_COLS]
    # (client, code, key, val, opid); the engine stamps the event's
    # virtual time and appends the row to the per-seed history buffer in
    # the same step (one masked write, like the coverage plane). A full
    # buffer latches the sticky ``hist_overflow`` flag and DROPS the row
    # — it never wraps, so the recorded prefix stays a valid history.
    # ``hist_slots == 0`` disables the plane entirely.
    record: Optional[Callable[..., Tuple[jnp.ndarray, jnp.ndarray]]] = None
    hist_slots: int = 0
    # Opt-in device-side event-mix plane (madsim_tpu/obs): per-seed
    # per-event-kind uint32 counters, one masked add per dispatched event
    # (same in-step write discipline as the coverage plane). Kinds >=
    # ``event_mix_kinds`` are simply not counted; 0 disables the plane
    # entirely (width-0 arrays, no loop-carry cost). The chunk summary
    # reduces it into an ``event_mix`` kind-histogram
    # (models/_common.make_sweep_summary) — heartbeat storms, election
    # churn and fault-window activity visible per sweep without host
    # decode.
    event_mix_kinds: int = 0


def cover_words(workload: Workload) -> int:
    """uint32 words of the per-seed coverage bitmap (0 when disabled)."""
    return (workload.cover_bits + 31) // 32


def hist_slots(workload: Workload) -> int:
    """Rows of the per-seed history buffer (0 when recording is off)."""
    return workload.hist_slots if workload.record is not None else 0


class EngineConfig(NamedTuple):
    """Static engine parameters (python ints — part of the jit cache key)."""

    queue_capacity: int = 64
    time_limit_ns: int = 10_000_000_000
    max_steps: int = 100_000
    jitter_lo_ns: int = 50
    jitter_hi_ns: int = 100


class EngineState(NamedTuple):
    """Per-seed simulator state; ``run_sweep`` holds one with a leading
    seed-batch axis on every leaf (struct-of-arrays)."""

    seed: jnp.ndarray  # int64
    key: jax.Array  # typed PRNG key
    now_ns: jnp.ndarray  # int64 virtual clock
    ctr: jnp.ndarray  # int32 events processed (RNG counter)
    done: jnp.ndarray  # bool
    overflow: jnp.ndarray  # bool sticky queue-overflow flag
    qmax: jnp.ndarray  # int32 queue-occupancy high-water mark
    cover: jnp.ndarray  # uint32[cover_words] per-seed coverage bitmap
    # operation-history plane (madsim_tpu/oracle); all empty-shaped when
    # the workload records no history
    hist_rec: jnp.ndarray  # int32[hist_slots, HIST_COLS] op records
    hist_t: jnp.ndarray  # int64[hist_slots] record virtual times
    hist_len: jnp.ndarray  # int32 rows appended so far
    hist_overflow: jnp.ndarray  # bool sticky history-overflow flag
    queue: EventQueue
    wstate: Any  # workload pytree
    # event-mix plane (uint32[event_mix_kinds], width 0 when disabled).
    # LAST field on purpose: checkpoint leaves are stored positionally
    # (checkpoint.py leaf_{i}), so appending after ``wstate`` keeps every
    # pre-v10 leaf index stable and old snapshots loadable.
    evmix: jnp.ndarray


def _init_one(
    workload: Workload, cfg: EngineConfig, seed: jnp.ndarray, params=None
) -> EngineState:
    if workload.max_emits > cfg.queue_capacity:
        raise ValueError(
            f"workload.max_emits ({workload.max_emits}) exceeds "
            f"queue_capacity ({cfg.queue_capacity}); every handler "
            "invocation must be able to enqueue its full emit batch"
        )
    key = seed_key(seed)
    # spec-as-data (engine/faults.py): a params-carrying workload builds
    # its fault schedule from this lane's traced FaultParams instead of a
    # static spec — the jit key stays the envelope shape
    wstate, emits = (
        workload.init(key) if params is None else workload.init(key, params)
    )
    q = equeue.make(cfg.queue_capacity, workload.payload_slots)
    q, overflow = equeue.push_many(q, emits.times, emits.kinds, emits.pays, emits.enables)
    return EngineState(
        seed=jnp.asarray(seed, jnp.int64),
        key=key,
        now_ns=jnp.zeros((), jnp.int64),
        ctr=jnp.zeros((), jnp.int32),
        done=jnp.zeros((), bool),
        overflow=overflow,
        qmax=equeue.size(q),
        cover=jnp.zeros((cover_words(workload),), jnp.uint32),
        hist_rec=jnp.zeros((hist_slots(workload), HIST_COLS), jnp.int32),
        hist_t=jnp.zeros((hist_slots(workload),), jnp.int64),
        hist_len=jnp.zeros((), jnp.int32),
        hist_overflow=jnp.zeros((), bool),
        queue=q,
        wstate=wstate,
        evmix=jnp.zeros((workload.event_mix_kinds,), jnp.uint32),
    )


def init_sweep(
    workload: Workload, cfg: EngineConfig, seeds: jnp.ndarray, params=None
) -> EngineState:
    """Build the batched state for a seed vector (int64[S]). ``params``
    (optional) is a PER-LANE pytree — leading axis S on every leaf, e.g.
    ``faults.tile_params`` of one candidate or a stacked candidate×seed
    grid — vmapped alongside the seed axis."""
    _procs_child_guard()
    seeds = jnp.asarray(seeds, jnp.int64)
    if params is None:
        return jax.vmap(partial(_init_one, workload, cfg))(seeds)
    return jax.vmap(partial(_init_one, workload, cfg))(seeds, params)


def _procs_child_guard() -> None:
    """Fail by name, not by hang, when the device tier is entered from a
    forked ``Builder(procs=N)`` sweep child (modules created before the
    fork hold real jax references the child's sys.modules poison cannot
    reach, so the engine checks the child's sentinel itself). The
    sentinel carries the child's pid: an exec'd DESCENDANT of a child
    (fresh interpreter, no inherited JAX state) inherits the env var but
    not the pid, and may use the engine legitimately."""
    import os

    if os.environ.get("MADSIM_IN_PROCS_CHILD") == str(os.getpid()):
        from ..builder import ProcsDeviceTierError

        raise ProcsDeviceTierError("madsim_tpu.engine")


def _pop_event(workload: Workload, s: EngineState, enable):
    """Draw this event's randomness and pop the next event.

    Draw layout: ``rand[0]`` clock jitter, ``rand[1]`` pop tie-break,
    ``rand[2:]`` workload handler draws. Shared by the sweep step and the
    traced replay so both consume identical streams.
    """
    with jax.named_scope("rng"):
        rand = event_bits(s.key, s.ctr, workload.num_rand + 2)
    with jax.named_scope("pop"):
        q, t, kind, pay, found = equeue.pop_min(
            s.queue, enable=enable, tie_u32=rand[1]
        )
    return rand, q, t, kind, pay, found


def step_one(workload: Workload, cfg: EngineConfig, s: EngineState) -> EngineState:
    """Advance one seed by one event (no-op once ``done``).

    Three masks compose: already-done seeds freeze entirely; a
    popped-empty queue or expired clock marks done without dispatching;
    only ``take`` applies the handler's writes. Queue mutations are gated
    at the mask level (pop ``enable`` / push ``enables``) so the big
    [Q]-sized arrays never need a whole-array select; only the workload
    state goes through a select tree."""
    active = ~s.done
    rand, q, t, kind, pay, found = _pop_event(workload, s, active)
    with jax.named_scope("commit"):
        jitter = bounded(rand[0], cfg.jitter_lo_ns, cfg.jitter_hi_ns + 1)
        now = jnp.maximum(s.now_ns, t) + jitter
        time_up = now > cfg.time_limit_ns
        dispatch = found & ~time_up
        take = active & dispatch

    with jax.named_scope("handler"):
        wstate, emits = workload.handle(s.wstate, now, kind, pay, rand[2:])
    with jax.named_scope("push"):
        q, ov = equeue.push_many(
            q, emits.times, emits.kinds, emits.pays, emits.enables & take
        )
    with jax.named_scope("commit"):
        # coverage: fold this event's bit into the per-seed bitmap — a masked
        # OR in the same step, so the signal costs one extra [W]-sized write,
        # never a second pass over the sweep
        cover = s.cover
        if workload.cover is not None and workload.cover_bits > 0:
            w = cover_words(workload)
            bit = jnp.asarray(
                workload.cover(s.wstate, wstate, now, kind, pay), jnp.uint32
            )
            hit = (jnp.arange(w, dtype=jnp.uint32) == (bit >> 5)) & take
            cover = cover | jnp.where(
                hit, jnp.uint32(1) << (bit & 31), jnp.uint32(0)
            )

        # history: append this event's op record (if any) at the write head —
        # one masked [H]-sized write in the same step, mirroring the coverage
        # plane. A full buffer latches the sticky overflow flag and drops the
        # row; the already-written prefix is never touched (no wrap).
        hist_rec, hist_t = s.hist_rec, s.hist_t
        hist_len, hist_ov = s.hist_len, s.hist_overflow
        if workload.record is not None and workload.hist_slots > 0:
            h = workload.hist_slots
            rec, ren = workload.record(s.wstate, wstate, now, kind, pay)
            want = take & jnp.asarray(ren, bool)
            fits = hist_len < h
            row = (jnp.arange(h, dtype=jnp.int32) == hist_len) & want & fits
            hist_rec = jnp.where(
                row[:, None], jnp.asarray(rec, jnp.int32)[None, :], hist_rec
            )
            hist_t = jnp.where(row, now, hist_t)
            hist_len = hist_len + jnp.where(want & fits, 1, 0)
            hist_ov = hist_ov | (want & ~fits)

        # event mix: count this event's kind — one masked [K]-sized add in
        # the same step, the cheapest of the three opt-in planes (no callback,
        # the popped ``kind`` is the index)
        evmix = s.evmix
        if workload.event_mix_kinds > 0:
            k = workload.event_mix_kinds
            slot = (jnp.arange(k, dtype=jnp.int32) == kind) & take
            evmix = evmix + slot.astype(jnp.uint32)

        def sel(pred, new, old):
            return jax.tree.map(lambda a, b: jnp.where(pred, a, b), new, old)

        return EngineState(
            seed=s.seed,
            key=s.key,
            now_ns=jnp.where(take, now, s.now_ns),
            ctr=jnp.where(take, s.ctr + 1, s.ctr),
            done=s.done | (active & (~found | time_up)),
            overflow=s.overflow | (take & ov),
            qmax=jnp.maximum(s.qmax, equeue.size(q)),
            cover=cover,
            hist_rec=hist_rec,
            hist_t=hist_t,
            hist_len=hist_len,
            hist_overflow=hist_ov,
            queue=q,
            wstate=sel(take, wstate, s.wstate),
            evmix=evmix,
        )


def step_batch(workload: Workload, cfg: EngineConfig, state: EngineState) -> EngineState:
    """One lockstep event for every live seed in the batch."""
    return jax.vmap(partial(step_one, workload, cfg))(state)


def drive(workload: Workload, cfg: EngineConfig, state: EngineState):
    """Step a batched state until every seed is done or ``max_steps`` is
    hit — the single shared sweep driver (used by ``run_sweep``,
    ``checkpoint.resume_sweep``; the sharded driver in parallel/mesh adds
    a psum but follows the same shape). Returns the final state and the
    loop's trip count.

    ONE flat ``while_loop``, cond evaluated every step: nesting a second
    device loop inside the body costs ~9x per step on TPU (4.57 vs 0.43
    ms/step at a 16k batch on v5e, docs/pallas_finding.md §1: the loop
    carry round-trips HBM per inner iteration), while the ``any(~done)``
    reduction in the cond is free. Exactly ``max_steps`` steps can run,
    keeping the sweep bit-identical to ``run_traced``'s
    ``length=max_steps`` scan for budget-cut seeds (finished seeds are
    frozen no-ops either way).
    """

    def cond(carry):
        state, iters = carry
        return jnp.any(~state.done) & (iters < cfg.max_steps)

    def body(carry):
        state, iters = carry
        return step_batch(workload, cfg, state), iters + 1

    return jax.lax.while_loop(cond, body, (state, jnp.zeros((), jnp.int64)))


@partial(jax.jit, static_argnums=(0, 1))
def _init(
    workload: Workload, cfg: EngineConfig, seeds: jnp.ndarray, params=None
) -> EngineState:
    return init_sweep(workload, cfg, seeds, params)


@partial(jax.jit, static_argnums=(0, 1))
def _drive(workload: Workload, cfg: EngineConfig, state: EngineState):
    """The drive program: the final state, the loop's trip count and the
    events the chunk dispatched (its lanes' summed ``ctr``)."""
    final, trips = drive(workload, cfg, state)
    return final, trips, jnp.sum(final.ctr, dtype=jnp.int64)


# Every drive program this process dispatched, for ``drive_phase_map``:
# (workload, cfg, lanes) -> [abstract state, abstract ``_init`` arguments
# (None for a resumed state), the phase map once built].
_DRIVE_PROGRAMS: dict = {}


def _abstract(tree):
    # shapes only: placed on the default device, as the sweep's arrays
    # are, so lowering them again finds the program already compiled
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), tree)


def run_drive(
    workload: Workload, cfg: EngineConfig, state: EngineState, init_args=None
) -> EngineState:
    """Dispatch the drive program on a batched state and return its final
    state, without waiting for it. The trip count and the events feed the
    process registry's ``engine_lane_steps_total`` (lanes x trips) and
    ``engine_events_total`` as device scalars, read only when someone
    reads the counters; their ratio is the lockstep loop's lane
    occupancy. ``init_args`` are the ``_init`` arguments that built
    ``state``, kept (as shapes) for ``drive_phase_map``."""
    final, trips, events = _drive(workload, cfg, state)
    lanes = int(state.seed.shape[0])
    key = (workload, cfg, lanes)
    prog = _DRIVE_PROGRAMS.get(key)
    if prog is None:
        prog = _DRIVE_PROGRAMS[key] = [_abstract(state), None, None]
    if prog[1] is None and init_args is not None:
        prog[1] = _abstract(init_args)
    reg = obs.default_registry()
    reg.counter(
        "engine_lane_steps_total",
        "lanes x trips of the drive loop: lane-steps stepped, live or done",
    ).inc_deferred(trips, scale=lanes)
    reg.counter(
        "engine_events_total", "events the drive loop dispatched"
    ).inc_deferred(events)
    return final


# The step's phases, as ``step_one`` and ``_pop_event`` scope them. A
# scope a workload's handler opens directly inside ``handler`` (raft's
# ``snapshot``) names a phase of its own.
PHASES = ("rng", "pop", "handler", "push", "commit")
_INSTR = re.compile(r"^\s+(ROOT )?(%[\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r" fusion\(.*?calls=(%[\w.\-]+)")
_SCOPE = re.compile(r"(?:vmap\()*(\w+)\)*")


def _scope_phase(op_name: str) -> Optional[str]:
    """The innermost phase scope named in an ``op_name`` path, through
    the ``vmap(...)`` the batch axis wraps it in, or the scope nested
    directly inside ``handler`` (a path part after it that is not the
    op's own name); None outside them."""
    parts = op_name.split("/")
    names = [m.group(1) if m else None for m in map(_SCOPE.fullmatch, parts)]
    for i in range(len(names) - 1, -1, -1):
        if names[i] in PHASES:
            inner = names[i + 1] if i + 2 < len(names) else None
            return inner if names[i] == "handler" and inner else names[i]
    return None


def hlo_phases(text: str) -> dict:
    """Map every instruction outside a fused computation of a compiled
    module's text (``compiled.as_text()``: the names the profiler's "XLA
    Ops" line shows, ``%while.31``) to the phase scope its
    ``metadata={op_name=...}`` names (the innermost, so an op under
    ``handler/snapshot`` is ``snapshot``), or None. A fusion takes the
    phase of its fused computation's root (for a tuple root, the first
    operand with one)."""
    comps, comp = {}, None  # computation -> [(name, rest of line, root?)]
    own, fused = {}, {}  # name -> phase of its own metadata / fused comp
    for line in text.splitlines():
        if line and not line[0].isspace():
            head = line.split(" ", 2)
            comp = head[1] if head[0] == "ENTRY" else head[0]
            comps[comp] = []
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        root, name, rest = m.groups()
        comps[comp].append((name, rest, bool(root)))
        op = _OP_NAME.search(rest)
        own[name] = _scope_phase(op.group(1)) if op else None
        calls = _CALLS.search(rest)
        if calls:
            fused[name] = calls.group(1)

    def root_phase(comp_name: str) -> Optional[str]:
        root = next((i for i in comps.get(comp_name, ()) if i[2]), None)
        if root is None:
            return None
        name, rest, _ = root
        if name in fused:
            return root_phase(fused[name])
        if own[name] is None and " tuple(" in rest:
            for op in re.findall(r"%[\w.\-]+", rest):
                p = root_phase(fused[op]) if op in fused else own.get(op)
                if p is not None:
                    return p
        return own[name]

    inside = set(fused.values())
    return {
        name: root_phase(fused[name]) if name in fused else own[name]
        for c, instrs in comps.items() if c not in inside
        for name, _rest, _root in instrs
    }


def drive_phase_map() -> dict:
    """HLO instruction name -> step phase (``PHASES``, or a scope of the
    handler's own) or None, over every drive program this process
    dispatched through ``run_drive``. Read from each compiled module's
    text, so ask for it after the measured window: the first call
    compiles each program again, which a persistent compilation cache
    turns into a load. A name the drive shares with its ``_init``
    program is left out (a trace keys ops by name across programs), and
    so is a name two drive programs give different phases."""
    out, clash = {}, set()
    for (workload, cfg, _lanes), prog in _DRIVE_PROGRAMS.items():
        if prog[2] is None:
            text = _drive.lower(workload, cfg, prog[0]).compile().as_text()
            phases = hlo_phases(text)
            if prog[1] is not None:
                init = _init.lower(workload, cfg, *prog[1]).compile()
                for name in hlo_phases(init.as_text()):
                    phases.pop(name, None)
            prog[2] = phases
        for name, phase in prog[2].items():
            if out.setdefault(name, phase) != phase:
                clash.add(name)
    for name in clash:
        del out[name]
    return out


def _run(
    workload: Workload, cfg: EngineConfig, seeds: jnp.ndarray, params=None
) -> EngineState:
    # init and the sweep loop are SEPARATE XLA programs on purpose: fusing
    # the unrolled per-seed init writes into the loop program pessimizes
    # the loop carry (measured 4.4 ms/step fused vs 0.43 ms/step split at
    # a 16k batch on v5e — layouts chosen for the init scatter leak into
    # every loop iteration). One extra dispatch per sweep is noise.
    state = _init(workload, cfg, seeds, params)
    return run_drive(workload, cfg, state, init_args=(seeds, params))


def run_sweep(workload: Workload, cfg: EngineConfig, seeds, params=None) -> EngineState:
    """Run a whole seed batch to completion; returns the final batched
    state (workload stats live in ``.wstate``). ``params`` carries
    per-lane spec-as-data (see ``init_sweep``); its leaves are traced jit
    arguments, so sweeping a new candidate costs NO recompile as long as
    the envelope (and thus every shape) is unchanged."""
    _procs_child_guard()
    return _run(workload, cfg, jnp.asarray(seeds, jnp.int64), params)


@partial(jax.jit, static_argnums=(0,))
def _concat_finals(total: int, *finals):
    """One program for the whole tree-concat + ragged-tail trim: eager
    per-leaf concatenates/slices would be one dispatch each (~40 leaves
    per chunk). Module-level so the jit cache persists across calls."""
    return jax.tree.map(
        lambda *ls: jnp.concatenate(ls, axis=0)[:total], *finals
    )


@partial(jax.jit, static_argnums=(1,))
def lane_slice(state, n: int, lo):
    """Lanes ``[lo, lo + n)`` of a batched state tree as ONE compiled
    program for every offset: ``lo`` is a traced scalar (dynamic slice),
    only the window size is static. The (candidate x seed) grid path
    carves its per-candidate summaries out of one flat sweep with this —
    K candidates cost K dispatches of one program, zero recompiles."""
    lo = jnp.asarray(lo, jnp.int32)
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, lo, n, axis=0), state
    )


def _pad_seeds(seeds, pad: int):
    """Append ``pad`` synthetic continuation seeds (max real seed + i +
    1); the padded lanes are sliced off inside ``_concat_finals``."""
    filler = jnp.max(seeds) + 1 + jnp.arange(pad, dtype=jnp.int64)
    return jnp.concatenate([seeds, filler])


def _pad_params(params, pad: int):
    """Edge-replicate per-lane params for ``pad`` synthetic lanes (their
    results are trimmed/masked like the padded seeds'; any valid params
    do — the last lane's are simply already there)."""
    return jax.tree.map(
        lambda a: np.concatenate(
            [np.asarray(a), np.broadcast_to(np.asarray(a)[-1:], (pad,) + np.shape(a)[1:])]
        ),
        params,
    )


def _slice_params(params, lo: int, hi: int):
    """Per-lane params for one chunk's lane slice."""
    return jax.tree.map(lambda a: np.asarray(a)[lo:hi], params)


def run_in_chunks(run_chunk, seeds, chunk_size: int, multiple: int = 1,
                  params=None, telemetry=None):
    """Shared chunk/pad/concat driver for large sweeps: run
    ``run_chunk(seed_chunk)`` over sequential ``chunk_size`` slices and
    concatenate the final states (single trim+concat program).

    A ragged final chunk is padded to the full ``chunk_size`` so every
    chunk reuses one compiled program; a batch smaller than one chunk is
    padded only to the next ``multiple`` (divisibility, e.g. a mesh
    size) — there is no program reuse to justify full-chunk padding.

    With per-lane ``params`` (spec-as-data), ``run_chunk(seed_chunk,
    param_chunk)`` receives the matching slice, edge-padded like the
    seeds.

    Each chunk's slice, pad and dispatch is one ``madsim.sweep.chunk``
    program span and the concat one ``madsim.sweep.concat`` (``obs.span``,
    recorded on ``telemetry``'s trace too if it has one); ``lo`` is the
    chunk's first lane, the index of its first seed in ``seeds``."""
    seeds = jnp.asarray(seeds, jnp.int64)
    n = int(seeds.shape[0])
    if n == 0:
        raise ValueError("seed batch is empty")

    def _run(chunk, pchunk):
        return run_chunk(chunk) if params is None else run_chunk(chunk, pchunk)

    if n <= chunk_size:
        pad = -n % multiple
        with obs.span("madsim.sweep.chunk", telemetry, lo=0):
            if pad == 0:
                return _run(seeds, params)
            padded = None if params is None else _pad_params(params, pad)
            final = _run(_pad_seeds(seeds, pad), padded)
        with obs.span("madsim.sweep.concat", telemetry, lo=0):
            return _concat_finals(n, final)
    finals = []
    for lo in range(0, n, chunk_size):
        with obs.span("madsim.sweep.chunk", telemetry, lo=lo):
            chunk = seeds[lo : lo + chunk_size]
            pchunk = None if params is None else _slice_params(params, lo, lo + chunk_size)
            pad = chunk_size - chunk.shape[0]
            if pad:
                chunk = _pad_seeds(chunk, pad)
                if pchunk is not None:
                    pchunk = _pad_params(pchunk, pad)
            finals.append(_run(chunk, pchunk))
    with obs.span("madsim.sweep.concat", telemetry, lo=0):
        return _concat_finals(n, *finals)


def state_bytes_per_seed(workload: Workload, cfg: EngineConfig, params=None) -> int:
    """Loop-carry bytes ONE seed lane holds through the sweep loop —
    the quantity whose batch-sized total stops fitting fast memory at
    the occupancy cliff (docs/pallas_finding.md §5). Computed from the
    abstract shapes of ``_init_one`` (no device work, no compile).
    ``params`` is one lane's spec-as-data pytree (unbatched) for
    envelope-keyed workloads, whose carry includes the per-lane
    ``FaultRt`` scalars."""
    pstruct = (
        None
        if params is None
        else jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
            params,
        )
    )
    shapes = jax.eval_shape(
        partial(_init_one, workload, cfg),
        jax.ShapeDtypeStruct((), jnp.int64),
        pstruct,
    )
    total = 0
    for leaf in jax.tree.leaves(shapes):
        try:
            itemsize = leaf.dtype.itemsize
        except (AttributeError, TypeError):
            itemsize = 8  # typed PRNG key: two uint32 words
        if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            itemsize = 8
        total += int(np.prod(leaf.shape, dtype=np.int64)) * itemsize
    return total


# The batch-occupancy knee, as a loop-carry budget: an earlier chip setup
# measured the 16,384-seed MadRaft batch (a ~70 MB carry) at full speed
# and the 65,536-seed batch (~4x) at ~0.75x seeds/s — the marginal
# per-step cost cliffs once the carry stops fitting fast memory (docs/
# pallas_finding.md §3/§5; not measured on today's code). 128 MiB keeps
# the auto-picked chunk at or below that knee for every bundled model;
# override with MADSIM_CHUNK_BUDGET_BYTES (or the explicit argument)
# after remeasuring bench.py's batch_curve on new hardware.
DEFAULT_CHUNK_BUDGET_BYTES = 128 * 1024 * 1024


def pick_chunk_size(
    workload: Workload,
    cfg: EngineConfig,
    budget_bytes: Optional[int] = None,
    lo: int = 1024,
    hi: int = 65536,
    params=None,
) -> int:
    """Largest power-of-two batch in ``[lo, hi]`` whose loop carry fits
    the fast-memory budget — the measured knee of the batch curve, not a
    guess. This is what ``run_sweep_chunked`` / the pipelined driver use
    when no explicit chunk size is given, so a history-recording
    workload (whose per-seed carry is several times a bare one's)
    automatically sweeps in smaller chunks instead of falling off the
    65k-seed cliff."""
    if budget_bytes is None:
        import os

        budget_bytes = int(
            os.environ.get(
                "MADSIM_CHUNK_BUDGET_BYTES", DEFAULT_CHUNK_BUDGET_BYTES
            )
        )
    per_seed = max(1, state_bytes_per_seed(workload, cfg, params=params))
    size = lo
    while size * 2 <= hi and size * 2 * per_seed <= budget_bytes:
        size *= 2
    return size


def run_sweep_chunked(
    workload: Workload,
    cfg: EngineConfig,
    seeds,
    chunk_size: Optional[int] = None,
    params=None,
    telemetry=None,
) -> EngineState:
    """Run a large seed sweep as sequential ``chunk_size`` batches of
    ONE compiled program, concatenating the final states (``telemetry``
    as ``run_in_chunks`` takes it).

    Measured on v5e: per-lane step cost cliffs ~9x somewhere between 16k
    and 32k seeds (0.13 -> 1.2 ms/step marginal; the loop working set
    stops fitting fast memory), so a 100k+ sweep runs several times
    faster as 16k chunks than as one giant batch — and a chunk is also
    the natural checkpoint/restart granule. Bit-identical to one big
    ``run_sweep`` per seed (seeds are independent). ``chunk_size=None``
    auto-picks the knee of the batch curve from the workload's measured
    loop-carry footprint (``pick_chunk_size``).

    The returned state keeps O(total seeds) device memory (per-seed
    event queues included) — fine to a few hundred thousand seeds on one
    chip. At the million-seed scale, don't hold finals at all: merge
    per-chunk ``sweep_summary`` dicts on host per chunk, as bench.py's
    bench_100k does."""
    if chunk_size is None:
        chunk_size = pick_chunk_size(
            workload, cfg,
            params=None
            if params is None
            else jax.tree.map(lambda a: np.asarray(a)[0], params),
        )
    if params is None:
        return run_in_chunks(
            lambda chunk: run_sweep(workload, cfg, chunk), seeds, chunk_size,
            telemetry=telemetry,
        )
    return run_in_chunks(
        lambda chunk, pchunk: run_sweep(workload, cfg, chunk, params=pchunk),
        seeds, chunk_size, params=params, telemetry=telemetry,
    )


@partial(jax.jit, static_argnums=(0, 1))
def _run_traced(workload: Workload, cfg: EngineConfig, seed: jnp.ndarray, params=None):
    state = _init_one(workload, cfg, seed, params)

    def scan_step(s, _):
        before_ctr = s.ctr
        _, q, t, kind, pay, found = _pop_event(workload, s, jnp.zeros((), bool))
        s2 = step_one(workload, cfg, s)
        fired = s2.ctr > before_ctr
        # probe AFTER the step: entry i is the violation-flavor bitmask
        # once event i has been applied, so the first i where it becomes
        # nonzero is the first violating event (explore/triage.py)
        probe = (
            jnp.asarray(workload.probe(s2.wstate), jnp.int32)
            if workload.probe is not None
            else jnp.zeros((), jnp.int32)
        )
        rec = (
            jnp.where(fired, s2.now_ns, jnp.int64(-1)),
            jnp.where(fired, kind, jnp.int32(-1)),
            jnp.where(fired, pay, jnp.zeros_like(pay)),
            fired,
            probe,
        )
        return s2, rec

    final, (times, kinds, pays, fired, probes) = jax.lax.scan(
        scan_step, state, None, length=cfg.max_steps
    )
    trace = {"time_ns": times, "kind": kinds, "pay": pays, "fired": fired}
    if workload.probe is not None:
        trace["probe"] = probes
    return final, trace


def run_traced(workload: Workload, cfg: EngineConfig, seed: int, params=None):
    """Replay ONE seed, recording every dispatched event in order.

    This is the debugging/bit-exact-replay path (SURVEY.md §7): run it on
    the CPU backend against a failure seed found by a TPU sweep — the
    integer-only engine guarantees the identical event sequence.
    ``params`` is ONE candidate's (unbatched) spec-as-data pytree for
    envelope-keyed workloads — ddmin shrink re-verifications replay
    every candidate schedule through one compiled traced program.
    """
    return _run_traced(workload, cfg, jnp.asarray(seed, jnp.int64), params)


def _host_leaves(tree) -> list:
    """Host arrays of every leaf; typed PRNG keys via their raw words."""
    return [
        np.asarray(
            jax.random.key_data(a)
            if jnp.issubdtype(a.dtype, jax.dtypes.prng_key)
            else a
        )
        for a in jax.tree.leaves(tree)
    ]


def cpu_parity(workload: Workload, cfg: EngineConfig, seeds) -> dict:
    """The framework's contract, checked: ``seeds`` swept on the default
    device and again on the CPU backend agree on every ``EngineState``
    leaf, and ``run_traced`` of the first seed on the CPU lands on that
    seed's lane of the device sweep. Returns the two verdicts; the
    caller decides whether a mismatch is fatal."""
    cpu = jax.devices("cpu")[0]
    seeds = jnp.asarray(seeds, jnp.int64)
    dev = run_sweep(workload, cfg, seeds)
    first = int(np.asarray(seeds)[0])
    with jax.default_device(cpu):
        ref = run_sweep(workload, cfg, jax.device_put(seeds, cpu))
        traced, _ = run_traced(workload, cfg, first)
    dev_leaves = _host_leaves(dev)
    lane = [a[0] for a in dev_leaves]
    return {
        "seeds": int(seeds.shape[0]),
        "leaves": len(dev_leaves),
        "leaves_equal": all(
            np.array_equal(a, b) for a, b in zip(dev_leaves, _host_leaves(ref))
        ),
        "traced_replay_seed": first,
        "traced_replay_equal": all(
            np.array_equal(a, b) for a, b in zip(lane, _host_leaves(traced))
        ),
    }
