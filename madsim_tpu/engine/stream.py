"""Persistent streaming sweep service: continuous lane refill.

The chunked drivers (core.run_sweep_chunked, checkpoint.run_sweep_pipelined)
run fixed-shape batches to completion: a lane whose seed finishes early —
or violates at t=2s of a 30s horizon — idles as a frozen no-op until the
slowest lane in its chunk retires, and the batch curve sags once the
chunk's loop carry outgrows fast memory (docs/pallas_finding.md §6: both
historical 10x sinks were structural, not micro). This module borrows
continuous batching from LLM serving instead:

- a fixed **lane pool** of ``pool_size`` lanes holds the loop carry at a
  constant, knee-sized working set for the whole sweep;
- each lane carries its own ``(seed, FaultParams, step budget)`` — the
  spec-as-data machinery (engine/faults.py) makes per-lane specs traced
  data, so lanes of one pool may run *different candidates*;
- one compiled **round program** advances every live lane up to
  ``round_steps`` events (``_round`` — the budget-freeze form of
  ``core.drive``'s loop, bit-identical per lane), exiting early once a
  refill quorum of lanes has retired so free slots turn over at the
  retirement flux, not the round boundary;
- retired lanes (done, or per-lane step budget spent) are captured into a
  host-side result buffer and **refilled in flight** from the work queue
  by one jitted fixed-width row re-init (``_refill_rows``: init quorum-many
  fresh lanes, scatter into the pool; the mesh path uses the full-pool
  masked form ``_refill``) — zero XLA compiles after warm-up
  (``engine/compiles.count_compiles`` asserts this in the bench leg and
  tests/test_stream.py).

Determinism contract (docs/streaming.md): a lane's final state is a pure
function of its ``(seed, params, budget)`` — the engine's per-lane masking
makes neighbors invisible — so per-seed results are **bit-identical to the
chunked driver**, and the merged report is **lane-order- and
refill-schedule-invariant**: results are buffered per work item and flushed
as *virtual chunks* in submission order (the same ``chunk_size`` granule,
``summarize``/``host_work``/``merge_summaries`` discipline, and therefore
the same bytes, as ``run_sweep_pipelined``). Two different
``queue_order`` permutations, or an interrupt/resume through a v9 stream
snapshot (``checkpoint.save_stream``), change wall-clock only — never a
report byte.

The budget-freeze trick: ``core.drive`` cuts the whole batch at
``iters < max_steps``, but a live (not-done) lane advances ``ctr`` by
exactly 1 per drive iteration, so the global cut equals a per-lane cut at
``ctr >= max_steps``. ``_round`` applies that cut per lane (temporarily
marking over-budget lanes done for the step, then restoring their true
``done`` bit), which is what lets one pool mix lanes of different ages —
and different per-lane budgets — while staying bit-identical to the
chunked driver for every lane.
"""

from __future__ import annotations

import hashlib
from functools import lru_cache, partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .core import EngineConfig, EngineState, Workload, init_sweep, step_batch


def _freeze_step(workload: Workload, cfg: EngineConfig, s: EngineState, budget):
    """One batch step with per-lane budget freeze: an over-budget lane is
    stepped as done (a bit-exact no-op pass-through) and keeps its TRUE
    ``done`` bit — the chunked driver leaves a budget-cut lane not-done
    at ``max_steps`` too, so capture-time states match bit for bit."""
    over = s.ctr >= budget
    s2 = step_batch(workload, cfg, s._replace(done=s.done | over))
    return s2._replace(done=jnp.where(over, s.done, s2.done))


@partial(jax.jit, static_argnums=(0, 1, 2))
def _round(
    workload: Workload, cfg: EngineConfig, round_steps: int,
    state: EngineState, budget, stop_live,
):
    """One device round: up to ``round_steps`` events for every live lane
    of the pool (live = not done AND under its own step budget), exiting
    early once the live count falls to ``stop_live`` — the host sets it a
    refill quorum below the round's starting count while the queue has
    work (so retired lanes hand their slots over promptly instead of
    burning frozen no-op steps to the round boundary) and to 0 for the
    drain. ONE flat while_loop, same shape as ``core.drive`` (a nested
    device loop costs ~9x per step on TPU)."""

    def cond(carry):
        s, i = carry
        live = jnp.sum(~s.done & (s.ctr < budget), dtype=jnp.int32)
        return (live > stop_live) & (i < round_steps)

    def body(carry):
        s, i = carry
        return _freeze_step(workload, cfg, s, budget), i + 1

    state, _ = jax.lax.while_loop(
        cond, body, (state, jnp.zeros((), jnp.int64))
    )
    return state


@lru_cache(maxsize=64)
def _round_sharded(
    workload: Workload, cfg: EngineConfig, round_steps: int, mesh
):
    """The round program shard_map'd over the mesh's seed axis — the
    sharded-variant composition with parallel/mesh.py: per-device stepping
    with one psum'd live count per iteration (the same collective as
    ``mesh._sharded_run``), so all devices leave the round together.
    Cached per (workload, cfg, round_steps, mesh) like every other
    sharded program."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import SEED_AXIS

    def device_run(state: EngineState, budget, stop_live):
        def cond(carry):
            s, i = carry
            live = jax.lax.psum(
                jnp.sum(~s.done & (s.ctr < budget), dtype=jnp.int32),
                SEED_AXIS,
            )
            return (live > stop_live[0]) & (i < round_steps)

        def body(carry):
            s, i = carry
            return _freeze_step(workload, cfg, s, budget), i + 1

        state, _ = jax.lax.while_loop(
            cond, body, (state, jnp.zeros((), jnp.int64))
        )
        return state

    return jax.jit(
        jax.shard_map(
            device_run, mesh=mesh,
            in_specs=(P(SEED_AXIS), P(SEED_AXIS), P(None)),
            out_specs=P(SEED_AXIS),
            check_vma=False,
        )
    )


def _mask_tree(mask, new, old):
    """Per-leaf ``where(mask, new, old)`` over two EngineStates; typed
    PRNG keys select through their raw uint32 words."""

    def pick(a, b):
        if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
            ad, bd = jax.random.key_data(a), jax.random.key_data(b)
            m = mask.reshape(mask.shape + (1,) * (ad.ndim - 1))
            return jax.random.wrap_key_data(jnp.where(m, ad, bd))
        m = mask.reshape(mask.shape + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)

    return jax.tree.map(pick, new, old)


@partial(jax.jit, static_argnums=(0, 1))
def _refill(
    workload: Workload, cfg: EngineConfig, state: EngineState,
    mask, seeds, params=None,
):
    """The full-pool in-flight refill (mesh path): re-init every lane
    and keep the fresh state only where ``mask`` is set. All inputs are
    traced (fixed shapes), so refilling costs ZERO recompiles — the
    whole point of spec-as-data. Re-initing the unmasked lanes too
    wastes a few vector ops but keeps the program shape independent of
    the retirement pattern (and of the mesh layout)."""
    fresh = init_sweep(workload, cfg, seeds, params)
    return _mask_tree(mask, fresh, state)


@partial(jax.jit, static_argnums=(0, 1))
def _refill_rows(
    workload: Workload, cfg: EngineConfig, state: EngineState,
    lanes, seeds, params=None,
):
    """The fixed-width row refill (local path): init exactly the refill
    quorum's worth of fresh lanes and scatter them into the pool at
    ``lanes``. Init work per stream then totals one init per work item —
    the same as the chunked driver — instead of a full-pool init per
    refill event. Short cohorts pad ``lanes`` with duplicates of their
    first entry; the duplicate rows carry identical (seed, params), so
    the repeated scatter writes are value-identical and the result is
    deterministic."""
    fresh = init_sweep(workload, cfg, seeds, params)

    def put(old, new):
        if jnp.issubdtype(old.dtype, jax.dtypes.prng_key):
            od, nd = jax.random.key_data(old), jax.random.key_data(new)
            return jax.random.wrap_key_data(od.at[lanes].set(nd))
        return old.at[lanes].set(new)

    return jax.tree.map(put, state, fresh)


def _leaf_info(state: EngineState):
    """(treedef, key-leaf mask) of a pool state — computed once per
    stream; rows travel host-side in raw form (key leaves as words)."""
    leaves, treedef = jax.tree.flatten(state)
    keymask = tuple(
        bool(jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key))
        for leaf in leaves
    )
    return treedef, keymask


def _pool_to_host(state: EngineState, keymask):
    """Every pool leaf as a host array (key leaves as raw words)."""
    return [
        np.asarray(jax.random.key_data(leaf) if isk else leaf)
        for isk, leaf in zip(keymask, jax.tree.leaves(state))
    ]


def _buf_state(leaves, treedef, keymask) -> EngineState:
    """A captured chunk buffer (host leaf arrays, submission order) as a
    batched EngineState — what ``summarize`` and ``host_work`` consume
    at flush time."""
    return jax.tree.unflatten(
        treedef,
        [
            jax.random.wrap_key_data(jnp.asarray(b)) if isk else b
            for isk, b in zip(keymask, leaves)
        ],
    )


def stream_sweep(
    workload: Workload,
    cfg: EngineConfig,
    seeds,
    summarize,
    *,
    params=None,
    budgets=None,
    chunk_size: Optional[int] = None,
    pool_size: Optional[int] = None,
    round_steps: int = 256,
    host_work: Optional[Callable] = None,
    screen: Optional[Callable] = None,
    mesh=None,
    queue_order=None,
    on_chunk: Optional[Callable] = None,
    stats: Optional[dict] = None,
    ckpt_path: Optional[str] = None,
    stop_after_rounds: Optional[int] = None,
    resume_from: Optional[str] = None,
    feed: Optional[Callable[[], Optional[dict]]] = None,
    reprioritize: Optional[Callable] = None,
    telemetry=None,
) -> dict:
    """Sweep ``seeds`` through a constant-occupancy lane pool; returns
    the merged summary dict, byte-identical to ``run_sweep_pipelined``
    over the same ``(seeds, params, chunk_size)``.

    Work items are ``(seed, params row, budget)`` triples in submission
    order; ``queue_order`` (a permutation of ``range(len(seeds))``)
    reorders only their *dispatch* onto lanes — results are buffered per
    item and flushed as virtual ``chunk_size`` chunks in submission
    order, so the report bytes are refill-schedule-invariant (the
    invariance tests/test_stream.py pins).

    - ``params``: per-item spec-as-data pytree (leading axis = items),
      ``engine.run_sweep``'s contract. Lanes of one pool may carry
      different candidates — this is how a campaign's candidate grid
      feeds the queue instead of chunk boundaries.
    - ``budgets``: optional per-item step budgets (int[n], default
      ``cfg.max_steps``) — the per-lane "horizon" knob.
    - ``screen``: ``final -> bool[S]`` suspect mask (e.g.
      ``oracle.screen.screen_sweep``); runs once per retirement cohort
      on the POOL state, and the per-item bits ride to the flush, where
      ``host_work(final, lo=, n=, seeds=, suspect=, summary=)`` sees
      exactly what the pipelined driver would hand it. A suspect bit is
      a pure per-lane function, so cohort screening == chunk screening.
    - ``mesh``: runs the round/refill/screen programs sharded over the
      mesh's seed axis (``pool_size`` rounds up to mesh divisibility).
    - ``stats``: a caller-owned dict filled with wall-clock-side
      telemetry (``rounds``, ``refills``, ``lanes``, ``occupancy_mean``)
      — kept OUT of the returned totals so the report stays a pure
      function of the work. Updated INCREMENTALLY (after every flush and
      before every snapshot), so an interrupted or crashed run still
      leaves occupancy records behind, not just a completed one.
    - ``telemetry`` (``obs.Telemetry`` or None): per-round occupancy and
      queue-depth gauges, round/refill-quorum/flush latency histograms,
      retirement-flux and drain-tail counters, seeds-done progress, and
      — when the handle carries a trace — "dispatch" round spans (the
      host's window from dispatch to the pool state) with "host" flush
      spans interleaved plus an occupancy counter track
      (the refill-cadence picture). Strictly OUT-OF-BAND: every recorder
      is behind an ``is not None`` guard; the report bytes are identical
      with telemetry on or off.

    Interrupt/resume (checkpoint format v9): ``stop_after_rounds=R``
    snapshots pool + pending results + merged totals to ``ckpt_path``
    after R rounds this call and returns the (partial) totals;
    ``resume_from=path`` continues — flushed chunks never recompute, and
    the final totals are bit-identical to the uninterrupted run.

    In-flight queue feed: ``feed`` is a nullary callable polled whenever
    free lanes outnumber queued items. It returns ``None`` (nothing more
    — the stream drains and returns) or a segment dict
    ``{"seeds": int[m], "params": rows or None, "budgets": int[m] or
    absent}`` appended to the work queue WITHOUT leaving the pool: fed
    lanes enter through the same traced refill programs, so a fleet
    worker's newly leased batches start at zero recompiles. Segments
    (and the initial ``seeds``) must be multiples of ``chunk_size`` —
    fed chunks flush in arrival order with the same virtual-chunk bytes
    as passing the concatenated queue up front (pinned by
    tests/test_stream.py). ``feed`` is incompatible with
    ``queue_order`` and with checkpointing (``ckpt_path``/
    ``resume_from``): the queue is open-ended, so there is no fixed
    submission order to permute or fingerprint.

    Live queue reorder: ``reprioritize`` is a callable polled before
    each dispatch with the UNDISPATCHED item indices (submission
    order positions); it returns a permutation of that array (or None
    to keep it) which replaces the dispatch order of the queued tail —
    the explore scheduler's zero-recompile "jump the queue" knob
    (explore/steer.py). Already-dispatched lanes and the initial pool
    fill are untouched, and because results flush as virtual chunks in
    SUBMISSION order regardless of dispatch order, a reprioritized
    stream changes wall-clock only, never a report byte (the same
    invariance ``queue_order`` pins). Incompatible with checkpointing:
    a mutable dispatch order has no stable ``order_sha`` to fingerprint.
    """
    import time as _time

    from .checkpoint import _sweep_fingerprint, params_digest
    from ..models._common import merge_summaries  # lazy: models import us

    tracer = telemetry.tracer if telemetry is not None else None
    seeds_host = np.asarray(jnp.asarray(seeds, jnp.int64))
    n = int(seeds_host.size)
    if n == 0:
        raise ValueError("seed batch is empty")
    if round_steps < 1:
        raise ValueError(f"round_steps must be >= 1, got {round_steps}")
    if chunk_size is None:
        from .core import pick_chunk_size

        chunk_size = pick_chunk_size(
            workload, cfg,
            params=None
            if params is None
            else jax.tree.map(lambda a: np.asarray(a)[0], params),
        )
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    multiple = 1 if mesh is None else int(mesh.devices.size)
    L = min(pool_size if pool_size is not None else chunk_size, n)
    L = -(-L // multiple) * multiple
    if stop_after_rounds is not None and ckpt_path is None:
        raise ValueError("stop_after_rounds requires ckpt_path")

    budgets_host = (
        np.full(n, cfg.max_steps, np.int32)
        if budgets is None
        else np.asarray(budgets, np.int32)
    )
    if budgets_host.shape != (n,):
        raise ValueError(
            f"budgets must be shape ({n},), got {budgets_host.shape}"
        )
    order = (
        np.arange(n, dtype=np.int64)
        if queue_order is None
        else np.asarray(queue_order, np.int64)
    )
    if not np.array_equal(np.sort(order), np.arange(n)):
        raise ValueError("queue_order must be a permutation of range(n)")
    if feed is not None:
        if queue_order is not None:
            raise ValueError("feed is incompatible with queue_order")
        if resume_from is not None or ckpt_path is not None:
            raise ValueError(
                "feed is incompatible with checkpointing "
                "(ckpt_path/resume_from)"
            )
        if n % chunk_size:
            raise ValueError(
                f"with feed, the initial seeds must be a multiple of "
                f"chunk_size={chunk_size}, got {n}"
            )
    if reprioritize is not None and (
        resume_from is not None or ckpt_path is not None
    ):
        raise ValueError(
            "reprioritize is incompatible with checkpointing "
            "(ckpt_path/resume_from): the dispatch order is mutable"
        )
    params_host = (
        None if params is None else jax.tree.map(np.asarray, params)
    )

    fp = _sweep_fingerprint(workload, cfg)
    if params is not None:
        fp += "|params" + params_digest(params)
    seeds_sha = hashlib.sha256(
        np.ascontiguousarray(seeds_host).tobytes()
    ).hexdigest()
    order_sha = hashlib.sha256(
        np.ascontiguousarray(order).tobytes()
    ).hexdigest()

    def pool_rows(items):
        """Per-lane params rows for an item-index vector."""
        return jax.tree.map(lambda a: a[items].copy(), params_host)

    def place_pool(arr):
        """A [L]-leading pool array, sharded over the mesh when given
        (dtype-preserving — the refill mask is bool)."""
        if mesh is None:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.mesh import SEED_AXIS

        return jax.device_put(
            jnp.asarray(arr), NamedSharding(mesh, P(SEED_AXIS))
        )

    def place_params(tree):
        if tree is None or mesh is None:
            return tree
        from ..parallel.mesh import shard_params

        return shard_params(mesh, tree)

    totals: dict = {}
    # budgeted incremental checking: a host_work advertising the
    # submit/poll/drain protocol (oracle.screen._HostWork) has its WGL
    # work interleaved with the DEVICE rounds — each flush submits its
    # chunk (cheap decode+dedup) and the verdict work is polled right
    # after every round's dispatch, inside the window where the device
    # is crunching and the host would otherwise just block on
    # state.done. The poll budget tracks the round wall time's EMA
    # (minus the poll's own cost), so checking consumes exactly the
    # host idle the rounds create and the pool never stalls on the
    # checker. OFF under checkpointing (ckpt_path/stop_after_rounds/
    # resume_from): snapshots need every flushed chunk's summary
    # finalized at its flush. Reports are byte-identical either way —
    # chunks finalize and merge strictly in submission order no matter
    # how the budget slices the checking.
    incr = (
        host_work is not None
        and getattr(host_work, "incremental", False)
        and ckpt_path is None
        and stop_after_rounds is None
        and resume_from is None
    )
    deferred: dict = {}  # lo -> (k, base summary) awaiting a verdict
    round_ema = 0.0
    # captured-but-unflushed results live in per-chunk host buffers
    # (one preallocated [k_c, ...] array per leaf — captures and flushes
    # are vectorized scatters/reads, never per-row python loops)
    pend: dict = {}  # chunk index -> per-leaf [k_c, ...] buffers
    pend_have: dict = {}  # chunk index -> bool[k_c] captured flags
    sus_buf: dict = {}  # chunk index -> bool[k_c] suspect bits
    resume_pending: dict = {}  # item -> row leaves (v9 load only)
    resume_susp: dict = {}
    rounds = refills = 0
    occ_sum = 0.0
    next_flush_lo = 0

    if resume_from is not None:
        from .checkpoint import load_stream

        pstruct = (
            None
            if params_host is None
            else jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    (L,) + np.shape(a)[1:], np.asarray(a).dtype
                ),
                params_host,
            )
        )
        like = jax.eval_shape(
            partial(init_sweep, workload, cfg),
            jax.ShapeDtypeStruct((L,), jnp.int64),
            pstruct,
        )
        state, resume_pending, resume_susp, meta = load_stream(
            resume_from, like
        )
        for key, want in (
            ("fingerprint", fp), ("seeds_sha", seeds_sha),
            ("order_sha", order_sha), ("chunk_size", int(chunk_size)),
            ("lanes", int(L)),
        ):
            if meta.get(key) != want:
                raise ValueError(
                    f"stream snapshot {resume_from} is from a different "
                    f"stream: {key}={meta.get(key)!r}, expected {want!r}"
                )
        lane_item = np.asarray(meta["lane_item"], np.int64)
        lane_budget = np.asarray(meta["lane_budget"], np.int32)
        next_q = int(meta["next_q"])
        next_flush_lo = int(meta["next_flush_lo"])
        totals = meta["totals"]
        rounds = int(meta["rounds"])
        refills = int(meta["refills"])
        occ_sum = float(meta["occ_sum"])
        pool_seeds = np.asarray(state.seed).copy()
        if params_host is not None:
            pool_params = pool_rows(np.where(lane_item >= 0, lane_item, 0))
        else:
            pool_params = None
        if mesh is not None:
            from ..parallel.mesh import shard_state

            state = shard_state(mesh, state)
    else:
        from .core import _init

        t = min(L, n)
        lane_item = np.full(L, -1, np.int64)
        lane_item[:t] = order[:t]
        next_q = t
        # budget 0 freezes an unassigned lane before its first event —
        # the pool's "live" mask is lane_item >= 0 plus this freeze
        lane_budget = np.zeros(L, np.int32)
        lane_budget[:t] = budgets_host[order[:t]]
        pool_seeds = np.empty(L, np.int64)
        pool_seeds[:t] = seeds_host[order[:t]]
        pool_seeds[t:] = seeds_host[order[0]]
        pool_params = (
            None
            if params_host is None
            else pool_rows(np.where(lane_item >= 0, lane_item, 0))
        )
        state = _init(
            workload, cfg, place_pool(pool_seeds), place_params(pool_params)
        )

    treedef, keymask = _leaf_info(state)

    def capture(items, sub, sus):
        """Scatter a retirement cohort's rows (``sub``: per-leaf
        [cohort, ...] slices, item order matching ``items``) into the
        per-chunk pending buffers — vectorized per (chunk, leaf)."""
        chunks = items // chunk_size
        for c in np.unique(chunks):
            c = int(c)
            lo = c * chunk_size
            k = min(chunk_size, n - lo)
            sel = chunks == c
            pos = items[sel] - lo
            if c not in pend:
                pend[c] = [
                    np.empty((k,) + s.shape[1:], s.dtype) for s in sub
                ]
                pend_have[c] = np.zeros(k, bool)
                sus_buf[c] = np.zeros(k, bool)
            for buf, s in zip(pend[c], sub):
                buf[pos] = s[sel]
            pend_have[c][pos] = True
            if sus is not None:
                sus_buf[c][pos] = sus[sel]

    if resume_pending:
        its = np.fromiter(resume_pending.keys(), np.int64)
        capture(
            its,
            [
                np.stack([resume_pending[int(i)][j] for i in its])
                for j in range(len(keymask))
            ],
            None
            if screen is None
            else np.array(
                [bool(resume_susp.get(int(i), False)) for i in its]
            ),
        )
        resume_pending = resume_susp = {}

    def publish_stats():
        """Surface the stream's internal telemetry NOW — called after
        every flush and before every snapshot (not just at return), so
        an interrupted run still has its occupancy record."""
        if stats is not None:
            stats.update(
                rounds=int(rounds),
                refills=int(refills),
                lanes=int(L),
                round_steps=int(round_steps),
                occupancy_mean=(occ_sum / rounds if rounds else 0.0),
            )

    def absorb(finished):
        """Merge finished incremental reports — ``(lo, extra)`` pairs
        in submission order, the only order ``_HostWork.poll`` ever
        returns them in, so the totals merge exactly as the sync path's
        would."""
        for flo, extra in finished:
            fk, summary = deferred.pop(flo)
            if extra:
                summary = {**summary, **extra}
            merge_summaries(totals, summary)
            if telemetry is not None:
                telemetry.count(
                    "stream_seeds_done_total", fk,
                    help="seeds flushed into the merged report",
                )
                telemetry.event_mix(summary)
                telemetry.event("flush", lo=flo, k=fk)
            if on_chunk is not None:
                on_chunk(lo=flo, k=fk, summary=summary)
            publish_stats()

    def flush_ready():
        nonlocal next_flush_lo
        while next_flush_lo < n:
            c = next_flush_lo // chunk_size
            k = min(chunk_size, n - next_flush_lo)
            if c not in pend or not pend_have[c].all():
                return
            if telemetry is not None:
                t_flush = _time.perf_counter()
                f0 = tracer._now_us() if tracer is not None else 0.0
            chunk_state = _buf_state(pend.pop(c), treedef, keymask)
            pend_have.pop(c)
            sus = sus_buf.pop(c)
            summary = summarize(chunk_state)
            if incr:
                # defer the verdict: submit runs decode+dedup now, the
                # WGL slices run from the per-round polls, and absorb()
                # merges when the chunk's report is final
                host_work.submit(
                    chunk_state,
                    lo=next_flush_lo,
                    n=k,
                    seeds=seeds_host[next_flush_lo : next_flush_lo + k],
                    suspect=None if screen is None else sus,
                    summary=summary,
                )
                deferred[next_flush_lo] = (k, summary)
                if telemetry is not None:
                    dt = _time.perf_counter() - t_flush
                    telemetry.observe(
                        "stream_flush_seconds", dt,
                        help="virtual-chunk flush (summary+host work)",
                    )
                    if tracer is not None:
                        tracer.complete(
                            f"flush lo={next_flush_lo}", f0,
                            tracer._now_us() - f0, track="host",
                            args={"lo": next_flush_lo, "k": k},
                        )
                next_flush_lo += k
                continue
            if host_work is not None:
                extra = host_work(
                    chunk_state,
                    lo=next_flush_lo,
                    n=k,
                    seeds=seeds_host[next_flush_lo : next_flush_lo + k],
                    suspect=None if screen is None else sus,
                    summary=summary,
                )
                if extra:
                    summary = {**summary, **extra}
            merge_summaries(totals, summary)
            if telemetry is not None:
                dt = _time.perf_counter() - t_flush
                telemetry.observe(
                    "stream_flush_seconds", dt,
                    help="virtual-chunk flush (summary+host work)",
                )
                telemetry.count(
                    "stream_seeds_done_total", k,
                    help="seeds flushed into the merged report",
                )
                telemetry.event_mix(summary)
                telemetry.event(
                    "flush", lo=next_flush_lo, k=k, wall_s=round(dt, 6)
                )
                if tracer is not None:
                    tracer.complete(
                        f"flush lo={next_flush_lo}", f0,
                        tracer._now_us() - f0, track="host",
                        args={"lo": next_flush_lo, "k": k},
                    )
            if on_chunk is not None:
                on_chunk(lo=next_flush_lo, k=k, summary=summary)
            next_flush_lo += k
            publish_stats()

    def poll_feed():
        """One feed poll: extend the open-ended work queue with a fed
        segment. False when feed is absent or dry — the stream then
        drains and returns as usual. Growing the host-side queue arrays
        never touches the pool: fed items reach lanes through the same
        traced refill programs, at zero recompiles."""
        nonlocal n, seeds_host, budgets_host, order, params_host
        if feed is None:
            return False
        seg = feed()
        if seg is None:
            return False
        new_seeds = np.asarray(jnp.asarray(seg["seeds"], jnp.int64)).ravel()
        m = int(new_seeds.size)
        if m == 0 or m % chunk_size:
            raise ValueError(
                f"fed segment must be a non-empty multiple of "
                f"chunk_size={chunk_size}, got {m} seeds"
            )
        if (seg.get("params") is None) != (params_host is None):
            raise ValueError(
                "fed segment params presence must match the stream's"
            )
        nb = seg.get("budgets")
        nb = (
            np.full(m, cfg.max_steps, np.int32)
            if nb is None
            else np.asarray(nb, np.int32)
        )
        if nb.shape != (m,):
            raise ValueError(
                f"fed budgets must be shape ({m},), got {nb.shape}"
            )
        seeds_host = np.concatenate([seeds_host, new_seeds])
        budgets_host = np.concatenate([budgets_host, nb])
        order = np.concatenate(
            [order, np.arange(n, n + m, dtype=np.int64)]
        )
        if params_host is not None:
            params_host = jax.tree.map(
                lambda a, b: np.concatenate([a, np.asarray(b)]),
                params_host, seg["params"],
            )
        n += m
        if telemetry is not None:
            telemetry.count(
                "stream_feed_segments_total",
                help="work segments fed into the running stream",
            )
            telemetry.count(
                "stream_feed_items_total", m,
                help="work items fed into the running stream",
            )
        return True

    def dispatch_free():
        """Assign free lanes from the queue, polling ``feed`` for more
        whenever the queue runs dry while lanes sit free — the point
        where a fleet worker's newly leased batches enter the running
        pool, mid-flight."""
        nonlocal next_q, refills, state
        if reprioritize is not None and next_q < n:
            # the live reorder: hand the scheduler the undispatched
            # tail, let it permute the DISPATCH order only (results
            # still flush in submission order — bytes cannot move)
            tail = order[next_q:].copy()
            new = reprioritize(tail)
            if new is not None:
                new = np.asarray(new, np.int64)
                if new.shape != tail.shape or not np.array_equal(
                    np.sort(new), np.sort(tail)
                ):
                    raise ValueError(
                        "reprioritize must return a permutation of the "
                        "undispatched item indices it was given"
                    )
                order[next_q:] = new
        while True:
            free = np.nonzero(lane_item < 0)[0]
            if free.size == 0:
                return
            if next_q >= n and not poll_feed():
                return
            take = min(int(free.size), n - next_q)
            if take == 0:
                return
            lanes_t = free[:take]
            items_t = order[next_q : next_q + take]
            next_q += take
            refills += take
            if telemetry is not None:
                telemetry.count(
                    "stream_refills_total", take,
                    help="lanes refilled from the work queue",
                )
            lane_item[lanes_t] = items_t
            lane_budget[lanes_t] = budgets_host[items_t]
            pool_seeds[lanes_t] = seeds_host[items_t]
            if pool_params is not None:
                for p, s in zip(
                    jax.tree.leaves(pool_params),
                    jax.tree.leaves(params_host),
                ):
                    p[lanes_t] = s[items_t]
            if mesh is None:
                # fixed-width row refill: init exactly quorum-many
                # fresh lanes per event (padding short cohorts with
                # duplicates of their first lane), so total init
                # work is one init per item — same as chunked
                w = max(1, L // 8)
                for off in range(0, take, w):
                    sub = lanes_t[off : off + w]
                    idx = np.concatenate(
                        [sub, np.full(w - sub.size, sub[0], sub.dtype)]
                    )
                    state = _refill_rows(
                        workload, cfg, state,
                        jnp.asarray(idx, jnp.int32),
                        jnp.asarray(pool_seeds[idx]),
                        None
                        if pool_params is None
                        else jax.tree.map(
                            lambda a: jnp.asarray(a[idx]), pool_params
                        ),
                    )
            else:
                # mesh path: full-pool masked re-init keeps the
                # refill shape independent of the mesh layout
                mask = np.zeros(L, bool)
                mask[lanes_t] = True
                state = _refill(
                    workload, cfg, state,
                    place_pool(mask),
                    place_pool(pool_seeds),
                    place_params(pool_params),
                )

    rounds_this_call = 0
    while True:
        flush_ready()
        if next_flush_lo >= n:
            # everything queued so far is flushed; only a fed segment
            # can extend the stream now (all lanes are free, so the
            # dispatch below must land work or we are done)
            if not poll_feed():
                break
            dispatch_free()
            continue
        assigned = int(np.count_nonzero(lane_item >= 0))
        occ_sum += assigned / L
        if telemetry is not None:
            t_round = _time.perf_counter()
            r0 = tracer._now_us() if tracer is not None else 0.0
            telemetry.gauge(
                "stream_occupancy", assigned / L,
                help="assigned lanes / pool size at round start",
            )
            telemetry.gauge(
                "stream_queue_depth", n - next_q,
                help="work items not yet dispatched onto lanes",
            )
            if next_q >= n:
                telemetry.count(
                    "stream_drain_rounds_total",
                    help="rounds run after the queue went dry (drain tail)",
                )
            telemetry.sample(
                "stream occupancy",
                occupancy=assigned / L, queue_depth=n - next_q,
            )
        # while the queue still has work, exit the round as soon as a
        # refill quorum (L/8 lanes) retires — retired lanes hand their
        # slots over instead of burning frozen steps to the round
        # boundary; once the queue is dry, drain to the end (with a
        # feed, quorum exits persist: more work may arrive at any
        # retirement, so slots keep turning over)
        stop = (
            max(assigned - max(1, L // 8), 0)
            if (next_q < n or feed is not None)
            else 0
        )
        budget_dev = jnp.asarray(lane_budget)
        stop_dev = jnp.asarray([stop], jnp.int32)
        if incr:
            t_disp = _time.perf_counter()
        if mesh is None:
            state = _round(
                workload, cfg, round_steps, state, budget_dev, stop_dev[0]
            )
        else:
            state = _round_sharded(workload, cfg, round_steps, mesh)(
                state, budget_dev, stop_dev
            )
        rounds += 1
        rounds_this_call += 1

        if incr:
            # the round program is dispatched but not synced: this is
            # the host's idle window, so burn it on deferred WGL work
            # under the round-time EMA budget (its own cost excluded —
            # the feedback otherwise inflates the budget it measures)
            t_poll = _time.perf_counter()
            absorb(host_work.poll(round_ema))
            poll_s = _time.perf_counter() - t_poll
        done = np.asarray(state.done)  # syncs on the round program
        if incr:
            dt = max(0.0, _time.perf_counter() - t_disp - poll_s)
            round_ema = dt if round_ema == 0.0 else (
                0.5 * round_ema + 0.5 * dt
            )
        if telemetry is not None:
            telemetry.observe(
                "stream_round_seconds", _time.perf_counter() - t_round,
                help="device round (dispatch -> pool state on host)",
            )
            telemetry.count("stream_rounds_total")
            if tracer is not None:
                tracer.complete(
                    f"round {rounds} dispatch-to-state", r0,
                    tracer._now_us() - r0, track="dispatch",
                    args={"occupancy": assigned / L, "queue": n - next_q},
                )
        ctr = np.asarray(state.ctr)
        retired = (lane_item >= 0) & (done | (ctr >= lane_budget))
        if retired.any():
            if telemetry is not None:
                telemetry.count(
                    "stream_retired_total", int(retired.sum()),
                    help="lanes retired (retirement flux)",
                )
                telemetry.observe(
                    "stream_refill_quorum_seconds",
                    _time.perf_counter() - t_round,
                    help="round dispatch -> retirement cohort on host "
                    "(refill quorum latency)",
                )
            # one screen per retirement cohort, on the pool state; the
            # suspect bit is a pure per-lane function, so these bits are
            # exactly what a per-chunk screen would produce
            susp = None if screen is None else np.asarray(screen(state))
            host_leaves = _pool_to_host(state, keymask)
            idx = np.nonzero(retired)[0]
            capture(
                lane_item[idx],
                [leaf[idx] for leaf in host_leaves],
                None if susp is None else susp[idx],
            )
            lane_item[idx] = -1
            lane_budget[idx] = 0  # freeze until refilled
            dispatch_free()

        if (
            stop_after_rounds is not None
            and rounds_this_call >= stop_after_rounds
        ):
            flush_ready()
            if next_flush_lo >= n:
                break
            publish_stats()  # snapshot leaves a current occupancy record
            if telemetry is not None:
                telemetry.event(
                    "snapshot", rounds=int(rounds),
                    next_flush_lo=int(next_flush_lo),
                )
            from .checkpoint import save_stream

            # the v9 row format: item -> per-leaf rows (views into the
            # pending chunk buffers)
            pending_rows: dict = {}
            susp_rows: dict = {}
            for c, bufs in pend.items():
                lo = c * chunk_size
                for p in np.nonzero(pend_have[c])[0]:
                    it = lo + int(p)
                    pending_rows[it] = [b[p] for b in bufs]
                    if screen is not None:
                        susp_rows[it] = bool(sus_buf[c][p])
            save_stream(
                ckpt_path, state,
                pending=pending_rows, susp=susp_rows,
                meta={
                    "fingerprint": fp,
                    "seeds_sha": seeds_sha,
                    "order_sha": order_sha,
                    "chunk_size": int(chunk_size),
                    "lanes": int(L),
                    "lane_item": [int(x) for x in lane_item],
                    "lane_budget": [int(x) for x in lane_budget],
                    "next_q": int(next_q),
                    "next_flush_lo": int(next_flush_lo),
                    "totals": totals,
                    "rounds": int(rounds),
                    "refills": int(refills),
                    "occ_sum": float(occ_sum),
                },
            )
            break

    if incr:
        # settle any WGL work still pending after the last flush so the
        # returned totals are complete (drain preserves submission order,
        # so the merged summary is byte-for-byte the sync path's).
        absorb(host_work.drain())

    publish_stats()
    return totals
