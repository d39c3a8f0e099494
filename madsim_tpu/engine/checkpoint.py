"""Sweep checkpoint/resume: the engine state is arrays, so snapshots are
free.

The reference has no core snapshotting — only the etcd sim's dump/load
(SURVEY.md §5 "checkpoint/resume"). The SoA engine generalizes the
pattern: a whole in-flight seed batch (clocks, queues, RNG counters,
workload actor state) round-trips through one ``.npz`` file, and
``resume_sweep`` continues stepping it — enabling long sweeps to survive
preemption and failed seeds to be re-examined from mid-run state.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .core import EngineConfig, EngineState, Workload

# v2: EngineState gained qmax; draw layout adds tie-break.
# v3: packed queue layout — the redundant bool valid[Q] plane left the
#     EventQueue, so v2 files would load positionally misaligned.
# v4: EngineState gained the per-seed coverage bitmap (``cover``), so v3
#     files would load positionally misaligned.
# v5: EngineState gained the operation-history plane (``hist_rec``,
#     ``hist_t``, ``hist_len``, ``hist_overflow`` — madsim_tpu/oracle),
#     so v4 files would load positionally misaligned.
# v6: gray-failure grammar — ``FaultState`` split ``part_cnt`` into
#     per-direction refcounts and gained ``fsync_cnt``/``skew_cnt``, and
#     the raft model grew its durability shadows, so v5 files would load
#     positionally misaligned.
# v7: pipelined checked sweeps — a snapshot may carry ``__inflight__``
#     chunk metadata (which chunk of a pipelined sweep the state belongs
#     to, plus host-phase progress), so interrupt/resume of an
#     overlapped sweep+check pipeline stays bit-identical. v6 readers
#     would silently drop it and resume the state as a whole-sweep
#     snapshot, double-counting completed chunks — which is why v6
#     REJECTS v7, while this reader still ACCEPTS v6 files (the leaf
#     layout is unchanged; an old snapshot simply has no inflight tag).
# v8: mesh-sharded pipelined sweeps — a snapshot may carry
#     ``__mesh_layout__`` (device count + per-device chunk of the
#     sharded driver, ``parallel.mesh.mesh_layout``), so a sweep
#     interrupted on an 8-device mesh resumes on ANY device count with
#     the same GLOBAL chunk boundaries (``chunk_size`` rides in the
#     metadata; the state arrays themselves are layout-free host data).
#     v7 readers would drop the layout and could resume with mismatched
#     chunk granules — their per-chunk files silently never matching —
#     hence the bump; this reader still ACCEPTS v6/v7 files (the leaf
#     layout is unchanged; an old snapshot simply has no mesh tag).
# v9: streaming sweeps (engine/stream.py) — a snapshot may carry a
#     heterogeneous IN-FLIGHT LANE POOL: ``__stream__`` bookkeeping
#     (which work item each lane runs, per-lane step budgets, the queue
#     cursor, merged totals so far) plus stacked ``pend_*`` arrays of
#     captured-but-unflushed per-item results. v8 readers would load the
#     pool as a plain whole-sweep snapshot and silently drop the pending
#     results and queue position — hence the bump; this reader still
#     ACCEPTS v6-v8 files (the leaf layout is unchanged; an old snapshot
#     simply has no stream tag).
# v10: opt-in device-side EVENT-MIX plane (madsim_tpu/obs) — EngineState
#     gained ``evmix`` as its LAST field, so every pre-v10 leaf index is
#     unchanged and this reader still ACCEPTS v6-v9 files whenever the
#     resuming workload leaves the plane disabled (width 0: the missing
#     trailing leaf is substituted from ``like``). A v6-v9 snapshot
#     CANNOT resume an event-mix-ENABLED sweep — the counters for the
#     already-run steps were never recorded — and the reader rejects
#     that combination instead of silently zero-filling.
# v11: the event queue carries its payload as P ``int32[Q]`` planes, one
#     per word (engine/queue.py), not one stacked ``int32[Q, P]`` leaf, so
#     every leaf after the queue's ``kind`` moved up by P - 1. This reader
#     still ACCEPTS v6-v10 files: it reads their stacked payload leaf in
#     the old position and splits it into the planes (``_from_stored``).
_FORMAT_VERSION = 11
_READABLE_VERSIONS = (6, 7, 8, 9, 10, 11)
_SPLIT_PAY_VERSION = 11  # first version with one leaf per payload plane


def _pay_span(like: EngineState) -> Tuple[int, int]:
    """(index of the queue's first payload plane among ``like``'s leaves,
    number of planes P)."""
    paths = [path for path, _ in jax.tree_util.tree_flatten_with_path(like)[0]]
    first = paths.index((
        jax.tree_util.GetAttrKey("queue"),
        jax.tree_util.GetAttrKey("pay"),
        jax.tree_util.SequenceKey(0),
    ))
    return first, len(like.queue.pay)


def _stored(leaves: list, found: int, first: int, planes: int) -> list:
    """``like``'s leaves in the order a v``found`` file stores them: before
    v11 the P payload planes were one ``[..., Q, P]`` leaf."""
    if found >= _SPLIT_PAY_VERSION:
        return leaves
    plane = leaves[first]
    stacked = jax.ShapeDtypeStruct((*plane.shape, planes), plane.dtype)
    return leaves[:first] + [stacked] + leaves[first + planes:]


def _from_stored(leaves: list, found: int, first: int, planes: int) -> list:
    """Leaves read from a v``found`` file in the current order: a
    pre-v11 stacked payload leaf split into its P planes."""
    if found >= _SPLIT_PAY_VERSION:
        return leaves
    pay = leaves[first]
    return leaves[:first] + [pay[..., p] for p in range(planes)] + leaves[first + 1:]


def _restore_leaf(data, i: int, leaf, path: str):
    """One positional leaf of a snapshot, honoring the v10 compat rule:
    a missing trailing leaf is legal ONLY when the resuming structure
    expects a width-0 plane there (``leaf.size == 0``) — then ``like``'s
    own empty leaf stands in for it."""
    if f"leaf_{i}__key" in data:
        return jax.random.wrap_key_data(jnp.asarray(data[f"leaf_{i}__key"]))
    if f"leaf_{i}" in data:
        return jnp.asarray(data[f"leaf_{i}"], dtype=leaf.dtype)
    if leaf.size == 0:
        return jnp.asarray(leaf)
    raise ValueError(
        f"{path} has no leaf_{i} but the resuming state expects a "
        f"non-empty array there (shape {leaf.shape}) — a pre-v10 "
        "snapshot cannot resume an event-mix-enabled sweep "
        "(engine/core.py event_mix_kinds); re-run from scratch"
    )


def save_sweep(
    state: EngineState,
    path: str,
    inflight: Optional[dict] = None,
    mesh_layout: Optional[dict] = None,
) -> None:
    """Serialize a batched EngineState to ``path`` (.npz).

    ``inflight`` (JSON-able dict, format v7) tags the snapshot as the
    IN-FLIGHT CHUNK of a pipelined sweep — at least ``{"lo": <chunk
    start index>, "k": <real lanes>}`` — so ``run_sweep_pipelined``
    can resume mid-chunk (``resume_from``) instead of restarting the
    chunk; read it back with ``load_inflight``. ``mesh_layout``
    (JSON-able dict, format v8 — ``parallel.mesh.mesh_layout``) records
    the sharded driver's device count and chunk sizing so a different-
    sized mesh resumes with identical global chunk boundaries; read it
    back with ``load_mesh_layout``."""
    import json

    leaves, treedef = jax.tree.flatten(state)
    arrays = {}
    for i, leaf in enumerate(leaves):
        if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            # typed PRNG keys serialize as their raw uint32 words
            arrays[f"leaf_{i}__key"] = np.asarray(jax.random.key_data(leaf))
        else:
            arrays[f"leaf_{i}"] = np.asarray(leaf)
    for name, meta in (
        ("__inflight__", inflight), ("__mesh_layout__", mesh_layout)
    ):
        if meta is not None:
            arrays[name] = np.frombuffer(
                json.dumps(meta, sort_keys=True).encode(), dtype=np.uint8
            )
    np.savez_compressed(path, __version__=_FORMAT_VERSION, **arrays)


def _load_meta(path: str, name: str) -> Optional[dict]:
    import json

    data = np.load(path)
    if name not in data:
        return None
    return json.loads(bytes(bytearray(data[name])).decode())


def load_inflight(path: str) -> Optional[dict]:
    """The ``inflight`` chunk metadata of a v7+ snapshot, or None."""
    return _load_meta(path, "__inflight__")


def load_mesh_layout(path: str) -> Optional[dict]:
    """The mesh-layout metadata of a v8 snapshot, or None (an unsharded
    or pre-v8 snapshot). Resuming callers that honor
    ``layout["chunk_size"]`` keep per-chunk checkpoint files aligned
    across device counts."""
    return _load_meta(path, "__mesh_layout__")


def load_sweep(path: str, like: EngineState) -> EngineState:
    """Restore a checkpoint; ``like`` supplies the pytree structure (build
    it with ``init_sweep`` on any seed vector of the same shape/config)."""
    data = np.load(path)
    found = int(data["__version__"])
    if found not in _READABLE_VERSIONS:
        raise ValueError(
            f"checkpoint format version mismatch: {path} is v{found}, "
            f"this engine reads v{_READABLE_VERSIONS} (the draw layout / "
            "state schema changed between versions; re-run the sweep to "
            "produce a fresh checkpoint)"
        )
    leaves, treedef = jax.tree.flatten(like)
    first, planes = _pay_span(like)
    out = [
        _restore_leaf(data, i, leaf, path)
        for i, leaf in enumerate(_stored(leaves, found, first, planes))
    ]
    return jax.tree.unflatten(treedef, _from_stored(out, found, first, planes))


def save_stream(
    path: str,
    state: EngineState,
    *,
    pending: dict,
    susp: dict,
    meta: dict,
) -> None:
    """Serialize a STREAMING sweep's full in-flight picture (checkpoint
    format v9; ``engine/stream.stream_sweep`` is the only writer):

    - the lane-pool ``EngineState`` (heterogeneous — each lane may run a
      different work item, candidate and step budget), leaf-encoded like
      ``save_sweep``;
    - ``pending``: item index -> captured row leaves (raw host arrays,
      key leaves as uint32 words — the stream's own row format) for
      results retired but not yet flushed into a virtual chunk; stored
      stacked per leaf (``pend_{j}``), item order in the meta;
    - ``susp``: item index -> device-screen suspect bit (absent when the
      stream runs unscreened);
    - ``meta``: JSON-able stream bookkeeping (stream.py owns the keys:
      lane->item map, budgets, queue cursor, flush cursor, merged totals,
      identity guards)."""
    import json

    leaves, _ = jax.tree.flatten(state)
    arrays = {}
    for i, leaf in enumerate(leaves):
        if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            arrays[f"leaf_{i}__key"] = np.asarray(jax.random.key_data(leaf))
        else:
            arrays[f"leaf_{i}"] = np.asarray(leaf)
    items = sorted(int(i) for i in pending)
    if items:
        for j in range(len(leaves)):
            arrays[f"pend_{j}"] = np.stack([pending[it][j] for it in items])
    stream_meta = dict(meta)
    stream_meta["items"] = items
    stream_meta["susp"] = [
        (None if it not in susp else bool(susp[it])) for it in items
    ]
    arrays["__stream__"] = np.frombuffer(
        json.dumps(stream_meta, sort_keys=True).encode(), dtype=np.uint8
    )
    np.savez_compressed(path, __version__=_FORMAT_VERSION, **arrays)


def load_stream(path: str, like: EngineState):
    """Restore a v9 stream snapshot: ``(pool state, pending rows dict,
    suspect-bit dict, stream meta)``. ``like`` supplies the pytree
    structure and dtypes — an ``init_sweep`` result of the same pool
    shape, or its ``jax.eval_shape`` (no device work needed)."""
    import json

    data = np.load(path)
    found = int(data["__version__"])
    if found not in _READABLE_VERSIONS or "__stream__" not in data:
        raise ValueError(
            f"{path} is not a readable stream snapshot (v{found}"
            f"{', no __stream__ tag' if '__stream__' not in data else ''}); "
            "stream snapshots are checkpoint format v9 "
            "(engine/stream.stream_sweep ckpt_path=)"
        )
    leaves, treedef = jax.tree.flatten(like)
    first, planes = _pay_span(like)
    out = [
        _restore_leaf(data, i, leaf, path)
        for i, leaf in enumerate(_stored(leaves, found, first, planes))
    ]
    state = jax.tree.unflatten(treedef, _from_stored(out, found, first, planes))
    meta = json.loads(bytes(bytearray(data["__stream__"])).decode())
    pending = {}
    susp = {}
    for idx, it in enumerate(meta["items"]):
        # pre-v10 stream snapshots have no pend_{j} for the trailing
        # evmix leaf; a width-0 plane row is an empty array of the
        # like-leaf's per-lane shape (the only legal missing case —
        # _restore_leaf already rejected non-empty gaps above)
        row = [
            (
                data[f"pend_{j}"][idx]
                if f"pend_{j}" in data
                else np.zeros(out[j].shape[1:], np.asarray(out[j]).dtype)
            )
            for j in range(len(out))
        ]
        pending[int(it)] = _from_stored(row, found, first, planes)
        bit = meta["susp"][idx]
        if bit is not None:
            susp[int(it)] = bool(bit)
    return state, pending, susp, meta


def resume_sweep(
    workload: Workload, cfg: EngineConfig, state: EngineState
) -> EngineState:
    """Continue a (possibly restored) sweep until every seed finishes."""
    from .core import run_drive

    return run_drive(workload, cfg, state)  # shares run_sweep's program


def _chunk_sha(seeds_host: np.ndarray, lo: int, k: int) -> str:
    """Identity of one chunk's full seed slice — endpoints alone can
    collide across different seed vectors ([0,5,9] vs [0,7,9])."""
    import hashlib

    return hashlib.sha256(
        np.ascontiguousarray(seeds_host[lo : lo + k]).tobytes()
    ).hexdigest()


def _load_chunk_summary(
    path: str, first: int, last: int, sha: str, fp: str
) -> dict:
    """Validate a per-chunk checkpoint file against this sweep's
    identity and return its summary — shared by both chunk drivers so
    the guard protocol cannot fork between them. Records from before
    the sha was added lack the key; their endpoint+fingerprint check
    still applies (legacy-compatible)."""
    import json

    with open(path) as f:
        rec = json.load(f)
    if (
        rec["first_seed"] != first
        or rec["last_seed"] != last
        or rec.get("seeds_sha256", sha) != sha
        or rec.get("fingerprint") != fp
    ):
        raise ValueError(
            f"checkpoint {path} is from a different sweep: holds "
            f"seeds [{rec['first_seed']}, {rec['last_seed']}] "
            f"(sha {rec.get('seeds_sha256')!r}) with "
            f"fingerprint {rec.get('fingerprint')!r}, expected "
            f"[{first}, {last}] (sha {sha!r}) with {fp!r}"
        )
    return rec["summary"]


def _write_chunk_summary(
    path: str, first: int, last: int, sha: str, fp: str, summary: dict
) -> None:
    """Atomically write one chunk's checkpoint record (tmp + rename: a
    crash never leaves half a file) — shared by both chunk drivers."""
    import json
    import os

    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(
            {
                "first_seed": first,
                "last_seed": last,
                "seeds_sha256": sha,
                "fingerprint": fp,
                "summary": summary,
            },
            f,
            sort_keys=True,
        )
    os.replace(tmp, path)


def params_digest(params) -> str:
    """Candidate identity of a per-lane spec-as-data pytree: a sha256
    over every leaf's bytes. Appended to ``_sweep_fingerprint`` so chunk
    checkpoints written for one candidate can never silently merge into
    another candidate's sweep (the envelope alone is shared by ALL
    candidates — that sharing is the point of the spec-as-data path, so
    the data itself must join the identity)."""
    import hashlib

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        a = np.ascontiguousarray(np.asarray(leaf))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def run_sweep_chunked_resumable(
    workload: Workload,
    cfg: EngineConfig,
    seeds,
    summarize,
    ckpt_dir: str,
    chunk_size: int = 16384,
    run_chunk: Optional[Callable] = None,
    params=None,
    telemetry=None,
) -> dict:
    """Pod-scale sweep that survives interruption at chunk granularity.

    Runs ``seeds`` as sequential ``chunk_size`` batches; after each chunk
    its ``summarize(final)`` dict is written atomically to ``ckpt_dir``,
    and a restarted call skips every chunk whose summary file already
    exists — sound because chunks are deterministic (re-running one
    yields bit-identical results). Returns the merged summary totals
    (per-chunk host merge, constant device memory — the million-seed
    pattern of scripts/sweep_million.py made preemption-safe; BASELINE
    config #5's recovery semantics at pod scale).

    Stale-reuse guard: each file records its seed range, a sha256 of
    the chunk's full seed array, AND a fingerprint of the workload +
    engine config; a mismatch (the directory belongs to a different
    sweep) raises instead of silently merging foreign counts. For mid-chunk snapshots of in-flight state
    use ``save_sweep``/``resume_sweep`` instead.

    ``run_chunk(seed_chunk) -> final state`` overrides the per-chunk
    sweep — the mesh driver injects ``parallel.run_sweep_sharded`` here
    (scripts/sweep_million.py ``--mesh``); the chunk files it writes are
    mesh-free (fingerprint + seed sha only), so a sweep can be
    interrupted under one device count and finished under another.

    ``telemetry`` (``obs.Telemetry`` or None) records chunk wall time,
    seeds-done progress and skip/resume events strictly OUT-OF-BAND:
    every recorder sits behind an ``is not None`` guard and never touches
    the summaries, so report bytes are identical with it on or off.
    """
    import os
    import time as _time

    from .core import (
        _concat_finals, _pad_params, _pad_seeds, _slice_params, run_sweep,
    )
    from ..models._common import merge_summaries  # lazy: models import us

    if run_chunk is None:
        if params is None:
            run_chunk = lambda chunk: run_sweep(workload, cfg, chunk)  # noqa: E731
        else:
            run_chunk = lambda chunk, pchunk: run_sweep(  # noqa: E731
                workload, cfg, chunk, params=pchunk
            )
    seeds = jnp.asarray(seeds, jnp.int64)
    seeds_host = np.asarray(seeds)  # bookkeeping reads skip the device
    n = int(seeds.shape[0])
    if n == 0:
        raise ValueError("seed batch is empty")
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    fp = _sweep_fingerprint(workload, cfg)
    if params is not None:
        fp += "|params" + params_digest(params)
    os.makedirs(ckpt_dir, exist_ok=True)
    totals: dict = {}
    for lo in range(0, n, chunk_size):
        k = min(chunk_size, n - lo)
        first, last = int(seeds_host[lo]), int(seeds_host[lo + k - 1])
        seeds_sha = _chunk_sha(seeds_host, lo, k)
        path = os.path.join(ckpt_dir, f"chunk_{lo:010d}_{k}.json")
        if os.path.exists(path):
            summary = _load_chunk_summary(path, first, last, seeds_sha, fp)
            if telemetry is not None:
                telemetry.count("sweep_chunks_skipped_total")
                telemetry.event("chunk_skipped", lo=lo, k=k)
        else:
            if telemetry is not None:
                t_chunk = _time.perf_counter()
            # pad a ragged final chunk so it reuses the one compiled
            # sweep program (a fresh batch shape recompiles for seconds);
            # a limit-aware summarize (models/_common.make_sweep_summary)
            # masks the padded lanes inside the SAME compiled summary
            # program, so the ragged chunk compiles nothing at all —
            # otherwise the padded lanes are trimmed by a (one-off)
            # k-shaped trim program
            chunk = seeds[lo : lo + chunk_size]
            pad = chunk_size - k
            if params is None:
                final = run_chunk(_pad_seeds(chunk, pad) if pad else chunk)
            else:
                pchunk = _slice_params(params, lo, lo + chunk_size)
                if pad:
                    pchunk = _pad_params(pchunk, pad)
                final = run_chunk(
                    _pad_seeds(chunk, pad) if pad else chunk, pchunk
                )
            if pad and getattr(summarize, "supports_limit", False):
                summary = summarize(final, limit=k)
            else:
                if pad:
                    final = _concat_finals(k, final)
                summary = summarize(final)
            _write_chunk_summary(path, first, last, seeds_sha, fp, summary)
            if telemetry is not None:
                dt = _time.perf_counter() - t_chunk
                telemetry.observe(
                    "sweep_chunk_seconds", dt,
                    help="device+summary wall time per chunk",
                )
                telemetry.count("sweep_chunks_total")
                telemetry.event("chunk", lo=lo, k=k, wall_s=round(dt, 6))
        if telemetry is not None:
            telemetry.count(
                "sweep_seeds_done_total", k, help="seeds merged so far"
            )
            telemetry.event_mix(summary)
        merge_summaries(totals, summary)
    return totals


def run_sweep_pipelined(
    workload: Workload,
    cfg: EngineConfig,
    seeds,
    summarize,
    *,
    host_work: Optional[Callable] = None,
    screen: Optional[Callable] = None,
    chunk_size: int = 16384,
    ckpt_dir: Optional[str] = None,
    stop_after: Optional[int] = None,
    resume_from: Optional[Tuple[EngineState, dict]] = None,
    run_chunk: Optional[Callable] = None,
    resume_chunk: Optional[Callable] = None,
    pad_multiple: int = 1,
    on_chunk: Optional[Callable] = None,
    params=None,
    telemetry=None,
) -> dict:
    """Chunked sweep with the host phase of chunk N overlapped against
    the device sweep of chunk N+1 — the driver that makes END-TO-END
    checked throughput (sweep + screen + check) the optimized quantity
    instead of raw sweep speed.

    Per chunk, in dispatch order:

    1. **device phase** — the chunk's sweep is enqueued, and ``screen``
       (``final -> bool[S]`` suspect mask, e.g.
       ``oracle.screen.screen_sweep``) is enqueued right behind it; both
       stay un-materialized device values.
    2. the PREVIOUS chunk's **host phase** runs while the device crunches
       this chunk: ``host_work(final, lo=, n=, seeds=, suspect=,
       summary=)`` gets the previous chunk's finished state, its host
       suspect mask (``np.asarray`` here costs a device->host transfer
       that overlaps compute, not a sync), and its summary dict; the
       dict it returns is folded into that chunk's summary. Decode,
       checking, triage — anything host-Python — belongs here.
    3. ``summarize(final)`` blocks until this chunk's sweep completes
       (its reduction program was enqueued behind the sweep, so the
       device never idles on it).

    A ragged final chunk is padded to ``chunk_size`` for program reuse;
    a limit-aware ``summarize`` masks the padded lanes in-program, and
    ``host_work`` always receives the trimmed real lanes.

    ``ckpt_dir`` makes the pipeline preemption-safe at chunk granularity
    exactly like ``run_sweep_chunked_resumable`` (per-chunk summary
    JSONs with seed-sha + workload fingerprint guards, written AFTER the
    chunk's host phase, atomically): a restarted call skips finished
    chunks and recomputes at most the in-flight one — bit-identical, as
    chunks are deterministic. ``stop_after`` returns after that many
    chunks were computed this call (preemption drills and tests).
    ``resume_from=(state, inflight)`` — a mid-chunk snapshot written by
    ``save_sweep(state, path, inflight={"lo": ..., "k": ...})`` and read
    back by ``load_sweep``/``load_inflight`` — finishes the in-flight
    chunk from its saved state instead of restarting it (checkpoint
    format v7), which is what keeps interrupt/resume bit-identical with
    overlap enabled.

    Determinism: chunk summaries merge in seed order regardless of
    overlap, and ``host_work`` must be a pure function of its chunk (the
    oracle's screened checker is), so the merged totals are byte-stable
    across pipelining, worker-pool sizes, and interruption points.

    A ``host_work`` advertising ``incremental = True`` (the oracle's
    ``history_host_work`` does) is driven through its
    ``submit``/``poll``/``drain`` protocol instead of being run to
    completion inside each overlap window: each chunk's checking is
    sliced under a budget tracking the device phase's EMA wall time, so
    one contended chunk's WGL work spreads across later chunks' device
    time rather than stalling dispatch. Disabled (sync fallback) under
    ``ckpt_dir``/``stop_after``/``resume_from``, whose chunk files need
    summaries finalized at their own boundaries. Byte-identical totals
    either way — the budget shapes scheduling, never verdict order.

    Scale-out hooks (``parallel.mesh.run_sweep_sharded_pipelined`` is
    the canonical injector): ``run_chunk(seed_chunk) -> final`` replaces
    the per-chunk sweep and ``resume_chunk(state) -> final`` the
    mid-chunk resume drive — the mesh driver passes the sharded sweep
    for both, so the identical pipeline spans 1 or N devices.
    ``pad_multiple`` pads a batch smaller than one chunk up to the next
    multiple (mesh divisibility) instead of not at all; the limit-masked
    summary and trimmed host phase treat that pad exactly like a ragged
    final chunk's. ``on_chunk(lo=, k=, summary=)`` fires as each chunk's
    summary is merged (in seed order) — progress reporting and
    time-to-first-violation measurement at the million-seed scale.

    ``params`` carries per-lane spec-as-data (engine/faults.py): each
    chunk's ``run_chunk(seed_chunk, param_chunk)`` receives the matching
    lane slice, edge-padded like the seeds; the checkpoint fingerprint
    gains the params digest so one candidate's chunk files can never
    merge into another candidate's sweep.

    ``telemetry`` (``obs.Telemetry`` or None) records chunk wall time,
    host-phase time, seeds-done progress and skip/resume events, and —
    when the handle carries a trace — one "dispatch" span per chunk (the
    host's window from dispatch to summary-done; device time is in the
    device trace only) with the previous chunk's "host" flush span
    nested inside it, which is exactly the overlap picture Perfetto
    renders. Strictly OUT-OF-BAND: every recorder is
    behind an ``is not None`` guard and summaries are never touched, so
    the merged report is byte-identical with telemetry on or off.
    """
    import os
    import time as _time

    from .core import (
        _concat_finals, _pad_params, _pad_seeds, _slice_params, run_sweep,
        run_drive,
    )
    from ..models._common import merge_summaries  # lazy: models import us

    if run_chunk is None:
        if params is None:
            run_chunk = lambda chunk: run_sweep(workload, cfg, chunk)  # noqa: E731
        else:
            run_chunk = lambda chunk, pchunk: run_sweep(  # noqa: E731
                workload, cfg, chunk, params=pchunk
            )
    if resume_chunk is None:
        resume_chunk = lambda state: run_drive(workload, cfg, state)  # noqa: E731
    seeds = jnp.asarray(seeds, jnp.int64)
    seeds_host = np.asarray(seeds)
    n = int(seeds.shape[0])
    if n == 0:
        raise ValueError("seed batch is empty")
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    fp = _sweep_fingerprint(workload, cfg)
    if params is not None:
        fp += "|params" + params_digest(params)
    if ckpt_dir is not None:
        os.makedirs(ckpt_dir, exist_ok=True)
    supports_limit = bool(getattr(summarize, "supports_limit", False))
    resume_lo = int(resume_from[1]["lo"]) if resume_from is not None else -1
    tracer = telemetry.tracer if telemetry is not None else None

    totals: dict = {}
    pending = None  # previous chunk awaiting its host phase
    computed = 0

    # budgeted incremental checking: a host_work advertising the
    # submit/poll/drain protocol (oracle.screen._HostWork) gets its WGL
    # work sliced under a per-chunk budget — the device phase's own EMA
    # wall time — instead of run to completion inside each overlap
    # window, so one expensive chunk's checking spreads across later
    # chunks' device time instead of stalling the dispatch loop. OFF
    # under checkpointing/stop/resume: those need each chunk's summary
    # finalized at its own boundary (the chunk file IS the resume
    # granule). Reports are byte-identical either way: verdicts are
    # computed and merged in submission (= seed) order regardless of
    # how the budget slices them.
    incr = (
        host_work is not None
        and getattr(host_work, "incremental", False)
        and ckpt_dir is None
        and stop_after is None
        and resume_from is None
    )
    deferred: dict = {}  # lo -> (k, base summary) awaiting a verdict
    ema = 0.0

    def absorb(finished) -> None:
        for flo, extra in finished:
            fk, summary = deferred.pop(flo)
            if extra:
                summary = {**summary, **extra}
            merge_summaries(totals, summary)
            if telemetry is not None:
                telemetry.count("sweep_chunks_total")
                telemetry.count(
                    "sweep_seeds_done_total", fk,
                    help="seeds merged so far",
                )
                telemetry.event_mix(summary)
                telemetry.event("chunk", lo=flo, k=fk)
            if on_chunk is not None:
                on_chunk(lo=flo, k=fk, summary=summary)

    def submit_pending(p, budget: float) -> None:
        lo, k, _sha, final, susp, summary, _path = p
        if telemetry is not None:
            t_host = _time.perf_counter()
        deferred[lo] = (k, summary)
        host_work.submit(
            final,
            lo=lo,
            n=k,
            seeds=seeds_host[lo : lo + k],
            suspect=None if susp is None else np.asarray(susp)[:k],
            summary=summary,
        )
        absorb(host_work.poll(budget))
        if telemetry is not None:
            telemetry.observe(
                "sweep_host_phase_seconds",
                _time.perf_counter() - t_host,
                help="host phase (decode/check/ckpt write) per chunk",
            )

    def flush(p) -> None:
        lo, k, sha, final, susp, summary, path = p
        if telemetry is not None:
            t_host = _time.perf_counter()
            h0 = tracer._now_us() if tracer is not None else 0.0
        if host_work is not None:
            extra = host_work(
                final,
                lo=lo,
                n=k,
                seeds=seeds_host[lo : lo + k],
                suspect=None if susp is None else np.asarray(susp)[:k],
                summary=summary,
            )
            if extra:
                summary = {**summary, **extra}
        if path is not None:
            _write_chunk_summary(
                path, int(seeds_host[lo]), int(seeds_host[lo + k - 1]),
                sha, fp, summary,
            )
        merge_summaries(totals, summary)
        if telemetry is not None:
            dt = _time.perf_counter() - t_host
            telemetry.observe(
                "sweep_host_phase_seconds", dt,
                help="host phase (decode/check/ckpt write) per chunk",
            )
            telemetry.count("sweep_chunks_total")
            telemetry.count(
                "sweep_seeds_done_total", k, help="seeds merged so far"
            )
            telemetry.event_mix(summary)
            telemetry.event("chunk", lo=lo, k=k, host_phase_s=round(dt, 6))
            if tracer is not None:
                tracer.complete(
                    f"host flush lo={lo}", h0, tracer._now_us() - h0,
                    track="host", args={"lo": lo, "k": k},
                )
        if on_chunk is not None:
            on_chunk(lo=lo, k=k, summary=summary)

    for lo in range(0, n, chunk_size):
        k = min(chunk_size, n - lo)
        sha = _chunk_sha(seeds_host, lo, k)
        path = (
            os.path.join(ckpt_dir, f"pchunk_{lo:010d}_{k}.json")
            if ckpt_dir is not None
            else None
        )
        if path is not None and os.path.exists(path):
            summary = _load_chunk_summary(
                path, int(seeds_host[lo]), int(seeds_host[lo + k - 1]),
                sha, fp,
            )
            if pending is not None:
                flush(pending)  # keep merge order = seed order
                pending = None
            merge_summaries(totals, summary)
            if telemetry is not None:
                telemetry.count("sweep_chunks_skipped_total")
                telemetry.count("sweep_seeds_done_total", k)
                telemetry.event_mix(summary)
                telemetry.event("chunk_skipped", lo=lo, k=k)
            if on_chunk is not None:
                on_chunk(lo=lo, k=k, summary=summary)
            continue

        # -- device phase: enqueue this chunk's sweep (+ screen) --------
        if telemetry is not None or incr:
            t_disp = _time.perf_counter()
        if telemetry is not None:
            d0 = tracer._now_us() if tracer is not None else 0.0
        pad = chunk_size - k if n > chunk_size else -k % pad_multiple
        if lo == resume_lo:
            state, inflight = resume_from
            if telemetry is not None:
                telemetry.count(
                    "sweep_resume_total",
                    help="mid-chunk snapshot resumes",
                )
                telemetry.event("chunk_resumed", lo=lo, k=k)
            if int(inflight.get("k", k)) != k or not np.array_equal(
                np.asarray(state.seed)[:k], seeds_host[lo : lo + k]
            ):
                raise ValueError(
                    f"resume_from snapshot does not match chunk at {lo}: "
                    f"inflight={inflight!r}"
                )
            # the snapshot carries its OWN padding (the saving process's
            # pad_multiple may differ across mesh sizes) — trust its lane
            # count, not this process's pad, so the limit mask/trim below
            # still hides exactly the synthetic lanes
            pad = int(state.seed.shape[0]) - k
            final = resume_chunk(state)
        else:
            chunk = seeds[lo : lo + chunk_size]
            if params is None:
                final = run_chunk(_pad_seeds(chunk, pad) if pad else chunk)
            else:
                pchunk = _slice_params(params, lo, lo + chunk_size)
                if pad:
                    pchunk = _pad_params(pchunk, pad)
                final = run_chunk(
                    _pad_seeds(chunk, pad) if pad else chunk, pchunk
                )
        susp = screen(final) if screen is not None else None

        # -- previous chunk's host phase overlaps this chunk's sweep ----
        if pending is not None:
            if incr:
                submit_pending(pending, ema)
            else:
                flush(pending)
            pending = None

        # -- this chunk's summary (blocks until its sweep completes) ----
        if pad and supports_limit:
            summary = summarize(final, limit=k)
        else:
            if pad:
                final = _concat_finals(k, final)
            summary = summarize(final)
        if pad and supports_limit and host_work is not None:
            # the host phase must never see the padded lanes (their
            # synthetic seeds would pollute e.g. violating-seed lists)
            final = _concat_finals(k, final)
        if susp is not None and pad:
            susp = susp[:k]
        if telemetry is not None or incr:
            # summarize() above synced on the device work, so this wall
            # window (dispatch -> summary materialized) IS the device
            # phase; the previous chunk's host flush ran inside it —
            # and its EMA is the incremental checker's poll budget (the
            # checking a chunk's device time can hide)
            dt = _time.perf_counter() - t_disp
            ema = dt if ema == 0.0 else 0.5 * ema + 0.5 * dt
        if telemetry is not None:
            telemetry.observe(
                "sweep_chunk_seconds", dt,
                help="device phase (dispatch -> summary) per chunk",
            )
            if tracer is not None:
                tracer.complete(
                    f"chunk lo={lo} dispatch-to-summary", d0,
                    tracer._now_us() - d0,
                    track="dispatch", args={"lo": lo, "k": k},
                )
        pending = (lo, k, sha, final, susp, summary, path)
        computed += 1
        if stop_after is not None and computed >= stop_after:
            break

    if pending is not None:
        if incr:
            submit_pending(pending, 0.0)
        else:
            flush(pending)
    if incr:
        absorb(host_work.drain())
    return totals


def _sweep_fingerprint(workload: Workload, cfg: EngineConfig) -> str:
    """Identity of (model, model config, engine config) for the resumable
    sweep's stale-checkpoint guard. Model configs are NamedTuples of
    plain values, so their repr is a stable fingerprint. ``cover_bits`` is
    INCLUDED: it changes the summary schema (``coverage_map`` appears),
    so chunk summaries written by a coverage-free workload must not
    silently merge into a coverage-guided sweep as zero coverage.
    ``hist_slots`` is included for the same reason in reverse: a resized
    history buffer changes which seeds latch ``hist_overflow``, so their
    chunk summaries are not interchangeable. ``event_mix_kinds`` is
    included because enabling the plane adds the ``event_mix`` key to
    every chunk summary (and disables pre-v10 snapshot reuse)."""
    from .core import hist_slots

    init = workload.init
    fn = getattr(init, "func", init)
    args = getattr(init, "args", ())
    cfg_id = tuple(cfg)
    return (
        f"{fn.__module__}.{fn.__qualname__}|{args!r}|{cfg_id!r}"
        f"|cover{workload.cover_bits}|hist{hist_slots(workload)}"
        f"|emix{workload.event_mix_kinds}"
    )
