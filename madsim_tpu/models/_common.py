"""Shared emit-packing helpers for device workload models.

Every model hands the engine a fixed-shape ``Emits`` batch per handler
invocation: ``num_nodes`` broadcast slots (one potential message per
destination node) followed by two "extra" slots (timer re-arms, unicast
replies). These helpers own that packing protocol in one place so the
models stay in sync with the engine's ``Emits`` contract.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..engine.core import Emits

# sentinel for an unused extra slot
DISABLED = None

# sweep_summary keys that merge by max, not sum, across chunks — owned
# here so every model's summary and every cross-chunk reducer agree
MAX_KEYS = frozenset({"queue_high_water"})

# keys that merge by elementwise bitwise-OR (coverage bitmaps: a bit is
# covered by the sweep iff any chunk covered it)
OR_KEYS = frozenset({"coverage_map"})

# keys that merge by elementwise ADD (fixed-width count vectors: the
# engine's event-mix kind histogram sums across chunks, not concatenates)
VEC_KEYS = frozenset({"event_mix"})


def merge_summaries(totals: dict, summary: dict) -> dict:
    """Fold one chunk's ``sweep_summary`` dict into a running total.

    Keys are additive counts except ``MAX_KEYS`` (high-water marks),
    ``OR_KEYS`` (bitmap words, elementwise OR), ``VEC_KEYS`` (count
    vectors, elementwise add), and list values (concatenated — e.g.
    per-chunk violating-seed samples). Mutates and returns ``totals``
    (start with ``{}``)."""
    for k, v in summary.items():
        if k in MAX_KEYS:
            totals[k] = max(totals.get(k, 0), v)
        elif k in VEC_KEYS:
            old = totals.get(k, [])
            if len(old) < len(v):
                old = old + [0] * (len(v) - len(old))
            totals[k] = [
                a + b for a, b in zip(old, list(v) + [0] * (len(old) - len(v)))
            ]
        elif k in OR_KEYS:
            old = totals.get(k, [])
            if len(old) < len(v):
                old = old + [0] * (len(v) - len(old))
            totals[k] = [a | b for a, b in zip(old, list(v) + [0] * (len(old) - len(v)))]
        elif isinstance(v, list):
            totals[k] = totals.get(k, []) + v
        else:
            totals[k] = totals.get(k, 0) + v
    return totals


def coverage_bit_count(coverage_map) -> int:
    """Population count of a ``coverage_map`` word list (covered bits)."""
    return sum(int(w).bit_count() for w in coverage_map)


def memoized_workload(cfg_cls):
    """Decorator for a model's ``workload(cfg)`` constructor: memoize per
    config (configs are hashable NamedTuples), normalizing an omitted
    argument to ``cfg_cls()`` BEFORE the cache so ``workload()`` and
    ``workload(cfg_cls())`` share one entry.

    Why: the engine's jit caches (engine/core.py ``_drive`` static args)
    key on the Workload's ``partial``s by identity, so an equal-but-
    distinct Workload silently recompiles the whole sweep program
    (~16 s). Same config -> same Workload object -> cache hit."""
    from functools import lru_cache, wraps

    def deco(build):
        cached = lru_cache(maxsize=None)(build)

        @wraps(build)
        def workload(cfg=None):
            return cached(cfg if cfg is not None else cfg_cls())

        return workload

    return deco


def make_sweep_summary(
    fields: Tuple[Tuple[str, Callable], ...]
) -> Callable[[object], dict]:
    """Build a ``sweep_summary(final) -> dict`` from ``(name, lane_fn)``
    pairs, where each ``lane_fn(final)`` returns a PER-LANE vector
    ``[S]`` over the batched EngineState; the reduction (sum, or max
    for ``MAX_KEYS`` names) is owned here. Per-lane on purpose: it lets
    the ``limit=`` variant mask padded lanes out of every field
    EXACTLY — a zeroed lane is the identity of sum, of max over the
    nonnegative fields, and of the coverage OR, whereas predicate
    fields like raft's ``elections == 0`` would miscount zeroed lanes
    if masking happened below the field function.

    All reductions run in ONE jitted device program that stacks the
    scalars into a single int64 vector, so the whole summary costs one
    small device->host transfer. The eager alternative — one
    ``np.asarray`` per field — moves each full per-lane array to host
    and pays a round-trip per field, which dominates chunked pod-scale
    sweeps."""
    # EngineState-level per-lane fields shared by every model, appended
    # here so a new model (or engine counter) can't silently drop them
    engine_fields = (
        ("overflow_seeds", lambda f: f.overflow),
        ("hist_overflow_seeds", lambda f: f.hist_overflow),
        ("queue_high_water", lambda f: f.qmax),
        ("events_total", lambda f: f.ctr),
        ("sim_ns_total", lambda f: f.now_ns),
    )
    fields = fields + engine_fields
    names = tuple(n for n, _ in fields)
    fns = tuple(f for _, f in fields)

    def _reduce(final, m):
        cols = []
        for name, fn in zip(names, fns):
            lanes = jnp.asarray(fn(final), jnp.int64)
            if lanes.ndim != 1:
                # catch the pre-round-6 contract at trace time: a field
                # written as a scalar reduction (lambda f: jnp.sum(...))
                # would survive whole-chunk summaries but silently
                # multiply by the lane count under the limit mask
                raise ValueError(
                    f"sweep_summary field {name!r} must return a "
                    f"PER-LANE vector [S], got shape {lanes.shape} — "
                    "drop the jnp.sum/jnp.max: the reduction is owned "
                    "by make_sweep_summary (docs/authoring_models.md)"
                )
            if m is not None:
                lanes = jnp.where(m, lanes, jnp.int64(0))
            cols.append(
                jnp.max(lanes) if name in MAX_KEYS else jnp.sum(lanes)
            )
        # coverage union rides in the same program/transfer: OR the
        # per-seed bitmaps down the batch axis — the "one extra
        # reduction" that turns the engine's in-loop signal into a
        # chunk-level coverage map (explore/campaign.py feeds on it).
        # NOT lax.reduce with a bitwise_or combiner: when the batch axis
        # is sharded over a mesh (parallel/mesh.py), GSPMD turns the
        # lane reduction into a cross-device all-reduce, and the CPU
        # runtime only implements the stock combiners (add/min/max) for
        # it — so the OR is decomposed into 32 bit-planes reduced by
        # MAX (identical words: the planes are disjoint, so the
        # recombining sum IS the or), which partitions on every backend.
        cover = final.cover
        if m is not None:
            cover = jnp.where(m[:, None], cover, jnp.uint32(0))
        shifts = jnp.arange(32, dtype=jnp.uint32)
        bits = (cover[:, :, None] >> shifts) & jnp.uint32(1)  # [S, W, 32]
        union = jnp.sum(jnp.max(bits, axis=0) << shifts, axis=1,
                        dtype=jnp.uint32)
        # the opt-in event-mix plane rides along too: per-seed per-kind
        # uint32 counters summed down the batch axis to one [K] vector
        # (width 0 when the workload doesn't enable it — free)
        emix = final.evmix
        if m is not None:
            emix = jnp.where(m[:, None], emix, jnp.uint32(0))
        emix = jnp.sum(emix.astype(jnp.int64), axis=0)
        return jnp.stack(cols), union, emix

    _summarize = jax.jit(lambda final: _reduce(final, None))

    @jax.jit
    def _summarize_limit(final, k):
        # mask the padded lanes instead of slicing: one compiled
        # program serves EVERY ragged tail length, where a [k]-shaped
        # trim would recompile per distinct k
        return _reduce(final, jnp.arange(final.seed.shape[0]) < k)

    def sweep_summary(final, limit=None) -> dict:
        """Reduction of a finished sweep's batched EngineState (one
        device program, one transfer). ``limit=k`` reduces only the
        first ``k`` lanes — the padded-ragged-chunk path: the masked
        variant is ONE compiled program for all ``k``, so a ragged
        final chunk costs no recompile (engine/checkpoint.py drivers
        and scripts/sweep_million.py rely on this). The call is one
        ``madsim.summary`` program span, its blocking readback a
        ``madsim.summary.wait`` span inside it (``obs.span``; ``lo`` is
        the first lane summed)."""
        with obs.span("madsim.summary", lo=0):
            if limit is None:
                vec, union, emix = _summarize(final)
                seeds = int(final.seed.shape[0])
            else:
                vec, union, emix = _summarize_limit(
                    final, jnp.asarray(limit, jnp.int32)
                )
                seeds = int(limit)
            with obs.span("madsim.summary.wait", lo=0):
                out = {"seeds": seeds}
                out.update((n, int(v)) for n, v in zip(names, np.asarray(vec)))
                if union.shape[0]:
                    out["coverage_map"] = [int(w) for w in np.asarray(union)]
                if emix.shape[0]:
                    out["event_mix"] = [int(v) for v in np.asarray(emix)]
        return out

    # the chunk drivers key program-reuse decisions on this marker
    sweep_summary.supports_limit = True
    return sweep_summary

ExtraSlot = Optional[Tuple]  # (time, kind, pay, enable) or DISABLED


def pay(*vals, slots: int) -> jnp.ndarray:
    """Pack scalar values into an int32 payload vector of ``slots`` width."""
    out = jnp.zeros((slots,), jnp.int32)
    for i, v in enumerate(vals):
        out = out.at[i].set(jnp.asarray(v, jnp.int32))
    return out


def no_bcast(num_nodes: int, payload_slots: int, msg_kind: int):
    """An all-disabled broadcast block (still shaped [num_nodes])."""
    return (
        jnp.zeros((num_nodes,), jnp.int64),
        jnp.full((num_nodes,), msg_kind, jnp.int32),
        jnp.zeros((num_nodes, payload_slots), jnp.int32),
        jnp.zeros((num_nodes,), bool),
    )


def pack_extras(payload_slots: int, *extras: ExtraSlot) -> Emits:
    """Pack standalone slots into an ``Emits`` of exactly ``len(extras)``
    events. Each slot is ``(time, kind, pay, enable)`` or ``DISABLED``."""
    ets, eks, eps, eos = [], [], [], []
    for extra in extras:
        if extra is None:
            ets.append(jnp.zeros((), jnp.int64))
            eks.append(jnp.zeros((), jnp.int32))
            eps.append(jnp.zeros((payload_slots,), jnp.int32))
            eos.append(jnp.zeros((), bool))
        else:
            et, ek, ep, eo = extra
            ets.append(jnp.asarray(et, jnp.int64))
            eks.append(jnp.asarray(ek, jnp.int32))
            eps.append(ep)
            eos.append(jnp.asarray(eo, bool))
    return Emits(
        times=jnp.stack(ets),
        kinds=jnp.stack(eks),
        pays=jnp.stack(eps),
        enables=jnp.stack(eos),
    )


def pack_emits(payload_slots: int, bcast, *extras: ExtraSlot) -> Emits:
    """Pack ``num_nodes`` broadcast slots + 2 extra slots into ``Emits``.

    Each extra is ``(time, kind, pay, enable)`` or ``DISABLED``; every
    handler emits the same fixed shape (num_nodes + 2 events). One
    concatenate per field — no per-extra chains."""
    times, kinds, pays, enables = bcast
    assert len(extras) == 2
    ex = pack_extras(payload_slots, *extras)
    return Emits(
        times=jnp.concatenate([times, ex.times]),
        kinds=jnp.concatenate([kinds, ex.kinds]),
        pays=jnp.concatenate([pays, ex.pays]),
        enables=jnp.concatenate([enables, ex.enables]),
    )
