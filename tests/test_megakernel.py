"""Megakernel probe: in-kernel helpers match their int64/jax references,
and the full multi-step kernel reproduces the XLA engine bit-for-bit
(interpret mode — the TPU run is covered by scripts/bench_megakernel.py,
whose numbers are recorded in docs/pallas_finding.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu.engine import core
from madsim_tpu.engine import megakernel as mk


def test_mulhi32_matches_int64_reference():
    rng = np.random.default_rng(7)
    xs = jnp.asarray(rng.integers(0, 1 << 32, size=256, dtype=np.uint64),
                     dtype=jnp.uint32)
    for c in (1, 5, 51, 19_000_001, 0x7FFFFFFF, 0xFFFFFFFF):
        ref = ((xs.astype(jnp.uint64) * c) >> 32).astype(jnp.uint32)
        got = mk._mulhi32(xs, c)
        assert jnp.array_equal(ref, got), c


def test_event_words_match_jax_random():
    """The in-kernel threefry must reproduce engine.rng.event_bits
    (fold_in + partitionable bits) word for word."""
    from madsim_tpu.engine.rng import event_bits, seed_key

    for seed in (0, 3, 123456):
        key = seed_key(jnp.asarray(seed, jnp.int64))
        kd = jax.random.key_data(key).astype(jnp.uint32)
        for ctr in (0, 1, 999):
            expect = event_bits(key, jnp.asarray(ctr, jnp.int32), 15)
            got = mk._event_words(
                kd[0].reshape(1, 1), kd[1].reshape(1, 1),
                jnp.full((1, 1), ctr, jnp.uint32), 15,
            )[0]
            assert jnp.array_equal(expect, got), (seed, ctr)


def test_split_join_roundtrip_and_order():
    ts = jnp.asarray(
        [0, 1, 50, 10_000_000_000, (1 << 62) - 1, int(mk.INVALID_TIME)],
        dtype=jnp.int64,
    )
    hi, lo = mk._split64(ts)
    assert jnp.array_equal(mk._join64(hi, lo), ts)
    # lexicographic signed order on the planes == int64 order
    for i in range(len(ts) - 1):
        a = bool(mk._gt64(hi[i + 1], lo[i + 1], hi[i], lo[i]))
        assert a == bool(ts[i + 1] > ts[i])


@pytest.mark.parametrize("steps,tile", [(40, 8), (17, 4)])
def test_megakernel_bit_exact_vs_xla(steps, tile):
    """Every EngineState leaf equal after `steps` events per seed."""
    wl = mk.probe_workload()
    cfg = mk.probe_config(max_steps=steps)
    seeds = jnp.arange(16, dtype=jnp.int64)
    s0 = core._init(wl, cfg, seeds)
    ref = core.run_drive(wl, cfg, s0)
    got = mk.run_megasweep(
        s0, steps=steps, time_limit=cfg.time_limit_ns, tile=tile,
        interpret=True,
    )
    eq = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), ref, got)
    assert all(jax.tree.leaves(eq)), eq


def test_megakernel_time_limit_semantics():
    """A reachable time limit must freeze seeds exactly like the XLA
    step's done/time_up masking (the budget-cut pop is still consumed)."""
    wl = mk.probe_workload()
    steps = 60
    cfg = core.EngineConfig(queue_capacity=mk._Q,
                            time_limit_ns=120_000_000,  # ~6-12 events in
                            max_steps=steps)
    seeds = jnp.arange(8, dtype=jnp.int64)
    s0 = core._init(wl, cfg, seeds)
    ref = core.run_drive(wl, cfg, s0)
    got = mk.run_megasweep(
        s0, steps=steps, time_limit=cfg.time_limit_ns, tile=8,
        interpret=True,
    )
    eq = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), ref, got)
    assert all(jax.tree.leaves(eq)), eq
    assert bool(jnp.any(got.done))  # the limit actually fired for some seeds
