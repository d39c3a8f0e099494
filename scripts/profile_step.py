"""Phase-level profiling of the engine step on the current backend.

Methodology (docs/pallas_finding.md §0): every phase runs ITERS times
inside ONE on-device fori_loop with per-iteration input variation (no
iteration repeats an input), every output leaf is folded into the loop
carry (so nothing dead-code-eliminates), and completion is bounded by a
host readback of that scalar. The fixed dispatch+readback cost is
measured and subtracted.

Run on TPU:  python scripts/profile_step.py [S]
"""

import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from madsim_tpu.engine import core, queue as equeue
from madsim_tpu.engine.compiles import use_compile_cache
from madsim_tpu.engine.rng import event_bits
from madsim_tpu.models import raft

use_compile_cache()
S = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
ITERS = 256

cfg = raft.RaftConfig(num_nodes=5, crashes=1)
ecfg = raft.engine_config(cfg, time_limit_ns=3_000_000_000)
wl = raft.workload(cfg)

state = jax.jit(partial(core.init_sweep, wl, ecfg))(jnp.arange(S, dtype=jnp.int64))
# a few real steps so queues/wstate have representative content
warm = jax.jit(partial(core.step_batch, wl, ecfg))
for _ in range(8):
    state = warm(state)
jax.block_until_ready(state)


def _fold(acc, out):
    """Fold every output leaf into the int64 carry (defeats DCE)."""
    for leaf in jax.tree.leaves(out):
        if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            leaf = jax.random.key_data(leaf)
        acc = acc + jnp.sum(leaf.astype(jnp.int64))
    return acc


def timeit(name, body, n=ITERS, reps=3):
    """body(i, acc) -> acc, looped on-device; prints per-iter ms.

    Two loop lengths (n and 4n) and the difference quotient, so the
    fixed (and noisy) per-call dispatch+readback cost cancels exactly
    instead of being subtracted as a separately-measured constant."""

    def make(k):
        @jax.jit
        def run(salt):
            return jax.lax.fori_loop(0, k, body, salt.astype(jnp.int64))

        return run

    run_n, run_4n = make(n), make(4 * n)
    int(run_n(jnp.int64(0)))  # compile
    int(run_4n(jnp.int64(0)))
    t_n = t_4n = float("inf")
    for r in range(1, reps + 1):
        t0 = time.perf_counter()
        int(run_n(jnp.int64(2 * r)))  # fresh salt → not memoizable
        t_n = min(t_n, time.perf_counter() - t0)
        t0 = time.perf_counter()
        int(run_4n(jnp.int64(2 * r + 1)))
        t_4n = min(t_4n, time.perf_counter() - t0)
    per = (t_4n - t_n) / (3 * n)
    print(f"{name:28s} {per * 1e3:8.3f} ms")
    return per

step = partial(core.step_batch, wl, ecfg)


def step_body(i, acc):
    # chain a salted state so every iteration differs
    s = state._replace(ctr=state.ctr + (acc % 7).astype(jnp.int32))
    return _fold(acc, step(s))


timeit("step_batch (full)", step_body)


def rng_body(i, acc):
    bits = jax.vmap(lambda k, c: event_bits(k, c, wl.num_rand + 2))(
        state.key, state.ctr + i.astype(jnp.int32)
    )
    return _fold(acc, bits)


timeit("event_bits", rng_body)

rand0 = jax.vmap(lambda k, c: event_bits(k, c, wl.num_rand + 2))(state.key, state.ctr)


def pop_body(i, acc):
    tie = rand0[:, 1] + i.astype(jnp.uint32)
    out = jax.vmap(lambda q, t: equeue.pop_min(q, tie_u32=t))(state.queue, tie)
    return _fold(acc, out)


timeit("pop_min (tie-break)", pop_body)

_, _, kind0, pay0, _ = jax.vmap(lambda q, t: equeue.pop_min(q, tie_u32=t))(
    state.queue, rand0[:, 1]
)


def handler_body(i, acc):
    rand = rand0[:, 2:] ^ i.astype(jnp.uint32)
    out = jax.vmap(wl.handle)(state.wstate, state.now_ns, kind0, pay0, rand)
    return _fold(acc, out)


timeit("handler (6-way switch)", handler_body)

_, emits0 = jax.vmap(wl.handle)(state.wstate, state.now_ns, kind0, pay0, rand0[:, 2:])


def push_body(i, acc):
    times = emits0.times + i
    out = jax.vmap(
        lambda q, t, k, p, e: equeue.push_many(q, t, k, p, e)
    )(state.queue, times, emits0.kinds, emits0.pays, emits0.enables)
    return _fold(acc, out)


timeit("push_many (rank-select)", push_body)

print(f"\nbatch={S}, iters={ITERS}, backend={jax.default_backend()}")
