"""A/B: Pallas pop-min kernel vs the XLA path, honest methodology.

Runs both implementations of the batched pop decision over identical
queue states, asserts bit-identical results (slots AND found flags — the
kernel must be a drop-in for replay parity), then times each with fresh
inputs per call and a forced scalar readback (see
docs/pallas_finding.md §0). Needs a TPU: without one it fails rather
than timing the Pallas interpreter.

    python scripts/bench_pallas.py [S ...]   (default 16384 65536)
"""

from __future__ import annotations

import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from madsim_tpu.engine import core, pallas_queue as pq
from madsim_tpu.engine.compiles import use_compile_cache
from madsim_tpu.models import raft

SIZES = [int(a) for a in sys.argv[1:]] or [16384, 65536]

cfg = raft.RaftConfig(num_nodes=5, crashes=1)
ecfg = raft.engine_config(cfg)
wl = raft.workload(cfg)


def fresh_inputs(s, offset, warm_steps=16):
    """A materialized queue batch with realistic occupancy + a tie draw."""
    state = jax.jit(partial(core.init_sweep, wl, ecfg))(
        jnp.arange(offset, offset + s, dtype=jnp.int64)
    )
    step = jax.jit(partial(core.step_batch, wl, ecfg))
    for _ in range(warm_steps):
        state = step(state)
    tie = jax.random.bits(jax.random.key(offset), (s,), dtype=jnp.uint32)
    jax.block_until_ready(state)
    return state.queue, tie


ITERS = 512  # on-device repetitions per timed call: one pop-min is
# microseconds, far below a dispatch's host overhead, so the op is
# amortized inside one program


def looped(fn):
    """fn repeated ITERS times on-device with varying tie draws; returns a
    jitted callable whose scalar output forces everything to run."""

    @jax.jit
    def run(q, ties):
        def body(i, acc):
            slot, found = fn(q, ties[i])
            return acc + jnp.sum(slot) + jnp.sum(found)

        return jax.lax.fori_loop(0, ITERS, body, jnp.zeros((), jnp.int64))

    return run


def timed(run, inputs_list):
    best = float("inf")
    for q, ties in inputs_list:
        t0 = time.perf_counter()
        int(run(q, ties))  # host readback = real completion
        best = min(best, time.perf_counter() - t0)
    return best


def main() -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("bench_pallas.py needs a TPU (no interpret-mode timing)")
    use_compile_cache()
    pallas = pq.pop_min_pallas
    for s in SIZES:
        # parity first: the kernel must pick bit-identical slots
        q, tie = fresh_inputs(s, offset=7 * s)
        sx, fx = pq.pop_min_xla(q, tie)
        sp, fp = pallas(q, tie)
        assert jnp.array_equal(sx, sp) and jnp.array_equal(fx, fp), (
            f"kernel diverged from XLA path at S={s}"
        )

        def with_ties(i):
            q, _ = fresh_inputs(s, offset=(i + 1) * 100 * s)
            ties = jax.random.bits(jax.random.key(i), (ITERS, s), dtype=jnp.uint32)
            return q, ties

        inputs = [with_ties(i) for i in range(3)]
        run_xla, run_pal = looped(pq.pop_min_xla), looped(pallas)
        int(run_xla(*inputs[0]))  # compile
        int(run_pal(*inputs[0]))
        t_xla = timed(run_xla, inputs[1:]) / ITERS
        t_pal = timed(run_pal, inputs[1:]) / ITERS
        print(
            f"S={s:6d}  xla={t_xla * 1e6:8.1f} us/op  "
            f"pallas={t_pal * 1e6:8.1f} us/op  "
            f"pallas/xla={t_pal / t_xla:5.2f}x  (parity: identical)"
        )
    dev = jax.devices()[0]
    print(f"device={dev.platform} {dev.device_kind} (iters={ITERS})")


if __name__ == "__main__":
    main()
