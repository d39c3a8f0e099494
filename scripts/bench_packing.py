"""A/B: packed queue layout (occupancy in the time plane) vs the legacy
layout (explicit bool valid[Q] plane in the loop carry).

Round-4's verdict asked for a measured answer on state packing in the
bandwidth-bound 64k regime (docs/pallas_finding.md §4: 0.04 µs/seed/step,
the loop carry streams through HBM every event). The shipped round-5
packing drops the one redundant plane — valid[Q] duplicates
``time == INVALID_TIME`` — cutting Q bytes/seed of carry plus a leaf of
XLA carry bookkeeping, with bit-identical schedules by construction
(tests/test_engine.py::test_legacy_queue_layout_bit_identical).

Methodology per docs/pallas_finding.md §0: both layouts compile side by
side (EngineConfig.legacy_queue is a static jit arg), reps interleave
A/B/A/B in one process (a slow stretch hits both layouts alike),
fresh seeds per timed call, completion bounded by a scalar readback,
min-of-REPS reported with spread.

Run on the TPU:  python scripts/bench_packing.py
"""

from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax
import jax.numpy as jnp

from madsim_tpu.engine import core
from madsim_tpu.engine.compiles import use_compile_cache
from madsim_tpu.models import raft

BATCHES = (16384, 65536)
REPS = 5
SIM_SECONDS = 3.0

_seed_base = [1]


def fresh_seeds(n: int) -> jnp.ndarray:
    lo = _seed_base[0]
    _seed_base[0] += n
    return jnp.arange(lo, lo + n, dtype=jnp.int64)


def main() -> None:
    use_compile_cache()
    cfg = raft.RaftConfig(num_nodes=5, crashes=1)
    packed_cfg = raft.engine_config(cfg, time_limit_ns=int(SIM_SECONDS * 1e9))
    legacy_cfg = packed_cfg._replace(legacy_queue=1)
    wl = raft.workload(cfg)
    print(f"# devices: {jax.devices()}", file=sys.stderr)

    variants = {"packed": packed_cfg, "legacy": legacy_cfg}
    results = []
    for S in BATCHES:
        # warmup/compile each variant once, and verify bit-equality of the
        # two layouts on a shared seed batch before timing anything
        vseeds = fresh_seeds(S)
        finals = {}
        for name, ecfg in variants.items():
            finals[name] = core.run_sweep(wl, ecfg, vseeds)
            int(finals[name].ctr.sum())
        assert jnp.array_equal(finals["packed"].ctr, finals["legacy"].ctr)
        assert jnp.array_equal(finals["packed"].now_ns, finals["legacy"].now_ns)
        events = int(finals["packed"].ctr.sum())

        times = {name: [] for name in variants}
        for _rep in range(REPS):
            for name, ecfg in variants.items():
                seeds = fresh_seeds(S)
                t0 = time.perf_counter()
                final = core.run_sweep(wl, ecfg, seeds)
                int(final.ctr.sum())
                times[name].append(time.perf_counter() - t0)

        row = {"batch": S, "events_per_seed": round(events / S, 1)}
        for name, ts in times.items():
            best = min(ts)
            row[name] = {
                "s": round(best, 3),
                "seeds_per_sec": round(S / best, 1),
                "spread": round((max(ts) - best) / best, 3),
            }
        row["packed_over_legacy"] = round(
            min(times["packed"]) / min(times["legacy"]), 3
        )
        row["bit_exact"] = True
        results.append(row)
        print(json.dumps(row))

    print(json.dumps({"summary": results}), file=sys.stderr)


if __name__ == "__main__":
    main()
