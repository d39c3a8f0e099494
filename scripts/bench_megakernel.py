"""A/B: VMEM-resident megakernel vs the flat XLA sweep loop.

Measures the round-3 headroom claim (docs/pallas_finding.md §3): the XLA
driver's ~65 MB loop carry round-trips HBM every event at a 16k batch —
does keeping each seed-tile's state resident in VMEM across many steps
buy the projected ≲2.7×?

Methodology (same rules as scripts/bench_pallas.py — see
docs/pallas_finding.md §0): fresh inputs per timed call (no timed call
repeats an input), completion bounded by a scalar readback, many steps
amortized inside one program (a call's fixed dispatch and readback cost
is spread over them), compile excluded by a warmup call per shape.

Run on the TPU:  python scripts/bench_megakernel.py
"""

from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax
import jax.numpy as jnp

from madsim_tpu.engine import core
from madsim_tpu.engine import megakernel as mk
from madsim_tpu.engine.compiles import use_compile_cache

STEPS = 512
BATCHES = (4096, 16384, 65536)
# >=512-seed tiles exceed the 16 MB scoped-VMEM budget (the compiler
# stages the kernel's in+out tuples, ~2x the tile state); 64 measured best
TILES = (64, 128, 256)
REPS = 5

_seed_base = [0]


def fresh_seeds(n: int) -> jnp.ndarray:
    lo = _seed_base[0]
    _seed_base[0] += n
    return jnp.arange(lo, lo + n, dtype=jnp.int64)


def readback(state) -> int:
    return int(jnp.sum(state.ctr)) + int(jnp.sum(state.wstate.acc))


def timed(fn, s0):
    t0 = time.perf_counter()
    out = fn(s0)
    rb = readback(out)
    return time.perf_counter() - t0, rb


def main() -> None:
    use_compile_cache()
    wl = mk.probe_workload()
    cfg = mk.probe_config(max_steps=STEPS)
    print(f"# devices: {jax.devices()}", file=sys.stderr)

    results = []
    for S in BATCHES:
        xla = lambda s0: jax.block_until_ready(core.run_drive(wl, cfg, s0))  # noqa: E731

        # one fixed verification batch per size: EVERY tile that gets
        # timed must first reproduce the XLA driver's final state
        # bit-exactly on it (a tile-size-dependent miscompile must not
        # publish a timing as verified); the comparison doubles as the
        # warmup/compile call
        s_verify = core._init(wl, cfg, fresh_seeds(S))
        ref = core.run_drive(wl, cfg, s_verify)

        # contenders, then INTERLEAVED reps, so a slow stretch of the
        # machine hits every contender alike (min-of-reps)
        contenders = {"xla": xla}
        for tile in TILES:
            if S % tile:
                continue
            mega = lambda s0, t=tile: mk.run_megasweep(  # noqa: E731
                s0, steps=STEPS, time_limit=cfg.time_limit_ns, tile=t
            )
            try:
                got = mega(s_verify)
            except Exception as e:  # e.g. a tile too big for scoped VMEM
                print(json.dumps({"batch": S, "tile": tile,
                                  "skipped": str(e).splitlines()[0][:120]}),
                      file=sys.stderr)
                continue
            leaves = jax.tree.leaves(
                jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), ref, got)
            )
            assert all(leaves), f"megakernel diverged at S={S} tile={tile}"
            contenders[f"mega{tile}"] = mega
        if len(contenders) == 1:
            print(json.dumps({"batch": S,
                              "skipped": "no megakernel tile compiled"}),
                  file=sys.stderr)
            continue
        s0 = core._init(wl, cfg, fresh_seeds(S))
        timed(xla, s0)  # warmup
        times = {name: [] for name in contenders}
        for _ in range(REPS):
            for name, fn in contenders.items():
                s0 = core._init(wl, cfg, fresh_seeds(S))
                dt, _ = timed(fn, s0)
                times[name].append(dt)
        xla_us = min(times["xla"]) / STEPS * 1e6
        tile_rows = {
            int(name[4:]): min(ts) / STEPS * 1e6
            for name, ts in times.items() if name.startswith("mega")
        }

        best_tile = min(tile_rows, key=tile_rows.get)
        row = {
            "batch": S,
            "steps": STEPS,
            "xla_us_per_step": round(xla_us, 1),
            "mega_us_per_step": {str(t): round(v, 1) for t, v in tile_rows.items()},
            "best_tile": best_tile,
            "mega_over_xla": round(tile_rows[best_tile] / xla_us, 2),
            "bit_exact": True,
        }
        results.append(row)
        print(json.dumps(row))

    print(json.dumps({"summary": results}), file=sys.stderr)


if __name__ == "__main__":
    main()
