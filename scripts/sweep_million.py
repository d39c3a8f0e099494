"""Million-seed MadRaft sweep — the scale demonstration beyond bench.py.

Runs 2**20 = 1,048,576 seeds of BASELINE config #3 (5-node Raft election +
replication with crash/restart injection, 3 virtual seconds each) as 16k
chunks of one compiled program, merging per-chunk summaries on host
(constant device memory — the pattern that extends indefinitely; see
engine.core.run_sweep_chunked). Prints one JSON line.

Any total works: a ragged final chunk is padded to the full chunk size
and its summary is computed through the LIMIT-MASKED reduction
(models/_common.make_sweep_summary ``limit=``), so the ragged tail
reuses both the compiled sweep program AND the compiled summary program
— zero recompiles in the timed region, which the summary line proves by
counting ``Finished XLA compilation`` events (``jax.log_compiles``)
while the timed loop runs.

Usage: python scripts/sweep_million.py [total_seeds] [ckpt_dir] [--mesh [N]]

Progress goes to stderr as an obs-registry heartbeat (seeds done,
seeds/s, ETA) every ``MADSIM_HB_SECONDS`` (default 5; 0 disables) —
stdout stays the single machine-readable JSON line.

With ``ckpt_dir`` the sweep is preemption-safe: per-chunk summaries are
checkpointed (engine.checkpoint.run_sweep_chunked_resumable) and a
restarted run skips completed chunks.

``--mesh`` (optionally ``--mesh N`` for an N-device mesh) runs every
chunk sharded over the device mesh (``parallel.run_sweep_sharded``) —
the same chunk granule spans all devices, summaries merge identically,
and the per-chunk checkpoint files are mesh-free, so a sweep can be
interrupted under one device count and finished under another. When the
process sees fewer devices than requested it re-execs itself under the
forced CPU host mesh (madsim_tpu._cpu_mesh_env).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from madsim_tpu import obs
from madsim_tpu.engine import core
from madsim_tpu.engine.compiles import count_compiles, use_compile_cache
from madsim_tpu.models import raft
from madsim_tpu.models._common import merge_summaries

# env-overridable so smoke runs can exercise the multi-chunk + ragged
# paths without paying for 16k-lane compiles
CHUNK = int(os.environ.get("MADSIM_SWEEP_CHUNK", 16384))
# heartbeat cadence (stderr; stdout stays the one JSON line). 0 disables.
HB_SECONDS = float(os.environ.get("MADSIM_HB_SECONDS", 5.0))


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("total", type=int, nargs="?", default=1 << 20)
    ap.add_argument("ckpt_dir", nargs="?", default=None)
    ap.add_argument("--mesh", type=int, nargs="?", const=8, default=None,
                    help="shard each chunk over an N-device mesh "
                         "(bare --mesh picks 8)")
    ns = ap.parse_args()
    total = ns.total
    mesh = None
    n_dev = 0
    if ns.mesh is not None:
        n_dev = ns.mesh
        from madsim_tpu._cpu_mesh_env import reexec_with_cpu_mesh

        reexec_with_cpu_mesh(n_dev)
        from madsim_tpu import parallel

        mesh = parallel.seed_mesh(jax.devices()[:n_dev])
        if CHUNK % n_dev or total % n_dev:
            raise SystemExit(
                f"chunk {CHUNK} and total {total} must divide the "
                f"{n_dev}-device mesh"
            )
    use_compile_cache()
    cfg = raft.RaftConfig(num_nodes=5, crashes=1)
    ecfg = raft.engine_config(cfg, time_limit_ns=3_000_000_000)
    wl = raft.workload(cfg)

    def run_chunk(seed_chunk):
        if mesh is None:
            return core.run_sweep(wl, ecfg, seed_chunk)
        from madsim_tpu import parallel

        return parallel.run_sweep_sharded(wl, ecfg, seed_chunk, mesh)

    base = 1 << 30
    tail = total % CHUNK if total > CHUNK else 0

    # compile once outside the timed region — at the batch shape the
    # timed loop will actually run (a sub-chunk total compiles and runs
    # at its own exact shape), including the limit-masked summary
    # program a ragged tail will hit
    # ... the warm seed range sits just below ``base`` so the offset
    # arange (an eager iota+add) is compiled here too, not in the loop
    warm_n = CHUNK if total > CHUNK else total
    warm = run_chunk(jnp.arange(base - warm_n, base, dtype=jnp.int64))
    raft.sweep_summary(warm)
    if tail:
        raft.sweep_summary(warm, limit=tail)

    # progress heartbeat driven by the obs registry (seeds done, seeds/s,
    # ETA), replacing ad-hoc perf_counter prints: the chunk drivers count
    # ``sweep_seeds_done_total`` as each chunk lands, and a daemon ticker
    # reads it back every HB_SECONDS — the same series a Prometheus
    # scrape would see (obs.Telemetry(http_port=...))
    telem = obs.Telemetry()
    hb = obs.Heartbeat(telem.registry, total, prefix="sweep")
    hb_stop = None
    if HB_SECONDS > 0:
        import threading

        hb_stop = threading.Event()

        def _beat():
            while not hb_stop.wait(HB_SECONDS):
                hb.tick()

        threading.Thread(target=_beat, daemon=True, name="hb").start()

    ckpt_dir = ns.ckpt_dir
    chunks_preloaded = 0
    try:
        with count_compiles() as compiles:
            t0 = time.perf_counter()
            if ckpt_dir:
                import glob

                from madsim_tpu.engine.checkpoint import (
                    run_sweep_chunked_resumable,
                )

                chunks_preloaded = len(
                    glob.glob(os.path.join(ckpt_dir, "chunk_*.json"))
                )
                seeds = jnp.arange(base, base + total, dtype=jnp.int64)
                # clamp the chunk granule to the total so a sub-chunk run
                # is not padded up to a full 16k-lane sweep
                totals = run_sweep_chunked_resumable(
                    wl, ecfg, seeds, raft.sweep_summary, ckpt_dir,
                    chunk_size=min(CHUNK, total), run_chunk=run_chunk,
                    telemetry=telem,
                )
            else:
                totals = {}
                for lo in range(base, base + total, CHUNK):
                    k = min(CHUNK, base + total - lo)
                    if k < CHUNK and total > CHUNK:
                        # ragged tail: extend the contiguous seed range
                        # to the compiled chunk shape (value-identical to
                        # core._pad_seeds' max+1+i filler) and mask the
                        # padded lanes inside the one compiled summary
                        # program — no trim program, no recompile, not
                        # even an eager pad op
                        final = run_chunk(
                            jnp.arange(lo, lo + CHUNK, dtype=jnp.int64)
                        )
                        merge_summaries(
                            totals, raft.sweep_summary(final, limit=k)
                        )
                    else:
                        final = run_chunk(
                            jnp.arange(lo, lo + k, dtype=jnp.int64)
                        )
                        merge_summaries(totals, raft.sweep_summary(final))
                    telem.count(
                        "sweep_seeds_done_total", k,
                        help="seeds retired across all chunks",
                    )
            wall = time.perf_counter() - t0
    finally:
        if hb_stop is not None:
            hb_stop.set()
    hb.tick(force=True)

    print(
        json.dumps(
            {
                "metric": "madraft_million_seed_sweep",
                "seeds": total,
                "chunk_size": CHUNK,
                "wall_s": round(wall, 2),
                "seeds_per_sec": round(total / wall, 1),
                "events_per_sec": round(totals["events_total"] / wall, 1),
                "sim_sec_per_wall_sec": round(
                    totals["sim_ns_total"] / wall / 1e9, 1
                ),
                "violations": totals["violations"],
                "elections_total": totals["elections_total"],
                # provenance: throughput above is only a device
                # measurement when every chunk was computed this run
                "chunks_loaded_from_checkpoint": chunks_preloaded,
                "chunks_computed": -(-total // CHUNK) - chunks_preloaded,
                # program reuse, measured: XLA compilations during the
                # timed loop (0 = the warm-up paid for everything,
                # ragged tail included)
                "compiles_in_timed_region": compiles.count,
                "mesh_devices": n_dev,
                "backend": jax.default_backend(),
            }
        )
    )


if __name__ == "__main__":
    main()
