"""Screened + pipelined checked-sweep demo (and determinism-gate leg).

Runs the etcd history workload (seeded ``bug_stale_read`` by default)
through ``oracle.screen.checked_sweep``: chunked sweep with the
on-device suspect screen folded behind each chunk, host-side decode +
WGL checking of chunk N overlapped with the device sweep of chunk N+1,
optionally fanned over a process pool.

The report written by ``--report`` is deterministic BY CONTRACT: it is
a pure function of (config, seed range) — no wall times, no paths, keys
sorted — and the worker-pool size must not change a byte of it
(``check_histories`` orders results by lane and each verdict is a pure
function of one history). ``scripts/check_determinism.sh`` runs this
twice x two pool sizes and byte-diffs the four reports. Timing goes to
stderr, where the gate ignores it.

Usage: python scripts/checked_sweep_demo.py [--seeds N] [--chunk-size C]
           [--workers W] [--clean] [--report PATH] [--mesh N]
           [--driver chunked|stream] [--telemetry-dir DIR]
           [--device-decode]

``--device-decode`` sources canonical history rows from the jitted
on-device decode kernel (``oracle.history.canon_sweep``) instead of
per-row host Python — the report must be byte-identical either way;
the gate's decode leg runs 2 processes x {device, host} and diffs all
four.

``--telemetry-dir DIR`` runs the identical pipeline under a full
``obs.Telemetry`` handle (metrics + journal + trace spans written to
DIR) — the report must be byte-identical to an uninstrumented run; the
gate's telemetry leg runs 2 processes x telemetry {on, off} and diffs
all four.

``--driver stream`` routes the identical pipeline through the
persistent streaming lane pool (``engine.stream.stream_sweep``,
docs/streaming.md); the report must be byte-identical to the chunked
driver's — the gate's streaming leg runs 2 processes x 2 drivers and
diffs all four.

``--mesh N`` runs the identical pipeline sharded over an N-device mesh
(under ``JAX_PLATFORMS=cpu`` it re-execs onto a forced N-device host
mesh; elsewhere fewer than N devices is an error) — the report
must be byte-identical to the unsharded one; the determinism gate runs
this across 2 processes x 2 mesh sizes and diffs all four.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=512)
    ap.add_argument("--seed0", type=int, default=0)
    ap.add_argument("--chunk-size", type=int, default=128)
    ap.add_argument("--workers", type=int, default=0)
    ap.add_argument(
        "--clean", action="store_true",
        help="default config (no seeded bug): the checker must stay quiet",
    )
    ap.add_argument("--report", default=None)
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the pipeline over an N-device mesh")
    ap.add_argument(
        "--driver", choices=("chunked", "stream"), default="chunked",
        help="sweep driver; the report bytes must not depend on this "
        "(the streaming leg of check_determinism.sh diffs the two)",
    )
    ap.add_argument(
        "--telemetry-dir", default=None,
        help="run under a full obs.Telemetry handle (metrics + journal + "
        "trace written HERE); the report bytes must not depend on this "
        "(the telemetry leg of check_determinism.sh diffs on vs off)",
    )
    ap.add_argument(
        "--device-decode", action="store_true",
        help="source canonical history rows from the on-device decode "
        "kernel instead of per-row host Python; the report bytes must "
        "not depend on this (the decode leg of check_determinism.sh "
        "diffs the two)",
    )
    args = ap.parse_args()

    mesh = None
    if args.mesh:
        from madsim_tpu._cpu_mesh_env import reexec_with_cpu_mesh

        reexec_with_cpu_mesh(args.mesh)
        from madsim_tpu import parallel

        mesh = parallel.seed_mesh(jax.devices()[: args.mesh])

    from madsim_tpu.engine.compiles import use_compile_cache
    from madsim_tpu.models import etcd
    from madsim_tpu.oracle.screen import checked_sweep

    use_compile_cache()

    cfg = etcd.EtcdConfig(
        hist_slots=256, bug_stale_read=not args.clean
    )
    ecfg = etcd.engine_config(
        cfg, time_limit_ns=2_000_000_000, max_steps=20_000
    )
    wl = etcd.workload(cfg)
    seeds = jnp.arange(
        args.seed0, args.seed0 + args.seeds, dtype=jnp.int64
    )

    telem = None
    if args.telemetry_dir:
        from madsim_tpu import obs

        os.makedirs(args.telemetry_dir, exist_ok=True)
        telem = obs.Telemetry(
            journal=os.path.join(args.telemetry_dir, "journal.jsonl"),
            trace=os.path.join(args.telemetry_dir, "trace.json"),
        )

    t0 = time.perf_counter()
    totals = checked_sweep(
        wl, ecfg, seeds, etcd.history_spec(), etcd.sweep_summary,
        chunk_size=args.chunk_size, workers=args.workers, mesh=mesh,
        driver=args.driver, telemetry=telem,
        device_decode=args.device_decode,
    )
    wall = time.perf_counter() - t0
    if telem is not None:
        telem.close()

    report = {
        "metric": "etcd_checked_sweep",
        "config": "clean" if args.clean else "bug_stale_read",
        "seed_range": [args.seed0, args.seed0 + args.seeds],
        "chunk_size": args.chunk_size,
        "totals": totals,
    }
    if args.report:
        with open(args.report, "w") as f:
            f.write(json.dumps(report, sort_keys=True) + "\n")
    else:
        print(json.dumps(report, sort_keys=True))
    print(
        f"checked {args.seeds} seeds in {wall:.2f}s "
        f"({args.seeds / wall:.1f} seeds/s end-to-end; "
        f"{totals['hist_suspects']} suspects, "
        f"{totals['hist_violations']} violations, "
        f"workers={args.workers}, backend={jax.default_backend()})",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
