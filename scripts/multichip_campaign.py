"""Million-seed sharded campaign + device-count curve — the multichip
publication artifact (MULTICHIP_r06 direction; docs/multichip.md).

Two phases, both through the sharded pipelined checked-sweep driver
(``parallel.run_sweep_sharded_pipelined``):

1. **curve** — one fixed-spec checked sweep (sweep + on-device screen +
   WGL checking) at each device count in ``--devices``, same seed range,
   compiles excluded; prints aggregate seeds/s, events/s and
   time-to-first-bug per count and ASSERTS the merged summary bytes,
   less the chunk-dependent dedup counters, are identical across every
   mesh size (the invariance contract).
2. **campaign** — a genuine coverage-guided fault campaign (seeded
   FaultSpec mutations, retain-on-new-bits, election-history screening
   + checking) over ``--campaign-seeds`` total seeds at the largest
   device count: a million seeds as ONE unit of work. ``--campaign-invariance``
   additionally re-runs a small campaign at two device counts and
   byte-compares the JSONL reports.

Under ``JAX_PLATFORMS=cpu`` it re-execs itself onto a forced CPU host
mesh of the largest requested size (``madsim_tpu._cpu_mesh_env``), the
same environment the multichip dryrun gate and the pytest suite use;
on an accelerator host too few devices is an error, never a CPU run.
``--smoke`` shrinks every knob to a ~1-minute CI gate (``make
multichip-smoke``).

Wall-clock metrics go to stdout JSON; the byte-compared artifacts
(checked-sweep totals, campaign JSONL) never contain times or paths.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _target(kind: str, smoke: bool):
    from madsim_tpu.explore.targets import (
        amnesia_gate,
        oracle_demo_faults,
        stale_etcd_target,
    )

    if kind == "raft":
        return amnesia_gate(smoke)
    t = stale_etcd_target(
        time_limit_ns=500_000_000 if smoke else 2_000_000_000,
        max_steps=6_000 if smoke else 20_000,
        hist_slots=128 if smoke else 256,
    )
    return t, oracle_demo_faults()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma-separated device counts for the curve")
    ap.add_argument("--curve-target", choices=("raft", "etcd"), default="raft")
    ap.add_argument("--curve-seeds", type=int, default=4096)
    ap.add_argument("--chunk-per-device", type=int, default=512)
    ap.add_argument("--workers", type=int, default=0,
                    help="history-checker process-pool size")
    ap.add_argument("--campaign-seeds", type=int, default=0,
                    help="total seeds of the big sharded campaign "
                         "(rounds x seeds-per-round; 0 = skip)")
    ap.add_argument("--seeds-per-round", type=int, default=65536)
    ap.add_argument("--campaign-ckpt-dir", default=None)
    ap.add_argument("--campaign-invariance", action="store_true",
                    help="re-run a small campaign at the smallest and "
                         "largest device counts and byte-compare reports")
    ap.add_argument("--report", default=None)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    counts = tuple(int(x) for x in args.devices.split(","))
    if args.smoke:
        counts = tuple(c for c in counts if c <= 2) or (1, 2)
        args.curve_seeds = min(args.curve_seeds, 512)
        args.chunk_per_device = min(args.chunk_per_device, 128)
        args.campaign_invariance = True

    from madsim_tpu._cpu_mesh_env import reexec_with_cpu_mesh

    reexec_with_cpu_mesh(max(counts))

    import jax

    from madsim_tpu.engine.compiles import use_compile_cache

    use_compile_cache()

    from madsim_tpu.explore import (
        CampaignConfig,
        checked_sweep_curve,
        sharded_campaign,
    )

    target, base = _target(args.curve_target, args.smoke)
    curve = checked_sweep_curve(
        target, base, device_counts=counts, seeds_total=args.curve_seeds,
        chunk_per_device=args.chunk_per_device, workers=args.workers,
    )
    assert curve["bytes_invariant"], (
        "sharded checked-sweep summary bytes differ across mesh sizes"
    )
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())}, "curve": curve}

    if args.campaign_seeds:
        ctarget, cbase = _target("raft", args.smoke)
        rounds = -(-args.campaign_seeds // args.seeds_per_round)
        ccfg = CampaignConfig(
            rounds=rounds,
            seeds_per_round=args.seeds_per_round,
            chunk_size=args.chunk_per_device * max(counts),
            check_workers=args.workers,
        )
        out["campaign"] = sharded_campaign(
            ctarget, cbase, ccfg, max(counts),
            ckpt_dir=args.campaign_ckpt_dir,
        )

    if args.campaign_invariance:
        lo_hi = (min(counts), max(counts))
        ctarget, cbase = _target("raft", True)
        ccfg = CampaignConfig(
            rounds=2, seeds_per_round=256,
            chunk_size=128 * max(lo_hi), check_workers=args.workers,
        )
        shas = {}
        with tempfile.TemporaryDirectory() as d:
            for nd in lo_hi:
                p = os.path.join(d, f"campaign_{nd}.jsonl")
                res = sharded_campaign(ctarget, cbase, ccfg, nd, report_path=p)
                shas[nd] = res["report_sha256"]
        assert len(set(shas.values())) == 1, (
            f"campaign report bytes differ across mesh sizes: {shas}"
        )
        out["campaign_invariance"] = {
            "device_counts": list(lo_hi),
            "report_sha256": next(iter(shas.values())),
            "bytes_invariant": True,
        }

    blob = json.dumps(out, sort_keys=True)
    if args.report:
        with open(args.report, "w") as f:
            f.write(blob + "\n")
    print(blob)
    return 0


if __name__ == "__main__":
    sys.exit(main())
