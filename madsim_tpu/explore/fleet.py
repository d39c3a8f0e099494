"""Fleet-scale checked sweeps: device-count curves + sharded campaigns.

This is the measurement/driver layer for the production-scale story —
"a million seeds is one unit of work". Two entry points:

- ``checked_sweep_curve``: run ONE fixed-spec checked sweep (sweep +
  on-device screen + WGL checking, ``oracle.screen.checked_sweep``)
  sharded over each requested device count, warm (compiles excluded
  from the timed region — each mesh size compiles its own programs),
  and report aggregate seeds/s, events/s and time-to-first-bug per
  count PLUS the byte-invariance verdict: the merged summary dict must
  be byte-identical across every mesh size (docs/multichip.md).
- ``sharded_campaign``: the full coverage-guided fault campaign
  (``explore.campaign.run_campaign``) routed through the sharded
  pipelined driver — mutation rounds, retain-on-new-bits, history
  screening + checking, per-round JSONL records — with wall-clock,
  throughput and time-to-first-bug instrumentation that stays OUT of
  the report bytes (the JSONL is byte-identical across mesh sizes and
  wall clocks by the campaign determinism contract).

Wall-clock numbers live only in the returned metrics dicts, never in
the byte-compared reports, so the invariance checks stay meaningful.
"""

from __future__ import annotations

import hashlib
import json
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp


def _ttfb_hook(t0: float, box: dict):
    """An ``on_chunk`` callback latching the wall time at which the
    first violating seed became KNOWN (its chunk's host phase merged) —
    the time-to-first-bug clock of a checked sweep or campaign."""

    def on_chunk(*, lo, k, summary) -> None:
        del lo, k
        if box.get("ttfb_s") is None and (
            summary.get("violations", 0) > 0
            or summary.get("hist_violations", 0) > 0
            or summary.get("violating_seeds")
            or summary.get("hist_violating_seeds")
        ):
            box["ttfb_s"] = time.perf_counter() - t0
        box["chunks"] = box.get("chunks", 0) + 1

    return on_chunk


def checked_sweep_curve(
    target,
    base_spec,
    device_counts: Sequence[int] = (1, 2, 4, 8),
    seeds_total: int = 4096,
    seed0: int = 0,
    chunk_per_device: int = 512,
    workers: int = 0,
    warm_seeds: Optional[int] = None,
    devices=None,
) -> dict:
    """Aggregate checked-sweep throughput vs device count, one fixed
    fault spec (``target.build(base_spec)``), same seed range at every
    count. Returns per-count metrics plus ``bytes_invariant`` — the
    merged summary JSON, less its chunk-dependent dedup counters
    (``oracle.screen.CHUNK_DEPENDENT``), must be identical on every mesh
    size even though the chunk boundaries differ
    (``chunk_per_device × n_dev``).
    """
    from ..oracle.screen import checked_sweep, chunk_invariant
    from ..parallel.mesh import seed_mesh

    if devices is None:
        devices = jax.devices()
    if len(devices) < max(device_counts):
        raise ValueError(
            f"need {max(device_counts)} devices, have {len(devices)} "
            "(force the CPU host mesh: madsim_tpu._cpu_mesh_env)"
        )
    workload, ecfg = target.build(base_spec)
    spec = target.hist_spec
    if spec is None:
        raise ValueError(f"target {target.name!r} records no history")
    seeds = jnp.arange(seed0, seed0 + seeds_total, dtype=jnp.int64)
    # warm seeds sit far above the measured range, so the timed run never
    # repeats an input the warm-up already swept
    warm_base = seed0 + (1 << 30)

    points = []
    blobs = []
    for n_dev in device_counts:
        mesh = seed_mesh(devices[:n_dev])
        # compile everything untimed at the exact chunk shapes — one
        # chunk per mesh size suffices (every later chunk reuses the
        # same programs), so small meshes don't re-sweep the whole
        # measured range in warm-up; a ragged seeds_total additionally
        # needs the tail's limit-masked summary program, so the warm
        # batch carries the same tail (one full + one ragged chunk)
        chunk = chunk_per_device * n_dev
        tail = seeds_total % chunk if seeds_total > chunk else 0
        warm = (
            warm_seeds if warm_seeds is not None
            else (chunk + tail if tail else min(seeds_total, chunk))
        )
        checked_sweep(
            workload, ecfg,
            jnp.arange(warm_base, warm_base + warm, dtype=jnp.int64),
            spec, target.summarize, mesh=mesh,
            chunk_per_device=chunk_per_device, workers=workers,
        )
        box: dict = {}
        t0 = time.perf_counter()
        totals = checked_sweep(
            workload, ecfg, seeds, spec, target.summarize, mesh=mesh,
            chunk_per_device=chunk_per_device, workers=workers,
            on_chunk=_ttfb_hook(t0, box),
        )
        wall = time.perf_counter() - t0
        blob = json.dumps(chunk_invariant(totals), sort_keys=True)
        blobs.append(blob)
        points.append(
            {
                "devices": n_dev,
                "seeds": seeds_total,
                "chunk_per_device": chunk_per_device,
                "wall_s": round(wall, 2),
                "seeds_per_sec": round(seeds_total / wall, 1),
                "events_per_sec": round(totals["events_total"] / wall, 1),
                "time_to_first_bug_s": (
                    round(box["ttfb_s"], 3) if box.get("ttfb_s") else None
                ),
                "suspects": totals.get("hist_suspects", 0),
                "violations": totals.get("hist_violations", 0),
                "chunks": box.get("chunks", 0),
                "report_sha256": hashlib.sha256(blob.encode()).hexdigest(),
            }
        )
    base = points[0]["seeds_per_sec"]
    for p in points:
        p["speedup"] = round(p["seeds_per_sec"] / base, 2)
    return {
        "metric": "sharded_checked_sweep_curve",
        "target": target.name,
        "workers": workers,
        "curve": points,
        "bytes_invariant": all(b == blobs[0] for b in blobs),
    }


def sharded_campaign(
    target,
    base_spec,
    ccfg,
    n_devices: int,
    report_path: Optional[str] = None,
    ckpt_dir: Optional[str] = None,
    devices=None,
) -> dict:
    """One coverage-guided fault campaign through the sharded pipelined
    driver on an ``n_devices`` mesh; returns throughput metrics (the
    campaign's own JSONL report — byte-identical across mesh sizes —
    goes to ``report_path``)."""
    from ..parallel.mesh import seed_mesh
    from .campaign import run_campaign

    if devices is None:
        devices = jax.devices()
    mesh = seed_mesh(devices[:n_devices])
    box: dict = {}
    t0 = time.perf_counter()
    result = run_campaign(
        target, base_spec, ccfg, report_path=report_path,
        ckpt_dir=ckpt_dir, mesh=mesh, on_chunk=_ttfb_hook(t0, box),
    )
    wall = time.perf_counter() - t0
    rounds = len(result.records)
    seeds_swept = rounds * ccfg.seeds_per_round
    events = sum(r["events_total"] for r in result.records)
    out = {
        "metric": "sharded_campaign",
        "target": target.name,
        "devices": n_devices,
        "rounds": rounds,
        "seeds_per_round": ccfg.seeds_per_round,
        "seeds_swept": seeds_swept,
        "wall_s": round(wall, 2),
        "seeds_per_sec": round(seeds_swept / wall, 1),
        "events_per_sec": round(events / wall, 1),
        "events_total": events,
        "violations_total": sum(r["violations"] for r in result.records),
        "distinct_failures": len(result.failures),
        "coverage_total_bits": (
            result.records[-1]["coverage_total_bits"] if result.records else 0
        ),
        "corpus_size": len(result.corpus),
        "time_to_first_bug_s": (
            round(box["ttfb_s"], 3) if box.get("ttfb_s") else None
        ),
    }
    if report_path is not None:
        with open(report_path, "rb") as f:
            out["report_sha256"] = hashlib.sha256(f.read()).hexdigest()
    return out
