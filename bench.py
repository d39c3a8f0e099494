"""Benchmark: MadRaft seed-sweep throughput, TPU engine vs host-tier CPU.

Prints ONE JSON line whose headline is the largest-batch MadRaft sweep
(BASELINE.md config #3: 5-node Raft election + replication with
crash/restart fault injection, 3 virtual seconds per seed), with:

- ``batch_curve``: seeds/sec at 4k/16k/64k (throughput scales with the
  lockstep batch; per-batch compile and run times reported separately);
- ``sweep_100k``: BASELINE config #5's pod-scale artifact — 131,072
  seeds run as 16,384-seed chunks of one compiled program, per-chunk
  summaries merged on host (constant device memory);
- ``recovery_e2e``: config #5's determinism half — a sweep interrupted
  at 300 steps, checkpointed to .npz, restored, resumed, and verified
  bit-identical to the uninterrupted run;
- ``cross_backend``: the hardware bit-parity contract, self-verified —
  a 4096-seed sweep on the TPU vs the same seeds on the CPU backend,
  every EngineState leaf compared, plus one CPU traced replay against
  its TPU sweep lane;
- ``kafka``: BASELINE config #4 as a second workload line (10k-seed
  broker crash/restart sweep with the acked-loss checker quiet);
- ``etcd``: BASELINE config #2 (8k-seed 3-node KV + lease sweep with
  partition injection, revision/lease checkers quiet);
- honest baseline framing: ``vs_baseline`` divides by THIS REPO's
  single-threaded Python host executor running the same workload — the
  reference publishes no numbers (BASELINE.md) and its Rust toolchain is
  not in this image, so ``baseline.reference_note`` records the honest
  order-of-magnitude estimate instead of a fake ratio.

Timing methodology per docs/pallas_finding.md §0: fresh seed ranges per
timed run (no timed run repeats an input), a scalar host readback to
bound completion, and every timed figure is the MIN of ``REPS``
interleaved repetitions (rep-outer, case-inner), with the max-over-min
spread reported per point. The headline ``value`` is the chunked 131k sweep (the production
pattern: ~3 s of device work per rep), not a single-shot curve point.

The full run and every standalone leg need a TPU and fail at start
without one; ``--smoke`` is the CPU rehearsal at tiny sizes and prints
only which result keys each leg produced, never a timing.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time as walltime

import jax
import jax.numpy as jnp

SIM_SECONDS = 3.0
# 48 seeds keeps the host-tier measurement under ~0.5 s now that the
# compiled executor core runs >100 seeds/s (was 8 when it ran at ~37/s —
# flagged as too thin for the vs_baseline denominator)
HOST_SEEDS = 48
# 32,768 brackets the occupancy knee: an earlier setup measured 16,384
# and 65,536 with nothing in between, so the cliff's location was a
# guess; each point now also reports its loop-carry HBM
# footprint (core.state_bytes_per_seed) so the knee is attributable
CURVE = (4096, 16384, 32768, 65536)
# 131,072 seeds — the "100k-seed" artifact — as 16k chunks of one
# compiled program: per-lane step cost cliffs ~9x above ~16k seeds
# (see core.run_sweep_chunked), so chunking IS the fast path
BIG_TOTAL = 131072
BIG_CHUNK = 16384
# min-of-REPS interleaved repetitions per timed figure (drift discipline;
# see module docstring)
REPS = 3
# seed-batch size for the recovery and cross-backend parity phases
PARITY_SEEDS = 4096
# checked-sweep leg (sweep + on-device screen + WGL check, end to end):
# the etcd history workload at 131k seeds through the pipelined driver,
# vs a naive decode-and-check-every-seed loop measured in the same run
CHECKED_TOTAL = 131072
CHECKED_CHUNK = None  # None = auto-pick the occupancy knee
CHECKED_SIM_SECONDS = 2.0  # hist_slots=256 is sized for a 2 s horizon
CHECKED_REPS = 2  # interleaved checked/unchecked reps (full-scale leg)
NAIVE_SEEDS = 4096
CHECK_WORKERS = 8
# pipelined-recovery leg: 2 chunks, interrupted mid-chunk-0
PIPE_SEEDS = 2048
PIPE_CHUNK = 1024
# campaign leg (explore-candidate throughput): K mutated candidates per
# measured batch, serial compile-per-candidate (the pre-refactor explore
# path) vs ONE batched (candidate x seed) spec-as-data grid — the
# compile-bound regime the spec-as-data refactor targets, so the figure
# of merit is end-to-end candidates/s including compiles
CAMPAIGN_K = 16
CAMPAIGN_SEEDS = 256
CAMPAIGN_REPS = 2
CAMPAIGN_SIM_SECONDS = 1.5
# streaming leg (persistent lane pool vs fixed-shape chunks): etcd
# under the gray-failure FaultSpec retires lanes at genuinely different
# ages (measured max/mean step spread ~1.46 per chunk — crashes starve
# some seeds of events while partition retries feed others), which is
# the straggler pattern a fixed-shape chunk drags on; the pool is
# HALF the chunk so the drain tail (the last pool-full of stragglers,
# the only stretch a stream cannot refill) stays small relative to the
# smallest curve point; round_steps can exceed the mean lane age
# (~161) because the round exits early once a refill quorum retires,
# so a large value just amortizes round dispatch
STREAM_CURVE = (4096, 16384, 32768, 65536)
STREAM_CHUNK = 1024
STREAM_POOL = 512
STREAM_ROUND_STEPS = 256
STREAM_REPS = 2
STREAM_SIM_SECONDS = 3.0
STREAM_MAX_STEPS = 2_000
# telemetry leg (obs overhead on the streaming checked-sweep path):
# the SAME stream_sweep-driven checked sweep with telemetry off
# (telemetry=None — the true zero-instrumentation baseline) vs on
# (full-fat handle: metrics + journal + trace spans), interleaved
# on/off reps per pallas_finding §0; the gate is ≤3% overhead, and the
# two legs' report dicts must be equal (the out-of-band contract,
# checked here on every bench run, byte-level in check_determinism.sh)
TELEM_SEEDS = 16384
TELEM_CHUNK = 1024
TELEM_REPS = 3
TELEM_SIM_SECONDS = 2.0
TELEM_OVERHEAD_GATE = 0.03
# steering leg (the self-steering scheduler A/B, docs/steering.md
# "What the A/B measures"): bandit vs uniform at the SAME deterministic
# device-event budget on two targets — the raft amnesia gate (10
# families, 2 crash-bearing: the uniform grid burns ~80% of its budget
# on amnesia-blind duds) and the partitioned stale-read etcd gate (its
# single reachable fingerprint saturates both policies, so its win
# metric is coverage bits, not fingerprints). One rep per cell: the
# figure of merit is fingerprints-at-matched-budget, a deterministic
# count, not a wall-clock rate (wall is reported for context only)
STEER_FAMILIES = (0x001, 0x002, 0x003, 0x004, 0x008,
                  0x010, 0x020, 0x040, 0x080, 0x100)
STEER_SEEDS_PER_ROUND = 16
STEER_ESCALATE_SEEDS = 8
STEER_KILL_PLAYS = 1
STEER_CAMPAIGN_SEED = 7
STEER_RAFT_BUDGET = 45_000
STEER_ETCD_BUDGET = 12_000
# wire-load leg (the serve/ async core under >=1k genuine-protocol
# clients; docs/wire.md "Async serving core"): one full-scale run for
# the SLO/oracle/replay gates + WIRE_REPS smaller reps for the
# throughput spread gate. Runs in SUBPROCESSES (scripts/wire_load.py):
# this process holds jax, and the rig forks worker processes — the
# parent of those forks must stay jax-free (thread-after-fork hazard)
WIRE_REP_CLIENTS = 264
WIRE_REP_SECS = 8.0
WIRE_REPS = 3

_seed_cursor = [1]


def _fresh(n: int) -> jnp.ndarray:
    lo = _seed_cursor[0]
    _seed_cursor[0] += n
    return jnp.arange(lo, lo + n, dtype=jnp.int64)


def _device() -> dict:
    """The device every result names, as JAX reports it."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _spread(times) -> float:
    """Max-over-min dispersion of a rep list: 0.0 = perfectly stable."""
    return round((max(times) - min(times)) / min(times), 3) if times else 0.0


# a timed figure is only comparable round-over-round when its rep
# dispersion is small; the kafka/etcd legs gate on the 3 FASTEST reps
# (the min is the figure, so extra reps tighten it — raw max/min spread
# can only grow with more reps) staying within this bound
SPREAD_GATE = 0.10
MAX_EXTRA_ROUNDS = 6


def _spread_best3(times) -> float:
    """Dispersion of the three fastest reps — the stability of the
    min-of-reps figure itself, immune to a single slow outlier."""
    return _spread(sorted(times)[:3])


def bench_host() -> dict:
    """Host-tier executor: one full simulation per seed (seeds/sec),
    min of REPS passes (the host number swings ±15% with machine load)."""
    sys.path.insert(0, __file__.rsplit("/", 1)[0] + "/examples")
    from raft_host import run_seed

    times = []
    for rep in range(REPS):
        t0 = walltime.perf_counter()
        for seed in range(HOST_SEEDS):
            run_seed(
                rep * HOST_SEEDS + seed, n=5, crashes=1, sim_seconds=SIM_SECONDS
            )
        times.append(walltime.perf_counter() - t0)
    return {
        "seeds_per_sec": round(HOST_SEEDS / min(times), 2),
        "reps": REPS,
        "spread": _spread(times),
    }


def bench_curve(wl, ecfg, raft):
    """seeds/sec at each batch size: REPS interleaved timed runs per size
    (rep-outer, size-inner, so a drift window hits every size equally),
    min taken per size; compile time split out per size. Each point
    carries its loop-carry HBM footprint so the occupancy knee
    (ROADMAP item 3) is attributable to a measured byte count.

    The AUTO-PICKED chunk size (``core.pick_chunk_size`` — what the
    chunked/pipelined drivers actually sweep at) is measured as its own
    curve point next to the raw sizes and flagged ``auto_chunk``, so
    the occupancy-cliff fix is visible in the curve itself round over
    round: the auto point must sit at or left of the knee."""
    from madsim_tpu.engine import core

    per_seed = core.state_bytes_per_seed(wl, ecfg)
    auto = core.pick_chunk_size(wl, ecfg)
    sizes = tuple(sorted(set(CURVE) | {auto}))
    compile_s = {}
    summaries = {}
    for s in sizes:
        t0 = walltime.perf_counter()
        warm = core.run_sweep(wl, ecfg, _fresh(s))
        int(warm.ctr.sum())
        compile_s[s] = walltime.perf_counter() - t0
    times = {s: [] for s in sizes}
    for _rep in range(REPS):
        for s in sizes:
            t0 = walltime.perf_counter()
            final = core.run_sweep(wl, ecfg, _fresh(s))
            int(final.ctr.sum())
            t = walltime.perf_counter() - t0
            # keep the summary PAIRED with its own rep's time: each rep
            # sweeps fresh seeds, so event totals differ slightly per rep
            if not times[s] or t < min(times[s]):
                summaries[s] = raft.sweep_summary(final)
            times[s].append(t)
    curve = []
    for s in sizes:
        best = min(times[s])
        summary = summaries[s]
        curve.append(
            {
                "seeds": s,
                "auto_chunk": s == auto,
                "seeds_per_sec": round(s / best, 1),
                "events_per_sec": round(summary["events_total"] / best, 1),
                "sim_sec_per_wall_sec": round(
                    summary["sim_ns_total"] / best / 1e9, 1
                ),
                "compile_plus_first_run_s": round(compile_s[s], 2),
                "run_s": round(best, 3),
                "reps": REPS,
                "spread": _spread(times[s]),
                "violations": summary["violations"],
                "hbm_bytes": s * per_seed,
            }
        )
    return curve


def bench_100k(wl, ecfg, raft):
    """BASELINE config #5 scale: pod-scale sweep as 16k chunks of one
    compiled program, summaries merged on host per chunk — constant
    device memory, the pattern that extends to millions of seeds (each
    chunk is also the checkpoint/restart granule). Min of REPS full
    passes; this is the headline figure."""
    from madsim_tpu.engine import core
    from madsim_tpu.models._common import merge_summaries

    times = []
    best_totals = None
    for _rep in range(REPS):
        t0 = walltime.perf_counter()
        totals = {}
        for _ in range(BIG_TOTAL // BIG_CHUNK):
            final = core.run_sweep(wl, ecfg, _fresh(BIG_CHUNK))
            merge_summaries(totals, raft.sweep_summary(final))
        wall = walltime.perf_counter() - t0
        if not times or wall < min(times):
            best_totals = totals
        times.append(wall)
        assert totals["violations"] == 0, totals
    wall = min(times)
    return {
        "seeds": BIG_TOTAL,
        "chunk_size": BIG_CHUNK,
        "wall_s": round(wall, 2),
        "seeds_per_sec": round(BIG_TOTAL / wall, 1),
        "events_per_sec": round(best_totals["events_total"] / wall, 1),
        "reps": REPS,
        "spread": _spread(times),
        "violations": best_totals["violations"],
    }


def bench_recovery(wl, raft_mod):
    """Config #5 determinism half: interrupt → checkpoint → restore →
    resume ≡ uninterrupted, bit for bit."""
    from madsim_tpu.engine import checkpoint, core

    cfg = raft_mod.RaftConfig(num_nodes=5, crashes=1)
    full_ecfg = raft_mod.engine_config(cfg, time_limit_ns=int(SIM_SECONDS * 1e9))
    part_ecfg = raft_mod.engine_config(
        cfg, time_limit_ns=int(SIM_SECONDS * 1e9), max_steps=300
    )
    seeds = _fresh(PARITY_SEEDS)
    straight = core.run_sweep(wl, full_ecfg, seeds)
    partial = core.run_sweep(wl, part_ecfg, seeds)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "mid.npz")
        checkpoint.save_sweep(partial, path)
        restored = checkpoint.load_sweep(path, like=partial)
    resumed = checkpoint.resume_sweep(wl, full_ecfg, restored)
    identical = all(
        bool(jnp.array_equal(a, b))
        for a, b in zip(
            jax.tree.leaves(
                (straight.ctr, straight.now_ns, straight.wstate.elections)
            ),
            jax.tree.leaves(
                (resumed.ctr, resumed.now_ns, resumed.wstate.elections)
            ),
        )
    )
    return {"seeds": PARITY_SEEDS, "interrupted_at_step": 300,
            "bit_identical": identical}


def bench_checked_sweep() -> dict:
    """END-TO-END checked throughput — the quantity this round makes
    the optimized one: seeds/s through sweep PLUS history validation.

    The pipelined leg runs the etcd history workload (clean config,
    hist_slots=256) at CHECKED_TOTAL seeds through
    ``oracle.screen.checked_sweep``: chunked sweep, on-device suspect
    screen folded behind each chunk, host-side decode + process-pool
    WGL checking of chunk N interleaved with the device rounds of chunk
    N+1 (budgeted incremental polling). Its UNCHECKED TWIN — the same
    pipelined sweep + summary with no screen, no decode, no checker —
    runs in the same process at the same seed count, interleaved
    rep-outer/case-inner so load drift hits both legs alike; the ratio
    ``checked_over_unchecked`` is the full price of history validation
    (acceptance: <= 2x at this scale on CPU). The naive baseline —
    sweep, decode EVERY lane, check serially, no overlap — is measured
    on a smaller seed count; rates compare directly since both are
    per-seed-linear."""
    from madsim_tpu.engine import core
    from madsim_tpu.engine.checkpoint import run_sweep_pipelined
    from madsim_tpu.models import etcd
    from madsim_tpu.oracle import check_histories, decode_sweep
    from madsim_tpu.oracle.screen import checked_sweep

    cfg = etcd.EtcdConfig(hist_slots=256)
    ecfg = etcd.engine_config(
        cfg, time_limit_ns=int(CHECKED_SIM_SECONDS * 1e9)
    )
    wl = etcd.workload(cfg)
    spec = etcd.history_spec()
    chunk = CHECKED_CHUNK or core.pick_chunk_size(wl, ecfg)
    total = max(CHECKED_TOTAL, 2 * chunk)

    # warm every program untimed — ALL legs: the pipeline's sweep/
    # screen/summary/pool at the chunk shape, the unchecked twin
    # (shares the sweep/summary programs — run once anyway so its
    # driver path holds no first-call surprises), AND the naive leg's
    # sweep at NAIVE_SEEDS (a compile inside nwall would hand the
    # pipeline a fake speedup) plus one decode+check rep
    checked_sweep(
        wl, ecfg, _fresh(chunk), spec, etcd.sweep_summary,
        chunk_size=chunk, workers=CHECK_WORKERS,
    )
    run_sweep_pipelined(
        wl, ecfg, _fresh(chunk), etcd.sweep_summary, chunk_size=chunk
    )
    warm_naive = core.run_sweep(wl, ecfg, _fresh(NAIVE_SEEDS))
    check_histories(decode_sweep(warm_naive), spec)

    cwalls, uwalls = [], []
    totals = None
    for _rep in range(CHECKED_REPS):
        t0 = walltime.perf_counter()
        totals = checked_sweep(
            wl, ecfg, _fresh(total), spec, etcd.sweep_summary,
            chunk_size=chunk, workers=CHECK_WORKERS,
        )
        cwalls.append(walltime.perf_counter() - t0)
        t0 = walltime.perf_counter()
        run_sweep_pipelined(
            wl, ecfg, _fresh(total), etcd.sweep_summary, chunk_size=chunk
        )
        uwalls.append(walltime.perf_counter() - t0)
    wall, uwall = min(cwalls), min(uwalls)

    t0 = walltime.perf_counter()
    nfinal = core.run_sweep(wl, ecfg, _fresh(NAIVE_SEEDS))
    hists = decode_sweep(nfinal)
    naive_bad = sum(
        1 for r in check_histories(hists, spec) if not r.ok
    )
    nwall = walltime.perf_counter() - t0

    rate, urate, nrate = total / wall, total / uwall, NAIVE_SEEDS / nwall
    return {
        "seeds": total,
        "chunk_size": chunk,
        "workers": CHECK_WORKERS,
        "reps": CHECKED_REPS,
        "wall_s": round(wall, 2),
        "seeds_per_sec": round(rate, 1),
        "spread": _spread(cwalls),
        "suspects": totals["hist_suspects"],
        "hist_violations": totals["hist_violations"],
        "hist_overflow_seeds": totals["hist_overflow_seeds"],
        "budget_exceeded": totals.get("budget_exceeded", 0),
        "unchecked": {
            "seeds": total,
            "wall_s": round(uwall, 2),
            "seeds_per_sec": round(urate, 1),
            "spread": _spread(uwalls),
        },
        "checked_over_unchecked": round(wall / uwall, 2),
        "naive": {
            "seeds": NAIVE_SEEDS,
            "wall_s": round(nwall, 2),
            "seeds_per_sec": round(nrate, 1),
            "hist_violations": naive_bad,
        },
        "speedup_vs_naive": round(rate / nrate, 1),
    }


def bench_recovery_pipelined() -> dict:
    """The pipelined half of config #5's determinism story: interrupt a
    checked sweep MID-CHUNK, checkpoint the in-flight chunk state with
    its chunk metadata (format v7 ``inflight``), restore, resume with
    overlap enabled — the merged checked-sweep report must be
    bit-identical to the uninterrupted pipelined run."""
    from madsim_tpu.engine import checkpoint, core
    from madsim_tpu.models import etcd
    from madsim_tpu.oracle.screen import checked_sweep

    cfg = etcd.EtcdConfig(hist_slots=256)
    full = etcd.engine_config(
        cfg, time_limit_ns=int(CHECKED_SIM_SECONDS * 1e9)
    )
    short = etcd.engine_config(
        cfg, time_limit_ns=int(CHECKED_SIM_SECONDS * 1e9), max_steps=300
    )
    wl = etcd.workload(cfg)
    spec = etcd.history_spec()
    seeds = _fresh(PIPE_SEEDS)
    straight = checked_sweep(
        wl, full, seeds, spec, etcd.sweep_summary, chunk_size=PIPE_CHUNK
    )
    partial = core.run_sweep(wl, short, seeds[:PIPE_CHUNK])
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "mid.npz")
        checkpoint.save_sweep(
            partial, path, inflight={"lo": 0, "k": PIPE_CHUNK}
        )
        restored = checkpoint.load_sweep(path, like=partial)
        inflight = checkpoint.load_inflight(path)
    resumed = checked_sweep(
        wl, full, seeds, spec, etcd.sweep_summary, chunk_size=PIPE_CHUNK,
        resume_from=(restored, inflight),
    )
    return {
        "pipelined_seeds": PIPE_SEEDS,
        "pipelined_interrupted_at_step": 300,
        "pipelined_bit_identical": resumed == straight,
    }


def bench_campaign() -> dict:
    """Explore-candidate throughput, serial vs batched grid.

    Per rep (interleaved A/B, docs/pallas_finding.md §0): leg A sweeps
    ``CAMPAIGN_K`` FRESH mutated candidates the pre-refactor way — every
    candidate a new jit cache key, so every candidate pays the sweep
    compile (the production regime a coverage-guided campaign used to
    live in); leg B stacks the same-count fresh candidates into one
    (candidate x seed) spec-as-data grid over the warmed envelope
    program. Fresh candidates every rep keep leg A honestly
    compile-bound and leg B honestly data-bound; compiles are COUNTED in
    both timed regions (engine/compiles.py), so the speedup is
    attributable, not asserted."""
    import random

    from madsim_tpu import explore
    from madsim_tpu.engine.compiles import count_compiles
    from madsim_tpu.engine.faults import FaultSpec

    target = explore.amnesia_raft_target(
        time_limit_ns=int(CAMPAIGN_SIM_SECONDS * 1e9), max_steps=15_000
    )
    base = FaultSpec(
        crashes=3,
        crash_window_ns=1_200_000_000,
        restart_lo_ns=50_000_000,
        restart_hi_ns=300_000_000,
    )
    env = explore.target_envelope(target, base)
    rng = random.Random(0xBE7C)
    seen = set()

    def fresh_candidates():
        # distinct across the whole bench: a repeated spec would hit the
        # serial leg's jit cache and understate its per-candidate compile
        out = []
        while len(out) < CAMPAIGN_K:
            spec = explore.mutate_spec(base, rng, 2)
            if spec not in seen:
                seen.add(spec)
                out.append(spec)
        return out

    def ccfg_at(seed0: int) -> explore.CampaignConfig:
        return explore.CampaignConfig(
            seeds_per_round=CAMPAIGN_SEEDS, seed0=seed0
        )

    # warm the grid's programs (envelope sweep, lane slice, summary)
    # outside every timed region; the serial leg has nothing to warm —
    # paying the compiler per candidate IS that leg
    explore.sweep_candidate_grid(
        target, fresh_candidates(), ccfg_at(int(_fresh(CAMPAIGN_SEEDS)[0])),
        env,
    )

    serial_times, grid_times = [], []
    serial_compiles = grid_compiles = 0
    for _ in range(CAMPAIGN_REPS):
        cand_a, cand_b = fresh_candidates(), fresh_candidates()
        s0a = int(_fresh(CAMPAIGN_SEEDS)[0])
        s0b = int(_fresh(CAMPAIGN_SEEDS)[0])
        with count_compiles() as c:
            t0 = walltime.perf_counter()
            for spec in cand_a:
                explore.campaign._sweep_candidate(
                    target, spec, ccfg_at(s0a), None
                )
            serial_times.append(walltime.perf_counter() - t0)
        serial_compiles += c.count
        with count_compiles() as c:
            t0 = walltime.perf_counter()
            explore.sweep_candidate_grid(target, cand_b, ccfg_at(s0b), env)
            grid_times.append(walltime.perf_counter() - t0)
        grid_compiles += c.count

    rate_serial = CAMPAIGN_K / min(serial_times)
    rate_grid = CAMPAIGN_K / min(grid_times)
    return {
        "candidates": CAMPAIGN_K,
        "seeds_per_candidate": CAMPAIGN_SEEDS,
        "reps": CAMPAIGN_REPS,
        "serial_per_candidate": {
            "candidates_per_sec": round(rate_serial, 2),
            "compiles_in_timed_region": serial_compiles,
            "spread": _spread(serial_times),
        },
        "batched_grid": {
            "candidates_per_sec": round(rate_grid, 2),
            "compiles_in_timed_region": grid_compiles,
            "spread": _spread(grid_times),
        },
        "speedup_vs_serial": round(rate_grid / rate_serial, 1),
    }


def bench_streaming() -> dict:
    """Streaming vs chunked seeds/s across the batch curve (ROADMAP
    item 1, docs/streaming.md): the SAME etcd history sweep through
    ``run_sweep_pipelined`` (fixed-shape chunks — each chunk drags to
    its slowest lane) and ``engine.stream.stream_sweep`` (a
    constant-occupancy lane pool continuously refilled from the work
    queue), interleaved A/B reps per pallas_finding §0 (rep-outer,
    driver-inner, fresh seed ranges, min-of-reps). The gray-failure
    FaultSpec makes lanes retire at genuinely different ages (crashes
    starve some seeds of events while partition retries feed others) —
    exactly the straggler pattern fixed-shape chunking pays for. Every
    rep asserts the two drivers' totals are identical (the byte
    contract) and that the warmed stream region performs 0 XLA
    compilations."""
    from madsim_tpu.engine.checkpoint import run_sweep_pipelined
    from madsim_tpu.engine.compiles import count_compiles
    from madsim_tpu.engine.faults import FaultSpec
    from madsim_tpu.engine.stream import stream_sweep
    from madsim_tpu.models import etcd

    cfg = etcd.EtcdConfig(
        hist_slots=64,
        bug_stale_read=True,
        faults=FaultSpec(
            crashes=2, partitions=2, spikes=1, losses=1, pauses=1
        ),
    )
    ecfg = etcd.engine_config(
        cfg, time_limit_ns=int(STREAM_SIM_SECONDS * 1e9),
        max_steps=STREAM_MAX_STEPS,
    )
    wl = etcd.workload(cfg)
    sizes = STREAM_CURVE
    chunk = min(STREAM_CHUNK, min(sizes))
    pool = min(STREAM_POOL, chunk)
    kw = dict(chunk_size=chunk)

    # warm both drivers' programs (the [chunk]/[pool]-shaped
    # round/refill/summary programs serve every curve point) on a
    # 2-chunk batch so the refill and merge paths are hot before any
    # timed region
    warm = _fresh(2 * chunk)
    run_sweep_pipelined(wl, ecfg, warm, etcd.sweep_summary, **kw)
    stream_sweep(
        wl, ecfg, warm, etcd.sweep_summary, pool_size=pool,
        round_steps=STREAM_ROUND_STEPS, **kw,
    )

    times_c = {s: [] for s in sizes}
    times_s = {s: [] for s in sizes}
    occs = {s: 0.0 for s in sizes}
    stream_compiles = 0
    for _rep in range(STREAM_REPS):
        for s in sizes:
            seeds = _fresh(s)  # same seeds for both drivers: the totals
            #                    equality below is then a real byte check
            t0 = walltime.perf_counter()
            chunked = run_sweep_pipelined(
                wl, ecfg, seeds, etcd.sweep_summary, **kw
            )
            times_c[s].append(walltime.perf_counter() - t0)
            stats: dict = {}
            with count_compiles() as c:
                t0 = walltime.perf_counter()
                streamed = stream_sweep(
                    wl, ecfg, seeds, etcd.sweep_summary, pool_size=pool,
                    round_steps=STREAM_ROUND_STEPS, stats=stats, **kw,
                )
                dt = walltime.perf_counter() - t0
            stream_compiles += c.count
            assert streamed == chunked, (
                f"driver totals diverge at {s} seeds"
            )
            if not times_s[s] or dt < min(times_s[s]):
                occs[s] = stats["occupancy_mean"]
            times_s[s].append(dt)
    assert stream_compiles == 0, (
        f"{stream_compiles} XLA compilations in the warmed stream region"
    )

    curve = []
    for s in sizes:
        rate_c = s / min(times_c[s])
        rate_s = s / min(times_s[s])
        curve.append(
            {
                "seeds": s,
                "chunked_seeds_per_sec": round(rate_c, 1),
                "stream_seeds_per_sec": round(rate_s, 1),
                "speedup": round(rate_s / rate_c, 2),
                "occupancy_mean": round(occs[s], 3),
                "totals_identical": True,
                "spread_chunked": _spread(times_c[s]),
                "spread_stream": _spread(times_s[s]),
            }
        )
    return {
        "workload": (
            "etcd bug_stale_read + gray-failure FaultSpec "
            "(straggler-heavy retirement, step spread ~1.46x)"
        ),
        "chunk_size": chunk,
        "pool_size": pool,
        "round_steps": STREAM_ROUND_STEPS,
        "reps": STREAM_REPS,
        "compiles_in_warmed_region": stream_compiles,
        "curve": curve,
    }


def bench_telemetry() -> dict:
    """Telemetry overhead on the streaming checked-sweep path.

    Per rep (interleaved on/off, docs/pallas_finding.md §0): leg OFF
    runs ``checked_sweep(driver="stream")`` with ``telemetry=None`` —
    every recorder is behind an ``if telemetry is not None`` guard, so
    this is the genuine uninstrumented baseline; leg ON runs the same
    seeds with a full-fat ``obs.Telemetry`` (metrics registry + JSONL
    journal + trace spans — the most expensive configuration a user can
    enable). Every rep asserts the two report dicts are EQUAL (the
    out-of-band contract; the determinism gate byte-diffs the same
    thing across processes). The figure is min-of-reps wall per leg;
    ``overhead`` is on/off − 1, gated ≤ TELEM_OVERHEAD_GATE."""
    import tempfile as _tmp

    from madsim_tpu.engine.faults import FaultSpec
    from madsim_tpu.models import etcd
    from madsim_tpu.obs import Telemetry
    from madsim_tpu.oracle.screen import checked_sweep

    cfg = etcd.EtcdConfig(
        hist_slots=64,
        faults=FaultSpec(crashes=2, partitions=2, spikes=1),
    )
    ecfg = etcd.engine_config(
        cfg, time_limit_ns=int(TELEM_SIM_SECONDS * 1e9),
        max_steps=STREAM_MAX_STEPS,
    )
    wl = etcd.workload(cfg)
    spec = etcd.history_spec()
    kw = dict(
        chunk_size=TELEM_CHUNK, workers=0, driver="stream",
    )

    # warm both legs' programs (identical programs — telemetry never
    # changes a traced computation, only wall-clock-side bookkeeping)
    checked_sweep(wl, ecfg, _fresh(TELEM_CHUNK), spec,
                  etcd.sweep_summary, **kw)

    times_off, times_on = [], []
    with _tmp.TemporaryDirectory() as d:
        for rep in range(TELEM_REPS):
            seeds = _fresh(TELEM_SEEDS)  # same seeds both legs: the
            #                              equality below is a real check
            t0 = walltime.perf_counter()
            off = checked_sweep(wl, ecfg, seeds, spec,
                                etcd.sweep_summary, **kw)
            times_off.append(walltime.perf_counter() - t0)
            telem = Telemetry(
                journal=os.path.join(d, f"rep{rep}.jsonl"),
                trace=os.path.join(d, f"rep{rep}.trace.json"),
            )
            t0 = walltime.perf_counter()
            on = checked_sweep(wl, ecfg, seeds, spec,
                               etcd.sweep_summary, telemetry=telem, **kw)
            times_on.append(walltime.perf_counter() - t0)
            telem.close()
            assert on == off, "telemetry changed the report — OUT-OF-BAND BROKEN"
        snapshot = telem.registry.snapshot()
    overhead = min(times_on) / min(times_off) - 1
    return {
        "seeds": TELEM_SEEDS,
        "chunk_size": TELEM_CHUNK,
        "reps": TELEM_REPS,
        "off_seeds_per_sec": round(TELEM_SEEDS / min(times_off), 1),
        "on_seeds_per_sec": round(TELEM_SEEDS / min(times_on), 1),
        "overhead": round(overhead, 4),
        "overhead_ok": overhead <= TELEM_OVERHEAD_GATE,
        "gate": TELEM_OVERHEAD_GATE,
        "reports_identical": True,
        "spread_off": _spread(times_off),
        "spread_on": _spread(times_on),
        # a few sanity series from the last ON rep, proving the
        # instrumentation actually fired while the reports stayed equal
        "sample_metrics": {
            k: snapshot.get(k)
            for k in ("stream_rounds_total", "stream_seeds_done_total",
                      "oracle_screened_total")
            if k in snapshot
        },
    }


def bench_cross_backend(wl, ecfg):
    """THE framework contract, machine-checked on hardware every round:
    a TPU sweep and a CPU sweep of the same seeds are bit-identical on
    every EngineState leaf, and the single-seed traced replay (the
    debugging path, engine/core.run_traced) lands on the same final
    state as the batched sweep lane (``core.cpu_parity``). Ref analogue:
    determinism checking as a first-class harness feature
    (madsim/src/sim/runtime/mod.rs:178-202)."""
    from madsim_tpu.engine import core

    return core.cpu_parity(wl, ecfg, _fresh(PARITY_SEEDS))


def bench_secondary_models():
    """BASELINE configs #4 (kafka broker crash/restart sweep) and #2
    (etcd 3-node KV + lease with partition injection), checkers quiet.

    The two legs INTERLEAVE their reps (rep-outer, model-inner, the
    A/B discipline of docs/pallas_finding.md §0) instead of running
    back-to-back rep blocks, so a slow stretch of the machine cannot land
    on one model wholesale. Two more disciplines apply: the first
    post-warm interleaved pass is a DISCARDED warm-up rep (it still pays
    allocator growth that the compile warm-up does not flush), and the legs
    gate on ``_spread_best3 < SPREAD_GATE`` — more interleaved rounds
    are taken (bounded by ``MAX_EXTRA_ROUNDS``) until the three fastest
    reps agree within 10%, so the min-of-reps figure is tight enough
    that a sharded-perf regression is actually detectable round over
    round. ``spread_ok`` records whether the gate was met.
    Returns ``(kafka_line, etcd_line)``."""
    from madsim_tpu.engine import core
    from madsim_tpu.models import etcd, kafka

    cases = {
        "kafka": (kafka, kafka.KafkaConfig(), 10240),
        "etcd": (etcd, etcd.EtcdConfig(), 8192),
    }
    built = {}
    for name, (mod, cfg, seeds) in cases.items():
        ecfg = mod.engine_config(cfg, time_limit_ns=int(SIM_SECONDS * 1e9))
        wl = mod.workload(cfg)
        warm = core.run_sweep(wl, ecfg, _fresh(seeds))  # compile/warm
        int(warm.ctr.sum())
        built[name] = (mod, wl, ecfg, seeds)

    times = {name: [] for name in cases}
    best_final = {}

    def one_round(discard: bool = False) -> None:
        for name, (mod, wl, ecfg, seeds) in built.items():
            t0 = walltime.perf_counter()
            final = core.run_sweep(wl, ecfg, _fresh(seeds))
            int(final.ctr.sum())
            t = walltime.perf_counter() - t0
            if discard:
                continue
            if not times[name] or t < min(times[name]):
                best_final[name] = final
            times[name].append(t)

    one_round(discard=True)  # warm-up discard (see docstring)
    for _rep in range(REPS):
        one_round()
    extra = 0
    while (
        max(_spread_best3(ts) for ts in times.values()) >= SPREAD_GATE
        and extra < MAX_EXTRA_ROUNDS
    ):
        one_round()
        extra += 1

    def line(name, extra_fields):
        mod, _wl, _ecfg, seeds = built[name]
        run_s = min(times[name])
        s = mod.sweep_summary(best_final[name])
        out = {
            "seeds": seeds,
            "seeds_per_sec": round(seeds / run_s, 1),
            "events_per_sec": round(s["events_total"] / run_s, 1),
            "reps": len(times[name]),
            "spread": _spread_best3(times[name]),
            "spread_all": _spread(times[name]),
            "spread_ok": _spread_best3(times[name]) < SPREAD_GATE,
            "violations": s["violations"],
        }
        out.update((k, s[src]) for k, src in extra_fields)
        return out

    return (
        line("kafka", (("broker_crashes", "crashes"), ("records_consumed", "fetched"))),
        line("etcd", (("partitions", "partitions"), ("lease_expiries", "expiries"))),
    )


def bench_carryover() -> dict:
    """The carry-over leg standalone (``--carryover``): re-run exactly
    the two measurements earlier rounds left flagged — the kafka/etcd
    interleaved spread gate (``spread_ok`` must hold round over round)
    and the auto-picked chunk-size batch-curve point (the auto pick
    must stay at or left of the occupancy knee) — without paying for
    the full pipeline. Recorded per round in ``BENCH_rNN.json``."""
    global CURVE
    from madsim_tpu.engine import core  # noqa: F401  (x64 setup)
    from madsim_tpu.models import raft

    cfg = raft.RaftConfig(num_nodes=5, crashes=1)
    ecfg = raft.engine_config(cfg, time_limit_ns=int(SIM_SECONDS * 1e9))
    wl = raft.workload(cfg)
    saved = CURVE
    CURVE = ()  # bench_curve unions in the auto pick: one point, flagged
    try:
        curve = bench_curve(wl, ecfg, raft)
    finally:
        CURVE = saved
    kafka_line, etcd_line = bench_secondary_models()
    return {
        "auto_chunk_point": next(p for p in curve if p["auto_chunk"]),
        "kafka": kafka_line,
        "etcd": etcd_line,
        "spread_gate": SPREAD_GATE,
        "spread_ok": kafka_line["spread_ok"] and etcd_line["spread_ok"],
        "device": _device(),
    }


def bench_steering() -> dict:
    """The self-steering scheduler A/B (``--steering``): bandit vs
    uniform family allocation at a MATCHED deterministic device-event
    budget, per target. Both policies run the same loop (run_steered),
    same families, same seeds-per-round, same campaign seed — the only
    difference is the pick rule (UCB + kill/escalate vs round-robin),
    so every delta is attributable to allocation. Per cell: distinct
    triage fingerprints (the acceptance metric — bandit/uniform >= 1.5x
    on the raft gate), covered coverage bits, events spent until the
    first violating candidate (the time-to-first-bug analogue in the
    budget currency — deterministic, unlike wall), decision count, and
    wall seconds for context. The etcd cell runs its checker-backed
    triage (history=True) and is EXPECTED to tie on fingerprints: one
    reachable flavor saturates both policies, and its delta shows up in
    coverage bits instead — reported, not gated."""
    from madsim_tpu.explore import CampaignConfig, SteerConfig, run_steered
    from madsim_tpu.explore.targets import etcd_steer_gate, steer_gate

    def cell(target, base, policy, budget, history):
        ccfg = CampaignConfig(
            rounds=999, seeds_per_round=STEER_SEEDS_PER_ROUND,
            campaign_seed=STEER_CAMPAIGN_SEED, max_recorded_seeds=8,
            scheduler=policy,
        )
        scfg = SteerConfig(
            scheduler=policy, families=STEER_FAMILIES,
            escalate_seeds=STEER_ESCALATE_SEEDS,
            kill_plays=STEER_KILL_PLAYS, budget_events=budget,
        )
        t0 = walltime.perf_counter()
        res = run_steered(target, base, ccfg, scfg, history=history)
        wall = walltime.perf_counter() - t0
        events_to_first_bug = None
        spent = 0
        for r in res.records:
            spent += r.get("events_total", 0)
            if r.get("violations", 0) > 0:
                events_to_first_bug = spent
                break
        kinds = [d["kind"] for d in res.decisions]
        return {
            "fingerprints": len(res.fingerprints),
            "fingerprint_list": res.fingerprints,
            "coverage_bits": sum(int(w).bit_count() for w in res.coverage_map),
            "events_to_first_bug": events_to_first_bug,
            "spent_events": res.spent_events,
            "decisions": kinds.count("decide"),
            "kills": kinds.count("kill"),
            "escalations": kinds.count("escalate"),
            "wall_s": round(wall, 2),
        }

    def ab(name, target, base, budget, history):
        bandit = cell(target, base, "bandit", budget, history)
        uniform = cell(target, base, "uniform", budget, history)
        ratio = (
            round(bandit["fingerprints"] / uniform["fingerprints"], 2)
            if uniform["fingerprints"] else None
        )
        return {
            "target": name,
            "budget_events": budget,
            "bandit": bandit,
            "uniform": uniform,
            "fingerprint_ratio": ratio,
            "coverage_ratio": round(
                bandit["coverage_bits"] / uniform["coverage_bits"], 2
            ) if uniform["coverage_bits"] else None,
        }

    rt, rb = steer_gate(smoke=True)
    et, eb = etcd_steer_gate(smoke=True)
    raft = ab("raft-amnesia", rt, rb, STEER_RAFT_BUDGET, False)
    etcd = ab("etcd-stale", et, eb, STEER_ETCD_BUDGET, True)
    return {
        "families": len(STEER_FAMILIES),
        "seeds_per_round": STEER_SEEDS_PER_ROUND,
        "campaign_seed": STEER_CAMPAIGN_SEED,
        "raft": raft,
        "etcd": etcd,
        # the acceptance gate rides on the raft cell; etcd saturates
        "ratio_ok": (raft["fingerprint_ratio"] or 0) >= 1.5,
        "device": _device(),
    }


def bench_wire_load() -> dict:
    """The async serving core under production-scale load
    (``--wire-load``): >=1k concurrent genuine-protocol clients (Kafka
    producers + consumer groups, S3 REST incl. multipart, framed etcd)
    against one sim-backed cluster, gray failure injected mid-run,
    LogSpec/S3Spec/KVSpec-checked histories, kafka+s3 transcripts
    replayed byte for byte, p50/p99 from the server-side histograms.
    The spread gate runs over WIRE_REPS smaller reps on the dominant
    op's p50 (kafka Fetch): latency SLOs come from the server-side
    histograms and are scheduling-stable, whereas raw ops/s on a
    shared single-core box swings with wall-clock contention — it is
    reported (``throughput_spread``) but not gated."""
    import subprocess

    script = os.path.join(os.path.dirname(__file__), "scripts",
                          "wire_load.py")

    def run(extra):
        with tempfile.NamedTemporaryFile(suffix=".json") as f:
            # host-only child: kept off the chip this process holds
            proc = subprocess.run(
                [sys.executable, script, "--report", f.name, *extra],
                capture_output=True, text=True, timeout=900,
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
            )
            try:
                report = json.load(open(f.name))
            except (json.JSONDecodeError, OSError):
                report = {}
        return proc.returncode, report

    rc, full = run([])
    reps = []
    for _ in range(WIRE_REPS):
        rep_rc, rep = run([
            "--clients", str(WIRE_REP_CLIENTS),
            "--run-secs", str(WIRE_REP_SECS),
            "--min-clients", str(WIRE_REP_CLIENTS // 2),
        ])
        fetch = (rep.get("slo", {}).get("kafka_api_seconds", {})
                 .get("Fetch", {}))
        reps.append({
            "rc": rep_rc,
            "throughput_ops_s": rep.get("throughput_ops_s", 0.0),
            "total_ops": rep.get("total_ops", 0),
            "fetch_p50_ms": fetch.get("p50_ms", 0.0),
            "fetch_p99_ms": fetch.get("p99_ms", 0.0),
        })
    p50s = [r["fetch_p50_ms"] for r in reps if r["fetch_p50_ms"]]
    spread = _spread(p50s) if p50s else 1.0
    rates = [r["throughput_ops_s"] for r in reps if r["throughput_ops_s"]]
    throughput_spread = _spread(rates) if rates else 1.0

    def pcts(hist_name):
        legs = full.get("slo", {}).get(hist_name, {})
        return {
            k: {"count": v["count"], "p50_ms": v["p50_ms"],
                "p99_ms": v["p99_ms"]}
            for k, v in sorted(legs.items())
        }

    return {
        "rc": rc,
        "clients": full.get("clients", 0),
        "workers": full.get("workers", 0),
        "elapsed_s": full.get("elapsed_s", 0),
        "total_ops": full.get("total_ops", 0),
        "throughput_ops_s": full.get("throughput_ops_s", 0),
        "peak_open_conns": full.get("peak_open_conns", 0),
        "errors": full.get("stats", {}).get("errors", -1),
        "histories_ok": full.get("histories_ok", False),
        "replay_ok": full.get("replay_ok", False),
        "chaos": full.get("chaos", {}),
        "gate_failures": full.get("gate_failures", ["no report"]),
        "kafka_slo": pcts("kafka_api_seconds"),
        "s3_slo": pcts("s3_api_seconds"),
        "etcd_slo": pcts("etcd_api_seconds"),
        "rep_clients": WIRE_REP_CLIENTS,
        "reps": reps,
        "spread": spread,
        "throughput_spread": throughput_spread,
        "spread_gate": SPREAD_GATE,
        "spread_ok": spread < SPREAD_GATE and all(
            r["rc"] == 0 for r in reps
        ),
        "ok": rc == 0 and spread < SPREAD_GATE,
    }


def main() -> None:
    from madsim_tpu.engine import core  # noqa: F401  (x64 setup)
    from madsim_tpu.models import raft

    cfg = raft.RaftConfig(num_nodes=5, crashes=1)
    ecfg = raft.engine_config(cfg, time_limit_ns=int(SIM_SECONDS * 1e9))
    wl = raft.workload(cfg)

    # host tier first: measured before device churn (GC/allocator
    # pressure from the TPU runs costs it ~2x)
    host = bench_host()
    host_rate = host["seeds_per_sec"]
    curve = bench_curve(wl, ecfg, raft)
    big = bench_100k(wl, ecfg, raft)
    recovery = bench_recovery(wl, raft)
    recovery.update(bench_recovery_pipelined())
    cross = bench_cross_backend(wl, ecfg)
    kafka_line, etcd_line = bench_secondary_models()
    checked = bench_checked_sweep()
    campaign = bench_campaign()
    streaming = bench_streaming()
    telemetry = bench_telemetry()

    # HEADLINE = the chunked 131k sweep: the production pattern, and at
    # ~3 s of device work per rep the least noisy figure
    _emit(
        {
            "metric": "madraft_sweep_seeds_per_sec",
            "value": big["seeds_per_sec"],
            "unit": "seeds/s",
            "vs_baseline": round(big["seeds_per_sec"] / host_rate, 1),
            "headline_note": (
                f"chunked {BIG_TOTAL}-seed sweep ({BIG_CHUNK}-seed "
                f"chunks), min of {REPS} full passes; spread "
                f"{big['spread']}. Curve points below are min-of-"
                f"{REPS} interleaved reps with per-point spread."
            ),
            "baseline": {
                "name": (
                    "host-tier single-thread executor, compiled C core "
                    "(this repo, native/simloop.c), min of "
                    f"{REPS} passes"
                ),
                "seeds_per_sec": host_rate,
                "spread": host["spread"],
                "reference_note": (
                    "the Rust reference publishes no benchmark numbers "
                    "(BASELINE.md) and no Rust toolchain exists in this "
                    "image to measure it. Round 4 compiled the host "
                    "executor's hot loop (ready queue, timer heap, "
                    "futures, context swap) to C — 3.3x over the "
                    "round-3 pure-Python tier (37 -> ~120 seeds/s), "
                    "closing most of the 'compiled executor' gap; user "
                    "coroutine bodies still run in CPython, so read "
                    "vs_baseline as 'vs this repo's own host tier'"
                ),
            },
            "events_per_sec": big["events_per_sec"],
            "batch_curve": curve,
            "auto_chunk": {
                "chunk_size": core.pick_chunk_size(wl, ecfg),
                "state_bytes_per_seed": core.state_bytes_per_seed(
                    wl, ecfg
                ),
            },
            "sweep_100k": big,
            "checked_sweep": checked,
            "campaign": campaign,
            "streaming": streaming,
            "telemetry": telemetry,
            "recovery_e2e": recovery,
            "cross_backend": cross,
            "kafka": kafka_line,
            "etcd": etcd_line,
            "device": _device(),
        }
    )


SMOKE = False


def _emit(result: dict) -> None:
    """Print a result line. A ``--smoke`` run (CPU rehearsal) prints only
    which keys each leg produced: its numbers are not device metrics."""
    if SMOKE:
        result = {
            "smoke": True, "metric": result.get("metric"),
            "platform": jax.devices()[0].platform,
            "keys": {k: sorted(v) if isinstance(v, dict) else None
                     for k, v in sorted(result.items())},
        }
    print(json.dumps(result))


def _smoke() -> None:
    """Shrink every knob so the full pipeline (host tier, curve, chunked
    sweep, recovery, cross-backend parity, kafka, etcd) runs in ~a minute
    — the CI/Make smoke target. Numbers are meaningless; the exit code
    and the JSON shape are the point."""
    global CURVE, BIG_TOTAL, BIG_CHUNK, HOST_SEEDS, REPS, SIM_SECONDS
    global PARITY_SEEDS, CHECKED_TOTAL, CHECKED_CHUNK, CHECKED_SIM_SECONDS
    global CHECKED_REPS, NAIVE_SEEDS, CHECK_WORKERS, PIPE_SEEDS, PIPE_CHUNK
    global CAMPAIGN_K, CAMPAIGN_SEEDS, CAMPAIGN_REPS, CAMPAIGN_SIM_SECONDS
    global STREAM_CURVE, STREAM_CHUNK, STREAM_POOL, STREAM_REPS
    global STREAM_SIM_SECONDS, STREAM_ROUND_STEPS, STREAM_MAX_STEPS
    global TELEM_SEEDS, TELEM_CHUNK, TELEM_REPS, TELEM_SIM_SECONDS, SMOKE
    SMOKE = True
    # shrink the auto-picked curve point too: the default 128 MiB budget
    # would land it at 16k lanes — ~45 s of CPU sweeps in a smoke run
    os.environ.setdefault("MADSIM_CHUNK_BUDGET_BYTES", str(8 << 20))
    CURVE = (64, 128)
    BIG_TOTAL = 256
    BIG_CHUNK = 128
    HOST_SEEDS = 2
    REPS = 2
    SIM_SECONDS = 0.5
    PARITY_SEEDS = 256
    CHECKED_TOTAL = 256
    CHECKED_CHUNK = 128
    CHECKED_SIM_SECONDS = 0.5
    CHECKED_REPS = 1
    NAIVE_SEEDS = 64
    CHECK_WORKERS = 2
    PIPE_SEEDS = 128
    PIPE_CHUNK = 64
    CAMPAIGN_K = 4
    CAMPAIGN_SEEDS = 32
    CAMPAIGN_REPS = 1
    CAMPAIGN_SIM_SECONDS = 0.5
    STREAM_CURVE = (64, 128)
    STREAM_CHUNK = 32
    STREAM_POOL = 16
    STREAM_ROUND_STEPS = 128
    STREAM_REPS = 1
    STREAM_SIM_SECONDS = 0.3
    STREAM_MAX_STEPS = 2_000
    TELEM_SEEDS = 128
    TELEM_CHUNK = 64
    TELEM_REPS = 2
    TELEM_SIM_SECONDS = 0.3


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        _smoke()
    else:
        from madsim_tpu.engine.compiles import use_compile_cache

        if jax.devices()[0].platform != "tpu":
            raise SystemExit(
                f"bench.py: no TPU (JAX found {jax.devices()[0].platform}); "
                "--smoke is the CPU rehearsal"
            )
        use_compile_cache()
    if "--campaign" in sys.argv:
        # the campaign leg standalone
        _emit({"metric": "campaign_leg", **bench_campaign()})
    elif "--streaming" in sys.argv:
        # the streaming leg standalone (the >=1x-at-every-batch-size
        # acceptance figure, incl. the 65,536 sag point)
        _emit({"metric": "streaming_leg", **bench_streaming()})
    elif "--telemetry" in sys.argv:
        # the telemetry-overhead leg standalone (the <=3% gate on the
        # streaming checked-sweep path)
        _emit({"metric": "telemetry_leg", **bench_telemetry()})
    elif "--checked" in sys.argv:
        # the checked-sweep leg standalone (checked vs its same-run
        # unchecked twin; the <=2x checked_over_unchecked acceptance
        # figure at CHECKED_TOTAL seeds)
        _emit({"metric": "checked_leg", **bench_checked_sweep()})
    elif "--steering" in sys.argv:
        # the steering A/B standalone (bandit vs uniform at a matched
        # device-event budget; the >=1.5x fingerprint acceptance figure
        # on the raft gate, coverage-bit delta on the saturated etcd one)
        _emit({"metric": "steering_leg", **bench_steering()})
    elif "--wire-load" in sys.argv:
        # the async-core serving leg standalone (>=1k-client SLO gate,
        # docs/wire.md; histories + replay checked in the subprocess)
        _emit({"metric": "wire_load_leg", **bench_wire_load()})
    elif "--carryover" in sys.argv:
        # the flagged-legs re-run (kafka/etcd spread gate + auto_chunk
        # curve point)
        _emit({"metric": "carryover_leg", **bench_carryover()})
    else:
        main()
