"""Smoke run of the device tier's main path on one TPU chip.

    python chip_smoke.py             # one chip: the phases below
    python chip_smoke.py --chips 4   # four chips: the sharded path only

Each phase drives the library through the entry points a user calls, at
the size users run, checks its result, and prints one JSON line. A
one-chip phase first runs a warm-up pass over other seeds (``WARM_SEED0``
up), which compiles, and then the measured pass, which should compile
nothing; the four-chip phase times each side once, compiles included.
Every timed block reports its wall seconds and the seconds and count of
XLA compiles in it (JAX's trace, lowering and backend compile spans).
``"on"`` names the backend the work ran on. This is a smoke run, not a
benchmark.

1. ``device``: the first device must be a TPU. There is no CPU branch.
2. ``raft_sweep``: the 5-node raft config with one crash, 3 virtual
   seconds per seed (bench.py's headline, BASELINE.md config #5), over
   131,072 seeds through ``core.run_sweep_chunked`` at the auto-picked
   chunk: no safety violation, commits made, peak device bytes.
3. ``cpu_parity``: 4,096 of those seeds on the TPU and again on the CPU
   backend, every ``EngineState`` leaf equal, plus a traced CPU replay
   of one seed equal to its TPU lane (``core.cpu_parity``).
4. ``checked_sweep``: the etcd history workload with the seeded
   stale-read bug over 32,768 seeds through ``oracle.screen
   .checked_sweep`` (auto chunk, so at least four chunks overlap with
   the 4-worker WGL pool): the bug is found, the clean config stays
   quiet, and ``driver="stream"`` gives byte-identical report JSON.

``--chips 4`` runs only the sharded path and what it is compared with:
the same checked sweep over 65,536 seeds on a 4-chip and a 1-chip mesh
(reports equal less their chunk-dependent counters), and sharded
chunked raft finals equal to a one-chip sweep seed for seed, with every
chip's peak memory showing its share.

The last line, printed only when every phase passed, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure raises and exits non-zero. Nothing here touches JAX at
import time: the checker pool's forkserver re-imports ``__main__``, and
its workers stay JAX-free.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

RAFT_SEEDS = 131_072
PARITY_SEEDS = 4_096
CHECKED_SEEDS = 32_768
CHECK_WORKERS = 4
MESH_CHIPS = 4
MESH_SEEDS = 65_536
RAFT_SIM_NS = 3_000_000_000
ETCD_SIM_NS = 2_000_000_000  # hist_slots=256 is sized for a 2 s horizon
ETCD_MAX_STEPS = 20_000
WARM_SEED0 = 1 << 30  # warm-up seeds sit far above every measured range

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_spans: list = []  # (start, end) of every compile step, host clock


def _on_span(event: str, start: float, end: float, **_kw) -> None:
    if event in _COMPILE_EVENTS:
        _spans.append((event, start, end))


def _union_s(spans) -> float:
    """Seconds covered by the union of intervals: tracing nests (an
    inner jit is traced inside its caller) and threads overlap."""
    total, reach = 0.0, float("-inf")
    for _event, start, end in sorted(spans, key=lambda s: s[1]):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


@contextlib.contextmanager
def _timed(out: dict):
    """Fill ``out`` with the block's wall seconds and the seconds and
    number of XLA compiles in it. Dispatch is asynchronous, so a compile
    can overlap device work and wall minus compile is no run time: the
    phases time a warm-up pass apart from the measured one instead."""
    import jax

    jax.monitoring.register_event_time_span_listener(_on_span)
    n0, t0 = len(_spans), time.perf_counter()
    try:
        yield out
    finally:
        jax.monitoring.unregister_event_time_span_listener(_on_span)
    wall = time.perf_counter() - t0
    out["wall_s"] = wall
    out["compile_s"] = _union_s(_spans[n0:])
    out["compiles"] = sum(e == _COMPILE_EVENTS[-1] for e, _s, _e in _spans[n0:])


def _require(ok, detail) -> None:
    """A failed check raises (unlike ``assert``, it survives ``-O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {detail}")


def _emit(line: dict) -> dict:
    print(json.dumps(line, sort_keys=True), flush=True)
    return line


def _raft():
    from madsim_tpu.models import raft

    cfg = raft.RaftConfig(num_nodes=5, crashes=1)
    return raft, raft.workload(cfg), raft.engine_config(cfg, time_limit_ns=RAFT_SIM_NS)


def _etcd(bug: bool):
    from madsim_tpu.models import etcd

    cfg = etcd.EtcdConfig(hist_slots=256, bug_stale_read=bug)
    ecfg = etcd.engine_config(cfg, time_limit_ns=ETCD_SIM_NS, max_steps=ETCD_MAX_STEPS)
    return etcd, etcd.workload(cfg), ecfg


def _peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def device_check() -> dict:
    import jax

    from madsim_tpu.engine.compiles import use_compile_cache

    dev = jax.devices()[0]
    return _emit({
        "phase": "device", "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "compile_cache": use_compile_cache(),
    })


def raft_sweep(seeds: int = RAFT_SEEDS, chunk_size=None) -> dict:
    import jax
    import jax.numpy as jnp

    from madsim_tpu.engine import core

    raft, wl, ecfg = _raft()
    chunk = chunk_size or core.pick_chunk_size(wl, ecfg)

    def run(seed0):  # the summary's host readback bounds completion
        seed_vec = jnp.arange(seed0, seed0 + seeds, dtype=jnp.int64)
        return raft.sweep_summary(
            core.run_sweep_chunked(wl, ecfg, seed_vec, chunk_size=chunk)
        )

    warm = {}
    line = {"phase": "raft_sweep", "on": jax.default_backend(), "seeds": seeds,
            "chunk_size": chunk, "warmup": warm}
    with _timed(warm):
        run(WARM_SEED0)
    with _timed(line):
        summary = run(0)
    _require(summary["seeds"] == seeds, summary)
    _require(summary["violations"] == 0, summary)
    _require(summary["commits_total"] > 0, summary)
    _require(summary["overflow_seeds"] == 0, summary)
    line["summary"] = summary
    line["peak_bytes_in_use"] = _peak_bytes(jax.devices()[0])
    return _emit(line)


def cpu_parity(seeds: int = PARITY_SEEDS) -> dict:
    import jax
    import jax.numpy as jnp

    from madsim_tpu.engine import core

    _raft_mod, wl, ecfg = _raft()
    line = {"phase": "cpu_parity", "on": f"{jax.default_backend()} vs cpu"}
    with _timed(line):
        line.update(core.cpu_parity(wl, ecfg, jnp.arange(seeds, dtype=jnp.int64)))
    _require(line["leaves_equal"], line)
    _require(line["traced_replay_equal"], line)
    return _emit(line)


def _checked(bug: bool, seeds: int, chunk_size, workers: int, seed0: int = 0,
             **kw) -> dict:
    import jax.numpy as jnp

    from madsim_tpu.oracle.screen import checked_sweep

    etcd, wl, ecfg = _etcd(bug)
    return checked_sweep(
        wl, ecfg, jnp.arange(seed0, seed0 + seeds, dtype=jnp.int64),
        etcd.history_spec(), etcd.sweep_summary, chunk_size=chunk_size,
        workers=workers, **kw,
    )


def checked_sweep(seeds: int = CHECKED_SEEDS, chunk_size=None,
                  workers: int = CHECK_WORKERS) -> dict:
    import jax

    from madsim_tpu.engine import core

    _mod, wl, ecfg = _etcd(True)
    chunk = chunk_size or core.pick_chunk_size(wl, ecfg)
    _require(seeds >= 4 * chunk, "fewer than four chunks: nothing overlaps")
    line = {"phase": "checked_sweep", "on": jax.default_backend(), "seeds": seeds,
            "chunk_size": chunk, "workers": workers}
    timings = {}
    for name, bug, driver in (("buggy", True, "chunked"),
                              ("clean", False, "chunked"),
                              ("stream", True, "stream")):
        warm, run = {}, {}
        with _timed(warm):  # one chunk compiles every program of the run
            _checked(bug, chunk, chunk, workers, seed0=WARM_SEED0, driver=driver)
        with _timed(run):
            line[name] = _checked(bug, seeds, chunk, workers, driver=driver)
        timings[name] = {"warmup": warm, "run": run}
    buggy, clean = line["buggy"], line["clean"]
    _require(buggy["hist_violations"] > 0, buggy)
    _require(buggy["violations"] == 0, buggy)  # the stale read is a history bug
    _require(clean["hist_suspects"] == 0 and clean["hist_violations"] == 0, clean)
    line["stream_bytes_equal"] = (
        json.dumps(line.pop("stream"), sort_keys=True)
        == json.dumps(buggy, sort_keys=True)
    )
    _require(line["stream_bytes_equal"], "stream and chunked reports differ")
    line["timing"] = timings
    for name in ("buggy", "clean"):
        r = line[name]
        line[name] = {k: r[k] for k in ("hist_screened", "hist_suspects",
                                        "hist_unique", "hist_violations",
                                        "violations", "events_total")}
    return _emit(line)


def sharded(devices, seeds: int = MESH_SEEDS, raft_chunk=None, etcd_chunk=None,
            workers: int = CHECK_WORKERS) -> dict:
    """The sharded path on ``devices`` against one device, same seeds.
    Chunk sizes are per device (auto-picked when None)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from madsim_tpu import parallel
    from madsim_tpu.engine import core
    from madsim_tpu.oracle.screen import chunk_invariant

    n = len(devices)
    mesh, one = parallel.seed_mesh(devices), parallel.seed_mesh(devices[:1])
    line = {"phase": "sharded", "on": devices[0].platform, "devices": n,
            "seeds": seeds}

    _raft_mod, wl, ecfg = _raft()
    raft_chunk = raft_chunk or core.pick_chunk_size(wl, ecfg)
    seed_vec = jnp.arange(seeds, dtype=jnp.int64)
    with _timed(t_mesh := {}):
        big = parallel.run_sweep_sharded_chunked(
            wl, ecfg, seed_vec, mesh, chunk_per_device=raft_chunk
        )
        ctr, now = np.asarray(big.ctr), np.asarray(big.now_ns)
    # first device work of the process: each chip's peak is its own share
    peaks = [_peak_bytes(d) for d in devices]
    line["raft_peak_bytes_per_device"] = peaks
    if peaks[0] is not None:
        _require(min(peaks) * 2 > max(peaks), f"uneven placement: {peaks}")
    with jax.default_device(devices[0]), _timed(t_one := {}):
        ref = core.run_sweep_chunked(wl, ecfg, seed_vec, chunk_size=raft_chunk)
        ref_ctr, ref_now = np.asarray(ref.ctr), np.asarray(ref.now_ns)
    line["raft_finals_equal"] = bool(
        np.array_equal(ctr, ref_ctr) and np.array_equal(now, ref_now)
    )
    _require(line["raft_finals_equal"], "sharded raft finals differ from one chip")
    line["raft_timing"] = {f"{n}_chips": t_mesh, "1_chip": t_one}

    reports, timing = {}, {}
    for label, m in ((f"{n}_chips", mesh), ("1_chip", one)):
        with _timed(t := {}):
            reports[label] = _checked(True, seeds, None, workers, mesh=m,
                                      chunk_per_device=etcd_chunk)
        timing[label] = t
    a, b = (json.dumps(chunk_invariant(r), sort_keys=True) for r in reports.values())
    line["checked_bytes_equal"] = a == b
    _require(line["checked_bytes_equal"], "mesh sizes disagree on the checked report")
    _require(all(r["hist_violations"] > 0 for r in reports.values()), reports)
    line["checked_timing"] = timing
    line["checked_unique"] = {k: r["hist_unique"] for k, r in reports.items()}
    line["hist_violations"] = reports["1_chip"]["hist_violations"]
    return _emit(line)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, MESH_CHIPS), default=1,
                    help=f"{MESH_CHIPS}: run only the sharded path")
    args = ap.parse_args(argv)

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {platform})")
    dev = device_check()
    if args.chips == 1:
        raft_sweep()
        cpu_parity()
        checked_sweep()
    else:
        devices = jax.devices()
        if len(devices) < MESH_CHIPS:
            raise SystemExit(
                f"chip_smoke --chips {MESH_CHIPS}: {len(devices)} TPU device(s)"
            )
        sharded(devices[:MESH_CHIPS])
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"], "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
