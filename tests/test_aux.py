"""Aux subsystems: tokio façade, tracing (sim-identity logs + chrome
trace), and engine sweep checkpoint/resume."""

import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import madsim_tpu as ms
from madsim_tpu import tokio, tracing
from madsim_tpu.engine import checkpoint
from madsim_tpu.engine import core as ecore
from madsim_tpu.models import raft


# -- tokio façade -----------------------------------------------------------


def test_tokio_runtime_aborts_spawned_on_shutdown():
    rt = ms.Runtime(seed=70)

    async def main():
        trt = tokio.runtime.Builder.new_multi_thread().enable_all().build()
        progress = []

        async def worker():
            try:
                while True:
                    await tokio.time.sleep(0.01)
                    progress.append(1)
            finally:
                progress.append("dropped")

        trt.spawn(worker())
        await ms.sleep(0.1)
        assert len(progress) > 3
        trt.shutdown()
        await ms.sleep(0.1)
        assert progress[-1] == "dropped"
        n_after = len(progress)
        await ms.sleep(0.1)
        assert len(progress) == n_after  # really stopped
        with pytest.raises(RuntimeError, match="shut down"):
            trt.spawn(worker())

    rt.block_on(main())


def test_tokio_block_on_is_an_error_in_sim():
    rt = ms.Runtime(seed=71)

    async def main():
        trt = tokio.runtime.Builder().build()
        with pytest.raises(RuntimeError, match="block_on"):
            trt.block_on(None)

    rt.block_on(main())


def test_tokio_reexports_surface():
    # the façade exposes the tokio module layout (lib.rs:38-50)
    assert tokio.sync.channel and tokio.sync.oneshot and tokio.sync.Notify
    assert tokio.time.sleep and tokio.net.Endpoint and tokio.task.spawn


# -- tracing ----------------------------------------------------------------

def test_log_records_carry_sim_identity(caplog):
    rt = ms.Runtime(seed=72)
    logger = logging.getLogger("test.tracing")

    async def main():
        h = ms.current_handle()
        node = h.create_node().name("worker-node").build()

        async def work():
            await ms.sleep(0.5)
            logger.info("hello from the node")

        with caplog.at_level(logging.INFO, logger="test.tracing"):
            caplog.handler.addFilter(tracing.SimContextFilter())
            await node.spawn(work())

    rt.block_on(main())
    rec = next(r for r in caplog.records if "hello" in r.message)
    assert rec.node == "worker-node"
    assert float(rec.sim_time) >= 0.5


def test_chrome_trace_export(tmp_path):
    rt = ms.Runtime(seed=73)
    tracer = tracing.Tracer().install(rt)

    async def main():
        h = ms.current_handle()
        node = h.create_node().name("traced").build()

        async def work():
            for _ in range(3):
                await ms.sleep(0.1)

        await node.spawn(work())

    rt.block_on(main())
    path = tmp_path / "trace.json"
    tracer.save(str(path))
    data = json.loads(path.read_text())
    events = data["traceEvents"]
    polls = [e for e in events if e.get("cat") == "poll"]
    assert len(polls) > 3
    names = {e["args"]["name"] for e in events if e.get("ph") == "M"}
    assert "traced" in names
    # virtual-time timestamps are monotone non-decreasing
    ts = [e["ts"] for e in polls]
    assert ts == sorted(ts)


# -- engine checkpoint/resume ----------------------------------------------


def test_sweep_checkpoint_resume_bit_exact(tmp_path):
    """Pause a sweep mid-flight, save, restore, resume: identical to an
    uninterrupted run."""
    cfg = raft.RaftConfig(num_nodes=3, crashes=1)
    ecfg = raft.engine_config(cfg, queue_capacity=32,
                              time_limit_ns=1_000_000_000, max_steps=8_000)
    wl = raft.workload(cfg)
    seeds = jnp.arange(8, dtype=jnp.int64)

    full = ecore.run_sweep(wl, ecfg, seeds)

    # run ~100 steps by hand, checkpoint, restore, resume
    state = ecore.init_sweep(wl, ecfg, seeds)
    import jax

    stepper = jax.jit(lambda s: ecore.step_batch(wl, ecfg, s))
    for _ in range(100):
        state = stepper(state)
    path = str(tmp_path / "sweep.npz")
    checkpoint.save_sweep(state, path)

    like = ecore.init_sweep(wl, ecfg, seeds)
    restored = checkpoint.load_sweep(path, like)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        pass  # structural restore is validated by resume equality below

    resumed = checkpoint.resume_sweep(wl, ecfg, restored)
    assert jnp.array_equal(resumed.ctr, full.ctr)
    assert jnp.array_equal(resumed.now_ns, full.now_ns)
    assert jnp.array_equal(resumed.wstate.elections, full.wstate.elections)
    assert jnp.array_equal(resumed.wstate.violation, full.wstate.violation)


def _rewrite_as_v10(src, dst, state):
    """The snapshot at ``src`` as format v10 stored it, written to ``dst``:
    the queue's P payload planes (``leaf_``/``pend_`` entries) stacked
    into one ``[..., Q, P]`` entry at the first plane's index, and every
    later entry numbered P - 1 lower."""
    first, planes = checkpoint._pay_span(state)
    data = dict(np.load(src))
    out = {"__version__": np.asarray(10)}
    for name, arr in data.items():
        prefix = name[:5]
        if prefix not in ("leaf_", "pend_"):
            if name != "__version__":
                out[name] = arr
            continue
        i, sep, suffix = name[5:].partition("__")
        i = int(i)
        if i == first:
            out[name] = np.stack([data[f"{prefix}{first + p}"] for p in range(planes)], -1)
        elif i > first:
            if i >= first + planes:
                out[f"{prefix}{i - planes + 1}{sep}{suffix}"] = arr
        else:
            out[name] = arr
    np.savez_compressed(dst, **out)


def test_v10_checkpoint_resumes_bit_exact(tmp_path):
    """A v10 snapshot, whose queue payload is one stacked leaf, restores
    every leaf bit for bit into the per-word planes and resumes to the
    uninterrupted run's final state."""
    import jax

    cfg = raft.RaftConfig(num_nodes=3, crashes=1)
    ecfg = raft.engine_config(cfg, queue_capacity=32,
                              time_limit_ns=1_000_000_000, max_steps=8_000)
    wl = raft.workload(cfg)
    seeds = jnp.arange(8, dtype=jnp.int64)
    state = ecore.init_sweep(wl, ecfg, seeds)
    stepper = jax.jit(lambda s: ecore.step_batch(wl, ecfg, s))
    for _ in range(100):
        state = stepper(state)
    v11, v10 = str(tmp_path / "v11.npz"), str(tmp_path / "v10.npz")
    checkpoint.save_sweep(state, v11)
    _rewrite_as_v10(v11, v10, state)
    assert "leaf_1__key" in np.load(v10)
    assert len(np.load(v10).files) == len(np.load(v11).files) - raft.PAYLOAD_SLOTS + 1

    restored = checkpoint.load_sweep(v10, ecore.init_sweep(wl, ecfg, seeds))
    assert jax.tree.structure(restored) == jax.tree.structure(state)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
        if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        assert a.dtype == b.dtype and np.array_equal(a, b)
    resumed = checkpoint.resume_sweep(wl, ecfg, restored)
    full = ecore.run_sweep(wl, ecfg, seeds)
    for a, b in zip(jax.tree.leaves(resumed), jax.tree.leaves(full)):
        if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        assert np.array_equal(a, b)


def test_v10_stream_snapshot_resumes_bit_exact(tmp_path):
    """An interrupted stream's v10 snapshot (payload stacked in the pool
    state and in the pending rows) resumes to the uninterrupted result."""
    from madsim_tpu.engine.stream import stream_sweep

    cfg = raft.RaftConfig(num_nodes=3)
    ecfg = raft.engine_config(cfg, time_limit_ns=500_000_000, max_steps=4_000)
    wl = raft.workload(cfg)
    seeds = jnp.arange(24, dtype=jnp.int64)
    kw = dict(chunk_size=8, pool_size=8, round_steps=64)
    full = stream_sweep(wl, ecfg, seeds, raft.sweep_summary, **kw)
    v11, v10 = str(tmp_path / "v11.npz"), str(tmp_path / "v10.npz")
    stream_sweep(wl, ecfg, seeds, raft.sweep_summary, ckpt_path=v11,
                 stop_after_rounds=2, **kw)
    _rewrite_as_v10(v11, v10, ecore.init_sweep(wl, ecfg, seeds[:8]))
    assert int(np.load(v10)["__version__"]) == 10
    resumed = stream_sweep(wl, ecfg, seeds, raft.sweep_summary,
                           resume_from=v10, **kw)
    assert resumed == full


def test_checkpoint_version_mismatch_raises(tmp_path):
    import numpy as np
    import pytest

    cfg = raft.RaftConfig(num_nodes=3)
    ecfg = raft.engine_config(cfg, queue_capacity=32)
    wl = raft.workload(cfg)
    state = ecore.init_sweep(wl, ecfg, jnp.arange(2, dtype=jnp.int64))
    path = str(tmp_path / "old.npz")
    checkpoint.save_sweep(state, path)
    # rewrite with a stale version stamp
    data = dict(np.load(path))
    data["__version__"] = np.asarray(1)
    np.savez_compressed(path, **data)
    with pytest.raises(ValueError, match="version mismatch"):
        checkpoint.load_sweep(path, state)


def test_resumable_chunked_sweep(tmp_path, monkeypatch):
    """Interrupted pod-scale sweeps resume at chunk granularity: completed
    chunks load from their summary files (zero device work), totals match
    an uninterrupted whole-batch run, and a directory from a different
    sweep is rejected instead of silently merged."""
    import madsim_tpu.engine.core as ecore_mod
    from madsim_tpu.engine import checkpoint, core as ecore
    from madsim_tpu.models import raft

    cfg = raft.RaftConfig(num_nodes=3, crashes=1)
    ecfg = raft.engine_config(cfg, time_limit_ns=500_000_000, max_steps=4_000)
    wl = raft.workload(cfg)
    seeds = jnp.arange(22, dtype=jnp.int64)  # 8+8+6: ragged final chunk
    d = str(tmp_path / "ckpts")

    totals = checkpoint.run_sweep_chunked_resumable(
        wl, ecfg, seeds, raft.sweep_summary, d, chunk_size=8
    )
    # ground truth: one whole-batch run (additive keys sum per chunk)
    whole = raft.sweep_summary(ecore.run_sweep(wl, ecfg, seeds))
    assert totals["events_total"] == whole["events_total"]
    assert totals["violations"] == whole["violations"]
    assert totals["queue_high_water"] == whole["queue_high_water"]

    # restart: every chunk must load from disk — no sweep may run
    def boom(*a, **k):
        raise AssertionError("run_sweep called on a fully-checkpointed sweep")

    monkeypatch.setattr(ecore_mod, "run_sweep", boom)
    resumed = checkpoint.run_sweep_chunked_resumable(
        wl, ecfg, seeds, raft.sweep_summary, d, chunk_size=8
    )
    assert resumed == totals
    monkeypatch.undo()

    # partial restart: drop one chunk file, only that chunk re-runs
    files = sorted(p for p in (tmp_path / "ckpts").iterdir() if p.suffix == ".json")
    assert len(files) == 3
    files[1].unlink()
    again = checkpoint.run_sweep_chunked_resumable(
        wl, ecfg, seeds, raft.sweep_summary, d, chunk_size=8
    )
    assert again == totals

    # foreign-sweep guards: different seeds, and same seeds under a
    # different engine config — both must refuse the stale directory
    with pytest.raises(ValueError, match="different sweep"):
        checkpoint.run_sweep_chunked_resumable(
            wl, ecfg, seeds + 1000, raft.sweep_summary, d, chunk_size=8
        )
    other = raft.engine_config(cfg, time_limit_ns=900_000_000, max_steps=4_000)
    with pytest.raises(ValueError, match="different sweep"):
        checkpoint.run_sweep_chunked_resumable(
            wl, other, seeds, raft.sweep_summary, d, chunk_size=8
        )
    with pytest.raises(ValueError, match="chunk_size"):
        checkpoint.run_sweep_chunked_resumable(
            wl, ecfg, seeds, raft.sweep_summary, d, chunk_size=-1
        )

    # a non-contiguous seed vector sharing a chunk's endpoints must not
    # reuse that chunk's summary (guard hashes the full seed array)
    shuffled = np.asarray(seeds).copy()
    shuffled[1], shuffled[2] = shuffled[2], shuffled[1]
    with pytest.raises(ValueError, match="different sweep"):
        checkpoint.run_sweep_chunked_resumable(
            wl,
            ecfg,
            jnp.asarray(shuffled),
            raft.sweep_summary,
            d,
            chunk_size=8,
        )

    # a pre-sha legacy record (endpoints + fingerprint only) still loads
    legacy = json.loads(files[0].read_text())
    del legacy["seeds_sha256"]
    files[0].write_text(json.dumps(legacy))
    assert (
        checkpoint.run_sweep_chunked_resumable(
            wl, ecfg, seeds, raft.sweep_summary, d, chunk_size=8
        )
        == totals
    )


_RAFT3_FINGERPRINT = (
    "madsim_tpu.models.raft._init|(RaftConfig(num_nodes=3, "
    "election_lo_ns=150000000, election_hi_ns=300000000, "
    "heartbeat_ns=50000000, commands=8, cmd_window_ns=4000000000, "
    "cmd_retry_ns=50000000, cmd_max_retries=64, log_cap=32, crashes=1, "
    "crash_window_ns=5000000000, restart_lo_ns=100000000, "
    "restart_hi_ns=1000000000, loss_q32=42949673, lat_lo_ns=1000000, "
    "lat_hi_ns=10000000, buggify_q32=0, history=16, volatile_state=False, "
    "hist_slots=0, faults=None, event_mix=False),)"
    "|(48, 500000000, 4000, 50, 100)|cover137|hist0|emix0"
)


_RAFT5_FINGERPRINT = (
    "madsim_tpu.models.raft._init|(RaftConfig(num_nodes=5, "
    "election_lo_ns=150000000, election_hi_ns=300000000, "
    "heartbeat_ns=50000000, commands=8, cmd_window_ns=4000000000, "
    "cmd_retry_ns=50000000, cmd_max_retries=64, log_cap=32, crashes=1, "
    "crash_window_ns=5000000000, restart_lo_ns=100000000, "
    "restart_hi_ns=1000000000, loss_q32=42949673, lat_lo_ns=1000000, "
    "lat_hi_ns=10000000, buggify_q32=0, history=16, volatile_state=False, "
    "hist_slots=0, faults=None, event_mix=False),)"
    "|(60, 3000000000, 200000, 50, 100)|cover227|hist0|emix0"
)


def test_raft_without_compaction_keeps_its_state_and_fingerprint():
    """Log compaction and command rounds are opt-in: at their defaults
    raft5 (the benchmark's configuration) carries no state leaf of
    them, 8 payload words and 5 event kinds, and its checkpoint
    fingerprint is the one it had before they existed."""
    from functools import partial

    cfg = raft.RaftConfig(num_nodes=5, crashes=1)
    ecfg = raft.engine_config(cfg, queue_capacity=60, time_limit_ns=3_000_000_000,
                              max_steps=200_000, jitter_lo_ns=50, jitter_hi_ns=100)
    wl = raft.workload(cfg)
    state = jax.eval_shape(partial(ecore._init_one, wl, ecfg),
                           jax.ShapeDtypeStruct((), jnp.int64), None)
    assert state.wstate.snap == ()
    assert len(jax.tree.leaves(state)) == 69
    assert ecore.state_bytes_per_seed(wl, ecfg) == 4256
    assert wl.payload_slots == raft.payload_slots(cfg) == raft.PAYLOAD_SLOTS == 8
    assert raft.n_kinds(cfg) == raft.N_KINDS and wl.cover_bits == 227
    assert checkpoint._sweep_fingerprint(wl, ecfg) == _RAFT5_FINGERPRINT
    snap = raft.RaftConfig(num_nodes=3, snapshot_every=4, rounds=2)
    assert raft.payload_slots(snap) == 9 and raft.n_kinds(snap) == 6


def test_checkpoint_fingerprint_is_stable(tmp_path, monkeypatch):
    """The resumable sweep's fingerprint is pinned to a literal, so a
    checkpoint directory written by an earlier build of the same config
    still resumes — all chunks from disk, zero device work — while a
    semantic config change is refused."""
    import madsim_tpu.engine.core as ecore_mod

    cfg = raft.RaftConfig(num_nodes=3, crashes=1)
    ecfg = raft.engine_config(cfg, time_limit_ns=500_000_000, max_steps=4_000)
    wl = raft.workload(cfg)
    assert checkpoint._sweep_fingerprint(wl, ecfg) == _RAFT3_FINGERPRINT
    seeds = jnp.arange(8, dtype=jnp.int64)
    d = str(tmp_path / "ckpts")

    totals = checkpoint.run_sweep_chunked_resumable(
        wl, ecfg, seeds, raft.sweep_summary, d, chunk_size=8
    )

    def boom(*a, **k):
        raise AssertionError("an unchanged config must not re-run the sweep")

    monkeypatch.setattr(ecore_mod, "run_sweep", boom)
    resumed = checkpoint.run_sweep_chunked_resumable(
        wl, ecfg, seeds, raft.sweep_summary, d, chunk_size=8
    )
    assert resumed == totals
    monkeypatch.undo()

    # a SEMANTIC config change must still be refused
    with pytest.raises(ValueError, match="different sweep"):
        checkpoint.run_sweep_chunked_resumable(
            wl,
            ecfg._replace(time_limit_ns=900_000_000),
            seeds,
            raft.sweep_summary,
            d,
            chunk_size=8,
        )
