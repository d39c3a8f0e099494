"""Mesh construction + sharded sweep driver.

Pure data parallelism over seeds (no cross-seed state exists), expressed
with ``shard_map`` so the collective structure is explicit and auditable:

- per-device: ``vmap``'d engine step over the local seed shard;
- cross-device: one ``psum`` of the local live-seed count per loop
  iteration — the global termination signal (the sharded analogue of the
  batch-level ``jnp.any(~done)`` in ``engine.core._run``).

On a multi-host slice the same code spans DCN automatically (the mesh just
contains all devices); seeds never migrate between devices, so there is no
resharding traffic to place.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.core import EngineConfig, EngineState, Workload, init_sweep, step_one

SEED_AXIS = "seeds"


def seed_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D mesh over all (or the given) devices, axis ``"seeds"``."""
    import numpy as np

    devs = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devs, (SEED_AXIS,))


def shard_seeds(mesh: Mesh, seeds: jnp.ndarray) -> jnp.ndarray:
    """Place a seed vector sharded over the mesh's seed axis (the batch
    size must divide the mesh size)."""
    sharding = NamedSharding(mesh, P(SEED_AXIS))
    return jax.device_put(jnp.asarray(seeds, jnp.int64), sharding)


def sharded_step(workload: Workload, cfg: EngineConfig, mesh: Mesh):
    """Build an explicit n-step sharded step, jitted: advances every
    local seed ``n_steps`` events and returns the global number of
    still-live seeds via ``psum``. Jitted because an eager ``shard_map``
    call on a state whose leaves carry mixed shardings (``init_sweep``
    output: some leaves single-device, some on the mesh) is refused by
    JAX 0.9 ("Unexpected XLA sharding override").

    Kept as the multichip dryrun/CI entry point (__graft_entry__ calls it
    with a fixed n_steps to demonstrate one sharded step + collective);
    the production sweep path is ``run_sweep_sharded``, whose flat
    per-device loop avoids the ~9x nested-device-loop penalty this
    chunked shape pays on TPU."""

    def local_step(state: EngineState, n_steps):
        # finished seeds are frozen no-ops, so over-stepping is harmless
        state = jax.lax.fori_loop(
            0,
            n_steps,
            lambda _, s: jax.vmap(partial(step_one, workload, cfg))(s),
            state,
        )
        live = jnp.sum(~state.done, dtype=jnp.int32)
        return state, jax.lax.psum(live, SEED_AXIS)

    # replication checking off: lax.switch branches mix mesh-constant and
    # mesh-varying outputs (e.g. a constant event-kind vector vs a
    # data-dependent one), which the varying-manual-axes checker rejects
    # even though the program is replication-safe (communication happens
    # only in the psum below).
    return jax.jit(
        jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P(SEED_AXIS), P()),
            out_specs=(P(SEED_AXIS), P()),
            check_vma=False,
        )
    )


@lru_cache(maxsize=64)
def _sharded_run(workload: Workload, cfg: EngineConfig, mesh: Mesh):
    """Cached jitted whole-sweep program for (workload, cfg, mesh) — a
    fresh wrapper per call would retrace and recompile every invocation."""

    def device_run(state: EngineState) -> EngineState:
        def cond(carry):
            state, iters = carry
            live = jax.lax.psum(
                jnp.sum(~state.done, dtype=jnp.int32), SEED_AXIS
            )
            return (live > 0) & (iters < cfg.max_steps)

        def body(carry):
            state, iters = carry
            return jax.vmap(partial(step_one, workload, cfg))(state), iters + 1

        state, _ = jax.lax.while_loop(
            cond, body, (state, jnp.zeros((), jnp.int64))
        )
        return state

    return jax.jit(
        jax.shard_map(
            device_run,
            mesh=mesh,
            in_specs=P(SEED_AXIS),
            out_specs=P(SEED_AXIS),
            check_vma=False,
        )
    )


def shard_params(mesh: Mesh, params):
    """Place a per-lane spec-as-data pytree (engine/faults.py) sharded
    over the mesh's seed axis — every leaf's leading axis is the lane
    batch, exactly like ``shard_state``'s contract."""
    sharding = NamedSharding(mesh, P(SEED_AXIS))
    return jax.device_put(params, sharding)


def run_sweep_sharded(
    workload: Workload, cfg: EngineConfig, seeds, mesh: Optional[Mesh] = None,
    params=None,
) -> EngineState:
    """Run a seed sweep sharded over a device mesh; bit-identical to the
    single-device ``engine.run_sweep`` for the same seeds.

    The whole sweep loop lives INSIDE ``shard_map`` — one flat per-device
    ``while_loop`` whose cond psums the live count every step, so all
    devices terminate together. Flat because a nested device loop costs
    ~9x per step on TPU (engine/core.py ``drive``); the per-step psum
    rides ICI and is noise next to a step.

    ``params`` is per-lane spec-as-data (``engine.run_sweep``'s
    contract), sharded alongside the seed axis — its leaves are traced,
    so sweeping a new candidate reuses the one compiled sharded
    program."""
    if mesh is None:
        mesh = seed_mesh()
    seeds = shard_seeds(mesh, seeds)
    # init and loop compile as separate programs (same split as
    # engine.core._run: fusing the init writes pessimizes the loop carry);
    # core._init shares run_sweep's trace cache
    from ..engine.core import _init

    if params is None:
        state = _init(workload, cfg, seeds)
    else:
        state = _init(workload, cfg, seeds, shard_params(mesh, params))
    return _sharded_run(workload, cfg, mesh)(state)


def run_sweep_sharded_chunked(
    workload: Workload,
    cfg: EngineConfig,
    seeds,
    mesh: Optional[Mesh] = None,
    chunk_per_device: int = 16384,
    params=None,
) -> EngineState:
    """Pod-scale composition of the two scaling axes: the seed batch is
    sharded over the mesh AND run as sequential fixed-size chunks of one
    compiled program.

    The ~9x per-lane step-cost cliff above ~16k lanes
    (engine.core.run_sweep_chunked) is a per-chip working-set limit, so
    the right chunk is ``chunk_per_device × mesh size`` lanes. A ragged
    batch is padded with continuation seeds (to the chunk multiple when
    chunking, or just to mesh divisibility for a single small batch) and
    trimmed inside one jitted concat. Bit-identical per seed to
    single-device ``run_sweep``. The returned state keeps O(total seeds)
    device memory — at the million-seed scale merge per-chunk
    ``sweep_summary`` dicts on host instead, as bench.py's bench_100k
    does."""
    from ..engine.core import run_in_chunks

    if mesh is None:
        mesh = seed_mesh()
    n_dev = mesh.devices.size
    if params is None:
        run_chunk = lambda chunk: run_sweep_sharded(  # noqa: E731
            workload, cfg, chunk, mesh
        )
    else:
        run_chunk = lambda chunk, pchunk: run_sweep_sharded(  # noqa: E731
            workload, cfg, chunk, mesh, params=pchunk
        )
    return run_in_chunks(
        run_chunk,
        seeds,
        chunk_per_device * n_dev,
        multiple=n_dev,
        params=params,
    )


def shard_state(mesh: Mesh, state: EngineState) -> EngineState:
    """Place a batched EngineState sharded over the mesh's seed axis
    (every leaf's leading axis is the seed batch, so one PartitionSpec
    covers the whole tree). Used to re-shard a checkpoint-restored state
    onto whatever mesh the resuming process has — the snapshot itself is
    host arrays with no layout, which is what makes a sweep interrupted
    on 8 devices resumable on 1 (checkpoint format v8 carries the
    original layout for chunk-boundary bookkeeping, not for data)."""
    sharding = NamedSharding(mesh, P(SEED_AXIS))
    return jax.device_put(state, sharding)


def resume_sweep_sharded(
    workload: Workload, cfg: EngineConfig, state: EngineState,
    mesh: Optional[Mesh] = None,
) -> EngineState:
    """Continue a (possibly restored) sweep sharded over a mesh until
    every seed finishes — the sharded analogue of
    ``engine.checkpoint.resume_sweep``, bit-identical to it per seed.
    The batch must divide the mesh size."""
    if mesh is None:
        mesh = seed_mesh()
    if int(state.seed.shape[0]) % mesh.devices.size:
        raise ValueError(
            f"cannot resume a {int(state.seed.shape[0])}-lane snapshot on "
            f"a {mesh.devices.size}-device mesh (batch must divide the "
            "mesh; resume on a divisor mesh or unsharded)"
        )
    return _sharded_run(workload, cfg, mesh)(shard_state(mesh, state))


def mesh_layout(mesh: Mesh, chunk_per_device: int) -> dict:
    """The mesh-layout metadata a sharded sweep records in its v8
    checkpoints (``engine.checkpoint.save_sweep(mesh_layout=)``): enough
    to rebuild the GLOBAL chunk boundaries (``chunk_size =
    chunk_per_device × n_dev``) on a resuming process with a different
    device count, so per-chunk checkpoint files keep lining up."""
    return {
        "n_dev": int(mesh.devices.size),
        "chunk_per_device": int(chunk_per_device),
        "chunk_size": int(chunk_per_device) * int(mesh.devices.size),
        "axis": SEED_AXIS,
    }


def run_sweep_sharded_pipelined(
    workload: Workload,
    cfg: EngineConfig,
    seeds,
    summarize,
    *,
    mesh: Optional[Mesh] = None,
    host_work: Optional[Callable] = None,
    screen: Optional[Callable] = None,
    chunk_per_device: Optional[int] = None,
    chunk_size: Optional[int] = None,
    ckpt_dir: Optional[str] = None,
    stop_after: Optional[int] = None,
    resume_from: Optional[Tuple[EngineState, dict]] = None,
    on_chunk: Optional[Callable] = None,
    params=None,
    telemetry=None,
) -> dict:
    """The pipelined checked-sweep driver lifted onto the mesh: chunked
    device sweeps run sharded over all devices (``run_sweep_sharded``),
    the screen/summary programs are enqueued behind each chunk sharded
    the same way, and the host phase (decode, WGL checking, triage) of
    chunk N overlaps the sharded sweep of chunk N+1 exactly as in
    ``engine.checkpoint.run_sweep_pipelined`` — a million-seed checked
    campaign becomes ONE unit of work spanning every chip.

    Chunk sizing: the device-memory knee is PER CHIP, so the global
    chunk is ``chunk_per_device × n_dev`` lanes, with ``chunk_per_device``
    auto-picked from the workload's measured loop-carry footprint
    (``engine.core.pick_chunk_size``) when not given. An explicit
    ``chunk_size`` (global) overrides both; either way the granule is
    rounded up to mesh divisibility.

    Report invariance contract: the merged summary dict is BYTE-IDENTICAL
    across mesh sizes — on 1, 2, 4 and 8 devices — even though the chunk
    boundaries differ (per-chunk summaries are exact integer reductions,
    list fields merge in seed order, and caps compose chunking-invariantly;
    tests/test_parallel.py pins the bytes). Checkpointing composes too:
    per-chunk files carry no mesh identity, and a mid-chunk v8 snapshot
    (``save_sweep(..., inflight=, mesh_layout=mesh_layout(mesh, cpd))``)
    resumes bit-identical on ANY mesh whose size divides the chunk —
    interrupt on 8 devices, resume on 1 (``resume_from=(state, inflight)``,
    with ``chunk_size`` taken from the snapshot's mesh layout).

    ``telemetry`` (``obs.Telemetry`` or None) rides through to the inner
    pipelined driver (chunk/host-phase timing, device/host trace spans)
    and adds the mesh-level view: a ``mesh_devices`` gauge and a
    PER-DEVICE seeds/s gauge sampled at each chunk merge. The per-step
    psum'd live count stays inside the compiled round — surfacing it
    per iteration would put host work on the step path; chunk-granule
    throughput is the out-of-band proxy.
    """
    import time as _time

    from ..engine.checkpoint import run_sweep_pipelined
    from ..engine.core import pick_chunk_size

    if mesh is None:
        mesh = seed_mesh()
    n_dev = int(mesh.devices.size)
    if chunk_size is None:
        if chunk_per_device is None:
            one_lane = (
                None
                if params is None
                else jax.tree.map(lambda a: np.asarray(a)[0], params)
            )
            chunk_per_device = pick_chunk_size(workload, cfg, params=one_lane)
        chunk_size = chunk_per_device * n_dev
    chunk_size = -(-chunk_size // n_dev) * n_dev  # mesh divisibility

    if params is None:
        run_chunk = lambda chunk: run_sweep_sharded(  # noqa: E731
            workload, cfg, chunk, mesh
        )
    else:
        run_chunk = lambda chunk, pchunk: run_sweep_sharded(  # noqa: E731
            workload, cfg, chunk, mesh, params=pchunk
        )
    if telemetry is not None:
        telemetry.gauge(
            "mesh_devices", n_dev, help="devices in the sweep mesh"
        )
        inner_on_chunk = on_chunk
        t_last = [_time.perf_counter()]

        def on_chunk(lo, k, summary):
            now = _time.perf_counter()
            dt, t_last[0] = now - t_last[0], now
            telemetry.gauge(
                "mesh_seeds_per_s_per_device",
                k / max(dt, 1e-9) / n_dev,
                help="chunk-merge throughput divided by device count",
            )
            if inner_on_chunk is not None:
                inner_on_chunk(lo=lo, k=k, summary=summary)

    return run_sweep_pipelined(
        workload,
        cfg,
        seeds,
        summarize,
        host_work=host_work,
        screen=screen,
        chunk_size=chunk_size,
        ckpt_dir=ckpt_dir,
        stop_after=stop_after,
        resume_from=resume_from,
        run_chunk=run_chunk,
        resume_chunk=lambda state: resume_sweep_sharded(
            workload, cfg, state, mesh
        ),
        pad_multiple=n_dev,
        on_chunk=on_chunk,
        params=params,
        telemetry=telemetry,
    )
