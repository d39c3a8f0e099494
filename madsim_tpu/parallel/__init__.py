"""Scale-out tier: shard the seed batch over a TPU device mesh.

The reference scales seed sweeps with OS threads — one seed per thread,
``MADSIM_TEST_JOBS`` at a time (madsim/src/sim/runtime/builder.rs:128-149).
The TPU-native axis is the same *logical* axis (seeds are independent —
SURVEY.md §2.3) mapped onto hardware the JAX way: the batched engine state
is sharded over a ``jax.sharding.Mesh`` axis named ``"seeds"`` and the
lockstep step runs under ``shard_map``; the only cross-device communication
is the tiny ``psum`` of live-seed counts that decides sweep termination, so
scaling rides ICI bandwidth-free.
"""

from .mesh import (
    seed_mesh,
    shard_seeds,
    shard_state,
    mesh_layout,
    run_sweep_sharded,
    run_sweep_sharded_chunked,
    run_sweep_sharded_pipelined,
    resume_sweep_sharded,
    sharded_step,
)

__all__ = [
    "seed_mesh",
    "shard_seeds",
    "shard_state",
    "mesh_layout",
    "run_sweep_sharded",
    "run_sweep_sharded_chunked",
    "run_sweep_sharded_pipelined",
    "resume_sweep_sharded",
    "sharded_step",
]
