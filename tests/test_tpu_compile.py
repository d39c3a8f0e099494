"""Ahead-of-time compiles of the device tier's main programs for a TPU v5e.

Nothing runs: each program is lowered against a *described* v5e chip
(``jax.experimental.topologies``) and compiled by the TPU compiler that
ships with libtpu, at the sizes ``chip_smoke.py`` runs them. That catches
what the CPU backend cannot — a program the TPU compiler refuses, or one
that overflows device memory — for no chip time. A compile that passes is
not a chip run.

The topology is described only inside the ``topo`` fixture (never at
import, in a ``skipif`` or in ``parametrize``): the first process that
loads libtpu holds it until exit, so every worker must collect the same
tests and only the one that runs this file may load the library. The
persistent compile cache is off around these compiles: an entry written
for a described chip cannot be read back without one.
"""

import os
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from madsim_tpu.engine import core
from madsim_tpu.models import etcd, kafka, raft, s3
from madsim_tpu.oracle import screen

RAFT_LANES = 16_384  # core.pick_chunk_size for the 5-node raft config
ETCD_LANES = 8_192  # core.pick_chunk_size for etcd at hist_slots=256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    """Abstract shapes of ``tree`` placed on the described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def _state_shapes(wl, ecfg, lanes, sharding):
    seeds = jax.ShapeDtypeStruct((lanes,), jnp.int64)
    return _on(jax.eval_shape(partial(core.init_sweep, wl, ecfg), seeds), sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("model", ["raft", "etcd", "kafka", "s3"])
def test_sweep_compiles_for_v5e(one_chip, model):
    """``init_sweep`` and ``drive`` for each device model: raft as the
    benchmark runs it (5 nodes, 3 virtual s), the others at their default
    configs and ``core.pick_chunk_size`` lanes."""
    if model == "raft":
        cfg = raft.RaftConfig(num_nodes=5, crashes=1)
        wl = raft.workload(cfg)
        ecfg = raft.engine_config(cfg, time_limit_ns=3_000_000_000)
        lanes = RAFT_LANES
    else:
        mod = {"etcd": etcd, "kafka": kafka, "s3": s3}[model]
        wl, ecfg = mod.workload(), mod.engine_config()
        lanes = core.pick_chunk_size(wl, ecfg)
    seeds = jax.ShapeDtypeStruct((lanes,), jnp.int64, sharding=one_chip)
    _compile(partial(core.init_sweep, wl, ecfg), seeds)
    state = _state_shapes(wl, ecfg, lanes, one_chip)
    mem = _compile(partial(core.drive, wl, ecfg), state).memory_analysis()
    assert mem.argument_size_in_bytes < 16 << 30


def test_etcd_screen_compiles_for_v5e(one_chip):
    cfg = etcd.EtcdConfig(hist_slots=256, bug_stale_read=True)
    ecfg = etcd.engine_config(cfg, time_limit_ns=2_000_000_000, max_steps=20_000)
    final = _state_shapes(etcd.workload(cfg), ecfg, ETCD_LANES, one_chip)
    spec = etcd.history_spec()

    def run(seed, rec, t, n):
        planes = SimpleNamespace(seed=seed, hist_rec=rec, hist_t=t, hist_len=n)
        return screen.screen_sweep(planes, spec)

    _compile(run, final.seed, final.hist_rec, final.hist_t, final.hist_len)
