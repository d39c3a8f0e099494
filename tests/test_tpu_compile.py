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

import json
import os
import re
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


def _model(model):
    """(workload, engine config, lanes): raft as the benchmark runs it
    (5 nodes, 3 virtual s), raft3snap as its benchmark configuration
    gives it, the others at their default configs, each at
    ``core.pick_chunk_size`` lanes."""
    if model == "raft":
        cfg = raft.RaftConfig(num_nodes=5, crashes=1)
        wl = raft.workload(cfg)
        return wl, raft.engine_config(cfg, time_limit_ns=3_000_000_000), RAFT_LANES
    if model == "raft3snap":
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs", "raft3snap.json")) as f:
            spec = json.load(f)
        cfg = raft.RaftConfig(**spec["config"])
        wl, ecfg = raft.workload(cfg), raft.engine_config(cfg, **spec["engine"])
        return wl, ecfg, core.pick_chunk_size(wl, ecfg)
    mod = {"etcd": etcd, "kafka": kafka, "s3": s3}[model]
    wl, ecfg = mod.workload(), mod.engine_config()
    return wl, ecfg, core.pick_chunk_size(wl, ecfg)


@pytest.fixture(scope="module")
def raft_drive(one_chip):
    """raft's compiled ``drive``, shared by the tests that read it."""
    wl, ecfg, lanes = _model("raft")
    return _compile(partial(core.drive, wl, ecfg), _state_shapes(wl, ecfg, lanes, one_chip))


@pytest.mark.parametrize("model", ["raft", "etcd", "kafka", "s3", "raft3snap"])
def test_sweep_compiles_for_v5e(one_chip, model, request):
    """``init_sweep`` and ``drive`` for each device model."""
    wl, ecfg, lanes = _model(model)
    seeds = jax.ShapeDtypeStruct((lanes,), jnp.int64, sharding=one_chip)
    _compile(partial(core.init_sweep, wl, ecfg), seeds)
    if model == "raft":
        drive = request.getfixturevalue("raft_drive")
    else:
        drive = _compile(partial(core.drive, wl, ecfg), _state_shapes(wl, ecfg, lanes, one_chip))
    assert drive.memory_analysis().argument_size_in_bytes < 16 << 30


def test_raft_drive_carries_pay_as_planes(raft_drive):
    """The queue's payload reaches the v5e as P ``[lanes, Q]`` planes
    tiled like the ``[lanes, Q]`` emit masks, Q on the sublanes
    (``{0,1:T(8,128)}``), and lives so in the drive loop's carry; no
    stacked ``[lanes, Q, P]`` array, tiled with P on the sublanes, is
    left anywhere in the program."""
    hlo = raft_drive.as_text()
    wl, ecfg, _ = _model("raft")
    q, p = ecfg.queue_capacity, wl.payload_slots
    assert f"s32[{RAFT_LANES},{q},{p}]" not in hlo
    plane = re.escape(f"s32[{RAFT_LANES},{q}]{{0,1:T(8,128)")
    params = re.findall(rf"%state_queue_pay_(\d+)_\S* = {plane}\S* parameter\(", hlo)
    assert sorted(map(int, params)) == list(range(p)), params
    (carry,) = [line for line in hlo.splitlines() if re.match(r"\s*%while\S* = \(", line)]
    assert len(re.findall(plane, carry)) >= p + 1  # the kind plane and P pay planes


def test_etcd_screen_compiles_for_v5e(one_chip):
    cfg = etcd.EtcdConfig(hist_slots=256, bug_stale_read=True)
    ecfg = etcd.engine_config(cfg, time_limit_ns=2_000_000_000, max_steps=20_000)
    final = _state_shapes(etcd.workload(cfg), ecfg, ETCD_LANES, one_chip)
    spec = etcd.history_spec()

    def run(seed, rec, t, n):
        planes = SimpleNamespace(seed=seed, hist_rec=rec, hist_t=t, hist_len=n)
        return screen.screen_sweep(planes, spec)

    _compile(run, final.seed, final.hist_rec, final.hist_t, final.hist_len)
