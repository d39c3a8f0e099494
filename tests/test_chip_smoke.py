"""chip_smoke.py's phases rehearsed on the CPU at tiny sizes.

The same functions run on the chip at full size; here they show that the
paths, arguments and assertions are right: the raft sweep is safe, the
CPU-replay contract holds, the seeded etcd bug is found while the clean
config stays quiet, the stream driver's report bytes equal the chunked
driver's, and the sharded path agrees with one device. ``main()`` itself
must refuse to run without a TPU.
"""

import jax
import pytest

import chip_smoke


def test_main_exits_nonzero_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_raft_sweep_phase():
    line = chip_smoke.raft_sweep(seeds=192, chunk_size=64)
    assert line["summary"]["violations"] == 0
    assert line["summary"]["commits_total"] > 0
    assert line["wall_s"] >= line["compile_s"] >= 0.0


def test_cpu_parity_phase():
    line = chip_smoke.cpu_parity(seeds=32)
    assert line["leaves_equal"] and line["traced_replay_equal"]
    assert line["leaves"] > 10


def test_checked_sweep_phase():
    line = chip_smoke.checked_sweep(seeds=256, chunk_size=64, workers=2)
    assert line["buggy"]["hist_violations"] > 0
    assert line["clean"]["hist_suspects"] == 0
    assert line["stream_bytes_equal"]


def test_sharded_phase():
    devices = jax.devices()[: chip_smoke.MESH_CHIPS]
    line = chip_smoke.sharded(devices, seeds=256, raft_chunk=16, etcd_chunk=16,
                              workers=0)
    assert line["raft_finals_equal"] and line["checked_bytes_equal"]
    assert line["hist_violations"] > 0


def test_import_is_jax_free():
    """The checker pool's forkserver re-imports ``__main__``: importing
    chip_smoke (and the checker) must not load JAX, or the workers would."""
    import subprocess
    import sys

    code = (
        "import sys, chip_smoke, madsim_tpu.oracle.check; "
        "sys.exit('jax' in sys.modules)"
    )
    root = chip_smoke.__file__.rsplit("/", 1)[0]
    subprocess.run([sys.executable, "-c", code], cwd=root, check=True, timeout=120)
