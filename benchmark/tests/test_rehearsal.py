"""Each cell of BENCHMARK.json runs end to end on the CPU at tiny sizes
and comes out correct; its control comes out not correct; without a TPU
the command exits non-zero and prints no result."""

import json
import os
import subprocess
import sys

import pytest

from helpers import rehearse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cell):
    line = rehearse(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"seeds_per_s", "setup_s"} <= set(line["metrics"])
    assert list(line)[-1] == "checks"
    for m in BENCH["end_to_end"]:
        if cell in m.get("workloads", [cell]):
            assert m["name"] in line["metrics"], m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    line = rehearse(cell, control=True)
    assert not line["correct"], line["checks"]
    assert line["checks"]["seeds_differing"]["value"] > 0


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "7", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "correct" not in p.stdout
