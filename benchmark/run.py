"""The benchmark's entry point: one cell, one seed, one measured window.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON line last on standard output (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and the compared numbers under ``checks``), and the compared numbers
beside their limits as the last lines on standard error. Exits 3, with
no result, when JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.time()  # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # the compile cache sits at a fixed path inside the checkout, whatever
    # the environment names: the parent and the change share nothing, and
    # only a checkout's first run compiles (engine.compiles reads this)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, ROOT)
    from benchmark import harness

    sys.exit(harness.main(sys.argv[1:], T_START))
