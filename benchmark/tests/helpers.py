"""Tiny sizes for the CPU rehearsals: every cell's control flow, at a
batch a test run can hold."""

import time

TINY = {"batch_seeds": 128, "chunk_size": 64, "gather_per_batch": 4,
        "reference_seeds": 6}
SEED = 2_400_000_017  # above 2**31, as the driver's seeds are


def rehearse(cell, control=False):
    """Run ``cell`` at TINY sizes on the CPU."""
    from benchmark import harness

    return harness.run(cell, SEED, 1.0, False, time.time(), require_tpu=False,
                       control=control, sizes=TINY)
