"""XLA compile counting: the honest program-reuse measurement.

``count_compiles()`` wraps a code region in ``jax.log_compiles`` and
counts "Finished XLA compilation" log records — the ground truth for
every zero-recompile claim in this repo (a ragged tail, a mutated
campaign candidate, or a differential-grid spec that recompiles anything
shows up here; self-reported shape bookkeeping does not count).

Grew out of scripts/sweep_million.py's one-script hack; now a first-class
metric shared by the explore demo, the campaign bench leg, and the
spec-as-data tests (tests/test_fault_params.py), so "compiles in the
timed region" is reported the same way everywhere.

``use_compile_cache()`` turns on JAX's persistent compile cache for an
entry point (chip_smoke.py, bench.py, the scripts that compile device
programs); importing the library never does.
"""

from __future__ import annotations

import logging
import os
from contextlib import contextmanager
from typing import Optional

import jax

# a fixed path, so a later process on the same checkout finds what an
# earlier one compiled (a per-run directory would never hit)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def use_compile_cache() -> Optional[str]:
    """Turn on the persistent compile cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no directory is set here. Otherwise the cache lives at
    ``<repo>/.jax_cache`` (git-ignored) — except in a CPU-only process
    (a rehearsal), which gets none: XLA:CPU warns on every reload of its
    own entries. Call before the first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


class CompileCounter(logging.Handler):
    """Counts finished XLA compilations surfaced by ``jax.log_compiles``."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.count = 0

    def emit(self, record):
        if "Finished XLA compilation" in record.getMessage():
            self.count += 1


@contextmanager
def count_compiles():
    """``with count_compiles() as c:`` ... ``c.count`` is the number of
    XLA compilations the region performed (0 after a proper warm-up is
    the spec-as-data contract — docs/faults.md)."""
    handler = CompileCounter()
    logger = logging.getLogger("jax")
    logger.addHandler(handler)
    try:
        with jax.log_compiles(True):
            yield handler
    finally:
        logger.removeHandler(handler)
