"""The comparison that decides ``correct``.

Two numbers, each exact (limit 0), each read after the window closed:

- ``seeds_unaccounted``: for every batch of the window, the seeds the
  summary counted against the seeds the batch sent;
- ``seeds_differing``: of a sample of the window's seeds, drawn from the
  run's seed and holding the seed that took the most events, those whose
  final state (clock, events, the model's state and its latched safety
  checks) differs from the plain reference's.
"""

from __future__ import annotations

import numpy as np

from .reference.engine import run_seed


def _equal(a, b) -> bool:
    return np.array_equal(np.asarray(a, np.int64), np.asarray(b, np.int64))


def check(config: dict, traffic: dict, ref, reports, rows, batch: int,
          seed: int) -> dict:
    unaccounted = sum(abs(batch - r["seeds"]) for r in reports)
    rng = np.random.default_rng([seed, 1])
    k = min(traffic["reference_seeds"], len(rows))
    picks = set(rng.choice(len(rows), k, replace=False).tolist())
    picks.add(int(np.argmax([int(r["ctr"]) for r in rows])))
    params = dict(config["config"], **traffic.get("overrides", {}))
    differing = 0
    for i in sorted(picks):
        row = rows[i]
        out = run_seed(ref.Model(params), row["lane_seed"], config["engine"])
        differing += not all(_equal(row[f], out[f]) for f in ("seed",) + ref.FIELDS)
    return {
        "seeds_unaccounted": {"value": unaccounted, "limit": 0},
        "seeds_differing": {"value": differing, "limit": 0, "of": len(picks)},
    }
