"""raft.installs_per_seed: InstallSnapshot messages the followers
installed, per seed, over the traced batches (their summaries'
``installs`` over their ``seeds``). A check that the traffic exercises
snapshot catch-up, not a goal: it follows the fault plan, and a change
that raises or lowers it has changed what the cell simulates. None where
the summaries carry no ``installs`` count."""


def read(ctx):
    reports = ctx["reports"]
    seeds = sum(r["seeds"] for r in reports)
    if seeds <= 0 or any("installs" not in r for r in reports):
        return None
    return sum(r["installs"] for r in reports) / seeds
