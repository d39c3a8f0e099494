"""device.idle_share: 1 - (union of the intervals in which an XLA op ran
on the chip) / (the traced window), averaged over the chips used."""


def read(ctx):
    tr = ctx["trace"]
    if tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
