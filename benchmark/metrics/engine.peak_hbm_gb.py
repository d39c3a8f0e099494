"""engine.peak_hbm_gb: ``peak_bytes_in_use`` of the fullest chip used,
read after the window, in GB (1e9 bytes)."""


def read(ctx):
    peaks = [p for p in ctx["peaks"] if p is not None]
    return max(peaks) / 1e9 if peaks else None
