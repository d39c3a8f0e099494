"""device.compiles_in_window: XLA compilations inside the measured
window (``engine.compiles.count_compiles``). It should read 0."""


def read(ctx):
    return ctx["compiles"]
