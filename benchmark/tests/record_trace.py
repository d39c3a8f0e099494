"""Record the small trace ``test_trace.py`` reads (run on a TPU):

    python3 benchmark/tests/record_trace.py

One ``bench.batch`` host span holds two jitted programs with a host
pause between them under ``bench.summary``, so the trace has device
work, an idle gap owned by a known span, and known module names."""

import os
import sys
import time


def main():
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: no TPU")
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    tmp = os.path.join(out, "tmp")

    @jax.jit
    def _drive(x):
        for _ in range(64):
            x = jnp.tanh(x @ x)
        return x

    @jax.jit
    def _screen(x):
        return jnp.sum(x * x)

    x = jnp.ones((1024, 1024), jnp.float32) / 1024
    _drive(x).block_until_ready()
    _screen(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.batch"):
        time.sleep(0.01)  # the device clock runs ~1 ms early on a v5e
        y = _drive(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.summary"):
            time.sleep(0.05)
        _screen(y).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.trace import find_xplane

    os.replace(find_xplane(tmp), os.path.join(out, "small.xplane.pb"))
    import shutil

    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
