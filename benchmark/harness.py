"""The benchmark harness: one cell, one seed, one measured window.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name
``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the program's parameters, the engine's and
  the name of the plain reference (``reference/<name>.py``);
- ``traffic/<traffic>.json``: the batch, the chunk, the overrides, the
  sample sizes, the traced batches and the control (overrides that
  break a guarantee);
- ``metrics/<metric>.py``: a ``read(ctx)`` that returns the metric or
  None when the run holds nothing to read.

A run builds the program from the config, warms up one whole batch of
the cell's own shapes on seeds far from the measured ones, then runs
back-to-back batches of fresh seeds until ``--seconds`` have passed,
and checks a sample of what the window produced against the reference.
Every batch goes through ``core.run_sweep_chunked`` and has its summary
read back. Nothing here touches JAX at import, so that ``run.py`` can
place the compile cache first.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")
WARM_BASE = 1 << 61  # warm-up seeds sit far above every measured range
SEED_SHIFT = 24  # each run's seeds start at seed << 24


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    return {
        "cell": cell,
        "config": load_json(BENCH, "configs", cell["config"] + ".json"),
        "traffic": load_json(BENCH, "traffic", cell["traffic"] + ".json"),
        "bench": bench,
    }


def device_check(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoDevice(
            f"the cell needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)"
        )
    return devs


def seed_batch(base: int, b: int, size: int):
    import jax.numpy as jnp

    lo = base + b * size
    return lo, jnp.arange(lo, lo + size, dtype=jnp.int64)


class Program:
    """The system under test, built from a config and a traffic mix."""

    def __init__(self, config: dict, traffic: dict, control: bool = False):
        from madsim_tpu.engine import core

        self.mod = importlib.import_module("madsim_tpu.models." + config["model"])
        params = dict(config["config"], **traffic.get("overrides", {}))
        if control:
            params.update(traffic["control"])
        self.cfg = getattr(self.mod, config["config_class"])(**params)
        self.wl = self.mod.workload(self.cfg)
        self.ecfg = self.mod.engine_config(self.cfg, **config["engine"])
        self.chunk = traffic.get("chunk_size") or core.pick_chunk_size(
            self.wl, self.ecfg
        )


def _leaf(final, path: str):
    for p in path.split("."):
        final = getattr(final, p)
    return final


class Sampler:
    """After each batch, gathers the compared fields of a few lanes,
    drawn from the seed, with one compiled program (warmed up with the
    rest). The gather is dispatched before the summary is read back, so
    it runs behind the batch's own drive and the device never waits on
    it; the gathered arrays stay on the device until ``rows()`` reads
    them after the window."""

    def __init__(self, summary_fn, fields, per_batch: int, seed: int):
        import jax

        self.summary_fn = summary_fn
        self.fields = ("seed",) + tuple(fields)
        self.per_batch = per_batch
        self.rng = np.random.default_rng(seed)
        self.pending: list = []  # (seed of lane 0, lanes, device arrays)
        self._gather = jax.jit(lambda leaves, idx: [leaf[idx] for leaf in leaves])

    def __call__(self, final, lo: int) -> dict:
        import jax

        idx = np.sort(self.rng.choice(int(final.seed.shape[0]), self.per_batch,
                                      replace=False))
        with jax.profiler.TraceAnnotation("bench.sample"):
            leaves = [_leaf(final, f) for f in self.fields]
            self.pending.append((lo, idx, self._gather(leaves, idx)))
        with jax.profiler.TraceAnnotation("bench.summary"):
            return self.summary_fn(final)

    def rows(self) -> list:
        """The gathered lanes as dicts of host arrays, ``lane_seed`` the
        seed the harness sent to that lane."""
        out = []
        for lo, idx, cols in self.pending:
            cols = [np.asarray(c) for c in cols]
            for j, lane in enumerate(idx):
                row = {f: cols[i][j] for i, f in enumerate(self.fields)}
                row["lane_seed"] = lo + int(lane)
                out.append(row)
        return out


def run_batch(prog: Program, sampler: Sampler, lo: int, seeds) -> dict:
    """One batch through ``core.run_sweep_chunked``; returns its summary."""
    import jax

    from madsim_tpu.engine import core

    with jax.profiler.TraceAnnotation("bench.batch"):
        finals = core.run_sweep_chunked(prog.wl, prog.ecfg, seeds,
                                        chunk_size=prog.chunk)
        return sampler(finals, lo)


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metric_applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def run(name: str, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True, control: bool = False, sizes=None) -> dict:
    """Run one cell and return the result line (a dict). ``sizes``
    overrides traffic keys (the CPU rehearsals' tiny batches);
    ``control`` runs the program with the traffic's control switched on."""
    spec = load_cell(name)
    cell, config, traffic = spec["cell"], spec["config"], dict(spec["traffic"])
    traffic.update(sizes or {})
    devs = device_check(cell["chips"], require_tpu)

    import jax

    from madsim_tpu.engine.compiles import count_compiles, use_compile_cache

    use_compile_cache()
    # cache every program, also those that compile in under a second:
    # each run is a new process, and set-up should only load
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from . import compare, trace as tr

    prog = Program(config, traffic, control)
    ref = importlib.import_module("benchmark.reference." + config["reference"])
    batch = traffic["batch_seeds"]
    sampler = Sampler(prog.mod.sweep_summary, ref.FIELDS,
                      traffic["gather_per_batch"], seed)

    # -- set-up: one whole batch of the cell's own shapes, far seeds ------
    warm_base = WARM_BASE + (seed << SEED_SHIFT)
    lo, seeds = seed_batch(warm_base, 0, batch)
    run_batch(prog, sampler, lo, seeds)
    sampler.pending.clear()

    tracing = None
    if trace:
        trace_dir = os.path.join(OUT, f"trace-{name}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host annotations and device ops only
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        tracing = traffic["trace_batches"]

    # -- the measured window ---------------------------------------------
    base = seed << SEED_SHIFT
    reports, t_first = [], time.perf_counter()
    setup_s = time.time() - t_start
    with count_compiles() as compiles:
        b = 0
        while not reports or time.perf_counter() - t_first < seconds:
            lo, seeds = seed_batch(base, b, batch)
            reports.append(run_batch(prog, sampler, lo, seeds))
            b += 1
            if tracing and b == tracing:
                jax.profiler.stop_trace()  # the traced window: these batches
                tracing = None
    t_last = time.perf_counter()
    window_s = t_last - t_first
    if tracing:
        jax.profiler.stop_trace()
    used = devs[: cell["chips"]]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
    known = [p for p in peaks if p is not None]
    peak = max(known) if known else None
    del prog

    attempted = batch * len(reports)
    metrics, device, breakdown = {}, {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_peak_bytes": peak,
    }, None
    if not trace:
        e2e = {"seeds_per_s": attempted / window_s, "setup_s": setup_s}
        for m in spec["bench"]["end_to_end"]:
            if _metric_applies(m, name) and m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        reduced = tr.reduce(trace_dir, len(used))
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = reduced["breakdown"]
        ctx = {
            "trace": reduced, "reports": reports[: traffic["trace_batches"]],
            "compiles": compiles.count, "peaks": peaks,
        }
        for m in spec["bench"]["per_layer"]:
            if _metric_applies(m, name):
                v = _reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- correctness, after the window, on the host ----------------------
    checks = compare.check(config, traffic, ref, reports, sampler.rows(),
                           batch, seed)
    failed = sum(r.get("overflow_seeds", 0) for r in reports)
    line = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def main(argv, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="madsim_tpu benchmark: one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="switch on the traffic's control (never used by "
                    "the driver: for setting the limits)")
    args = ap.parse_args(argv)
    try:
        line = run(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_start, control=args.control)
    except NoDevice as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    for k, c in line["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
