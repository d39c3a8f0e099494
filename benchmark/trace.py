"""Reduction of a profiler trace to the numbers the readers take.

Reads the ``.xplane.pb`` that ``jax.profiler`` wrote with
``jax.profiler.ProfileData``, and returns:

- ``window_s``: from the start of the first ``bench.batch`` host span to
  the end of the last, on the trace's own clock (the device's events sit
  about a millisecond early against the host's on a v5e; over a window
  of seconds that is noise);
- ``busy_s``: per device plane used, the union of the intervals in which
  an XLA op ran inside that window, averaged over the chips used;
  ``busy_per_chip`` keeps each;
- ``modules``: device seconds per XLA module (program) name, summed over
  the chips used; ``ops`` the same per op name;
- ``breakdown``: the ten ops that took most device time, and the ten
  host spans under which the device sat idle longest (a gap is given to
  the innermost host span that covers its middle).
"""

from __future__ import annotations

import glob
import os

BATCH_SPAN = "bench.batch"


def _union(intervals, lo, hi):
    """Seconds covered by the union of ``intervals`` clipped to [lo, hi],
    and the gaps between them inside [lo, hi]."""
    total, reach, gaps = 0, lo, []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > reach:
            gaps.append((reach, s))
        if e > reach:
            total += e - max(s, reach)
            reach = e
    if reach < hi:
        gaps.append((reach, hi))
    return total, gaps


def _device_planes(planes, chips):
    devs = sorted(
        (p for p in planes if p.name.startswith("/device:TPU:")
         and p.name[len("/device:TPU:"):].isdigit()),
        key=lambda p: int(p.name[len("/device:TPU:"):]),
    )
    return devs[:chips]


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def reduce_file(path: str, chips: int) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    spans = []  # (start, end, name) of host annotations
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    batches = [s for s in spans if s[2] == BATCH_SPAN]
    if not batches:
        raise ValueError(f"{path}: no {BATCH_SPAN} span in the trace")
    lo = min(s for s, _e, _n in batches)
    hi = max(e for _s, e, _n in batches)
    busy, modules, ops, gaps0 = [], {}, {}, None
    for plane in _device_planes(planes, chips):
        lines = {line.name: line for line in plane.lines}
        op_line = lines.get("XLA Ops")
        intervals = []
        if op_line is not None:
            for ev in op_line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                intervals.append((s, e))
                d = min(e, hi) - max(s, lo)
                name = ev.name.split(" = ")[0]  # "%while.31", not the HLO text
                ops[name] = ops.get(name, 0) + d
        mod_line = lines.get("XLA Modules")
        if mod_line is not None:
            for ev in mod_line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                d = min(e, hi) - max(s, lo)
                name = ev.name.split("(")[0]
                modules[name] = modules.get(name, 0) + d
        total, gaps = _union(intervals, lo, hi)
        busy.append(total)
        if gaps0 is None:
            gaps0 = gaps
    if not busy:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    idle = {}
    inner = sorted(spans, key=lambda s: s[1] - s[0])  # innermost first
    for s, e in gaps0 or []:
        mid = (s + e) // 2
        owner = next((n for a, b, n in inner if a <= mid < b), "no bench span")
        idle[owner] = idle.get(owner, 0) + (e - s)

    def top(d):
        return [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "busy_per_chip": [b / 1e9 for b in busy],
        "modules": {k: v / 1e9 for k, v in modules.items()},
        "ops": {k: v / 1e9 for k, v in ops.items()},
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(idle)},
    }


def reduce(trace_dir: str, chips: int) -> dict:
    return reduce_file(find_xplane(trace_dir), chips)
