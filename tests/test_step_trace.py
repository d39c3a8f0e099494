"""Inside the sweep: the step's phase scopes in the compiled drive
program, the lane-occupancy counters, and the program spans on the
profiler's clock."""

import glob
import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu import obs
from madsim_tpu.engine import core
from madsim_tpu.models import raft

CFG = raft.RaftConfig(num_nodes=3, crashes=1)
ECFG = raft.engine_config(CFG, time_limit_ns=300_000_000)
WL = raft.workload(CFG)
N, CHUNK = 100, 64  # two chunks, the second padded with 28 lanes


def test_every_phase_maps_an_instruction_of_the_drive(monkeypatch):
    monkeypatch.setattr(core, "_DRIVE_PROGRAMS", {})  # this drive alone
    core.run_sweep(WL, ECFG, jnp.arange(8, dtype=jnp.int64))
    phases = core.drive_phase_map()
    assert set(core.PHASES) <= set(phases.values())
    assert None in phases.values()  # the loop's cond and carry copies
    init = core._init.lower(WL, ECFG, jax.ShapeDtypeStruct((8,), jnp.int64))
    shared = set(core.hlo_phases(init.compile().as_text())) & set(phases)
    assert not shared  # names the init program also has count for none


HLO = """HloModule m
%fused_computation.1 (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  ROOT %add.1 = s32[4]{0} add(%p, %p), metadata={op_name="jit(_drive)/while/body/vmap(push)/add"}
}
%fused_computation.2 (p: s32[4]) -> (s32[4], s32[4]) {
  %p.1 = s32[4]{0} parameter(0)
  %mul.2 = s32[4]{0:T(256)} multiply(%p.1, %p.1), metadata={op_name="jit(_drive)/while/body/vmap(rng)/mul"}
  ROOT %tuple.2 = (s32[4]{0:T(256)}, s32[4]{0}) tuple(s32[4]{0:T(256)} %p.1, s32[4]{0:T(256)} %mul.2)
}
ENTRY %main (x: s32[4]) -> s32[4] {
  %x = s32[4]{0} parameter(0)
  %fusion.1 = s32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_drive)/while/body/vmap(pop)/sub"}
  %fusion.2 = (s32[4]{0}, s32[4]{0}) fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %copy.3 = s32[4]{0} copy(%x)
  ROOT %sort.4 = s32[4]{0} sort(%copy.3), metadata={op_name="jit(_drive)/while/body/vmap(handler)/jit(pop)/sort"}
}
"""


def test_hlo_phases_reads_fusion_roots_and_scopes():
    phases = core.hlo_phases(HLO)
    # a fusion takes its root's phase, not its own metadata's; a tuple
    # root, its first operand with one; a jit(...) of the same name as a
    # phase is not a phase scope
    assert phases == {"%x": None, "%fusion.1": "push", "%fusion.2": "rng",
                      "%copy.3": None, "%sort.4": "handler"}


def test_hlo_phases_names_the_innermost_phase():
    hlo = HLO.replace("vmap(handler)/jit(pop)/sort",
                      "vmap(handler)/vmap(snapshot)/sort")
    assert core.hlo_phases(hlo)["%sort.4"] == "snapshot"


def _drive_text(wl, ecfg):
    state = jax.eval_shape(partial(core.init_sweep, wl, ecfg),
                           jax.ShapeDtypeStruct((8,), jnp.int64))
    return core._drive.lower(wl, ecfg, state).compile().as_text()


def test_snapshot_scope_is_a_phase_and_leaves_raft_without_it_unchanged(
        monkeypatch):
    snap = raft.RaftConfig(num_nodes=3, crashes=0, commands=1, log_cap=16,
                           snapshot_every=4, rounds=2)
    wl = raft.workload(snap)
    phases = core.hlo_phases(_drive_text(wl, raft.engine_config(snap)))
    assert "snapshot" in phases.values() and "handler" in phases.values()
    text = _drive_text(WL, ECFG)
    plain = core.hlo_phases(text)
    assert "snapshot" not in plain.values()

    def step_phase(op_name):  # the rule before handler scopes were phases
        for part in reversed(op_name.split("/")):
            m = core._SCOPE.fullmatch(part)
            if m and m.group(1) in core.PHASES:
                return m.group(1)
        return None

    monkeypatch.setattr(core, "_scope_phase", step_phase)
    assert core.hlo_phases(text) == plain


def _host_finals(seeds):
    """Each chunk's final state as the chunk driver ran it, padding
    included."""
    out = []
    for lo in range(0, N, CHUNK):
        chunk = seeds[lo : lo + CHUNK]
        if chunk.shape[0] < CHUNK:
            chunk = core._pad_seeds(chunk, CHUNK - chunk.shape[0])
        out.append(core.run_sweep(WL, ECFG, chunk))
    return out


def test_lane_occupancy_counters_match_the_final_state():
    reg = obs.default_registry()
    seeds = jnp.arange(5000, 5000 + N, dtype=jnp.int64)
    core.run_sweep_chunked(WL, ECFG, seeds[:8])  # the counters exist
    events0 = reg.get("engine_events_total")
    steps0 = reg.get("engine_lane_steps_total")
    core.run_sweep_chunked(WL, ECFG, seeds, chunk_size=CHUNK)
    events = reg.get("engine_events_total") - events0
    steps = reg.get("engine_lane_steps_total") - steps0
    want_events = want_steps = 0
    for final in _host_finals(seeds):
        ctr, done = np.asarray(final.ctr), np.asarray(final.done)
        want_events += int(ctr.sum())
        want_steps += ctr.shape[0] * int((ctr + done).max())
    assert (events, steps) == (want_events, want_steps)
    assert 0 < events / steps < 1


class _Deferred:
    """A device scalar stand-in: counts how often it is read."""

    def __init__(self, v, ready=True):
        self.v, self.ready, self.reads = v, ready, 0

    def is_ready(self):
        return self.ready

    def __int__(self):
        self.reads += 1
        return self.v


def test_deferred_counter_waits_on_nothing_and_stays_bounded():
    c = obs.Registry().counter("c_total")
    late = _Deferred(5, ready=False)
    c.inc_deferred(late, scale=3)
    vals = [_Deferred(1) for _ in range(c.MAX_PENDING)]
    for v in vals:
        c.inc_deferred(v)
    # past the bound only the leading values already computed are
    # folded: the first one is still running, so nothing was read
    assert late.reads == 0 and not any(v.reads for v in vals)
    late.ready = True
    c.inc_deferred(_Deferred(2))
    assert len(c._pending) == 0  # folded on the way in, all computed
    assert c.get() == 15 + c.MAX_PENDING + 2
    c.inc_deferred(_Deferred(4), scale=2)
    assert c.get() == 15 + c.MAX_PENDING + 2 + 8  # read folds the rest


def test_a_reader_waiting_on_the_device_holds_up_no_writer():
    import threading

    c = obs.Registry().counter("c_total")
    computed = threading.Event()

    class Running(_Deferred):
        def __int__(self):
            computed.wait(10)  # a read of a drive still on the device
            return super().__int__()

    c.inc_deferred(Running(7, ready=False))
    got = []
    reader = threading.Thread(target=lambda: got.append(c.get()))
    reader.start()
    try:
        c.inc_deferred(_Deferred(1))  # returns while the reader waits
        assert reader.is_alive() and not computed.is_set()
    finally:
        computed.set()
        reader.join(10)
    assert not reader.is_alive()
    assert got[0] in (7, 8) and c.get() == 8


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny chunked sweep and its summary under a profiler trace and a
    Telemetry that records a trace: (xplane host events, SpanTracer
    events, the xplane's session start in epoch ns)."""
    from jax.profiler import ProfileData

    d = tmp_path_factory.mktemp("trace")
    seeds = jnp.arange(N, dtype=jnp.int64)
    final = core.run_sweep_chunked(WL, ECFG, seeds, chunk_size=CHUNK)  # warm
    raft.sweep_summary(final)
    telem = obs.Telemetry(trace=str(d / "spans.json"))
    jax.profiler.start_trace(str(d / "xplane"))
    try:
        with jax.profiler.TraceAnnotation("bench.batch"):
            final = core.run_sweep_chunked(WL, ECFG, seeds, chunk_size=CHUNK,
                                           telemetry=telem)
            with jax.profiler.TraceAnnotation("bench.summary"):
                raft.sweep_summary(final)
    finally:
        jax.profiler.stop_trace()
    telem.close()
    (path,) = glob.glob(str(d / "xplane" / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    start = next(dict(p.stats)["profile_start_time"] for p in pd.planes
                 if p.name == "Task Environment")
    host = [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
        for p in pd.planes if p.name.startswith("/host:")
        for line in p.lines for ev in line.events
        if ev.name.startswith(("bench.", "madsim."))
    ]
    spans = json.loads((d / "spans.json").read_text())["traceEvents"]
    return host, spans, start


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_program_spans_nest_under_the_callers_annotation(traced):
    host, _spans, _start = traced
    by = {}
    for ev in host:
        by.setdefault(ev[0], []).append(ev)
    (batch,), (summary,) = by["bench.batch"], by["bench.summary"]
    chunks = by["madsim.sweep.chunk"]
    assert sorted(ev[3]["lo"] for ev in chunks) == [0, CHUNK]
    assert all(_inside(ev, batch) for ev in chunks + by["madsim.sweep.concat"])
    (summ,), (wait,) = by["madsim.summary"], by["madsim.summary.wait"]
    assert _inside(summ, summary) and _inside(wait, summ)


def test_span_tracer_lies_on_the_xplane_clock(traced):
    host, spans, start = traced
    xplane = sorted(start + ev[1] for ev in host if ev[0] == "madsim.sweep.chunk")
    tracer = sorted(e["ts"] * 1000 for e in spans
                    if e.get("ph") == "X" and e["name"] == "madsim.sweep.chunk")
    assert len(tracer) == len(xplane) == 2
    for a, b in zip(tracer, xplane):
        assert abs(a - b) < 1e6  # within 1 ms
