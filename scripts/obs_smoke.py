"""Observability smoke (docs/observability.md, `make obs-smoke`).

End-to-end assertions of the fleet telemetry subsystem on the CPU
backend, small enough for `make stest`, producing ONE Perfetto-loadable
trace file as the run's artifact:

1. out-of-band: the pipelined checked sweep and the streaming checked
   sweep each produce byte-equal report dicts with telemetry on vs off
   (the process-level byte diff lives in scripts/check_determinism.sh);
2. trace spans: the saved Chrome-trace JSON has named "dispatch" and
   "host" tracks, the dispatch-to-summary window of chunk N visibly
   OVERLAPS the host decode/check of chunk N-1 (interval intersection
   asserted), and the stream pool's occupancy rides along as counter
   samples (the refill cadence view);
3. journal: the run's JSONL stream has run_start/run_end plus per-chunk
   and per-flush events, all carrying the same run ID;
4. exposition: the opt-in localhost HTTP endpoint serves the registry
   in Prometheus text format while the sweep runs;
5. event mix: a raft sweep with the opt-in device-side event-mix plane
   enabled lands per-kind counters in `engine_events_by_kind_total`,
   and the default-config report stays free of the "event_mix" key.

Usage: python scripts/obs_smoke.py [out_dir]   (default ./obs_smoke_out)
"""

from __future__ import annotations

import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"  # CPU smoke: never the chip

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _spans(events, track_tid):
    return [
        e for e in events
        if e.get("ph") == "X" and e.get("tid") == track_tid
    ]


def _overlaps(a, b) -> bool:
    return max(a["ts"], b["ts"]) < min(a["ts"] + a["dur"], b["ts"] + b["dur"])


def main() -> int:
    from madsim_tpu import obs
    from madsim_tpu.engine.checkpoint import run_sweep_pipelined
    from madsim_tpu.models import etcd, raft
    from madsim_tpu.obs import read_journal
    from madsim_tpu.oracle.screen import checked_sweep

    out_dir = sys.argv[1] if len(sys.argv) > 1 else "obs_smoke_out"
    os.makedirs(out_dir, exist_ok=True)
    trace_path = os.path.join(out_dir, "trace.json")
    journal_path = os.path.join(out_dir, "journal.jsonl")
    for p in (trace_path, journal_path):
        if os.path.exists(p):
            os.remove(p)

    cfg = etcd.EtcdConfig(hist_slots=128, bug_stale_read=True)
    ecfg = etcd.engine_config(cfg, time_limit_ns=1_000_000_000,
                              max_steps=6_000)
    wl = etcd.workload(cfg)
    spec = etcd.history_spec()
    seeds = jnp.arange(128, dtype=jnp.int64)
    kw = dict(chunk_size=32, workers=0)

    # warm both drivers' programs so the traced region shows steady-state
    # pipelining, not one giant compile span
    checked_sweep(wl, ecfg, seeds, spec, etcd.sweep_summary, **kw)
    checked_sweep(wl, ecfg, seeds, spec, etcd.sweep_summary,
                  driver="stream", **kw)

    telem = obs.Telemetry(journal=journal_path, trace=trace_path,
                          http_port=0)
    run_id = telem.run_id

    # -- leg 1: pipelined chunked checked sweep (device/host overlap) --
    piped = checked_sweep(wl, ecfg, seeds, spec, etcd.sweep_summary,
                          telemetry=telem, **kw)
    piped_off = checked_sweep(wl, ecfg, seeds, spec, etcd.sweep_summary,
                              **kw)
    assert piped == piped_off, "telemetry changed the pipelined report"
    print(f"pipelined report out-of-band: OK "
          f"({piped['hist_violations']} violations)")

    # -- leg 2: streaming checked sweep (refill cadence) ---------------
    streamed = checked_sweep(wl, ecfg, seeds, spec, etcd.sweep_summary,
                             driver="stream", telemetry=telem, **kw)
    streamed_off = checked_sweep(wl, ecfg, seeds, spec, etcd.sweep_summary,
                                 driver="stream", **kw)
    assert streamed == streamed_off, "telemetry changed the stream report"
    print("stream report out-of-band: OK")

    # -- leg 3: the opt-in device-side event-mix plane -----------------
    rcfg = raft.RaftConfig(num_nodes=3, crashes=1, event_mix=True)
    recfg = raft.engine_config(rcfg, time_limit_ns=500_000_000)
    mixed = run_sweep_pipelined(
        raft.workload(rcfg), recfg, jnp.arange(64, dtype=jnp.int64),
        raft.sweep_summary, chunk_size=32, telemetry=telem,
    )
    assert "event_mix" in mixed and len(mixed["event_mix"]) == raft.N_KINDS
    assert sum(mixed["event_mix"]) > 0, "event-mix plane counted nothing"
    plain = run_sweep_pipelined(
        raft.workload(raft.RaftConfig(num_nodes=3, crashes=1)),
        raft.engine_config(raft.RaftConfig(num_nodes=3, crashes=1),
                           time_limit_ns=500_000_000),
        jnp.arange(64, dtype=jnp.int64), raft.sweep_summary, chunk_size=32,
    )
    assert "event_mix" not in plain, "default report grew an event_mix key"
    by_kind = telem.registry.get("engine_events_by_kind_total", kind="0")
    assert by_kind and by_kind > 0, "event-mix counters missing from registry"
    print(f"event-mix plane: OK (mix={mixed['event_mix']})")

    # -- leg 4: live Prometheus exposition -----------------------------
    body = urllib.request.urlopen(telem.server.url, timeout=5).read().decode()
    for needle in ("sweep_chunk_seconds_bucket", "stream_rounds_total",
                   "oracle_screened_total", "engine_events_by_kind_total"):
        assert needle in body, f"exposition missing {needle}"
    print(f"exposition endpoint: OK ({telem.server.url}, "
          f"{len(body.splitlines())} lines)")

    telem.close()

    # -- leg 5: the trace artifact -------------------------------------
    with open(trace_path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    tracks = {
        e["args"]["name"]: e["tid"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert "dispatch" in tracks and "host" in tracks, f"tracks: {tracks}"
    dev = _spans(events, tracks["dispatch"])
    host = _spans(events, tracks["host"])
    assert dev and host, f"empty tracks: {len(dev)} dispatch, {len(host)} host"
    overlapped = sum(
        1 for h in host if any(_overlaps(h, d) for d in dev)
    )
    assert overlapped > 0, "no dispatch/host phase overlap visible in trace"
    occ_samples = [
        e for e in events
        if e.get("ph") == "C" and e.get("name") == "stream occupancy"
    ]
    assert len(occ_samples) >= 2, "no refill-cadence counter samples"
    rounds = [e for e in dev if e["name"].startswith("round ")]
    assert rounds, "no stream round spans on the dispatch track"
    print(
        f"trace: OK ({len(dev)} dispatch spans, {len(host)} host spans, "
        f"{overlapped} host spans overlap a dispatch window, "
        f"{len(occ_samples)} occupancy samples) -> {trace_path}"
    )

    # -- leg 6: the run journal ----------------------------------------
    recs = read_journal(journal_path)
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end", kinds[:3]
    assert "chunk" in kinds and "flush" in kinds, sorted(set(kinds))
    assert all(r["run"] == run_id for r in recs), "run ID drifted"
    print(f"journal: OK ({len(recs)} events, run {run_id}) "
          f"-> {journal_path}")

    print("obs smoke: ALL OK "
          f"(backend={jax.default_backend()}); load {trace_path} in "
          "https://ui.perfetto.dev to see the overlap")
    return 0


if __name__ == "__main__":
    sys.exit(main())
