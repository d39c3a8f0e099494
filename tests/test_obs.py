"""The fleet telemetry subsystem (madsim_tpu/obs, docs/observability.md).

Direct unit coverage for the substrate the drivers instrument against:
the metrics registry and its Prometheus rendering, the JSONL run
journal, the opt-in HTTP exposition endpoint, the ``Telemetry`` handle's
recorder surface, the obs-registry heartbeat, the host-tier
``RuntimeMetrics`` shim joined to the exposition path, and the Chrome-
trace JSON shape of both exporters (``tracing.Tracer`` for one seed's
polls, ``tracing.SpanTracer`` for fleet driver phases). The end-to-end
out-of-band property (report bytes identical with telemetry on/off)
lives in scripts/obs_smoke.py and the determinism gate; here each piece
is pinned in isolation.
"""

import io
import json
import urllib.request

import pytest

import madsim_tpu as ms
from madsim_tpu import obs, tracing
from madsim_tpu.obs import metrics as obsm


# -- metrics registry -------------------------------------------------------


def test_counter_labels_and_monotonicity():
    c = obsm.Counter("frames_total", "frames", labels=("api",))
    c.inc(api="Produce")
    c.inc(2, api="Produce")
    c.inc(api="Fetch")
    assert c.get(api="Produce") == 3
    assert c.get(api="Fetch") == 1
    assert c.get(api="Metadata") == 0
    assert c.series() == [(("Fetch",), 1), (("Produce",), 3)]
    with pytest.raises(ValueError):
        c.inc(-1, api="Produce")
    with pytest.raises(ValueError):
        c.inc(bogus_label="x")


def test_gauge_set_inc():
    g = obsm.Gauge("depth")
    g.set(7)
    assert g.get() == 7
    g.inc(-2)
    assert g.get() == 5  # gauges may go down; counters may not


def test_histogram_buckets_cumulative():
    h = obsm.Histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    count, total = h.get()
    assert count == 5
    assert total == pytest.approx(56.05)
    ((key, row),) = h.series()
    assert key == ()
    # per-bucket (non-cumulative) counts + the +Inf bucket + the sum
    assert row == [1.0, 2.0, 1.0, 1.0, pytest.approx(56.05)]
    with pytest.raises(ValueError):
        obsm.Histogram("bad", buckets=(1.0, 0.1))


def test_registry_idempotent_and_kind_checked():
    r = obsm.Registry()
    assert r.counter("a") is r.counter("a")
    with pytest.raises(ValueError):
        r.gauge("a")
    assert r.get("missing") is None
    r.counter("a").inc(4)
    assert r.get("a") == 4


def test_registry_callback_gauge_and_snapshot():
    r = obsm.Registry()
    r.counter("done_total", "finished").inc(3)
    r.callback_gauge("live_tasks", lambda: 11, help="census")
    r.callback_gauge(
        "by_node", lambda: {"n1": 2, "n2": 1}, help="per node", label="node"
    )
    r.callback_gauge("broken", lambda: 1 / 0)  # must not break collection
    snap = r.snapshot()
    assert snap["done_total"] == 3
    assert snap["live_tasks"] == 11
    assert snap["by_node"] == {"node=n1": 2, "node=n2": 1}
    assert "broken" not in snap
    with pytest.raises(ValueError):
        r.callback_gauge("done_total", lambda: 0)


def test_render_prometheus_text_shape():
    r = obsm.Registry()
    r.counter("frames_total", "frames served", labels=("api",)).inc(
        5, api="Produce"
    )
    r.gauge("occupancy", "pool occupancy").set(0.75)
    r.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(0.5)
    text = obs.render_prometheus(r)
    assert "# HELP frames_total frames served" in text
    assert "# TYPE frames_total counter" in text
    assert 'frames_total{api="Produce"} 5' in text
    assert "occupancy 0.75" in text
    # histogram buckets render CUMULATIVE with the +Inf cap
    assert 'lat_seconds_bucket{le="0.1"} 0' in text
    assert 'lat_seconds_bucket{le="1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_sum 0.5" in text
    assert "lat_seconds_count 1" in text


# -- run journal ------------------------------------------------------------


def test_journal_roundtrip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    j = obs.Journal(path, run_id="cafe" * 4)
    j.write("chunk", lo=0, k=32)
    j.write("flush", lo=0, wall_s=0.25)
    j.close()
    j.write("late", x=1)  # post-close writes are dropped, not crashes
    recs = obs.read_journal(path)
    assert [r["kind"] for r in recs] == ["run_start", "chunk", "flush",
                                        "run_end"]
    assert all(r["run"] == "cafe" * 4 for r in recs)
    assert all("ts" in r for r in recs)
    assert recs[1]["lo"] == 0 and recs[1]["k"] == 32
    # every line is standalone JSON (append-only, crash-durable)
    with open(path) as f:
        for line in f:
            json.loads(line)


def test_journal_torn_final_line_every_offset(tmp_path):
    # the crash-recovery contract: a writer killed mid-append leaves a
    # torn partial FINAL line; read_journal must return the valid prefix
    # with .truncated set — at EVERY byte offset of the last record
    path = str(tmp_path / "run.jsonl")
    j = obs.Journal(path, run_id="dead" * 4)
    j.write("chunk", lo=0, k=32)
    j.write("flush", lo=0, wall_s=0.25, note="padding so the torn line "
            "has structure worth truncating through")
    j.close()
    data = open(path, "rb").read()
    last_start = data.rstrip(b"\n").rfind(b"\n") + 1
    last_len = len(data) - last_start  # includes the trailing newline
    whole = obs.read_journal(path)
    assert not whole.truncated and len(whole) == 4
    for off in range(last_len + 1):
        with open(path, "wb") as f:
            f.write(data[: last_start + off])
        recs = obs.read_journal(path)
        if off in (0, last_len - 1, last_len):
            # clean cuts: the record absent, or complete (a cut that
            # drops only the trailing newline still parses whole)
            assert not recs.truncated
            assert len(recs) == (3 if off == 0 else 4)
        else:
            assert recs.truncated, f"offset {off} not flagged"
            assert recs == whole[:3]
    # a malformed line with more data AFTER it is corruption, not a torn
    # tail — that still raises
    with open(path, "wb") as f:
        f.write(data[: last_start + 5] + b"\n" + data[last_start:])
    with pytest.raises(json.JSONDecodeError):
        obs.read_journal(path)


def test_new_run_id_unique_hex():
    ids = {obs.new_run_id() for _ in range(64)}
    assert len(ids) == 64
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)


# -- exposition endpoint ----------------------------------------------------


def test_http_metrics_endpoint():
    r = obsm.Registry()
    r.counter("hits_total").inc(2)
    server = obs.start_http_server(r, port=0)
    try:
        body = urllib.request.urlopen(server.url, timeout=5).read().decode()
        assert "hits_total 2" in body
        r.counter("hits_total").inc()
        body = urllib.request.urlopen(server.url, timeout=5).read().decode()
        assert "hits_total 3" in body  # live: renders at scrape time
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://{server.host}:{server.port}/nope", timeout=5
            )
    finally:
        server.close()


# -- the Telemetry handle ---------------------------------------------------


def test_telemetry_recorders(tmp_path):
    t = obs.Telemetry(journal=str(tmp_path / "j.jsonl"),
                      trace=str(tmp_path / "t.json"))
    t.count("chunks_total", help="chunks")
    t.count("chunks_total", 2)
    t.gauge("occupancy", 0.9)
    t.observe("chunk_seconds", 0.5)
    t.event("chunk", lo=0)
    with t.span("phase", track="dispatch", lo=0):
        pass
    t.sample("occupancy", pool=0.9)
    t.event_mix({"event_mix": [3, 0, 7]})
    t.event_mix({})  # reports without the plane are a no-op
    assert t.registry.get("chunks_total") == 3
    assert t.registry.get("occupancy") == 0.9
    assert t.registry.get("engine_events_by_kind_total", kind="0") == 3
    assert t.registry.get("engine_events_by_kind_total", kind="2") == 7
    t.close()
    kinds = [r["kind"] for r in obs.read_journal(str(tmp_path / "j.jsonl"))]
    assert kinds == ["run_start", "chunk", "run_end"]
    trace = json.loads((tmp_path / "t.json").read_text())
    assert any(e.get("ph") == "X" for e in trace["traceEvents"])


def test_telemetry_planes_off_are_noops():
    t = obs.Telemetry()  # metrics only: no journal, trace, or server
    t.event("chunk", lo=0)
    t.sample("occupancy", pool=1.0)
    with t.span("phase"):
        pass
    t.count("ok_total")
    assert t.journal is None and t.tracer is None and t.server is None
    t.close()


def test_heartbeat_reads_registry():
    r = obsm.Registry()
    out = io.StringIO()
    hb = obs.Heartbeat(r, total_seeds=1000, prefix="sweep", out=out)
    r.counter("sweep_seeds_done_total").inc(250)
    r.gauge("sweep_occupancy").set(0.875)
    line = hb.tick(force=True)
    assert "250/1000 seeds" in line
    assert "occ 0.875" in line
    assert "ETA" in line
    assert out.getvalue().strip() == line
    # min_interval throttling: a second immediate tick is suppressed
    hb2 = obs.Heartbeat(r, 1000, prefix="sweep", out=out,
                        min_interval_s=3600)
    assert hb2.tick(force=True) is not None
    assert hb2.tick() is None


# -- RuntimeMetrics shim joined to the exposition path ----------------------


def test_runtime_metrics_shim_exposed():
    rt = ms.Runtime(seed=9)

    async def main():
        h = ms.current_handle()
        node = h.create_node().name("svc").build()

        async def forever():
            await ms.sleep(1000.0)

        node.spawn(forever())
        await ms.sleep(0.1)
        # census mid-sim, while the task is live
        m = h.metrics()
        assert m.num_nodes() >= 1
        assert m.num_tasks() >= 1
        by_node = m.num_tasks_by_node()
        assert any("svc" in str(k) for k in by_node)
        r = obsm.Registry()
        obs.bind_runtime_metrics(r, m)
        text = obs.render_prometheus(r)
        assert "madsim_runtime_nodes" in text
        assert "madsim_runtime_tasks" in text
        assert 'madsim_runtime_tasks_by_node{node="' in text
        snap = r.snapshot()
        assert snap["madsim_runtime_tasks"] == m.num_tasks()

    rt.block_on(main())


# -- Chrome-trace JSON golden shape -----------------------------------------

# every event the exporters may emit must carry exactly these keys —
# the contract chrome://tracing and Perfetto parse against
_REQUIRED = {
    "X": {"name", "ph", "pid", "tid", "ts", "dur"},
    "M": {"name", "ph", "pid", "args"},
    "i": {"name", "ph", "pid", "tid", "ts", "s"},
    "C": {"name", "ph", "pid", "ts", "args"},
}


def _check_shape(events):
    assert events, "no trace events"
    for e in events:
        need = _REQUIRED[e["ph"]]
        missing = need - set(e)
        assert not missing, f"{e['ph']} event missing {missing}: {e}"
        if e["ph"] == "X":
            assert e["dur"] > 0 and e["ts"] >= 0


def test_tracer_golden_shape(tmp_path):
    rt = ms.Runtime(seed=41)
    tracer = tracing.Tracer().install(rt)

    async def main():
        h = ms.current_handle()
        node = h.create_node().name("golden").build()

        async def work():
            await ms.sleep(0.2)

        await node.spawn(work())

    rt.block_on(main())
    path = tmp_path / "trace.json"
    tracer.save(str(path))
    data = json.loads(path.read_text())
    assert set(data) == {"traceEvents"}
    _check_shape(data["traceEvents"])
    polls = [e for e in data["traceEvents"] if e.get("cat") == "poll"]
    assert polls and all(e["ph"] == "X" for e in polls)
    meta = [e for e in data["traceEvents"] if e["ph"] == "M"]
    assert any(e["args"]["name"] == "golden" for e in meta)


def test_span_tracer_golden_shape(tmp_path):
    st = tracing.SpanTracer()
    with st.span("chunk lo=0 dispatch-to-summary", track="dispatch",
                 args={"k": 32}):
        with st.span("host flush lo=0", track="host"):
            pass
    st.complete("round 1 dispatch-to-state", 10.0, 5.0, track="dispatch")
    st.instant("snapshot", track="host")
    st.counter("stream occupancy", occupancy=0.875, queue=96)
    path = tmp_path / "spans.json"
    st.save(str(path))
    data = json.loads(path.read_text())
    events = data["traceEvents"]
    _check_shape(events)
    # named tracks announced via thread_name metadata (numbered in
    # first-RECORD order: the nested host span completes before the
    # dispatch span that encloses it)
    tracks = {
        e["args"]["name"]: e["tid"]
        for e in events
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert set(tracks) == {"dispatch", "host"}
    by_name = {e["name"]: e for e in events if e["ph"] == "X"}
    chunk = "chunk lo=0 dispatch-to-summary"
    assert by_name[chunk]["tid"] == tracks["dispatch"]
    assert by_name["host flush lo=0"]["tid"] == tracks["host"]
    assert by_name[chunk]["args"] == {"k": 32}
    assert by_name["round 1 dispatch-to-state"]["ts"] == 10.0
    assert by_name["round 1 dispatch-to-state"]["dur"] == 5.0
    # the nested host span's window sits inside the dispatch span's
    dev, host = by_name[chunk], by_name["host flush lo=0"]
    assert dev["ts"] <= host["ts"]
    assert host["ts"] + host["dur"] <= dev["ts"] + dev["dur"] + 1e-6
    (c,) = [e for e in events if e["ph"] == "C"]
    assert c["args"] == {"occupancy": 0.875, "queue": 96.0}
