"""Genuine Kafka binary wire tests: the vendored probe client (or
kafka-python, when importable) driving ``kafka/wire.py`` — ApiVersions
negotiation, Metadata, Produce/Fetch with record-batch v2 + CRC32C,
ListOffsets, and the full consumer-group session
(FindCoordinator/Join/Sync/Heartbeat/OffsetCommit/OffsetFetch/Leave) —
over BOTH tiers: real TCP and the simulator's Endpoint pipes, where the
transcript must be byte-deterministic across runs of one seed."""

import asyncio
import hashlib

import pytest

import madsim_tpu as ms
from madsim_tpu.kafka import wire
from madsim_tpu.kafka.probe import (
    LoopbackTransport,
    ProbeClient,
    RealTransport,
    SimTransport,
)

# -- codec units ------------------------------------------------------------


def test_crc32c_vectors():
    # RFC 3720 test vector + the empty string
    assert wire.crc32c(b"") == 0
    assert wire.crc32c(b"123456789") == 0xE3069283


def test_varint_zigzag_roundtrip():
    for v in (0, 1, -1, 63, -64, 64, 300, -301, 2**31 - 1, -(2**31),
              2**62, -(2**62)):
        w = wire.Writer().varint(v)
        assert wire.Reader(w.done()).varint() == v, v


def test_record_batch_roundtrip_and_crc():
    records = [(1_000, b"k0", b"v0"), (1_007, None, b"v1"),
               (1_014, b"k2", None)]
    blob = wire.encode_record_batch(37, records)
    rows = wire.decode_record_batches(blob)
    assert rows == [(37, 1_000, b"k0", b"v0"), (38, 1_007, None, b"v1"),
                    (39, 1_014, b"k2", None)]
    # a flipped payload byte must fail the CRC32C check, not half-decode
    bad = bytearray(blob)
    bad[-1] ^= 0x01
    with pytest.raises(wire.WireError):
        wire.decode_record_batches(bytes(bad))


def test_frame_buffer_reassembles_arbitrary_chunking():
    frames = [b"alpha", b"", b"a much longer frame body " * 7]
    stream = b"".join(wire.frame(f) for f in frames)
    for chunk in (1, 2, 3, 5, len(stream)):
        buf = wire.FrameBuffer()
        got = []
        for i in range(0, len(stream), chunk):
            got.extend(buf.feed(stream[i:i + chunk]))
        assert got == frames, chunk


def test_unsupported_api_version_answers_apiversions_v0_error():
    """KIP-511: an out-of-range ApiVersions request still gets a v0 body
    with UNSUPPORTED_VERSION + the full matrix, so clients can downshift;
    any other API out of range (or an unknown key) drops the connection."""
    k = wire.KafkaWire()
    req = (wire.Writer().i16(wire.API_VERSIONS).i16(99).i32(7)
           .nullable_string("probe"))
    rsp = k.handle_frame(req.done())
    r = wire.Reader(rsp)
    assert r.i32() == 7  # correlation id
    assert r.i16() == wire.ERR_UNSUPPORTED_VERSION
    apis = {}
    r.array(lambda: apis.update({r.i16(): (r.i16(), r.i16())}))
    assert apis == {a: (lo, hi) for a, (lo, hi, _f) in
                    wire.SUPPORTED_APIS.items()}

    with pytest.raises(wire.WireError):
        k.handle_frame(wire.Writer().i16(wire.API_FETCH).i16(0).i32(1)
                       .nullable_string(None).done())
    with pytest.raises(wire.WireError):
        k.handle_frame(wire.Writer().i16(12345).i16(0).i32(1).done())


def test_produce_acks_zero_gets_no_response():
    async def main():
        k = wire.KafkaWire()
        c = ProbeClient(LoopbackTransport(k))
        await c.create_topics([("t", 1)])
        err, base = await c.produce("t", 0, [(5, None, b"x")], acks=0)
        assert (err, base) == (0, -1)
        err, _high, rows = await c.fetch("t", 0, 0)
        assert err == 0 and [r[3] for r in rows] == [b"x"]

    asyncio.run(main())


# -- the canonical session, shared by both tiers ----------------------------


async def run_probe_session(client: ProbeClient, recorder=None) -> dict:
    """ApiVersions -> Metadata -> CreateTopics -> Produce -> Fetch ->
    ListOffsets -> a full two-member consumer-group session with a
    mid-session rebalance. Returns the outcome summary; records a
    HostRecorder history checked against the kafka LogSpec when asked."""
    from madsim_tpu.oracle import HostRecorder, check_history
    from madsim_tpu.oracle.history import OP_FETCH, OP_PRODUCE
    from madsim_tpu.oracle.specs import LogSpec

    rec = recorder or HostRecorder(clock=lambda: 0)

    err, apis = await client.api_versions(ver=0)
    assert err == 0 and apis == {
        a: (lo, hi) for a, (lo, hi, _f) in wire.SUPPORTED_APIS.items()
    }
    err, apis3 = await client.api_versions(ver=3)  # the flexible form
    assert err == 0 and apis3 == apis

    out = await client.create_topics([("wt", 2)])
    assert out == [("wt", 0, None)]
    md = await client.metadata()
    assert md == {"wt": 2}

    produced = []
    for i in range(8):
        p = i % 2
        opid = rec.invoke(client=0, op=OP_PRODUCE, key=p, inp=i)
        err, off = await client.produce(
            "wt", p, [(1_000 + i, f"k{i}".encode(), f"v{i}".encode())]
        )
        assert err == 0
        rec.complete(client=0, opid=opid, out=off + 1)
        produced.append((p, off))

    # fetch both partitions from 0, contiguously (LogSpec structural)
    fetched = {}
    for p in (0, 1):
        offset = 0
        rows_all = []
        while True:
            opid = rec.invoke(client=1, op=OP_FETCH, key=p, inp=offset)
            err, high, rows = await client.fetch("wt", p, offset)
            assert err == 0
            rec.complete(client=1, opid=opid, out=len(rows))
            if not rows:
                break
            rows_all.extend(rows)
            offset = rows[-1][0] + 1
        assert [r[3] for r in rows_all] == [
            f"v{i}".encode() for i in range(8) if i % 2 == p
        ]
        fetched[p] = len(rows_all)

    result = check_history(rec.history(), LogSpec())
    assert result.ok, result.reason

    err, _ts, latest = await client.list_offsets("wt", 0, -1)
    assert err == 0 and latest == 4
    err, _ts, earliest = await client.list_offsets("wt", 0, -2)
    assert err == 0 and earliest == 0

    # consumer-group session with a mid-session rebalance
    m0, g0, a0 = await client.group_session("cg", ["wt"])
    assert len(a0) == 2
    assert await client.heartbeat("cg", g0, m0) == 0
    m1, g1, a1 = await client.group_session("cg", ["wt"])
    assert g1 == g0 + 1 and len(a1) == 1
    assert await client.heartbeat("cg", g0, m0) == wire.ERR_REBALANCE_IN_PROGRESS
    m0b, g0b, a0b = await client.group_session("cg", ["wt"], member_id=m0)
    assert m0b == m0 and g0b == g1 and len(a0b) == 1
    assert sorted(a0b + a1) == [("wt", 0), ("wt", 1)]

    commits = await client.offset_commit("cg", g0b, m0, [a0b[0] + (3,)])
    assert commits == [(a0b[0][0], a0b[0][1], 0)]
    stale = await client.offset_commit("cg", g0, m0, [a0b[0] + (1,)])
    assert stale[0][2] == wire.ERR_ILLEGAL_GENERATION
    got = await client.offset_fetch("cg", [a0b[0], a1[0]])
    assert (a0b[0][0], a0b[0][1], 3) in got
    assert (a1[0][0], a1[0][1], None) in got

    assert await client.leave_group("cg", m1) == 0
    assert await client.heartbeat("cg", g0b, m0) == wire.ERR_REBALANCE_IN_PROGRESS

    return {"produced": produced, "fetched": fetched,
            "group": [m0, m1, g0, g1]}


# -- real tier: genuine TCP --------------------------------------------------


def test_wire_session_over_real_tcp():
    from madsim_tpu import real

    async def main():
        server = wire.WireServer()
        task = real.spawn(server.serve(("127.0.0.1", 0)))
        while server.bound_addr is None:
            if task.done():
                task.result()
            await real.sleep(0.005)
        client = ProbeClient(await RealTransport.connect(server.bound_addr))
        out = await run_probe_session(client)
        assert out["fetched"] == {0: 4, 1: 4}
        client.close()
        task.abort()

    real.Runtime().block_on(main())


def test_wire_session_with_kafka_python_if_available():
    """The stock-client leg proper: kafka-python against the wire server
    (skipped when the library is absent — the vendored probe then holds
    the round-trip story, as the module docstring explains)."""
    kafka_lib = pytest.importorskip("kafka")
    from madsim_tpu import real

    async def main():
        server = wire.WireServer()
        task = real.spawn(server.serve(("127.0.0.1", 0)))
        while server.bound_addr is None:
            await real.sleep(0.005)
        host, port = server.bound_addr
        loop = asyncio.get_running_loop()

        def stock_roundtrip():
            admin = kafka_lib.KafkaAdminClient(
                bootstrap_servers=f"{host}:{port}"
            )
            from kafka.admin import NewTopic

            admin.create_topics([NewTopic("st", 2, 1)])
            prod = kafka_lib.KafkaProducer(bootstrap_servers=f"{host}:{port}")
            for i in range(4):
                prod.send("st", key=b"k%d" % i, value=b"v%d" % i,
                          partition=i % 2)
            prod.flush()
            cons = kafka_lib.KafkaConsumer(
                "st", bootstrap_servers=f"{host}:{port}",
                group_id="stock-grp", auto_offset_reset="earliest",
                consumer_timeout_ms=5000,
            )
            got = sorted(m.value for m in cons)
            cons.close()
            prod.close()
            admin.close()
            return got

        got = await loop.run_in_executor(None, stock_roundtrip)
        assert got == [b"v0", b"v1", b"v2", b"v3"]
        task.abort()

    real.Runtime().block_on(main())


# -- sim tier: Endpoint pipes + byte-deterministic transcripts ---------------

BROKER = "10.0.0.1:9092"


def _sim_session(seed: int) -> str:
    """One full probe session inside the simulator; returns the sha256
    of the server's (request, clock, response) transcript."""
    rt = ms.Runtime(seed=seed)

    async def main():
        h = ms.current_handle()
        server = wire.SimWireServer()
        h.create_node().name("broker").ip("10.0.0.1").init(
            lambda: server.serve(BROKER)
        ).build()
        node = h.create_node().name("client").ip("10.0.0.2").build()
        await ms.sleep(0.1)
        server.wire.recorder = transcript = []

        async def run():
            client = ProbeClient(await SimTransport.connect(BROKER))
            out = await run_probe_session(client)
            assert out["fetched"] == {0: 4, 1: 4}
            client.close()

        await node.spawn(run())
        acc = hashlib.sha256()
        for req, now, rsp in transcript:
            acc.update(req)
            acc.update(str(now).encode())
            acc.update(rsp if rsp is not None else b"\x00")
        return acc.hexdigest()

    return rt.block_on(main())


def test_wire_session_over_sim_pipes_transcript_deterministic():
    """The same genuine protocol session runs over the sim tier's
    Endpoint/connect1 pipes, and two runs of one seed produce
    byte-identical wire transcripts (the cross-process variant is the
    determinism gate's wire leg)."""
    d1 = _sim_session(1234)
    d2 = _sim_session(1234)
    assert d1 == d2
    assert d1 != _sim_session(1235)  # different schedule, different times


def test_wire_replay_of_recorded_transcript_is_byte_identical():
    """The purity contract the load gate leans on: re-feeding a recorded
    (frame, clock) transcript through a FRESH broker reproduces every
    response byte."""

    async def main():
        k = wire.KafkaWire(clock_ms=lambda: 4_200)
        k.recorder = transcript = []
        client = ProbeClient(LoopbackTransport(k))
        await run_probe_session(client)

        clock_feed = [now for _req, now, _rsp in transcript]
        replay = wire.KafkaWire(clock_ms=lambda: clock_feed.pop(0))
        for req, _now, rsp in transcript:
            assert replay.handle_frame(req) == rsp

    asyncio.run(main())


# -- the legacy A/B flag -----------------------------------------------------


def test_real_mode_legacy_codec_flag_roundtrip(monkeypatch):
    """MADSIM_KAFKA_LEGACY=1 swaps BOTH sides back to the pre-wire
    private framed codec (the A/B escape hatch); the client API is
    oblivious."""
    monkeypatch.setenv("MADSIM_KAFKA_LEGACY", "1")
    from madsim_tpu import real
    from madsim_tpu.kafka import NewTopic
    from madsim_tpu.real import kafka as rkafka

    async def main():
        broker = rkafka.SimBroker()
        task = real.spawn(broker.serve(("127.0.0.1", 0)))
        while broker.bound_addr is None:
            if task.done():
                task.result()
            await real.sleep(0.005)
        assert broker.wire_server is None  # the legacy dispatcher is up
        addr = "%s:%d" % broker.bound_addr
        config = rkafka.ClientConfig().set("bootstrap.servers", addr)
        admin = await config.create(rkafka.AdminClient)
        assert await admin.create_topics([NewTopic("lg", 1)]) == [None]
        producer = await config.create(rkafka.FutureProducer)
        assert await producer.send(
            rkafka.FutureRecord.to("lg").with_payload("old-school")
        ) == (0, 0)
        consumer = await config.create(rkafka.BaseConsumer)
        await consumer.subscribe(["lg"])
        msg = await consumer.poll(timeout_s=1.0)
        assert msg is not None and msg.payload == b"old-school"
        task.abort()

    real.Runtime().block_on(main())
