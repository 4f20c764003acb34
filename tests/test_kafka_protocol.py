"""Kafka wire-protocol client tests against the in-process stub broker
(real sockets, real encoding — the integration the reference only ever got
by deploying to a live cluster, SURVEY.md §4)."""

import asyncio
import json
import time

import numpy as np
import pytest

from storm_tpu.config import Config, OffsetsConfig
from storm_tpu.connectors.kafka_protocol import (
    KafkaProtocolError,
    KafkaWireBroker,
    KafkaWireClient,
    decode_message_set,
    encode_message_set,
)
from tests.kafka_stub import KafkaStubBroker


@pytest.fixture()
def stub():
    b = KafkaStubBroker(partitions=2)
    yield b
    b.close()


@pytest.fixture()
def client(stub):
    c = KafkaWireClient(f"127.0.0.1:{stub.port}")
    yield c
    c.close()


def test_message_set_roundtrip():
    recs = [(b"k1", b"v1"), (None, b"v2")]
    data = encode_message_set(recs, 1234567, offsets=[5, 6])
    out = decode_message_set("t", 0, data)
    assert [(r.key, r.value, r.offset) for r in out] == [
        (b"k1", b"v1", 5), (None, b"v2", 6)
    ]


def test_metadata_and_partitions(client):
    assert client.partitions_for("topic-a") == 2


def test_produce_fetch_roundtrip(client):
    base = client.produce("t", 0, [(None, b"hello"), (b"k", b"world")])
    assert base == 0
    recs = client.fetch("t", 0, 0)
    assert [r.value for r in recs] == [b"hello", b"world"]
    assert recs[1].key == b"k"
    # fetch from mid-offset
    recs2 = client.fetch("t", 0, 1)
    assert [r.value for r in recs2] == [b"world"]


def test_list_offsets(client):
    assert client.list_offset("t2", 0, -1) == 0
    client.produce("t2", 0, [(None, b"x")] * 3)
    assert client.list_offset("t2", 0, -1) == 3
    assert client.list_offset("t2", 0, -2) == 0


def test_offset_commit_fetch(client):
    assert client.offset_fetch("g1", "t3", 0) is None
    client.offset_commit("g1", "t3", 0, 42)
    assert client.offset_fetch("g1", "t3", 0) == 42


def test_wire_broker_surface(stub):
    broker = KafkaWireBroker(f"127.0.0.1:{stub.port}")
    p, off = broker.produce("t4", "payload-1")
    assert off == 0
    assert broker.latest_offset("t4", p) == 1
    recs = broker.fetch("t4", p, 0)
    assert recs[0].value == b"payload-1"
    broker.commit("g", "t4", p, 1)
    assert broker.committed("g", "t4", p) == 1
    broker.close()


def test_wire_broker_key_affinity(stub):
    broker = KafkaWireBroker(f"127.0.0.1:{stub.port}")
    parts = {broker.produce("t5", f"v{i}", key="samekey")[0] for i in range(5)}
    assert len(parts) == 1
    broker.close()


def test_end_to_end_topology_over_sockets(stub, run):
    """Full streaming topology with ingress AND egress over the real wire
    protocol: socket in -> spout -> bolt -> sink -> socket out."""
    from tests.test_runtime import PassBolt
    from storm_tpu.connectors import BrokerSink, BrokerSpout
    from storm_tpu.connectors.sink import Producer
    from storm_tpu.runtime import TopologyBuilder
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    broker = KafkaWireBroker(f"127.0.0.1:{stub.port}")

    class WireProducer(Producer):
        async def send(self, topic, value, key):
            await asyncio.to_thread(broker.produce, topic, value, key)

    class WireSink(BrokerSink):
        def make_producer(self):
            return WireProducer()

    async def go():
        cfg = Config()
        tb = TopologyBuilder()
        tb.set_spout(
            "in",
            BrokerSpout(broker, "wire-in", OffsetsConfig(policy="earliest", max_behind=None)),
            2,
        )
        tb.set_bolt("mid", PassBolt(), 2).shuffle_grouping("in")
        tb.set_bolt("out", WireSink(None, "wire-out", cfg.sink), 1).shuffle_grouping("mid")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("wire", cfg, tb.build())
        for i in range(6):
            broker.produce("wire-in", f"msg-{i}")
        deadline = asyncio.get_event_loop().time() + 30
        while asyncio.get_event_loop().time() < deadline:
            if stub.topic_size("wire-out") >= 6:
                break
            await asyncio.sleep(0.05)
        out = []
        for p in range(2):
            out.extend(broker.fetch("wire-out", p, 0, 100))
        await cluster.shutdown()
        return out

    out = run(go(), timeout=60)
    assert sorted(r.value.decode() for r in out) == [f"msg-{i}" for i in range(6)]
    broker.close()


def test_wire_broker_fetch_buffers_remainder(stub):
    """A wire fetch decoding more than max_records must buffer the tail and
    serve it on the next poll instead of re-fetching the same bytes."""
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker

    b = KafkaWireBroker(f"127.0.0.1:{stub.port}")
    try:
        for i in range(20):
            b.produce("bulk", f"m{i}", partition=0)
        first = b.fetch("bulk", 0, 0, max_records=5)
        assert [r.offset for r in first] == [0, 1, 2, 3, 4]
        assert ("bulk", 0) in b._prefetch
        second = b.fetch("bulk", 0, 5, max_records=5)
        assert [r.offset for r in second] == [5, 6, 7, 8, 9]
        # A seek (offset mismatch) invalidates the buffer instead of serving it.
        seek = b.fetch("bulk", 0, 12, max_records=5)
        assert [r.offset for r in seek][0] == 12
    finally:
        b.close()


def test_gzip_wrapper_message_decode():
    """gzip-compressed wrapper (magic 1, KIP-31 relative inner offsets) is
    transparently decompressed; snappy/lz4 still reject."""
    import gzip
    import struct
    import zlib

    from storm_tpu.connectors.kafka_protocol import (
        Writer,
        decode_message_set,
        encode_message_set,
    )

    inner = encode_message_set(
        [(None, b"v0"), (None, b"v1"), (b"k", b"v2")],
        ts_ms=1_700_000_000_000,
        offsets=[0, 1, 2],  # relative per KIP-31
    )
    wrapped = gzip.compress(inner)
    msg = Writer()
    msg.i8(1)  # magic
    msg.i8(1)  # attributes: gzip
    msg.i64(1_700_000_000_000)
    msg.bytes_(None)
    msg.bytes_(wrapped)
    crc = zlib.crc32(bytes(msg.buf)) & 0xFFFFFFFF
    full = Writer()
    full.i64(107)  # wrapper offset = offset of LAST inner message
    full.i32(4 + len(msg.buf))
    full.buf += struct.pack(">I", crc)
    full.raw(bytes(msg.buf))

    recs = decode_message_set("t", 0, bytes(full.buf))
    assert [r.value for r in recs] == [b"v0", b"v1", b"v2"]
    assert [r.offset for r in recs] == [105, 106, 107]
    assert recs[2].key == b"k"

    # unsupported codec (zstd=4) still raises; gzip/snappy/lz4 all decode
    from storm_tpu.connectors.kafka_protocol import KafkaProtocolError

    msg2 = Writer()
    msg2.i8(1)
    msg2.i8(4)  # zstd
    msg2.i64(0)
    msg2.bytes_(None)
    msg2.bytes_(b"xx")
    crc2 = zlib.crc32(bytes(msg2.buf)) & 0xFFFFFFFF
    full2 = Writer()
    full2.i64(0)
    full2.i32(4 + len(msg2.buf))
    full2.buf += struct.pack(">I", crc2)
    full2.raw(bytes(msg2.buf))
    with pytest.raises(KafkaProtocolError, match="codec"):
        decode_message_set("t", 0, bytes(full2.buf))


def test_snappy_block_decode_literals_and_copies():
    """Raw snappy block format: literals, 1/2-byte-offset backref copies,
    and overlapping (RLE) copies — decoded against hand-crafted streams so
    the decoder is validated independently of our own encoder."""
    from storm_tpu.connectors.snappy import (SnappyError, compress,
                                             decompress, decompress_raw)

    # "abcdabcdabcd": literal "abcd" + overlapping copy len=8 off=4
    # tag copy-1: kind=1, len 8 -> ((8-4)&7)<<2 | 1 ; off=4 -> hi=0, lo=4
    crafted = bytearray()
    crafted.append(12)  # uncompressed length varint = 12
    crafted.append((3 << 2) | 0)  # literal, len 4
    crafted += b"abcd"
    crafted.append(((8 - 4) << 2) | 1)  # copy-1: len 8, offset hi bits 0
    crafted.append(4)  # offset lo byte = 4
    assert decompress_raw(bytes(crafted)) == b"abcdabcdabcd"

    # 2-byte-offset copy: 70 literal bytes then re-copy the first 10
    lit = bytes(range(60)) + b"0123456789"
    crafted2 = bytearray()
    crafted2.append(80)  # uncompressed length
    crafted2.append(60 << 2)  # literal code 60: 1-byte explicit length
    crafted2.append(len(lit) - 1)
    crafted2 += lit
    crafted2.append((9 << 2) | 2)  # copy-2: len 10
    crafted2 += (70).to_bytes(2, "little")  # offset 70 = start
    assert decompress_raw(bytes(crafted2)) == lit + lit[:10]

    # our literal-only encoder round-trips through the real decoder
    data = b"storm-tpu " * 500
    assert decompress(compress(data)) == data
    assert decompress(compress(data, xerial=True)) == data  # framed

    # corrupt streams fail loudly, not silently
    with pytest.raises(SnappyError):
        decompress_raw(b"\x05\x00")  # truncated literal
    with pytest.raises(SnappyError):
        decompress_raw(bytes([4, (3 << 2) | 1, 9]))  # offset past output
    # xerial magic present but version/compat ints truncated: must raise,
    # not silently decode a corrupt message as b"".
    from storm_tpu.connectors.snappy import _XERIAL_MAGIC
    with pytest.raises(SnappyError):
        decompress(_XERIAL_MAGIC + b"\x00\x01")


def test_snappy_record_batch_and_wrapper_fetch(stub):
    """End-to-end over sockets: a producer shipping snappy record batches
    (the stub parses them through the shared decode path) delivers intact
    records back on fetch — Kafka-0.11-era snappy producers are readable
    (reference pom.xml:55-78)."""
    from storm_tpu.connectors.kafka_protocol import (
        KafkaWireBroker, decode_message_set, encode_record_batch)
    from storm_tpu.connectors.snappy import compress

    # over real sockets: snappy-compressed v2 batches to the stub broker
    b = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2",
                        compression="snappy")
    try:
        b.produce("snap", b"s0", partition=0)
        b.produce("snap", b"s1", key=b"k", partition=0)
        recs = b.fetch("snap", 0, 0)
        assert [r.value for r in recs] == [b"s0", b"s1"]
        assert recs[1].key == b"k"
    finally:
        b.close()

    # unit: snappy batch encodes -> shared decode path reads it back
    batch = encode_record_batch(
        [(None, b"s0"), (b"k", b"s1")], ts_ms=1_700_000_000_000,
        base_offset=5, compression="snappy")
    recs = decode_message_set("t", 0, batch)
    assert [r.value for r in recs] == [b"s0", b"s1"]
    assert [r.offset for r in recs] == [5, 6]
    assert recs[1].key == b"k"

    # xerial-framed wrapper value (what snappy-java producers emit for
    # magic-1 message sets)
    import struct
    import zlib

    from storm_tpu.connectors.kafka_protocol import (Writer,
                                                     encode_message_set)

    inner = encode_message_set(
        [(None, b"x0"), (None, b"x1")], ts_ms=1_700_000_000_000,
        offsets=[0, 1])
    msg = Writer()
    msg.i8(1)  # magic
    msg.i8(2)  # attributes: snappy
    msg.i64(1_700_000_000_000)
    msg.bytes_(None)
    msg.bytes_(compress(inner, xerial=True))
    crc = zlib.crc32(bytes(msg.buf)) & 0xFFFFFFFF
    full = Writer()
    full.i64(1)  # wrapper offset = last inner
    full.i32(4 + len(msg.buf))
    full.buf += struct.pack(">I", crc)
    full.raw(bytes(msg.buf))
    recs = decode_message_set("t", 0, bytes(full.buf))
    assert [r.value for r in recs] == [b"x0", b"x1"]
    assert [r.offset for r in recs] == [0, 1]


# ---- record batches (format v2, KIP-98) --------------------------------------


def test_record_batch_roundtrip():
    from storm_tpu.connectors.kafka_protocol import (
        decode_record_batch,
        encode_record_batch,
    )

    records = [(None, b"v0"), (b"k1", b"v1"), (b"", b""), (b"k3", b"x" * 500)]
    batch = encode_record_batch(records, ts_ms=1_700_000_000_000, base_offset=42)
    out, consumed = decode_record_batch("t", 0, batch, verify_crc=True)
    assert consumed == len(batch)
    assert [(r.key, r.value) for r in out] == records
    assert [r.offset for r in out] == [42, 43, 44, 45]
    assert abs(out[0].timestamp - 1_700_000_000.0) < 1e-6


def test_record_batch_crc_is_crc32c():
    from storm_tpu.connectors.kafka_protocol import encode_record_batch
    from storm_tpu.native import crc32c

    batch = encode_record_batch([(b"k", b"v")], ts_ms=0)
    crc = int.from_bytes(batch[17:21], "big")
    assert crc == crc32c(batch[21:])


def test_record_batch_corruption_detected():
    from storm_tpu.connectors.kafka_protocol import (
        KafkaProtocolError,
        decode_record_batch,
        encode_record_batch,
    )

    batch = bytearray(encode_record_batch([(b"k", b"hello")], ts_ms=0))
    batch[-2] ^= 0xFF  # flip a payload byte
    with pytest.raises(KafkaProtocolError, match="CRC32C"):
        decode_record_batch("t", 0, bytes(batch), verify_crc=True)


def test_decode_message_set_sniffs_magic2():
    """A fetch response mixing v2 batches is decoded transparently."""
    from storm_tpu.connectors.kafka_protocol import (
        decode_message_set,
        encode_record_batch,
    )

    b1 = encode_record_batch([(None, b"a"), (None, b"b")], ts_ms=0, base_offset=0)
    b2 = encode_record_batch([(None, b"c")], ts_ms=0, base_offset=2)
    records = decode_message_set("t", 1, b1 + b2)
    assert [r.value for r in records] == [b"a", b"b", b"c"]
    assert [r.offset for r in records] == [0, 1, 2]


@pytest.mark.parametrize(
    "v", [0, 1, -1, 63, -64, 64, 300, -300, 2**31, -(2**31), 2**62])
def test_varint_zigzag_edges(v):
    from storm_tpu.connectors.kafka_protocol import _read_varint, _write_varint

    buf = bytearray()
    _write_varint(buf, v)
    got, pos = _read_varint(bytes(buf), 0)
    assert got == v and pos == len(buf)


def test_wire_client_produces_and_fetches_v2_batches():
    """Full socket round trip: Produce v3 with a RecordBatch up, Fetch
    serving RecordBatches down."""
    from kafka_stub import KafkaStubBroker
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker

    stub = KafkaStubBroker(partitions=1)
    stub.serve_batches = True
    try:
        broker = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2")
        for i in range(5):
            broker.produce("t2", f"m{i}")
        got = broker.fetch("t2", 0, 0, max_records=10)
        assert [r.value for r in got] == [f"m{i}".encode() for i in range(5)]
        assert [r.offset for r in got] == list(range(5))
    finally:
        stub.close()


def test_record_batch_gzip_roundtrip():
    from storm_tpu.connectors.kafka_protocol import (
        decode_record_batch,
        encode_record_batch,
    )

    records = [(None, b"x" * 400)] * 10  # compressible
    plain = encode_record_batch(records, ts_ms=0)
    gz = encode_record_batch(records, ts_ms=0, compression="gzip")
    assert len(gz) < len(plain) / 3
    out, consumed = decode_record_batch("t", 0, gz, verify_crc=True)
    assert consumed == len(gz)
    assert [(r.key, r.value) for r in out] == records


def test_wire_client_gzip_v2_over_socket():
    from kafka_stub import KafkaStubBroker
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker

    stub = KafkaStubBroker(partitions=1)
    try:
        broker = KafkaWireBroker(f"127.0.0.1:{stub.port}",
                                 message_format="v2", compression="gzip")
        for i in range(4):
            broker.produce("gz", f"msg-{i}" * 50)
        got = broker.fetch("gz", 0, 0, max_records=10)
        assert [r.value for r in got] == [f"msg-{i}".encode() * 50 for i in range(4)]
    finally:
        stub.close()


# ---- consumer-group coordination ---------------------------------------------


def _stabilize(members, timeout=20.0):
    """One loop per member, like real consumers: heartbeat; on rebalance,
    rejoin. Stops once every member is stable with an assignment."""
    import threading
    import time as _time

    assigns: dict = {}
    done = threading.Event()

    def run(m):
        end = _time.monotonic() + timeout
        while not done.is_set() and _time.monotonic() < end:
            try:
                if m not in assigns or m.generation < 0 or not m.heartbeat():
                    assigns[m] = m.join(max_attempts=5)
                else:
                    _time.sleep(0.02)
            except Exception:
                _time.sleep(0.05)

    threads = [threading.Thread(target=run, args=(m,)) for m in members]
    for t in threads:
        t.start()
    end = _time.monotonic() + timeout
    while _time.monotonic() < end:
        if all(m in assigns for m in members) and \
                all(m.heartbeat() for m in members):
            break
        _time.sleep(0.05)
    done.set()
    for t in threads:
        t.join(timeout=5)
    assert all(m in assigns for m in members), "members never stabilized"
    assert all(m.heartbeat() for m in members)
    return [assigns[m] for m in members]


def test_group_membership_splits_and_rebalances():
    """Two members split partitions via the join/sync protocol; one leaving
    rebalances the survivor onto everything — over real sockets."""
    from kafka_stub import KafkaStubBroker
    from storm_tpu.connectors.kafka_protocol import GroupMembership, KafkaWireClient

    stub = KafkaStubBroker(partitions=4)
    try:
        c1 = KafkaWireClient(f"127.0.0.1:{stub.port}")
        c2 = KafkaWireClient(f"127.0.0.1:{stub.port}")
        c1.partitions_for("t")  # create the topic
        m1 = GroupMembership(c1, "g", ["t"])
        m2 = GroupMembership(c2, "g", ["t"])

        (a1,) = _stabilize([m1])
        assert sorted(a1) == [("t", 0), ("t", 1), ("t", 2), ("t", 3)]

        a1, a2 = _stabilize([m1, m2])
        assert sorted(a1 + a2) == [("t", 0), ("t", 1), ("t", 2), ("t", 3)]
        assert len(a1) == len(a2) == 2
        assert not set(a1) & set(a2)

        # member 2 leaves: survivor rebalances onto all partitions
        m2.leave()
        assert not m1.heartbeat()
        (a1,) = _stabilize([m1])
        assert sorted(a1) == [("t", 0), ("t", 1), ("t", 2), ("t", 3)]
        m1.leave()
    finally:
        stub.close()


def test_group_membership_three_members_range():
    from kafka_stub import KafkaStubBroker
    from storm_tpu.connectors.kafka_protocol import GroupMembership, KafkaWireClient

    stub = KafkaStubBroker(partitions=5)
    try:
        clients = [KafkaWireClient(f"127.0.0.1:{stub.port}") for _ in range(3)]
        clients[0].partitions_for("t")
        members = [GroupMembership(c, "g3", ["t"]) for c in clients]
        assigns = _stabilize(members)
        allp = sorted(p for a in assigns for p in a)
        assert allp == [("t", i) for i in range(5)]
        sizes = sorted(len(a) for a in assigns)
        assert sizes == [1, 2, 2]  # 5 partitions over 3 members, range-style
    finally:
        stub.close()


def test_group_dead_member_expires():
    """A member that vanishes without leave() is expired by its session
    timeout, unwedging the survivors."""
    import time as _time

    from kafka_stub import KafkaStubBroker
    from storm_tpu.connectors.kafka_protocol import GroupMembership, KafkaWireClient

    stub = KafkaStubBroker(partitions=2)
    try:
        c1 = KafkaWireClient(f"127.0.0.1:{stub.port}")
        c2 = KafkaWireClient(f"127.0.0.1:{stub.port}")
        c1.partitions_for("t")
        m1 = GroupMembership(c1, "g", ["t"], session_timeout_ms=500)
        m2 = GroupMembership(c2, "g", ["t"], session_timeout_ms=500)
        a1, a2 = _stabilize([m1, m2])
        assert len(a1) == len(a2) == 1
        # m2 dies silently (no leave, no heartbeats)
        _time.sleep(0.8)
        assert not m1.heartbeat()  # expiry triggered a rebalance
        (a1,) = _stabilize([m1])
        assert sorted(a1) == [("t", 0), ("t", 1)]
    finally:
        stub.close()


def test_idempotent_produce_dedups_retried_batch():
    """KIP-98 idempotence: resending a batch with the same (pid, sequence)
    appends at most once; a sequence gap errors OUT_OF_ORDER (45)."""
    from storm_tpu.connectors.kafka_protocol import (
        KafkaProtocolError, KafkaWireClient)

    stub = KafkaStubBroker(partitions=1)
    try:
        c = KafkaWireClient(f"127.0.0.1:{stub.port}")
        pid, epoch = c.init_producer_id()
        assert pid >= 0 and epoch == 0
        # two distinct producers get distinct ids
        assert KafkaWireClient(f"127.0.0.1:{stub.port}").init_producer_id()[0] != pid

        off0 = c.produce("t", 0, [(None, b"a")], message_format="v2",
                         producer=(pid, epoch, 0))
        # simulated retry: same sequence again -> no second append, same offset
        off_dup = c.produce("t", 0, [(None, b"a")], message_format="v2",
                            producer=(pid, epoch, 0))
        assert off_dup == off0
        assert stub.topic_size("t") == 1
        # next sequence appends
        c.produce("t", 0, [(None, b"b")], message_format="v2",
                  producer=(pid, epoch, 1))
        assert stub.topic_size("t") == 2
        # gap -> out-of-order error
        with pytest.raises(KafkaProtocolError, match="45"):
            c.produce("t", 0, [(None, b"c")], message_format="v2",
                      producer=(pid, epoch, 5))
        assert stub.topic_size("t") == 2
        c.close()
    finally:
        stub.close()


def test_idempotent_broker_wrapper_sequences():
    """KafkaWireBroker(idempotent=True) stamps monotone sequences per
    partition and records survive a full produce/fetch round trip."""
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker

    stub = KafkaStubBroker(partitions=2)
    try:
        b = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2",
                            idempotent=True)
        parts = set()
        for i in range(6):
            p, off = b.produce("t", f"m{i}".encode(), partition=i % 2)
            parts.add(p)
        assert parts == {0, 1}
        assert stub.topic_size("t") == 6
        got = sorted(r.value.decode() for p in (0, 1)
                     for r in b.fetch("t", p, 0))
        assert got == [f"m{i}" for i in range(6)]
        # config validation: idempotent requires v2
        from storm_tpu.connectors.kafka_protocol import KafkaProtocolError
        with pytest.raises(KafkaProtocolError, match="message_format"):
            KafkaWireBroker(f"127.0.0.1:{stub.port}", idempotent=True)
        b.close()
    finally:
        stub.close()


def test_kafka_txn_commit_abort_fencing():
    """KafkaTxn over the wire: commit makes records visible atomically,
    abort drops them, and a re-initialized transactional id fences the
    old producer (INVALID_PRODUCER_EPOCH)."""
    from storm_tpu.connectors.kafka_protocol import (
        KafkaProtocolError, KafkaWireBroker)

    stub = KafkaStubBroker(partitions=1)
    try:
        b = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2")
        txn = b.txn("txn-test-0")
        txn.begin()
        txn.produce("t", b"a")
        txn.produce("t", b"b")
        assert stub.topic_size("t") == 0  # buffered, not visible
        txn.commit()
        assert stub.topic_size("t") == 2

        txn.begin()
        txn.produce("t", b"dropped")
        txn.abort()
        assert stub.topic_size("t") == 2

        # zombie fencing: a second handle re-inits the same txn id (epoch
        # bump); the old handle's next transaction is rejected
        b2 = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2")
        t2 = b2.txn("txn-test-0")
        t2.begin()
        txn.begin()  # zombie: stale epoch
        with pytest.raises(KafkaProtocolError):
            txn.produce("t", b"zombie")
            txn.commit()
        t2.produce("t", b"winner")
        t2.commit()
        vals = [r.value for r in b.fetch("t", 0, 0)]
        assert vals == [b"a", b"b", b"winner"]
        b.close(); b2.close()
    finally:
        stub.close()


def test_kafka_txn_network_failure_resets_producer_id():
    """A socket-level failure (OSError) mid-transaction must reset the
    producer id so the next begin() re-runs InitProducerId: the epoch bump
    makes the coordinator abort the dangling open transaction. Without the
    reset, the replay is produced into the SAME open transaction and the
    eventual commit makes both the failed attempt's records and the replay
    visible — duplicates under read-committed (exactly-once broken)."""
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker

    stub = KafkaStubBroker(partitions=1)
    try:
        b = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2")
        txn = b.txn("txn-net-0")
        txn.begin()
        txn.produce("t", b"attempt1")

        real_end_txn = b.client.end_txn

        def dead_socket(*a, **kw):
            raise OSError("connection reset by peer")

        # Records get appended (add_partitions + produce succeed), then the
        # socket dies on EndTxn: coordinator still holds the txn OPEN.
        b.client.end_txn = dead_socket
        with pytest.raises(OSError):
            txn.commit()
        assert txn._pid is None  # forces InitProducerId on next begin()
        b.client.end_txn = real_end_txn

        # Replay path: fresh begin() bumps the epoch, which drops the
        # dangling transaction's pending records at the coordinator.
        txn.begin()
        txn.produce("t", b"replay")
        txn.commit()

        vals = [r.value for r in b.fetch("t", 0, 0)]
        assert vals == [b"replay"], vals  # attempt1 aborted, no duplicate
        b.close()
    finally:
        stub.close()


def test_txn_offsets_commit_atomically(stub):
    """AddOffsetsToTxn (api 25) + TxnOffsetCommit (api 28): offsets staged
    via ``send_offsets`` become the group's committed position only when
    EndTxn commits — atomically with the produced records — and vanish on
    abort. The KIP-98 consume-transform-produce half the reference's Kafka
    0.11 era defined (pom.xml:55-78)."""
    b = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2")
    try:
        txn = b.txn("eos-wire-0")
        txn.begin()
        txn.produce("eow-out", b"r0", partition=0)
        txn.send_offsets("eow-grp", {("eow-in", 0): 5})
        # nothing visible before commit: records pending, offsets unstaged
        assert b.committed("eow-grp", "eow-in", 0) is None
        assert b.client.fetch("eow-out", 0, 0) == []
        txn.commit()
        assert b.committed("eow-grp", "eow-in", 0) == 5
        assert [r.value for r in b.client.fetch("eow-out", 0, 0)] == [b"r0"]

        # abort drops the staged offsets along with the records
        txn.begin()
        txn.produce("eow-out", b"dropped", partition=0)
        txn.send_offsets("eow-grp", {("eow-in", 0): 9})
        txn.abort()
        assert b.committed("eow-grp", "eow-in", 0) == 5
        assert [r.value for r in b.client.fetch("eow-out", 0, 0)] == [b"r0"]

        # max-wins merge across send_offsets calls within one transaction
        txn.begin()
        txn.send_offsets("eow-grp", {("eow-in", 0): 7, ("eow-in", 1): 3})
        txn.send_offsets("eow-grp", {("eow-in", 0): 6})
        txn.commit()
        assert b.committed("eow-grp", "eow-in", 0) == 7
        assert b.committed("eow-grp", "eow-in", 1) == 3
    finally:
        b.close()


def test_txn_offset_commit_requires_add_offsets(client):
    """TxnOffsetCommit for a group never registered via AddOffsetsToTxn is
    rejected (INVALID_TXN_STATE) — the stub enforces the KIP-98 ordering so
    the client can't silently skip the registration step."""
    pid, epoch = client.init_producer_id(transactional_id="eos-order")
    with pytest.raises(KafkaProtocolError):
        client.txn_offset_commit("eos-order", "never-added", pid, epoch,
                                 {("t", 0): 1})


def test_eos_consume_transform_produce_crash(stub, run):
    """The canonical exactly-once loop over the stub broker, with a crash
    in its worst window. Spout (``policy='txn'``) -> transform ->
    TransactionalBrokerSink committing consumed offsets INSIDE the producer
    transaction. Between runs, a 'crashed' task leaves a transaction OPEN
    at the coordinator with records AND offsets already shipped but EndTxn
    never sent; the restarted task's epoch bump fences it. A read-committed
    consumer must see every input exactly once (no ghost, no dupes, no
    loss) and the group offset must cover the whole log. Closes the
    documented produce-vs-checkpoint 'effectively-once' window (VERDICT r2
    missing #2)."""
    from tests.test_runtime import PassBolt
    from storm_tpu.config import SinkConfig
    from storm_tpu.connectors import BrokerSpout, TransactionalBrokerSink
    from storm_tpu.runtime import TopologyBuilder
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    GROUP = "eos-g"
    offsets_cfg = OffsetsConfig(policy="txn", group_id=GROUP,
                                max_behind=None)
    sink_cfg = SinkConfig(mode="transactional", txn_batch=4, txn_ms=30.0,
                          offsets_group=GROUP)

    def make_broker():
        return KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2")

    async def run_topology(broker, expect_out):
        tb = TopologyBuilder()
        tb.set_spout("in", BrokerSpout(broker, "eos-src", offsets_cfg), 1)
        tb.set_bolt("mid", PassBolt(), 1).shuffle_grouping("in")
        tb.set_bolt("sink",
                    TransactionalBrokerSink(broker, "eos-out", sink_cfg),
                    1).shuffle_grouping("mid")
        cluster = AsyncLocalCluster()
        await cluster.submit("eos-topo", Config(), tb.build())
        deadline = asyncio.get_event_loop().time() + 30
        while asyncio.get_event_loop().time() < deadline:
            if stub.topic_size("eos-out") >= expect_out:
                break
            await asyncio.sleep(0.05)
        await cluster.shutdown()

    # ---- run 1: six records flow through and commit --------------------------
    feeder = make_broker()
    for i in range(6):
        feeder.produce("eos-src", f"rec-{i}", partition=i % 2)
    b1 = make_broker()
    run(run_topology(b1, 6), timeout=60)
    b1.close()
    committed_after_1 = {
        p: feeder.committed(GROUP, "eos-src", p) for p in (0, 1)}
    assert committed_after_1 == {0: 3, 1: 3}, committed_after_1

    # ---- the crash: a task dies between produce and commit -------------------
    # Low-level on purpose: records and offsets are ALREADY at the broker
    # inside an open transaction for the SAME transactional id the
    # restarted sink task will claim ('<topology>-<component>-<task>');
    # EndTxn is never sent — the exact window runtime/transactional.py
    # documented as effectively-once.
    ghost = make_broker()
    txn_id = "eos-topo-sink-0"
    pid, epoch = ghost.client.init_producer_id(transactional_id=txn_id)
    ghost.client.add_partitions_to_txn(txn_id, pid, epoch, [("eos-out", 0)])
    ghost.client.produce("eos-out", 0, [(None, b"GHOST")], acks=-1,
                         message_format="v2", producer=(pid, epoch, 0),
                         transactional_id=txn_id)
    ghost.client.add_offsets_to_txn(txn_id, pid, epoch, GROUP)
    ghost.client.txn_offset_commit(txn_id, GROUP, pid, epoch,
                                   {("eos-src", 0): 999})
    ghost.close()  # crash: no EndTxn

    # open-transaction state is invisible to read-committed consumers
    assert feeder.committed(GROUP, "eos-src", 0) == 3
    assert stub.topic_size("eos-out") == 6

    # ---- run 2: restart fences the ghost, finishes the log -------------------
    for i in range(6, 10):
        feeder.produce("eos-src", f"rec-{i}", partition=i % 2)
    b2 = make_broker()
    run(run_topology(b2, 10), timeout=60)
    b2.close()

    out = []
    for p in range(2):
        out.extend(feeder.fetch("eos-out", p, 0, max_records=100))
    vals = sorted(r.value.decode() for r in out)
    assert vals == sorted(f"rec-{i}" for i in range(10)), vals
    committed = {p: feeder.committed(GROUP, "eos-src", p) for p in (0, 1)}
    assert committed == {0: 5, 1: 5}, committed
    feeder.close()


def test_eos_chaos_soak_moves_and_failures(run):
    """Exactly-once under COMBINED churn — the individual machines are
    each tested above; this soaks them together: txn spout -> fan-out
    transform (two outputs per record + one forced mid-stream tuple
    failure and replay) -> transactional sink, while the output
    partition's leader AND the group/txn coordinator both migrate
    mid-stream. A read-committed consumer must see each input's two
    outputs exactly once (no loss from the moves, no dupes from the
    replay), and the committed group offsets must cover the whole log."""
    from storm_tpu.config import SinkConfig
    from storm_tpu.connectors import BrokerSpout, TransactionalBrokerSink
    from storm_tpu.runtime import Bolt, TopologyBuilder, Values
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    GROUP = "soak-g"
    N = 16
    stub = KafkaStubBroker(partitions=2, nodes=2)
    offsets_cfg = OffsetsConfig(policy="txn", group_id=GROUP,
                                max_behind=None)
    sink_cfg = SinkConfig(mode="transactional", txn_batch=4, txn_ms=30.0,
                          offsets_group=GROUP)

    class FanOut(Bolt):
        failed_once = False

        async def execute(self, t):
            msg = t.get("message")
            if not FanOut.failed_once and msg.endswith("-7"):
                FanOut.failed_once = True
                self.collector.fail(t)  # forced failure -> entry replay
                return
            await self.collector.emit(Values([f"{msg}/a"]), anchors=[t])
            await self.collector.emit(Values([f"{msg}/b"]), anchors=[t])
            self.collector.ack(t)

    async def wait_out(n, timeout=60.0):
        deadline = asyncio.get_event_loop().time() + timeout
        while asyncio.get_event_loop().time() < deadline:
            if stub.topic_size("soak-out") >= n:
                return True
            await asyncio.sleep(0.05)
        return False

    async def go():
        FanOut.failed_once = False
        feeder = KafkaWireBroker(f"127.0.0.1:{stub.port}",
                                 message_format="v2")
        # phase 1: first half (incl. the forced r-7 failure + replay)
        for i in range(N // 2):
            feeder.produce("soak-src", f"r-{i}", partition=i % 2)
        broker = KafkaWireBroker(f"127.0.0.1:{stub.port}",
                                 message_format="v2")
        tb = TopologyBuilder()
        tb.set_spout("in", BrokerSpout(broker, "soak-src", offsets_cfg), 1)
        tb.set_bolt("fan", FanOut(), 1).shuffle_grouping("in")
        tb.set_bolt("sink",
                    TransactionalBrokerSink(broker, "soak-out", sink_cfg),
                    1).shuffle_grouping("fan")
        cluster = AsyncLocalCluster()
        await cluster.submit("soak-topo", Config(), tb.build())
        assert await wait_out(N), "phase 1 never completed"

        # churn strikes with ESTABLISHED state everywhere: live producer
        # id/epoch and sequences at the sink, cached coordinator, spout
        # mid-group — every retry path must renegotiate, not re-create
        stub.move_leader("soak-out", 0, 1)
        stub.move_leader("soak-src", 1, 1)
        stub.move_coordinator(1)

        # phase 2: second half must flow THROUGH the moved cluster
        for i in range(N // 2, N):
            feeder.produce("soak-src", f"r-{i}", partition=i % 2)
        assert await wait_out(2 * N), "phase 2 stalled after the moves"
        await cluster.shutdown()
        broker.close()
        rc = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2",
                             isolation="read_committed")
        out = []
        for p in range(2):
            out.extend(rc.fetch("soak-out", p, 0, max_records=200))
        rc.close()
        vals = sorted(r.value.decode() for r in out)
        expect = sorted(f"r-{i}/{s}" for i in range(N) for s in "ab")
        assert vals == expect, (len(vals), vals[:8])
        committed = {p: feeder.committed(GROUP, "soak-src", p)
                     for p in (0, 1)}
        assert committed == {0: N // 2, 1: N // 2}, committed
        feeder.close()

    try:
        run(go(), timeout=120)
    finally:
        stub.close()


def test_txn_policy_orders_per_partition(run):
    """policy='txn' delivers per-partition ORDERED: while one entry's tuple
    tree is open, the spout must not fetch (let alone emit) later offsets
    of that partition — otherwise a later offset could commit in the sink's
    transaction and a crash would resume past the earlier, unprocessed
    record. Other partitions keep flowing (Kafka Streams' model)."""
    from storm_tpu.connectors.memory import MemoryBroker
    from storm_tpu.connectors.spout import BrokerSpout
    from storm_tpu.runtime.base import TopologyContext

    class _Emits:
        def __init__(self):
            self.emitted = []

        async def emit(self, values, *, msg_id=None, root_ts=None,
                       origins=None, **kw):
            self.emitted.append(msg_id)
            return 1

    async def go():
        broker = MemoryBroker(default_partitions=2)
        for i in range(6):
            broker.produce("t", f"m{i}", partition=i % 2)
        spout = BrokerSpout(
            broker, "t",
            OffsetsConfig(policy="txn", group_id="g", max_behind=None))
        col = _Emits()

        class _Ctx(TopologyContext):
            pass

        ctx = _Ctx("in", 0, 1, Config())

        class _M:
            def counter(self, *a):
                class C:
                    def inc(self, *_a):  # pragma: no cover
                        pass
                return C()
        ctx.metrics = _M()
        spout.open(ctx, col)

        # first poll round: exactly ONE entry per partition, not the log
        await spout.next_tuple()
        await spout.next_tuple()
        assert sorted(col.emitted) == [(0, 0), (1, 0)], col.emitted
        # both partitions blocked until their trees complete
        for _ in range(4):
            assert not await spout.next_tuple()
        assert sorted(col.emitted) == [(0, 0), (1, 0)]
        # ack partition 0's entry: ONLY partition 0 advances
        spout.ack((0, 0))
        await spout.next_tuple()
        assert not await spout.next_tuple()
        assert sorted(col.emitted) == [(0, 0), (0, 1), (1, 0)]
        # a FAILED entry keeps its partition blocked for new fetches; the
        # replay re-emits the same entry, and only its ack unblocks
        spout.fail((1, 0))
        await spout.next_tuple()  # serves the replay queue
        assert col.emitted.count((1, 0)) == 2
        assert not any(m == (1, 1) for m in col.emitted)
        spout.ack((1, 0))
        await spout.next_tuple()
        assert (1, 1) in col.emitted

    run(go(), timeout=10)


def test_lz4_block_decode_and_frame_roundtrip():
    """LZ4 decoder validated against hand-crafted block streams (literals,
    backref matches, overlapping RLE copies) independently of our encoder;
    frame round-trip through the literal-only encoder; corrupt streams
    fail loudly. xxh32 (frame header checksum) checked against published
    test vectors inside the module tests below."""
    from storm_tpu.connectors.lz4 import (Lz4Error, _xxh32, compress_frame,
                                          decompress_block, decompress_frame)

    # known xxh32 vectors (seed 0)
    assert _xxh32(b"") == 0x02CC5D05
    assert _xxh32(b"a") == 0x550D7456
    assert _xxh32(b"abc") == 0x32D153FF

    # literal 'abcd' + match len 8 off 4 (overlapping) -> 'abcdabcdabcd'
    blk = bytes([(4 << 4) | (8 - 4)]) + b"abcd" + bytes([4, 0])
    assert decompress_block(blk) == b"abcdabcdabcd"

    # extended lengths: 20 literals (15+5), then match len 23 (15+4+4)
    lit = bytes(range(20))
    blk2 = bytes([(15 << 4) | 15]) + bytes([5]) + lit + bytes([20, 0, 4])
    assert decompress_block(blk2) == lit + (lit * 2)[:23]

    # non-overlapping 2-byte offset match
    lit3 = b"0123456789" * 7  # 70 bytes
    blk3 = (bytes([(15 << 4) | (10 - 4)]) + bytes([70 - 15]) + lit3
            + bytes([70, 0]))
    assert decompress_block(blk3) == lit3 + lit3[:10]

    data = b"storm-tpu lz4 " * 500
    assert decompress_frame(compress_frame(data)) == data

    with pytest.raises(Lz4Error):
        decompress_block(bytes([(4 << 4)]) + b"ab")  # truncated literals
    with pytest.raises(Lz4Error):
        decompress_block(bytes([(0 << 4) | 0, 9, 0]))  # offset past output
    with pytest.raises(Lz4Error):
        decompress_frame(b"\x00\x01\x02\x03\x04\x05\x06\x07")  # bad magic
    with pytest.raises(Lz4Error):
        decompress_frame(compress_frame(data)[:-6])  # truncated block


def test_lz4_wrapper_message_and_batch_decode():
    """Both fetch decode paths read lz4: a v1 wrapper message (codec 3,
    KIP-31 relative inner offsets) and a v2 record batch (codec bits 3) —
    the last 0.11-era producer codec the ingest path was missing
    (reference pom.xml:55-78)."""
    import struct as _struct

    from storm_tpu.connectors.kafka_protocol import (decode_message_set,
                                                     encode_record_batch)
    from storm_tpu.connectors.lz4 import compress_frame

    # ---- v0/v1 wrapper: inner message set, lz4-framed, codec attrs=3 ----
    inner = encode_message_set([(None, b"in0"), (None, b"in1")], 1234,
                               offsets=[0, 1])
    compressed = compress_frame(inner)
    msg = bytearray()
    msg.append(1)   # magic 1
    msg.append(3)   # attributes: lz4
    msg += _struct.pack(">q", 1234)
    msg += _struct.pack(">i", -1)  # null key
    msg += _struct.pack(">i", len(compressed)) + compressed
    import zlib as _zlib
    full = bytearray()
    full += _struct.pack(">q", 11)  # wrapper offset = last inner (KIP-31)
    full += _struct.pack(">i", 4 + len(msg))
    full += _struct.pack(">I", _zlib.crc32(bytes(msg)) & 0xFFFFFFFF)
    full += msg
    recs = decode_message_set("t", 0, bytes(full))
    assert [(r.offset, r.value) for r in recs] == [(10, b"in0"), (11, b"in1")]

    # ---- v2 record batch with codec bits 3 ----
    batch = encode_record_batch([(b"k", b"v0"), (None, b"v1")], 5678,
                                compression="lz4")
    out = decode_message_set("t", 1, batch)
    assert [(r.key, r.value) for r in out] == [(b"k", b"v0"), (None, b"v1")]


def test_lz4_record_batch_over_sockets(stub):
    """End-to-end over real sockets: a producer shipping lz4 record batches
    delivers intact records back on fetch (stub parses through the shared
    decode path)."""
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker

    stub.serve_batches = True
    b = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2",
                        compression="lz4")
    try:
        for i in range(5):
            b.produce("lz", f"lz4-{i}", partition=0)
        got = [r.value.decode() for r in b.fetch("lz", 0, 0)]
        assert got == [f"lz4-{i}" for i in range(5)], got
    finally:
        b.close()
        stub.serve_batches = False


def test_api_versions_probe_and_compat(stub):
    """The connect-time ApiVersions probe: a broker advertising the pinned
    surface passes; one that dropped the legacy versions (KIP-896-era)
    fails LOUDLY with a per-api compatibility matrix; one that hangs up on
    the probe (pre-0.10) is assumed era-compatible."""
    from storm_tpu.connectors.kafka_protocol import PINNED_API_VERSIONS

    # happy path: stub advertises everything we pin
    c1 = KafkaWireClient(f"127.0.0.1:{stub.port}")
    try:
        advertised = c1.probe_api_versions()
        assert advertised is not None and 0 in advertised
        c1.check_broker_compat()  # no raise
        c1.refresh_metadata(["t"])  # probe integrated into first metadata
    finally:
        c1.close()

    # modern broker: legacy produce/fetch versions removed
    stub.api_versions = {key: (9, 17) for key in PINNED_API_VERSIONS}
    c2 = KafkaWireClient(f"127.0.0.1:{stub.port}")
    try:
        with pytest.raises(KafkaProtocolError) as ei:
            c2.refresh_metadata(["t"])
        msg = str(ei.value)
        assert "KIP-896" in msg and "Produce (api 0)" in msg \
            and "broker serves v9-v17" in msg
    finally:
        c2.close()
        stub.api_versions = None

    # pre-0.10 broker: connection dropped on the probe -> compatible
    stub.api_versions = "closed"
    c3 = KafkaWireClient(f"127.0.0.1:{stub.port}")
    try:
        assert c3.probe_api_versions() is None
        c3.refresh_metadata(["t"])  # proceeds, no raise
    finally:
        c3.close()
        stub.api_versions = None

    # genuine 0.10 broker: core apis served, NO transaction apis. The core
    # path must work (feature-aware check, not all-or-nothing); asking for
    # a transaction handle then fails loudly with the [txn] matrix.
    stub.api_versions = {0: (0, 2), 1: (0, 3), 2: (0, 1), 3: (0, 2),
                         8: (0, 2), 9: (0, 1), 10: (0, 0), 18: (0, 0)}
    c4 = KafkaWireBroker(f"127.0.0.1:{stub.port}")  # message_format v1
    try:
        c4.client.refresh_metadata(["t"])  # core OK, no raise
        with pytest.raises(KafkaProtocolError) as ei:
            c4.txn("t-0")
        assert "[txn]" in str(ei.value) and "EndTxn" in str(ei.value)
    finally:
        c4.close()
        stub.api_versions = None


def test_lz4_multiblock_frame_roundtrip():
    """Frames larger than one block: block boundaries must reassemble
    exactly, and truncating at a boundary fails loudly."""
    from storm_tpu.connectors.lz4 import Lz4Error, compress_frame, decompress_frame

    data = bytes(range(256)) * 2048  # 512KB
    framed = compress_frame(data, block_size=64 * 1024)  # 8 blocks
    assert decompress_frame(framed) == data
    with pytest.raises(Lz4Error):
        # drop the EndMark + final block's tail
        decompress_frame(framed[:-(64 * 1024 + 8)])


def test_txn_produce_with_lz4_codec(stub):
    """Transactional produce honors broker.compression: the committed
    records round-trip through the stub's shared decode path (codec 3)."""
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker

    b = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2",
                        compression="lz4")
    try:
        txn = b.txn("lz4-txn-0")
        txn.begin()
        for i in range(3):
            txn.produce("lzt", f"tx-{i}", partition=0)
        txn.send_offsets("lzg", {("src", 0): 3})
        txn.commit()
        got = [r.value.decode() for r in b.fetch("lzt", 0, 0)]
        assert got == ["tx-0", "tx-1", "tx-2"], got
        assert b.committed("lzg", "src", 0) == 3
    finally:
        b.close()


def test_read_committed_filters_aborted_transactions(stub):
    """Fetch v4 + isolation_level=read_committed (KIP-98, the reference's
    own Kafka 0.11): with REAL-broker transactional log semantics
    (records append immediately, EndTxn appends a control marker), a
    read_committed consumer must see only committed transactions' records
    — aborted data is filtered via the broker's aborted_transactions
    ranges — while a read_uncommitted (v2-era) consumer sees everything."""
    # per-test stub instance: no cross-test leak to undo
    stub.log_transactional = True
    good = KafkaWireBroker(f"127.0.0.1:{stub.port}",
                           message_format="v2", client_id="good")
    bad = KafkaWireBroker(f"127.0.0.1:{stub.port}",
                          message_format="v2", client_id="bad")
    t_good = good.txn("rc-good")

    # interleave: good txn 1, aborted txn, good txn 2 — all partition 0
    t_good.begin()
    t_good.produce("rc", b"ok-0", partition=0)
    t_good.produce("rc", b"ok-1", partition=0)
    t_good.commit()
    # the aborting producer ships its records EAGERLY (low-level path:
    # KafkaTxn only puts buffered records on the wire at commit, so an
    # abort via the handle leaves nothing at the broker to filter)
    pid, epoch = bad.client.init_producer_id(transactional_id="rc-bad")
    bad.client.add_partitions_to_txn("rc-bad", pid, epoch, [("rc", 0)])
    bad.client.produce("rc", 0, [(None, b"POISON-0"),
                                 (None, b"POISON-1")], acks=-1,
                       message_format="v2", producer=(pid, epoch, 0),
                       transactional_id="rc-bad")
    bad.client.end_txn("rc-bad", pid, epoch, commit=False)
    t_good.begin()
    t_good.produce("rc", b"ok-2", partition=0)
    t_good.commit()

    # read_uncommitted (v2 era): sees committed AND aborted data
    all_vals = [r.value for r in good.client.fetch("rc", 0, 0)]
    assert b"POISON-0" in all_vals and b"ok-2" in all_vals

    # read_committed: aborted records filtered, committed kept, order
    # and offsets preserved (markers occupy offsets but carry no data)
    rc = good.client.fetch("rc", 0, 0, isolation="read_committed")
    assert [r.value for r in rc] == [b"ok-0", b"ok-1", b"ok-2"]
    offs = [r.offset for r in rc]
    assert offs == sorted(offs) and offs[0] == 0

    # KafkaWireBroker-level isolation plumbs through fetch()
    rc_broker = KafkaWireBroker(f"127.0.0.1:{stub.port}",
                                message_format="v2",
                                isolation="read_committed")
    vals = [r.value for r in rc_broker.fetch("rc", 0, 0)]
    assert vals == [b"ok-0", b"ok-1", b"ok-2"]
    rc_broker.close()
    good.close()
    bad.close()


def test_read_committed_bounded_at_open_transaction(stub):
    """An OPEN transaction's records sit past the LSO: read_committed
    consumers must not see them (the broker serves nothing beyond the
    LSO); after commit they appear."""
    # per-test stub instance: no cross-test leak to undo
    stub.log_transactional = True
    b = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2",
                        isolation="read_committed")
    txn = b.txn("rc-open")
    txn.begin()
    txn.produce("rco", b"inflight", partition=0)
    # KafkaTxn buffers locally; push the records to the broker inside
    # the open transaction via the low-level path
    txn._client.add_partitions_to_txn("rc-open", txn._pid, txn._epoch,
                                      [("rco", 0)])
    txn._client.produce("rco", 0, [(None, b"inflight")], acks=-1,
                        message_format="v2",
                        producer=(txn._pid, txn._epoch, 0),
                        transactional_id="rc-open")
    txn._pending.clear()

    assert b.fetch("rco", 0, 0) == []  # open txn: invisible
    txn._open = True
    txn.commit()
    vals = [r.value for r in b.fetch("rco", 0, 0)]
    assert vals == [b"inflight"]
    b.close()


def test_read_committed_fencing_aborts_dangling_txn(stub):
    """A crashed producer's dangling transaction (records at the broker,
    EndTxn never sent) is epoch-fenced by the restarted task; the fencing
    abort must make those records invisible to read_committed consumers —
    the consume-side half of the crash test, under real-broker log
    semantics."""
    # per-test stub instance: no cross-test leak to undo
    stub.log_transactional = True
    b = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2")
    pid, epoch = b.client.init_producer_id(transactional_id="rc-crash")
    b.client.add_partitions_to_txn("rc-crash", pid, epoch, [("rcc", 0)])
    b.client.produce("rcc", 0, [(None, b"GHOST")], acks=-1,
                     message_format="v2", producer=(pid, epoch, 0),
                     transactional_id="rc-crash")
    # crash: no EndTxn. Restarted task re-inits the same id -> fence.
    txn2 = b.txn("rc-crash")
    txn2.begin()
    txn2.produce("rcc", b"real", partition=0)
    txn2.commit()

    rc = b.client.fetch("rcc", 0, 0, isolation="read_committed")
    assert [r.value for r in rc] == [b"real"]
    # the ghost IS in the raw log (real-broker semantics)...
    raw = [r.value for r in b.client.fetch("rcc", 0, 0)]
    assert b"GHOST" in raw
    b.close()


def test_read_committed_fetch_past_abort_marker(stub):
    """Fetching from an offset PAST an abort marker must not re-activate
    the stale aborted range and drop the same producer's later COMMITTED
    records (regression: the stub reported every historical range, so the
    ABORT marker — outside the fetched region — never deactivated the
    producer and committed data vanished)."""
    stub.log_transactional = True
    b = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2")
    pid, epoch = b.client.init_producer_id(transactional_id="rc-mid")
    # txn 1: aborted -> GHOST@0, ABORT marker@1
    b.client.add_partitions_to_txn("rc-mid", pid, epoch, [("rcm", 0)])
    b.client.produce("rcm", 0, [(None, b"GHOST")], acks=-1,
                     message_format="v2", producer=(pid, epoch, 0),
                     transactional_id="rc-mid")
    b.client.end_txn("rc-mid", pid, epoch, commit=False)
    # txn 2, SAME producer: committed -> real@2, COMMIT marker@3
    b.client.add_partitions_to_txn("rc-mid", pid, epoch, [("rcm", 0)])
    b.client.produce("rcm", 0, [(None, b"real")], acks=-1,
                     message_format="v2", producer=(pid, epoch, 1),
                     transactional_id="rc-mid")
    b.client.end_txn("rc-mid", pid, epoch, commit=True)

    # from 0: ghost filtered, real kept
    rc0 = b.client.fetch("rcm", 0, 0, isolation="read_committed")
    assert [r.value for r in rc0] == [b"real"]
    # from 2 (past the abort marker): the committed record must survive
    rc2 = b.client.fetch("rcm", 0, 2, isolation="read_committed")
    assert [r.value for r in rc2] == [b"real"], [r.value for r in rc2]
    b.close()


def test_api_versions_probe_parses_error_35(stub):
    """UNSUPPORTED_VERSION (35) replies still carry the supported-versions
    array (KIP-511): the probe must parse and validate it rather than
    treating the error as a silent no-answer — a modern broker answering
    v0 with error 35 is exactly what the loud KIP-896 check exists for
    (ADVICE r3-low)."""
    from storm_tpu.connectors.kafka_protocol import PINNED_API_VERSIONS

    # error 35 + modern ranges: must fail LOUDLY, not bypass the check
    stub.api_versions = ("error35",
                         {key: (9, 17) for key in PINNED_API_VERSIONS})
    c = KafkaWireClient(f"127.0.0.1:{stub.port}")
    try:
        advertised = c.probe_api_versions()
        assert advertised is not None and advertised[0] == (9, 17)
        with pytest.raises(KafkaProtocolError, match="KIP-896"):
            c.refresh_metadata(["t"])
    finally:
        c.close()
        stub.api_versions = None

    # error 35 + EMPTY array: nothing to learn -> era-compatible assumed
    stub.api_versions = ("error35", {})
    c2 = KafkaWireClient(f"127.0.0.1:{stub.port}")
    try:
        assert c2.probe_api_versions() is None
        c2.refresh_metadata(["t"])  # proceeds
    finally:
        c2.close()
        stub.api_versions = None


# ---- leader-election survival (VERDICT r3 missing #3) ------------------------


def test_produce_fetch_survive_leader_move():
    """Mid-stream leader election: the old leader answers
    NOT_LEADER_FOR_PARTITION (6); the client must refresh metadata and
    retry onto the new leader instead of dying — the 0.11-era
    kafka-clients behavior the wire client replaces."""
    stub = KafkaStubBroker(partitions=2, nodes=2)
    client = KafkaWireClient(f"127.0.0.1:{stub.port}")
    try:
        for i in range(3):
            client.produce("t", 0, [(None, f"a{i}".encode())])
        stub.move_leader("t", 0, 1)  # election: node 1 now leads t[0]
        for i in range(3):
            client.produce("t", 0, [(None, f"b{i}".encode())])
        recs = client.fetch("t", 0, 0, max_wait_ms=10)
        assert [r.value.decode() for r in recs] == \
            ["a0", "a1", "a2", "b0", "b1", "b2"]
        # move back mid-consumption: fetch survives the reverse move too
        stub.move_leader("t", 0, 0)
        recs = client.fetch("t", 0, 3, max_wait_ms=10)
        assert [r.value.decode() for r in recs] == ["b0", "b1", "b2"]
        assert client.list_offset("t", 0, -1) == 6
    finally:
        client.close()
        stub.close()


def test_idempotent_sequences_survive_leader_move():
    """An idempotent producer's sequence numbers stay valid across the
    election: the retried/continued batches neither duplicate nor hit
    OUT_OF_ORDER_SEQUENCE_NUMBER."""
    stub = KafkaStubBroker(partitions=1, nodes=2)
    client = KafkaWireClient(f"127.0.0.1:{stub.port}")
    try:
        pid, epoch = client.init_producer_id()
        client.produce("t", 0, [(None, b"s0"), (None, b"s1")],
                       message_format="v2", producer=(pid, epoch, 0))
        stub.move_leader("t", 0, 1)
        client.produce("t", 0, [(None, b"s2")],
                       message_format="v2", producer=(pid, epoch, 2))
        client.produce("t", 0, [(None, b"s3")],
                       message_format="v2", producer=(pid, epoch, 3))
        recs = client.fetch("t", 0, 0, max_wait_ms=10)
        assert [r.value for r in recs] == [b"s0", b"s1", b"s2", b"s3"]
    finally:
        client.close()
        stub.close()


def test_offset_commit_survives_coordinator_move():
    """NOT_COORDINATOR (16) drops the cached coordinator and re-finds it
    — commits keep landing after the group coordinator migrates."""
    stub = KafkaStubBroker(partitions=1, nodes=2)
    client = KafkaWireClient(f"127.0.0.1:{stub.port}")
    try:
        client.offset_commit("g", "t", 0, 5)
        assert client.offset_fetch("g", "t", 0) == 5
        stub.move_coordinator(1)
        client.offset_commit("g", "t", 0, 9)  # cached addr now answers 16
        assert client.offset_fetch("g", "t", 0) == 9
    finally:
        client.close()
        stub.close()


def test_open_transaction_survives_leader_and_coordinator_moves():
    """The hard case: an OPEN transaction rides out BOTH a partition
    leader election (mid-produce) and a coordinator migration (before the
    offsets commit + EndTxn). A read-committed consumer must see the
    whole transaction exactly once, with its offsets committed."""
    stub = KafkaStubBroker(partitions=1, nodes=2)
    client = KafkaWireClient(f"127.0.0.1:{stub.port}")
    try:
        txn_id = "eos-move"
        pid, epoch = client.init_producer_id(transactional_id=txn_id)
        client.add_partitions_to_txn(txn_id, pid, epoch, [("out", 0)])
        client.produce("out", 0, [(None, b"t0")], acks=-1,
                       message_format="v2", producer=(pid, epoch, 0),
                       transactional_id=txn_id)
        stub.move_leader("out", 0, 1)  # election mid-transaction
        client.produce("out", 0, [(None, b"t1")], acks=-1,
                       message_format="v2", producer=(pid, epoch, 1),
                       transactional_id=txn_id)
        stub.move_coordinator(1)  # coordinator migrates before commit
        client.add_offsets_to_txn(txn_id, pid, epoch, "g")
        client.txn_offset_commit(txn_id, "g", pid, epoch, {("in", 0): 7})
        client.end_txn(txn_id, pid, epoch, commit=True)

        recs = client.fetch("out", 0, 0, max_wait_ms=10)
        assert [r.value for r in recs] == [b"t0", b"t1"]
        assert client.offset_fetch("g", "t", 0) is None  # other topic clean
        assert client.offset_fetch("g", "in", 0) == 7
    finally:
        client.close()
        stub.close()


def test_leader_retry_exhaustion_surfaces():
    """A leadership error that never heals exhausts the bounded backoff
    and surfaces as a CODED error for the spout/sink fail path — no
    infinite retry loop. Simulated by electing a leader node that is not
    in the broker list: every reachable node keeps answering
    NOT_LEADER_FOR_PARTITION and metadata never heals."""
    stub = KafkaStubBroker(partitions=1, nodes=2)
    client = KafkaWireClient(f"127.0.0.1:{stub.port}")
    try:
        stub.move_leader("t", 0, 7)  # phantom node: election never settles
        t0 = time.perf_counter()
        with pytest.raises(KafkaProtocolError) as ei:
            client.produce("t", 0, [(None, b"x")])
        assert ei.value.code == 6, ei.value
        assert "NOT_LEADER_FOR_PARTITION" in str(ei.value)
        assert time.perf_counter() - t0 < 30  # bounded, not forever
    finally:
        client.close()
        stub.close()


def test_produce_survives_leader_broker_death():
    """The common real election trigger: the leader BROKER dies, so the
    stale cached leader address yields a socket error (not an in-band
    NOT_LEADER reply). The client must treat that as retriable, refresh
    metadata, and land on the re-elected leader."""
    stub = KafkaStubBroker(partitions=1, nodes=2)
    client = KafkaWireClient(f"127.0.0.1:{stub.port}")
    try:
        stub.move_leader("t", 0, 1)
        client.produce("t", 0, [(None, b"a")])  # leader is node 1, cached
        # node 1 dies; the controller re-elects node 0
        stub._socks[1].close()
        stub.move_leader("t", 0, 0)
        time.sleep(0.2)
        client.produce("t", 0, [(None, b"b")])  # stale addr -> OSError -> retry
        recs = client.fetch("t", 0, 0, max_wait_ms=10)
        assert [r.value for r in recs] == [b"a", b"b"]
    finally:
        client.close()
        stub.close()


# ---- transport security (SASL/PLAIN + SSL) -----------------------------------


def test_sasl_plain_round_trip():
    """SASL_PLAINTEXT: the 0.11-era handshake (Kafka-framed SaslHandshake
    api 17 + raw pre-KIP-152 token frames) authenticates every connection;
    produce/fetch work over the authenticated socket."""
    stub = KafkaStubBroker(partitions=1)
    stub.sasl = ("alice", "s3cret")
    sec = {"protocol": "SASL_PLAINTEXT", "sasl_username": "alice",
           "sasl_password": "s3cret"}
    client = KafkaWireClient(f"127.0.0.1:{stub.port}", security=sec)
    try:
        client.produce("t", 0, [(None, b"locked")])
        recs = client.fetch("t", 0, 0, max_wait_ms=10)
        assert [r.value for r in recs] == [b"locked"]
    finally:
        client.close()
        stub.close()

    # wrong password: the broker closes the connection -> loud failure
    stub2 = KafkaStubBroker(partitions=1)
    stub2.sasl = ("alice", "s3cret")
    bad = KafkaWireClient(
        f"127.0.0.1:{stub2.port}",
        security={"protocol": "SASL_PLAINTEXT", "sasl_username": "alice",
                  "sasl_password": "wrong"})
    try:
        with pytest.raises((KafkaProtocolError, OSError)):
            bad.produce("t", 0, [(None, b"x")])
    finally:
        bad.close()
        stub2.close()

    # unauthenticated client against a SASL broker: dropped pre-auth
    stub3 = KafkaStubBroker(partitions=1)
    stub3.sasl = ("alice", "s3cret")
    plain = KafkaWireClient(f"127.0.0.1:{stub3.port}")
    try:
        with pytest.raises((KafkaProtocolError, OSError)):
            plain.produce("t", 0, [(None, b"x")])
    finally:
        plain.close()
        stub3.close()


@pytest.mark.parametrize("mech", ["SCRAM-SHA-256", "SCRAM-SHA-512"])
def test_sasl_scram_round_trip(mech):
    """SASL/SCRAM (KIP-84): full RFC 5802 exchange over raw token frames —
    salted-password proof verified server-side, server signature verified
    client-side; produce/fetch work over the authenticated socket."""
    stub = KafkaStubBroker(partitions=1)
    stub.sasl = ("svc", "scram-pw")
    stub.sasl_mechanism = mech
    sec = {"protocol": "SASL_PLAINTEXT", "sasl_mechanism": mech,
           "sasl_username": "svc", "sasl_password": "scram-pw"}
    client = KafkaWireClient(f"127.0.0.1:{stub.port}", security=sec)
    try:
        client.produce("t", 0, [(None, b"scrammed")])
        recs = client.fetch("t", 0, 0, max_wait_ms=10)
        assert [r.value for r in recs] == [b"scrammed"]
    finally:
        client.close()
        stub.close()


def test_sasl_scram_wrong_password_fails_loudly():
    stub = KafkaStubBroker(partitions=1)
    stub.sasl = ("svc", "scram-pw")
    stub.sasl_mechanism = "SCRAM-SHA-256"
    bad = KafkaWireClient(
        f"127.0.0.1:{stub.port}",
        security={"protocol": "SASL_PLAINTEXT",
                  "sasl_mechanism": "SCRAM-SHA-256",
                  "sasl_username": "svc", "sasl_password": "nope"})
    try:
        with pytest.raises((KafkaProtocolError, OSError)):
            bad.produce("t", 0, [(None, b"x")])
    finally:
        bad.close()
        stub.close()


def test_sasl_scram_refuses_downgraded_iteration_count():
    """A server (or MITM) requesting i < 4096 (RFC 7677 floor) must be
    refused — accepting would let an attacker dictionary-crack the proof
    thousands of times faster."""
    stub = KafkaStubBroker(partitions=1)
    stub.sasl = ("svc", "scram-pw")
    stub.sasl_mechanism = "SCRAM-SHA-256"
    stub.scram_iterations = 512
    client = KafkaWireClient(
        f"127.0.0.1:{stub.port}",
        security={"protocol": "SASL_PLAINTEXT",
                  "sasl_mechanism": "SCRAM-SHA-256",
                  "sasl_username": "svc", "sasl_password": "scram-pw"})
    try:
        with pytest.raises(KafkaProtocolError, match="iteration count"):
            client.produce("t", 0, [(None, b"x")])
    finally:
        client.close()
        stub.close()


def test_scram_auth_survives_leader_move():
    """A leader election makes the client open a connection to a broker it
    has never spoken to; that fresh connection must run the full SCRAM
    exchange (multi-round-trip) before the retried produce — re-auth on
    the retry path, not just at bootstrap."""
    stub = KafkaStubBroker(partitions=1, nodes=2)
    stub.sasl = ("svc", "scram-pw")
    stub.sasl_mechanism = "SCRAM-SHA-256"
    client = KafkaWireClient(
        f"127.0.0.1:{stub.port}",
        security={"protocol": "SASL_PLAINTEXT",
                  "sasl_mechanism": "SCRAM-SHA-256",
                  "sasl_username": "svc", "sasl_password": "scram-pw"})
    try:
        client.produce("t", 0, [(None, b"pre")])
        stub.move_leader("t", 0, 1)  # node 1: never-contacted broker
        client.produce("t", 0, [(None, b"post")])
        recs = client.fetch("t", 0, 0, max_wait_ms=10)
        assert [r.value for r in recs] == [b"pre", b"post"]
    finally:
        client.close()
        stub.close()


def test_sasl_scram_mechanism_mismatch_names_brokers_offer():
    """A PLAIN-only broker refusing SCRAM surfaces error 33 + the broker's
    supported list, not a hang or a silent close."""
    stub = KafkaStubBroker(partitions=1)
    stub.sasl = ("svc", "pw")  # mechanism stays PLAIN
    client = KafkaWireClient(
        f"127.0.0.1:{stub.port}",
        security={"protocol": "SASL_PLAINTEXT",
                  "sasl_mechanism": "SCRAM-SHA-256",
                  "sasl_username": "svc", "sasl_password": "pw"})
    try:
        with pytest.raises(KafkaProtocolError, match="PLAIN"):
            client.produce("t", 0, [(None, b"x")])
    finally:
        client.close()
        stub.close()


@pytest.fixture(scope="module")
def ssl_certs(tmp_path_factory):
    import subprocess

    d = tmp_path_factory.mktemp("certs")
    crt, key = str(d / "broker.crt"), str(d / "broker.key")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", crt, "-days", "2", "-subj",
         "/CN=127.0.0.1", "-addext", "subjectAltName=IP:127.0.0.1"],
        check=True, capture_output=True)
    return crt, key


def _ssl_server_context(ssl_certs):
    import ssl

    crt, key = ssl_certs
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    ctx.load_cert_chain(crt, key)
    return ctx


def test_ssl_round_trip(ssl_certs):
    """SSL: every broker connection is TLS-wrapped; the broker's cert is
    verified against the configured CA bundle."""
    crt, _ = ssl_certs
    stub = KafkaStubBroker(partitions=1)
    stub.ssl_context = _ssl_server_context(ssl_certs)
    client = KafkaWireClient(
        f"127.0.0.1:{stub.port}",
        security={"protocol": "SSL", "ssl_cafile": crt,
                  "ssl_check_hostname": False})
    try:
        client.produce("t", 0, [(None, b"tls")])
        assert [r.value for r in client.fetch("t", 0, 0, max_wait_ms=10)] \
            == [b"tls"]
    finally:
        client.close()
        stub.close()


def test_sasl_ssl_round_trip(ssl_certs):
    """SASL_SSL: TLS first, then SASL/PLAIN over the encrypted channel —
    the full production transport stack of the 0.11 era."""
    crt, _ = ssl_certs
    stub = KafkaStubBroker(partitions=1)
    stub.ssl_context = _ssl_server_context(ssl_certs)
    stub.sasl = ("svc", "pw")
    client = KafkaWireClient(
        f"127.0.0.1:{stub.port}",
        security={"protocol": "SASL_SSL", "sasl_username": "svc",
                  "sasl_password": "pw", "ssl_cafile": crt,
                  "ssl_check_hostname": False})
    try:
        client.produce("t", 0, [(None, b"both")])
        assert [r.value for r in client.fetch("t", 0, 0, max_wait_ms=10)] \
            == [b"both"]
    finally:
        client.close()
        stub.close()


def test_group_membership_survives_coordinator_move():
    """Consumer-group membership survives a coordinator migration IN
    PLACE: the stale node answers NOT_COORDINATOR, the member re-finds
    the coordinator and retries the heartbeat — member and generation
    stay valid (group state lives in __consumer_offsets), so a routine
    broker roll does NOT force a group-wide rebalance. Join after the
    move also lands on the new coordinator."""
    from storm_tpu.connectors.kafka_protocol import GroupMembership

    stub = KafkaStubBroker(partitions=4, nodes=2)
    client = KafkaWireClient(f"127.0.0.1:{stub.port}")
    try:
        m = GroupMembership(client, "mv-g", ["t"])
        parts = m.join()
        assert sorted(p for _, p in parts) == [0, 1, 2, 3]
        assert m.heartbeat()

        stub.move_coordinator(1)
        # stale cached coordinator answers 16 -> re-find + retry in place
        assert m.heartbeat() is True
        # a later rejoin (e.g. after a REAL rebalance) finds node 1 too
        parts2 = m.join()
        assert sorted(p for _, p in parts2) == [0, 1, 2, 3]
        assert m.heartbeat()

        stub.move_coordinator(0)  # and back
        assert m.heartbeat() is True
    finally:
        client.close()
        stub.close()


def test_idempotent_duplicate_sequence_reply_is_success():
    """A broker answering an idempotent resend with
    DUPLICATE_SEQUENCE_NUMBER (46) is saying 'already appended' — the
    client must treat it as success, not reset the producer and
    re-produce under a fresh pid (which would create the duplicate
    idempotence exists to prevent)."""
    stub = KafkaStubBroker(partitions=1)
    stub.duplicate_error = True
    client = KafkaWireClient(f"127.0.0.1:{stub.port}")
    try:
        pid, epoch = client.init_producer_id()
        client.produce("t", 0, [(None, b"once")],
                       message_format="v2", producer=(pid, epoch, 0))
        # resend of the same sequence (lost-response retry): broker says 46
        client.produce("t", 0, [(None, b"once")],
                       message_format="v2", producer=(pid, epoch, 0))
        recs = client.fetch("t", 0, 0, max_wait_ms=10)
        assert [r.value for r in recs] == [b"once"]  # exactly one copy
    finally:
        client.close()
        stub.close()
