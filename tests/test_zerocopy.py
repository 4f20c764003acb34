"""Zero-copy batch-native record path (r19): RecordFrame ingress, view
decode, wire v2 frame slots, the shared-memory delivery lane, and batch
egress — proven BIT-IDENTICAL against the legacy per-record path, locally
and across a 2-worker cluster.

The path's rate is not measured here, nor by the benchmark; this file owns
correctness: same inputs in, the
same prediction rows out, regardless of which data plane carried them.
"""

import asyncio
import json
import random
import time

import numpy as np
import pytest

from storm_tpu.api.schema import decode_instances, decode_predictions
from storm_tpu.config import (BatchConfig, Config, ModelConfig,
                              OffsetsConfig, ShardingConfig)
from storm_tpu.connectors import BrokerSink, BrokerSpout, MemoryBroker
from storm_tpu.dist import shm as shm_lane
from storm_tpu.dist import transport, wire
from storm_tpu.infer import InferenceBolt
from storm_tpu.runtime import TopologyBuilder
from storm_tpu.runtime.cluster import AsyncLocalCluster
from storm_tpu.runtime.frames import RecordFrame
from storm_tpu.runtime.tuples import Tuple
from storm_tpu.serve.marshal import encode_tensor


def _image(seed: int, shape=(1, 28, 28, 1)) -> np.ndarray:
    """Whole-number float32 pixels: bit-exact through EVERY path under
    test, including a JSON round trip (ints <= 255 are exact in both
    float32 and JSON's decimal text)."""
    rng = np.random.RandomState(seed)
    return rng.randint(0, 256, size=shape).astype(np.float32)


def mk_tuple(values) -> Tuple:
    return Tuple(values=values, fields=tuple(f"f{i}" for i in range(len(values))),
                 source_component="spout", source_task=0)


# ---- RecordFrame -------------------------------------------------------------


def test_record_frame_round_trip():
    recs = [b"hello", b"", bytes(range(256)), b"x" * 10_000]
    f = RecordFrame(recs)
    assert len(f) == 4
    assert f.nbytes == sum(len(r) for r in recs)
    assert [bytes(r) for r in f] == recs

    body = b"".join(bytes(p) for p in f.encode_parts())
    assert len(body) == f.encoded_nbytes()
    f2 = RecordFrame.from_buffer(body)
    assert [bytes(r) for r in f2] == recs
    # decoded records are views over the buffer, not copies
    assert all(isinstance(r, memoryview) for r in f2)
    assert f2.tolist() == recs


def test_record_frame_rejects_corrupt_buffers():
    body = b"".join(bytes(p) for p in RecordFrame([b"abc", b"defg"]).encode_parts())
    for cut in range(len(body)):
        with pytest.raises(ValueError):
            RecordFrame.from_buffer(body[:cut])
    with pytest.raises(ValueError):
        RecordFrame.from_buffer(body + b"trailing")
    # record length pointing past the end of the buffer
    bad = bytearray(body)
    bad[4:8] = (1 << 20).to_bytes(4, "little")
    with pytest.raises(ValueError):
        RecordFrame.from_buffer(bytes(bad))


# ---- view decode -------------------------------------------------------------


def test_tensor_decode_is_zero_copy_view():
    x = _image(0)
    payload = encode_tensor(x)
    inst = decode_instances(payload)
    assert inst.view
    assert np.array_equal(inst.data, x)
    # the decoded array aliases the payload buffer — the whole point
    assert np.shares_memory(inst.data, np.frombuffer(payload, dtype=np.uint8))
    # frame views decode too (the batch path hands out memoryviews)
    inst2 = decode_instances(memoryview(payload))
    assert inst2.view and np.array_equal(inst2.data, x)


def test_tensor_decode_casts_are_not_views():
    x = _image(1).astype(np.float64)
    inst = decode_instances(encode_tensor(x))
    assert not inst.view  # dtype cast had to materialize
    assert inst.data.dtype == np.float32
    assert np.array_equal(inst.data, x.astype(np.float32))


def test_json_decode_unchanged_and_not_view():
    x = _image(2)
    inst = decode_instances(json.dumps({"instances": x.tolist()}))
    assert not inst.view
    assert np.array_equal(inst.data, x)


# ---- wire v2: frame slot + version negotiation -------------------------------


def test_wire_v2_carries_record_frames():
    f = RecordFrame([b"r0", b"r1" * 100, bytes(1000)])
    payload = wire.encode_deliveries([("bolt", 3, mk_tuple([f, "tag"]))])
    assert payload[1] == wire.WIRE_VERSION == 2
    (comp, task, t), = wire.decode_deliveries(payload)
    assert (comp, task) == ("bolt", 3)
    out = t.values[0]
    assert isinstance(out, RecordFrame)
    assert out.tolist() == f.tolist()
    assert t.values[1] == "tag"


def test_wire_v1_peers_get_frames_decomposed():
    """A negotiated v1 peer must receive a frame-free v1 frame: the
    rolling-restart contract (mixed-version mesh keeps decoding)."""
    f = RecordFrame([b"a", b"bb"])
    payload = wire.encode_deliveries([("bolt", 0, mk_tuple([f]))],
                                     version=1)
    assert payload[1] == 1
    (_, _, t), = wire.decode_deliveries(payload)
    assert isinstance(t.values[0], list)  # decomposed, not a frame
    assert [bytes(v) for v in t.values[0]] == [b"a", b"bb"]


def test_unsealed_view_decode_round_trip():
    f = RecordFrame([b"payload-bytes" * 50])
    parts, _flags = wire.encode_delivery_parts([("bolt", 0, mk_tuple([f]))])
    body = b"".join(bytes(p) for p in parts)
    (_, _, t), = wire.decode_deliveries_view(body)
    assert t.values[0].tolist() == f.tolist()
    # magic/version are still enforced on the mapped body
    with pytest.raises(wire.WireError, match="magic"):
        wire.decode_deliveries_view(b"\xff" + body[1:])
    newer = bytearray(body)
    newer[1] = wire.WIRE_VERSION + 1
    with pytest.raises(wire.WireError, match="version"):
        wire.decode_deliveries_view(bytes(newer))


# ---- shm header: fuzz + lifecycle --------------------------------------------


def test_shm_header_round_trip():
    hdr = wire.encode_shm_header("psm_zerocopy_test", 64, 123456)
    assert hdr[:1] == bytes((wire.SHM_MAGIC,))
    assert wire.decode_shm_header(hdr) == ("psm_zerocopy_test", 64, 123456)


def test_shm_header_every_byte_flip_detected():
    """Mirror of test_wire's corruption sweep: the header names a segment
    to ATTACH, so a corrupt one must never decode."""
    hdr = wire.encode_shm_header("psm_fuzz", 0, 4096)
    rng = random.Random(0xB9)
    for i in range(len(hdr)):
        bad = bytearray(hdr)
        flip = rng.randrange(1, 256)
        bad[i] ^= flip
        with pytest.raises(wire.WireError):
            wire.decode_shm_header(bytes(bad))


def test_shm_header_truncations_and_magic_rejected():
    hdr = wire.encode_shm_header("psm_fuzz2", 8, 99)
    for cut in range(len(hdr)):
        with pytest.raises(wire.WireError):
            wire.decode_shm_header(hdr[:cut])
    with pytest.raises(wire.WireError):
        wire.decode_shm_header(b"\xb7" + hdr[1:])  # delivery magic
    newer = bytearray(hdr)
    newer[1] = wire.WIRE_VERSION + 1
    with pytest.raises(wire.WireError, match="version"):
        wire.decode_shm_header(bytes(newer))


@pytest.mark.skipif(not shm_lane.available(), reason="no shared memory")
def test_shm_segment_round_trip_through_transport():
    f = RecordFrame([b"seg-record" * 100, bytes(5000)])
    parts, _ = wire.encode_delivery_parts([("bolt", 1, mk_tuple([f]))])
    seg, length = shm_lane.write_segment(parts)
    try:
        hdr = wire.encode_shm_header(seg.name, 0, length)
        (comp, task, t), = transport.decode_deliveries(hdr)
        assert (comp, task) == ("bolt", 1)
        assert t.values[0].tolist() == f.tolist()
        del t  # release the mapped views before unlink
    finally:
        seg.close()
        seg.unlink()


@pytest.mark.skipif(not shm_lane.available(), reason="no shared memory")
def test_shm_vanished_segment_is_a_wire_error():
    """A header naming an unlinked/never-created segment must surface as
    WireError (accounted, tree left to replay) — not an uncaught OSError
    that kills the Deliver handler."""
    hdr = wire.encode_shm_header("psm_never_created_xyz", 0, 128)
    with pytest.raises(wire.WireError, match="unavailable"):
        transport.decode_deliveries(hdr)


@pytest.mark.skipif(not shm_lane.available(), reason="no shared memory")
def test_shm_range_overrun_is_a_wire_error():
    seg, length = shm_lane.write_segment([b"tiny"])
    try:
        hdr = wire.encode_shm_header(seg.name, 0, length + 10_000_000)
        with pytest.raises(wire.WireError):
            transport.decode_deliveries(hdr)
    finally:
        seg.close()
        seg.unlink()


def test_host_key_is_stable():
    assert shm_lane.host_key() == shm_lane.host_key()
    assert shm_lane.host_key()


# ---- spout: frame ingress + whole-frame replay -------------------------------


def test_frames_require_raw_scheme():
    with pytest.raises(ValueError, match="raw"):
        BrokerSpout(MemoryBroker(), "in", scheme="string", frames=True)


def test_frame_replay_is_whole_frame(run):
    """Exactly-once granularity: one frame = one anchor tree; a fail
    replays the SAME records as one frame tuple (mirrors
    test_chunked.test_chunk_replay_is_whole_chunk)."""

    async def go():
        broker = MemoryBroker(default_partitions=1)
        for i in range(6):
            broker.produce("in", f"m{i}".encode())
        spout = BrokerSpout(broker, "in",
                            OffsetsConfig(policy="earliest", max_behind=None),
                            chunk=3, scheme="raw", frames=True)
        emits = []

        class Cap:
            def set_output_fields(self, f):
                pass

            async def emit(self, values, **kw):
                emits.append((list(values), kw.get("msg_id")))
                return 1

        class Ctx:
            task_index = 0
            parallelism = 1
            component_id = "spout"
            config = None
            metrics = None

        spout.open(Ctx(), Cap())
        assert await spout.next_tuple()
        (frame1,), mid1 = emits[0]
        (frame2,), mid2 = emits[1]
        assert isinstance(frame1, RecordFrame)
        assert frame1.tolist() == [b"m0", b"m1", b"m2"]
        assert frame2.tolist() == [b"m3", b"m4", b"m5"]
        spout.fail(mid1)
        assert await spout.next_tuple()
        (frame1r,), mid1r = emits[2]
        assert isinstance(frame1r, RecordFrame)
        assert frame1r.tolist() == frame1.tolist() and mid1r == mid1
        spout.ack(mid1r)
        spout.ack(mid2)
        assert not await spout.next_tuple()

    run(go(), timeout=30)


# ---- end-to-end: bit-identical A/B -------------------------------------------


async def _run_local(n_msgs, frames, chunk=4, frame_egress=True):
    """One local topology run; returns the prediction rows."""
    broker = MemoryBroker(default_partitions=2)
    cfg = Config()
    tb = TopologyBuilder()
    tb.set_spout(
        "spout",
        BrokerSpout(broker, "input",
                    OffsetsConfig(policy="earliest", max_behind=None),
                    chunk=chunk, scheme="raw", frames=frames),
        parallelism=1,
    )
    tb.set_bolt(
        "infer",
        InferenceBolt(ModelConfig(name="lenet5", input_shape=(28, 28, 1)),
                      BatchConfig(max_batch=8, max_wait_ms=10, buckets=(8,),
                                  frame_egress=frame_egress),
                      ShardingConfig(data_parallel=0), warmup=False),
        parallelism=1,
    ).shuffle_grouping("spout")
    tb.set_bolt("sink", BrokerSink(broker, "output", cfg.sink), parallelism=1)\
        .shuffle_grouping("infer")
    tb.set_bolt("dlq", BrokerSink(broker, "dead-letter", cfg.sink), parallelism=1)\
        .shuffle_grouping("infer", stream="dead_letter")

    for i in range(n_msgs):
        broker.produce("input", encode_tensor(_image(i)))

    cluster = AsyncLocalCluster()
    rt = await cluster.submit("zc-local", cfg, tb.build())
    rows = 0
    deadline = asyncio.get_event_loop().time() + 60
    while asyncio.get_event_loop().time() < deadline:
        rows = sum(
            decode_predictions(r.value).batch_size
            for r in broker.drain_topic("output"))
        if rows >= n_msgs:
            break
        await asyncio.sleep(0.05)
    await rt.drain(timeout_s=30)
    snap = rt.metrics.snapshot()
    outs = broker.drain_topic("output")
    await cluster.shutdown()
    return outs, snap


def _sorted_rows(outs):
    rows = []
    for r in outs:
        rows.extend(decode_predictions(r.value).data.tolist())
    return sorted(map(tuple, rows))


def test_local_frames_bit_identical_to_legacy(run):
    """Same tensor payloads through the legacy per-record raw path and
    the batch-frame path: identical prediction rows, bit for bit. The
    frame arm must also COALESCE egress (fewer sink messages than rows)
    — that cardinality drop is the duplicated-encode fix."""
    n = 16
    legacy_outs, legacy_snap = run(_run_local(n, frames=False), timeout=180)
    frame_outs, frame_snap = run(_run_local(n, frames=True), timeout=180)

    legacy = _sorted_rows(legacy_outs)
    framed = _sorted_rows(frame_outs)
    assert len(legacy) == len(framed) == n
    assert legacy == framed  # bit-identical (sorted: arrival order differs)

    assert legacy_snap["infer"]["instances_inferred"] == n
    assert frame_snap["infer"]["instances_inferred"] == n
    # frame egress: one message per dispatched batch, not per record
    assert len(frame_outs) < n
    # frame arm sinks bytes payloads straight through
    assert all(isinstance(r.value, (bytes, bytearray)) for r in frame_outs)


def test_frame_egress_off_keeps_per_record_output(run):
    """batch.frame_egress=False: frame INGRESS (raw scheme + RecordFrame
    tuples, zero-copy decode) with the legacy one-output-message-per-record
    contract on egress — the compatibility knob for consumers that count
    or key individual output messages."""
    n = 16
    outs, snap = run(_run_local(n, frames=True, frame_egress=False),
                     timeout=180)
    assert snap["infer"]["instances_inferred"] == n
    # one output message per record, each a single prediction row
    assert len(outs) == n
    assert all(decode_predictions(r.value).batch_size == 1 for r in outs)


@pytest.mark.slow
def test_dist_frames_bit_identical_and_shm_engaged():
    """2-worker cluster, raw + binary, buckets=(8,): the batch-frame +
    shm default data plane produces bit-identical predictions to the
    legacy per-record plane, with a clean exactly-once audit and the
    shared-memory lane demonstrably engaged."""
    import sys
    sys.path.insert(0, "tests")
    from kafka_stub import KafkaStubBroker
    from storm_tpu.dist import DistCluster
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker

    def topic_rows(stub, topic):
        rows = []
        with stub._lock:
            for p in range(stub.partitions):
                for rec in stub._logs.get((topic, p), []):
                    if rec[0] in ("c", "d") and len(rec) == 4:
                        continue  # txn marker bookkeeping
                    rows.extend(
                        decode_predictions(rec[1]).data.tolist())
        return sorted(map(tuple, rows))

    def run_arm(frames: bool):
        stub = KafkaStubBroker(partitions=1)
        try:
            cfg = Config()
            cfg.broker.kind = "kafka"
            cfg.broker.bootstrap = f"127.0.0.1:{stub.port}"
            cfg.broker.input_topic = "zc-in"
            cfg.broker.output_topic = "zc-out"
            cfg.broker.dead_letter_topic = "zc-dlq"
            cfg.model.name = "lenet5"
            cfg.model.dtype = "float32"
            cfg.model.input_shape = (28, 28, 1)
            cfg.offsets.policy = "earliest"
            cfg.offsets.max_behind = None
            cfg.batch.max_batch = 8
            cfg.batch.max_wait_ms = 20
            cfg.batch.buckets = (8,)
            cfg.topology.spout_parallelism = 1
            cfg.topology.inference_parallelism = 1
            cfg.topology.sink_parallelism = 1
            cfg.topology.message_timeout_s = 60.0
            cfg.topology.spout_scheme = "raw"
            cfg.topology.spout_frames = frames
            cfg.topology.shm_min_bytes = 1  # engage shm for any batch
            placement = {"kafka-spout": 0, "inference-bolt": 1,
                         "kafka-bolt": 1, "dlq-bolt": 1}
            n = 12
            with DistCluster(2, env={"JAX_PLATFORMS": "cpu"}) as cluster:
                cluster.submit("zc-dist", cfg, placement)
                producer = KafkaWireBroker(cfg.broker.bootstrap)
                for i in range(n):
                    producer.produce("zc-in", encode_tensor(_image(i)))
                deadline = time.time() + 90
                while time.time() < deadline:
                    if len(topic_rows(stub, "zc-out")) >= n:
                        break
                    time.sleep(0.1)
                assert cluster.drain(timeout_s=30)
                rows = topic_rows(stub, "zc-out")
                snap = cluster.metrics()
                cluster.kill()
            return rows, snap, n
        finally:
            stub.close()

    legacy_rows, legacy_snap, n = run_arm(frames=False)
    frame_rows, frame_snap, _ = run_arm(frames=True)

    assert len(legacy_rows) == len(frame_rows) == n
    assert legacy_rows == frame_rows  # bit-identical across the planes

    # exactly-once audit: every tree acked, none failed, on BOTH arms
    for snap in (legacy_snap, frame_snap):
        assert snap["kafka-spout"].get("tree_failed", 0) in (0, None)
        assert snap["kafka-spout"]["tree_acked"] >= 1
        assert snap["inference-bolt"]["instances_inferred"] == n
    # the frame arm demonstrably used the shared-memory lane
    assert frame_snap["_transport"]["dist_shm_batches"] > 0


# ---- config: dist-run default flip -------------------------------------------


def test_explicit_spout_scheme_is_pinned():
    """config files that SET spout_scheme mark it pinned, so the
    dist-run raw+frames default flip (main.py) never overrides an
    explicit operator choice."""
    cfg = Config.from_dict({"topology": {"spout_scheme": "string"}})
    assert getattr(cfg.topology, "_scheme_pinned", False)
    cfg2 = Config.from_dict({"topology": {"wire_format": "binary"}})
    assert not getattr(cfg2.topology, "_scheme_pinned", False)
