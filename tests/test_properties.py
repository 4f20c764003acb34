"""Property-based tests (hypothesis) for the framework's algebraic cores:
the XOR ack ledger, the Kafka varint/record-batch codec, the wire schema,
and the engine queue's batch formation — invariants that example-based tests undersample."""

import json

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis; skip (not error) without it")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from storm_tpu.runtime.acker import AckLedger
from storm_tpu.runtime.tuples import new_id

# ---- acker: XOR tuple-tree algebra -------------------------------------------


@settings(max_examples=200, deadline=None)
@given(
    n_edges=st.integers(min_value=1, max_value=40),
    order=st.randoms(use_true_random=False),
)
def test_ledger_completes_iff_every_edge_acked(n_edges, order):
    """Emit n edges, ack them in ANY order -> exactly one completion, ok."""
    led = AckLedger(timeout_s=0)
    done = []
    root = new_id()
    led.init_root(root, "m", lambda m, ok, ts: done.append(ok), 0.0)
    edges = [new_id() for _ in range(n_edges)]
    for e in edges:
        led.xor(root, e)  # emit
    assert led.inflight == 1 and done == []
    acks = list(edges)
    order.shuffle(acks)
    for i, e in enumerate(acks):
        led.xor(root, e)  # ack
        if i < len(acks) - 1:
            assert done == [], "completed before all edges acked"
    assert done == [True]
    assert led.inflight == 0


@settings(max_examples=100, deadline=None)
@given(
    n_children=st.integers(min_value=0, max_value=10),
    fail_at=st.integers(min_value=0, max_value=10),
)
def test_ledger_fail_wins_once(n_children, fail_at):
    """fail_root mid-tree (after fail_at of the acks) -> exactly one
    callback, ok=False, regardless of how many acks straggle afterwards."""
    led = AckLedger(timeout_s=0)
    done = []
    root = new_id()
    led.init_root(root, "m", lambda m, ok, ts: done.append(ok), 0.0)
    edges = [new_id() for _ in range(n_children)]
    for e in edges:
        led.xor(root, e)
    k = min(fail_at, n_children)
    for e in edges[:k]:
        led.xor(root, e)  # acks before the failure
    led.fail_root(root)
    for e in edges[k:]:
        led.xor(root, e)  # stragglers must be ignored
    if k == n_children and n_children > 0:
        # every edge acked BEFORE the fail: the tree already completed
        # successfully and the late fail_root must be a no-op
        assert done == [True]
    else:
        assert done == [False]
    assert led.inflight == 0


# ---- kafka codec -------------------------------------------------------------


@settings(max_examples=300, deadline=None)
@given(st.integers(min_value=-(2**63), max_value=2**63 - 1))
def test_varint_roundtrip_any_int64(v):
    from storm_tpu.connectors.kafka_protocol import _read_varint, _write_varint

    buf = bytearray()
    _write_varint(buf, v)
    got, pos = _read_varint(bytes(buf), 0)
    assert got == v and pos == len(buf)


_record = st.tuples(
    st.one_of(st.none(), st.binary(max_size=64)),  # key (nullable)
    st.binary(max_size=256),  # value
)


@settings(max_examples=100, deadline=None)
@given(
    records=st.lists(_record, min_size=1, max_size=20),
    base_offset=st.integers(min_value=0, max_value=2**40),
    ts_ms=st.integers(min_value=0, max_value=2**41),
)
def test_record_batch_roundtrip_any_records(records, base_offset, ts_ms):
    from storm_tpu.connectors.kafka_protocol import (
        decode_record_batch,
        encode_record_batch,
    )

    batch = encode_record_batch(records, ts_ms=ts_ms, base_offset=base_offset)
    out, consumed = decode_record_batch("t", 0, batch, verify_crc=True)
    assert consumed == len(batch)
    assert [(r.key, r.value) for r in out] == records
    assert [r.offset for r in out] == list(range(base_offset, base_offset + len(records)))


@settings(max_examples=100, deadline=None)
@given(records=st.lists(_record, min_size=1, max_size=8))
def test_message_set_v1_roundtrip(records):
    from storm_tpu.connectors.kafka_protocol import (
        decode_message_set,
        encode_message_set,
    )

    data = encode_message_set(records, ts_ms=1000, offsets=list(range(len(records))))
    out = decode_message_set("t", 0, data)
    # v1 sets normalize a None value to b"" on decode; keys survive exactly
    assert [(r.key, r.value) for r in out] == [(k, v) for k, v in records]


# ---- wire schema -------------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=4),
    h=st.integers(min_value=1, max_value=6),
    w=st.integers(min_value=1, max_value=6),
    c=st.integers(min_value=1, max_value=3),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_instances_json_roundtrip(n, h, w, c, seed):
    from storm_tpu.api.schema import decode_instances

    rng = np.random.RandomState(seed)
    x = rng.rand(n, h, w, c).astype(np.float32)
    inst = decode_instances(json.dumps({"instances": x.tolist()}))
    assert inst.data.shape == (n, h, w, c)
    np.testing.assert_allclose(inst.data, x, rtol=1e-6, atol=1e-7)


@settings(max_examples=150, deadline=None)
@given(
    vals=st.lists(
        st.floats(allow_nan=False, allow_infinity=False, width=32),
        min_size=1, max_size=16),
    indent=st.sampled_from([None, 1]),
)
def test_native_parser_matches_python_fallback(vals, indent):
    """Differential fuzz: the C++ parser and the pure-Python json path must
    agree to 1 ulp on arbitrary float32 JSON — including scientific
    notation ('1e-07'), 17-significant-digit repr output (exceeds the
    fixed-point fast path, exercising the from_chars fallback), negative
    zero, subnormals, and indent whitespace."""
    import pytest

    from storm_tpu.native import native_available, parse_instances_native

    if not native_available():
        pytest.skip("native library not built")
    payload = json.dumps({"instances": [vals]}, indent=indent)
    native = parse_instances_native(payload)
    expected = np.asarray(json.loads(payload)["instances"],
                          dtype=np.float32)
    assert native.shape == expected.shape

    def ulp_ordered(x):
        # monotonic integer mapping of float32 bit patterns (+0 == -0);
        # np.testing's nulp helper overflows np.spacing near float32 max
        u = np.ascontiguousarray(x, np.float32).view(np.uint32)\
            .astype(np.int64)
        return np.where(u < 1 << 31, u + (1 << 31), (1 << 32) - u)

    diff = np.abs(ulp_ordered(native) - ulp_ordered(expected))
    assert int(diff.max()) <= 1, (native, expected)


# ---- the engine's queue: batch formation --------------------------------------


@settings(max_examples=100, deadline=None)
@given(
    records=st.lists(
        st.tuples(st.integers(min_value=1, max_value=7),   # rows
                  st.integers(min_value=0, max_value=2)),  # source
        min_size=1, max_size=30),
    max_batch=st.integers(min_value=4, max_value=32),
)
def test_queue_formation_conserves_records(records, max_batch):
    """Any sequence of record sizes from any mix of sources: every record
    leaves the queue exactly once with all its rows, in arrival order per
    source, and no batch holds more than ``max_batch`` rows (a single
    record of more rows than that ships alone)."""
    from collections import deque

    from storm_tpu.config import BatchConfig
    from storm_tpu.infer.continuous import ContinuousBatcher, Submission

    class _Engine:
        ring_capacity = 1

    engine = _Engine()  # the queue holds its engine weakly
    cb = ContinuousBatcher(engine, BatchConfig(
        max_batch=max_batch, max_wait_ms=1e9, buckets=(max_batch,)))
    for idx, (rows, source) in enumerate(records):
        sub = Submission(np.full((rows, 2), idx, np.float32), idx, 0.0, 0.0,
                         None, None, f"s{source}", 0.0)
        cb._queues.setdefault(cb._key(None, None), deque()).append(sub)
        cb._pending_rows += rows
    seen = []
    while len(cb):
        batch = cb._form_locked()
        assert batch, "rows pending but nothing formed"
        assert len(batch) == 1 or sum(s.rows for s in batch) <= max_batch
        seen.extend(batch)
    assert sorted(s.payload for s in seen) == list(range(len(records)))
    assert all(s.rows == records[s.payload][0] for s in seen)
    for source in range(3):
        mine = [s.payload for s in seen if s.source == f"s{source}"]
        assert mine == sorted(mine), "order kept per source"


@given(
    keys=st.lists(st.one_of(st.text(max_size=20), st.integers(),
                            st.tuples(st.text(max_size=8), st.integers())),
                  min_size=1, max_size=50),
    n=st.integers(min_value=1, max_value=16),
)
def test_stable_hash_affinity_and_range(keys, n):
    """stable_hash is deterministic, value-based, and FieldsGrouping maps
    every key to a valid instance consistently."""
    from storm_tpu.runtime.groupings import stable_hash

    for k in keys:
        h1, h2 = stable_hash(k), stable_hash(k)
        assert h1 == h2 and 0 <= h1 < 2**32
        assert 0 <= h1 % n < n
        # value-based: an equal reconstructed key hashes identically
        if isinstance(k, tuple):
            assert stable_hash(tuple(list(k))) == h1
        elif isinstance(k, str):
            assert stable_hash(str(k)) == h1


@given(
    records=st.lists(
        st.tuples(st.one_of(st.none(), st.binary(max_size=16)),
                  st.binary(max_size=64)),
        min_size=1, max_size=8),
    pid=st.integers(min_value=0, max_value=2**31),
    epoch=st.integers(min_value=0, max_value=100),
    seq=st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=50)
def test_record_batch_roundtrip_with_producer_fields(records, pid, epoch, seq):
    """Producer-stamped (idempotent) batches survive encode/decode and the
    stub's header parse recovers the exact KIP-98 fields."""
    from kafka_stub import KafkaStubBroker
    from storm_tpu.connectors.kafka_protocol import (
        decode_record_batch, encode_record_batch)

    data = encode_record_batch(records, ts_ms=123456, base_offset=7,
                               producer=(pid, epoch, seq))
    got, consumed = decode_record_batch("t", 0, data, verify_crc=True)
    assert consumed == len(data)
    assert [(r.key, r.value) for r in got] == [
        (k, v) for k, v in records]
    fields = KafkaStubBroker._batch_producer_fields(data)
    assert fields == (pid, seq, len(records), epoch)


# ---- dist wire codecs (binary frames + JSON envelope) ------------------------


def _mk_tuple(values, trace=None, origins=frozenset(), anchors=frozenset()):
    from storm_tpu.runtime.tuples import Tuple

    return Tuple(values=list(values),
                 fields=tuple(f"f{i}" for i in range(len(values))),
                 source_component="spout", source_task=2, stream="default",
                 edge_id=(7 << 56) | 12345, anchors=anchors, root_ts=100.0,
                 origins=origins, trace=trace)


def _values_eq(a, b):
    """Equality that treats NaN as self-equal and demands type fidelity
    for the scalar kinds the binary wire tags (bool is not 1)."""
    if isinstance(a, bool) or isinstance(b, bool):
        return a is b
    if isinstance(a, float) and isinstance(b, float):
        return (a != a and b != b) or a == b
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return len(a) == len(b) and all(map(_values_eq, a, b))
    return type(a) is type(b) and a == b


# Surrogates included on purpose (satellite: unicode incl. surrogates):
# the binary wire must carry lone surrogates via surrogatepass.
_any_text = st.text(
    alphabet=st.characters(min_codepoint=0, max_codepoint=0x10FFFF),
    max_size=48)
_wire_scalar = st.one_of(
    st.none(),
    st.booleans(),
    st.integers(min_value=-(2**63), max_value=2**63 - 1),
    st.integers(min_value=2**63, max_value=2**80),  # JSON-slot fallback
    st.floats(allow_nan=True, allow_infinity=True),
    _any_text,
    st.binary(max_size=128),
)
_wire_values = st.lists(
    st.one_of(_wire_scalar, st.lists(_wire_scalar, max_size=4)), max_size=6)


@settings(max_examples=200, deadline=None)
@given(
    batches=st.lists(_wire_values, min_size=0, max_size=5),
    sampled=st.booleans(),
    origins=st.lists(st.tuples(st.text(max_size=12),
                               st.integers(min_value=0, max_value=2**31 - 1),
                               st.integers(min_value=0, max_value=2**63 - 1)),
                     max_size=3),
    anchors=st.lists(st.integers(min_value=0, max_value=2**64 - 1),
                     max_size=4),
)
def test_binary_wire_roundtrip_any_values(batches, sampled, origins, anchors):
    """Any mix of None/bool/int/bigint/NaN-Inf float/unicode-with-
    surrogates/bytes/nested-list values survives the binary frame exactly,
    with type fidelity, along with anchors/origins/trace headers. Covers
    empty deliveries and empty (zero-arity) tuples."""
    from storm_tpu.dist import wire
    from storm_tpu.runtime.tracing import TraceContext

    trace = TraceContext("ab" * 16, "cd" * 8) if sampled else None
    deliveries = [
        ("inference-bolt", i % 3,
         _mk_tuple(vals, trace=trace, origins=frozenset(origins),
                   anchors=frozenset(anchors)))
        for i, vals in enumerate(batches)
    ]
    frame = wire.encode_deliveries(deliveries, now=200.0)
    out = wire.decode_deliveries(frame, now=200.0)
    assert len(out) == len(deliveries)
    for (c0, i0, t0), (c1, i1, t1) in zip(deliveries, out):
        assert (c0, i0) == (c1, i1)
        assert _values_eq(t0.values, t1.values), (t0.values, t1.values)
        assert t1.fields == t0.fields
        assert t1.stream == t0.stream
        assert t1.source_component == t0.source_component
        assert t1.source_task == t0.source_task
        assert t1.edge_id == t0.edge_id
        assert t1.anchors == t0.anchors
        assert t1.origins == t0.origins
        assert abs(t1.root_ts - t0.root_ts) < 1e-6
        if sampled:
            assert t1.trace.trace_id == "ab" * 16
            assert t1.trace.span_id == "cd" * 8
        else:
            assert t1.trace is None


@settings(max_examples=150, deadline=None)
@given(
    vals=st.lists(
        st.one_of(st.none(), st.booleans(), _any_text,
                  st.integers(min_value=-(2**63), max_value=2**63 - 1),
                  st.floats(allow_nan=True, allow_infinity=True)),
        max_size=6),
)
def test_json_wire_roundtrip_json_safe_values(vals):
    """The JSON envelope (the mixed-version fallback) round-trips
    every JSON-safe value mix, including NaN/Inf floats, lone-surrogate
    text, and zero-arity tuples."""
    from storm_tpu.dist import transport

    deliveries = [("inference-bolt", 1, _mk_tuple(vals))]
    payload = transport.encode_deliveries(deliveries)
    out = transport.decode_deliveries(payload)
    assert len(out) == 1
    c, i, t = out[0]
    assert (c, i) == ("inference-bolt", 1)
    assert _values_eq(t.values, list(vals))
    assert t.edge_id == (7 << 56) | 12345


@settings(max_examples=150, deadline=None)
@given(
    ops=st.lists(st.tuples(st.sampled_from(["xor", "anc", "ake", "fail"]),
                           st.integers(min_value=0, max_value=2**64 - 1),
                           st.integers(min_value=0, max_value=2**64 - 1)),
                 max_size=40),
    use_json=st.booleans(),
)
def test_ack_codecs_roundtrip_and_autodetect(ops, use_json):
    """Both ack codecs round-trip any op/root/edge mix; the receiving
    decoder auto-detects which one the peer used."""
    from storm_tpu.dist import transport, wire

    payload = (transport.encode_acks(ops) if use_json
               else wire.encode_acks(ops))
    assert transport.decode_acks(payload) == list(ops)


@settings(max_examples=100, deadline=None)
@given(
    vals=_wire_values,
    flip=st.integers(min_value=0, max_value=2**31 - 1),
    xor=st.integers(min_value=1, max_value=255),
)
def test_binary_wire_corruption_fails_loudly(vals, flip, xor):
    """Any single-byte corruption of a binary frame raises WireError —
    never returns garbage deliveries. (Flipping a byte can only go
    undetected if CRC32 collides, which a single-byte xor cannot cause.)"""
    import pytest

    from storm_tpu.dist import wire

    frame = bytearray(wire.encode_deliveries(
        [("b", 0, _mk_tuple(vals))], now=50.0))
    frame[flip % len(frame)] ^= xor
    with pytest.raises(wire.WireError):
        wire.decode_deliveries(bytes(frame), now=50.0)


_BIG_BYTES = bytes(range(256)) * 400          # 102,400 B
_BIG_STR = "packet-é" * 9000             # > 64 KiB utf-8


def _big_frame():
    from storm_tpu.dist import wire

    return wire.encode_deliveries(
        [("b", 3, _mk_tuple([_BIG_BYTES, _BIG_STR]))], now=1.0)


def test_binary_wire_large_values_cross_intact():
    """>64 KiB str and bytes values cross intact."""
    from storm_tpu.dist import wire

    out = wire.decode_deliveries(_big_frame(), now=1.0)
    assert out[0][2].values[0] == _BIG_BYTES
    assert out[0][2].values[1] == _BIG_STR


@pytest.mark.parametrize("cut", [0, 3, 11, "half", -1])
def test_binary_wire_truncated_large_frame_fails_loudly(cut):
    from storm_tpu.dist import wire

    frame = _big_frame()
    if cut == "half":
        cut = len(frame) // 2
    with pytest.raises(wire.WireError):
        wire.decode_deliveries(frame[:cut], now=1.0)


def test_binary_wire_corrupted_ack_frames_fail_loudly():
    from storm_tpu.dist import wire

    acks = wire.encode_acks([("xor", 1, 2)])
    bad = bytearray(acks)
    bad[9] ^= 0x40
    with pytest.raises(wire.WireError):
        wire.decode_acks(bytes(bad))
    with pytest.raises(wire.WireError):
        wire.decode_acks(acks[:-2])


def test_binary_wire_empty_frames_are_valid():
    from storm_tpu.dist import wire

    assert wire.decode_deliveries(
        wire.encode_deliveries([], now=0.0), now=0.0) == []
    assert wire.decode_acks(wire.encode_acks([])) == []


def test_binary_wire_ndarray_slot_roundtrip():
    """ndarray values ride the Arrow IPC marshaller inside the frame and
    come back dtype/shape/byte-identical (zero-copy view on decode)."""
    import pytest

    from storm_tpu.dist import wire

    try:
        from storm_tpu.serve.marshal import decode_tensor, encode_tensor
        encode_tensor(np.zeros((1,), np.float32))
    except ImportError:
        pytest.skip("no tensor marshaller available (native or pyarrow)")

    arr = np.arange(2 * 28 * 28, dtype=np.float32).reshape(2, 28, 28)
    frame = wire.encode_deliveries([("b", 0, _mk_tuple([arr]))], now=0.0)
    got = wire.decode_deliveries(frame, now=0.0)[0][2].values[0]
    assert isinstance(got, np.ndarray)
    assert got.dtype == arr.dtype and got.shape == arr.shape
    assert np.array_equal(got, arr)


def test_binary_wire_rejects_newer_version_and_bad_magic():
    """A frame stamped with a future version or an unknown magic byte is
    rejected before any payload parsing (negotiation must prevent this;
    the decoder is the backstop)."""
    import pytest

    from storm_tpu.dist import wire

    frame = bytearray(wire.encode_deliveries([], now=0.0))
    frame[1] = wire.WIRE_VERSION + 1
    with pytest.raises(wire.WireError, match="version"):
        wire.decode_deliveries(bytes(frame), now=0.0)
    frame = bytearray(wire.encode_deliveries([], now=0.0))
    frame[0] = 0x7B  # '{' — not a JSON array either
    with pytest.raises(wire.WireError, match="magic"):
        wire.decode_deliveries(bytes(frame), now=0.0)
