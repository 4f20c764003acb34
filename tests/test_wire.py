"""Dist wire codecs: binary frames (storm_tpu/dist/wire.py) and the JSON
envelope fallback (storm_tpu/dist/transport.py).

The hypothesis versions of these round-trips live in test_properties.py;
this file carries the same coverage as deterministic examples plus
seeded-random fuzz loops so the codec contract is enforced in tier-1 even
where hypothesis isn't installed (the property suite is collection-skipped
there). Satellite checklist coverage: unicode incl. lone surrogates,
bytes, NaN/Inf floats, empty tuples, >64 KiB values, corrupted-CRC frames
failing loudly.
"""

from __future__ import annotations

import math
import random

import numpy as np
import pytest

from storm_tpu.dist import transport, wire
from storm_tpu.runtime.tracing import TraceContext
from storm_tpu.runtime.tuples import Tuple


def mk_tuple(values, trace=None, origins=frozenset(), anchors=frozenset(),
             fields=None):
    return Tuple(values=list(values),
                 fields=tuple(fields) if fields is not None
                 else tuple(f"f{i}" for i in range(len(values))),
                 source_component="spout", source_task=2, stream="default",
                 edge_id=(7 << 56) | 12345, anchors=anchors, root_ts=100.0,
                 origins=origins, trace=trace)


def values_eq(a, b):
    """NaN-tolerant, type-faithful equality (bool is not 1)."""
    if isinstance(a, bool) or isinstance(b, bool):
        return a is b
    if isinstance(a, float) and isinstance(b, float):
        return (a != a and b != b) or a == b
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return len(a) == len(b) and all(map(values_eq, a, b))
    return type(a) is type(b) and a == b


def rand_value(rng: random.Random, depth=0):
    kinds = ["none", "bool", "int", "bigint", "float", "str", "surrogate",
             "bytes"]
    if depth == 0:
        kinds.append("list")
    k = rng.choice(kinds)
    if k == "none":
        return None
    if k == "bool":
        return rng.random() < 0.5
    if k == "int":
        return rng.randint(-(2**63), 2**63 - 1)
    if k == "bigint":
        return rng.randint(2**63, 2**80) * rng.choice((1, -1))
    if k == "float":
        return rng.choice([float("nan"), float("inf"), float("-inf"),
                           -0.0, rng.uniform(-1e300, 1e300)])
    if k == "str":
        return "".join(chr(rng.randint(32, 0x2FFF)) for _ in range(rng.randint(0, 24)))
    if k == "surrogate":
        # lone surrogates: must cross via surrogatepass, not crash
        return "a" + chr(rng.randint(0xD800, 0xDFFF)) + "z"
    if k == "bytes":
        return rng.randbytes(rng.randint(0, 64))
    return [rand_value(rng, depth + 1) for _ in range(rng.randint(0, 4))]


# ---- binary frame round trips ------------------------------------------------


def test_binary_roundtrip_exhaustive_example():
    trace = TraceContext("ab" * 16, "cd" * 8)
    t = mk_tuple(
        [b"\x00\xffraw", "unié" + chr(0xD800), 3.5, float("nan"),
         float("-inf"), None, True, False, -(2**63), 2**70,
         [1, "a", b"b", [None]], {"k": 1}],
        trace=trace,
        origins=frozenset({("topic-x", 2, 999), ("topic-y", 0, 2**60)}),
        anchors=frozenset({(7 << 56) | 1, 2, 2**64 - 1}))
    frame = wire.encode_deliveries([("inference-bolt", 1, t)], now=200.0)
    assert frame[0] == wire.DELIVERY_MAGIC and frame[1] == wire.WIRE_VERSION
    (c, i, t2), = wire.decode_deliveries(frame, now=200.0)
    assert (c, i) == ("inference-bolt", 1)
    assert values_eq(t2.values[:11], t.values[:11])
    assert t2.values[11] == {"k": 1}
    assert t2.fields == t.fields
    assert t2.stream == "default" and t2.source_component == "spout"
    assert t2.source_task == 2 and t2.edge_id == t.edge_id
    assert t2.anchors == t.anchors and t2.origins == t.origins
    assert abs(t2.root_ts - t.root_ts) < 1e-6
    assert t2.trace.trace_id == "ab" * 16 and t2.trace.span_id == "cd" * 8


def test_binary_roundtrip_seeded_fuzz():
    """300 random delivery batches (the hypothesis strategy, seeded)."""
    rng = random.Random(0xB7)
    for _ in range(300):
        deliveries = []
        for i in range(rng.randint(0, 4)):
            vals = [rand_value(rng) for _ in range(rng.randint(0, 5))]
            trace = (TraceContext(f"{rng.getrandbits(128):032x}",
                                  f"{rng.getrandbits(64):016x}")
                     if rng.random() < 0.3 else None)
            origins = frozenset(
                ("t" * rng.randint(1, 3), rng.randint(0, 2**31 - 1),
                 rng.randint(0, 2**63 - 1))
                for _ in range(rng.randint(0, 2)))
            anchors = frozenset(rng.randint(0, 2**64 - 1)
                                for _ in range(rng.randint(0, 3)))
            deliveries.append(
                ("bolt", i, mk_tuple(vals, trace, origins, anchors)))
        frame = wire.encode_deliveries(deliveries, now=50.0)
        out = wire.decode_deliveries(frame, now=50.0)
        assert len(out) == len(deliveries)
        for (c0, i0, t0), (c1, i1, t1) in zip(deliveries, out):
            assert (c0, i0) == (c1, i1)
            assert values_eq(t0.values, t1.values), (t0.values, t1.values)
            assert t1.anchors == t0.anchors and t1.origins == t0.origins
            assert t1.edge_id == t0.edge_id
            if t0.trace is None:
                assert t1.trace is None
            else:
                assert t1.trace.trace_id == t0.trace.trace_id
                assert t1.trace.span_id == t0.trace.span_id


def test_binary_empty_frame_and_empty_tuple():
    assert wire.decode_deliveries(
        wire.encode_deliveries([], now=0.0), now=0.0) == []
    (c, i, t), = wire.decode_deliveries(
        wire.encode_deliveries([("b", 0, mk_tuple([]))], now=0.0), now=0.0)
    assert t.values == [] and t.fields == ()


def test_binary_large_values_cross_intact():
    big_bytes = bytes(range(256)) * 400              # 102,400 B
    big_str = "packet-é" * 9000                 # > 64 KiB utf-8
    frame = wire.encode_deliveries(
        [("b", 3, mk_tuple([big_bytes, big_str]))], now=1.0)
    (_, _, t), = wire.decode_deliveries(frame, now=1.0)
    assert t.values[0] == big_bytes
    assert t.values[1] == big_str


def test_binary_numpy_scalars_and_age_rebase():
    t = mk_tuple([np.float32(1.5), np.int64(-7), np.bool_(True)])
    frame = wire.encode_deliveries([("b", 0, t)], now=130.0)  # age 30
    (_, _, t2), = wire.decode_deliveries(frame, now=500.0)
    assert t2.values == [1.5, -7, True]
    assert abs(t2.root_ts - 470.0) < 1e-6  # rebased: new_now - age


def test_binary_wire_ndarray_slot_roundtrip():
    try:
        from storm_tpu.serve.marshal import encode_tensor
        encode_tensor(np.zeros((1,), np.float32))
    except ImportError:
        pytest.skip("no tensor marshaller available (native or pyarrow)")
    arr = np.arange(2 * 28 * 28, dtype=np.float32).reshape(2, 28, 28)
    frame = wire.encode_deliveries([("b", 0, mk_tuple([arr]))], now=0.0)
    got = wire.decode_deliveries(frame, now=0.0)[0][2].values[0]
    assert isinstance(got, np.ndarray)
    assert got.dtype == arr.dtype and got.shape == arr.shape
    assert np.array_equal(got, arr)


# ---- corruption must fail loudly ---------------------------------------------


def test_corrupted_crc_fails_loudly():
    frame = bytearray(wire.encode_deliveries(
        [("b", 0, mk_tuple([b"payload", 1.0]))], now=5.0))
    frame[len(frame) // 2] ^= 0x5A
    with pytest.raises(wire.WireError, match="CRC"):
        wire.decode_deliveries(bytes(frame), now=5.0)
    # trailer corruption too
    frame = bytearray(wire.encode_deliveries(
        [("b", 0, mk_tuple(["x"]))], now=5.0))
    frame[-1] ^= 0x01
    with pytest.raises(wire.WireError, match="CRC"):
        wire.decode_deliveries(bytes(frame), now=5.0)


def test_every_single_byte_flip_is_detected():
    """CRC32 detects every burst <= 32 bits, so no single-byte corruption
    may ever decode (at any position: magic, version, flags, lengths,
    payload, trailer)."""
    frame = wire.encode_deliveries(
        [("bolt", 2, mk_tuple(["msg", b"\x01\x02", 3]))], now=9.0)
    for pos in range(len(frame)):
        bad = bytearray(frame)
        bad[pos] ^= 0x80
        with pytest.raises(wire.WireError):
            wire.decode_deliveries(bytes(bad), now=9.0)


@pytest.mark.parametrize("cut", [0, 3, 11, "half", -1])
def test_truncated_frames_fail_loudly(cut):
    frame = wire.encode_deliveries([("b", 0, mk_tuple(["hello"]))], now=1.0)
    if cut == "half":
        cut = len(frame) // 2
    with pytest.raises(wire.WireError):
        wire.decode_deliveries(frame[:cut], now=1.0)


def test_newer_version_and_bad_magic_rejected():
    frame = bytearray(wire.encode_deliveries([], now=0.0))
    frame[1] = wire.WIRE_VERSION + 1
    with pytest.raises(wire.WireError, match="version"):
        wire.decode_deliveries(bytes(frame), now=0.0)
    frame = bytearray(wire.encode_deliveries([], now=0.0))
    frame[0] = 0x7B
    with pytest.raises(wire.WireError, match="magic"):
        wire.decode_deliveries(bytes(frame), now=0.0)


# ---- acks --------------------------------------------------------------------


def test_ack_codecs_roundtrip_and_autodetect():
    rng = random.Random(7)
    for _ in range(100):
        ops = [(rng.choice(("xor", "anc", "ake", "fail")),
                rng.randint(0, 2**64 - 1), rng.randint(0, 2**64 - 1))
               for _ in range(rng.randint(0, 40))]
        assert transport.decode_acks(wire.encode_acks(ops)) == ops
        assert transport.decode_acks(transport.encode_acks(ops)) == ops


def test_ack_frame_corruption_fails_loudly():
    acks = wire.encode_acks([("xor", 1, 2), ("fail", 3, 4)])
    bad = bytearray(acks)
    bad[9] ^= 0x40
    with pytest.raises(wire.WireError):
        wire.decode_acks(bytes(bad))
    with pytest.raises(wire.WireError):
        wire.decode_acks(acks[:-2])


def test_ack_unknown_op_dropped_not_fatal():
    """Forward compat: an op code from a future sender is skipped, matching
    the JSON decoder's unknown-op stance (worker logs + tree replays)."""
    frame = bytearray(wire.encode_acks([("xor", 5, 6)]))
    body = frame[:-4]
    body[8] = 250  # unknown op code
    flags = body[2]
    import zlib

    from storm_tpu.native import crc32c
    crc = (zlib.crc32(body) & 0xFFFFFFFF) if flags & 1 else crc32c(bytes(body))
    reframed = bytes(body) + crc.to_bytes(4, "little")
    assert wire.decode_acks(reframed) == []


# ---- format auto-detection + JSON fallback -----------------------------------


def test_transport_decoders_autodetect_both_formats():
    t = mk_tuple(["hello", 1, 2.5])
    jpay = transport.encode_deliveries([("b", 0, t)])
    bpay = wire.encode_deliveries([("b", 0, t)], now=100.0)
    assert jpay[:1] == b"["          # JSON array
    assert bpay[0] == wire.DELIVERY_MAGIC
    for payload in (jpay, bpay):
        (c, i, t2), = transport.decode_deliveries(payload)
        assert (c, i) == ("b", 0)
        assert t2.values == ["hello", 1, 2.5]


def test_json_wire_roundtrip_preserves_nan_and_surrogates():
    vals = ["a" + chr(0xDC80), float("nan"), float("inf"), None, True,
            -(2**63)]
    payload = transport.encode_deliveries([("b", 1, mk_tuple(vals))])
    (_, _, t), = transport.decode_deliveries(payload)
    assert values_eq(t.values, vals)


def test_json_wire_still_rejects_bytes_values():
    """The fallback wire keeps its loud TypeError on bytes — that is what
    negotiation falls back TO, so the restriction must stay visible."""
    with pytest.raises(TypeError, match="binary"):
        transport.encode_deliveries([("b", 0, mk_tuple([b"raw"]))])


def test_math_extremes_roundtrip_binary():
    vals = [math.pi, 5e-324, 1.7976931348623157e308, -0.0]
    frame = wire.encode_deliveries([("b", 0, mk_tuple(vals))], now=0.0)
    out = wire.decode_deliveries(frame, now=0.0)[0][2].values
    assert out == vals
    assert math.copysign(1.0, out[3]) == -1.0
