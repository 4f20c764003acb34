"""Binary tuple/ack wire codec for the distributed runtime.

The JSON envelope in :mod:`storm_tpu.dist.transport` re-stringifies every
value on every worker hop and rejects ``bytes`` outright, which forced
``scheme="string"`` (two extra copies per record) in exactly the mode that
is supposed to scale.  This module is the binary replacement: one
length-prefixed frame per destination per flush, a compact per-tuple header
(stream, component, task, edge id, anchors, origins, W3C trace context as
24 raw bytes), and tagged value slots that carry ``bytes``/``str``/numeric
values without re-encoding.  ndarrays ride the existing Arrow IPC
marshaller (:mod:`storm_tpu.serve.marshal`), so broker bytes and tensors
flow spout -> worker -> worker -> sink with zero JSON round-trips.

Like the instance parser, the codec is layered pure-Python over native
pieces: framing is ``struct`` packing either way, while the byte-heavy
work — tensor marshalling and the frame checksum — uses
``libstormtpu.so`` when built.  Without it, tensors fall back to pyarrow
and the checksum falls back to ``zlib.crc32`` (also C speed, stdlib); the
flags byte records which algorithm stamped the frame so a mixed cluster
verifies correctly.

Frame layouts (all little-endian)::

    deliveries frame
      0xB7 | ver u8 | flags u8 | 0 | count u32
      count * [ component vstr | task u32 | tuple ]
      crc u32                      (over everything before the trailer)

    tuple
      stream vstr | source_component vstr | source_task u32
      edge_id u64 | age f64
      n_anchors u16,  n * u64
      n_origins u16,  n * (topic vstr | partition u32 | next_offset u64)
      trace u8 (0|1), 24 raw bytes when 1
      n_fields u16,   n * vstr
      n_values u16,   n * slot

    slot  = tag u8 + payload
      0 None | 1 False | 2 True | 3 i64 | 4 f64
      5 str  (u32 + utf-8, surrogatepass)
      6 bytes (u32 + raw)
      7 ndarray (u32 + Arrow IPC via serve.marshal)
      8 list (u32 count + nested slots)
      9 json (u32 + utf-8 json.dumps — dicts, big ints, exotica)
      10 record frame (u32 + runtime.frames.RecordFrame body;
         wire v2 — senders decompose to a list-of-bytes slot and stamp
         version 1 for peers that only advertise {"wire": 1})

    acks frame
      0xB8 | ver u8 | flags u8 | 0 | count u32
      count * ( op u8 | root u64 | edge u64 )      # 17-byte records
      crc u32

    shm header frame (wire v2, co-located workers)
      0xB9 | ver u8 | flags u8 | 0
      segment-name vstr | offset u64 | length u64
      crc u32                      (over the HEADER only — the body
      already crossed through a local shared-memory segment, where the
      failure mode a body CRC guards against (bit rot on the network
      path) does not exist; skipping it is the lane's perf point)

    The shm segment holds an UNSEALED deliveries frame (``0xB7 | ver |
    flags | 0 | count`` + payload, no CRC trailer), written part-by-part
    by the sender — that single segment write is the ``shm_transport``
    ledger hop that replaces socket send+recv AND the encoder's seal
    join. The receiver decodes zero-copy views over the mapped segment
    (``decode_deliveries_view``).

``flags`` bit 0 selects the checksum: 0 = CRC32C (native), 1 = zlib.crc32.
Decoders raise :class:`WireError` on any magic/version/CRC/structure
mismatch — a corrupted frame must fail loudly, never deliver garbage; the
failed RPC surfaces at the sender, which retries, and pending trees replay.

Version negotiation lives in the worker control plane: ``ping`` responses
advertise ``{"wire": WIRE_VERSION}`` and senders fall back to the JSON
envelope for peers that don't (mixed-version clusters) or
when ``TopologyConfig.wire_format = "json"`` pins the fallback.
"""

from __future__ import annotations

import json
import struct
import time
import zlib
from typing import List, Optional, Sequence
from typing import Tuple as Tup

import numpy as np

from storm_tpu.native import crc32c, native_available
from storm_tpu.obs import copyledger as _copyledger
from storm_tpu.runtime.frames import RecordFrame
from storm_tpu.runtime.tracing import TraceContext
from storm_tpu.runtime.tuples import Tuple

__all__ = [
    "WIRE_VERSION", "WireError",
    "DELIVERY_MAGIC", "ACK_MAGIC", "SHM_MAGIC",
    "encode_deliveries", "decode_deliveries",
    "encode_delivery_parts", "decode_deliveries_view",
    "encode_shm_header", "decode_shm_header",
    "encode_acks", "decode_acks",
]

#: Bumped whenever a frame change is not trailing-compatible. Advertised in
#: worker ping responses; senders only emit binary to peers that advertise
#: a version >= the frames they produce. v2 adds the record-frame value
#: slot (tag 10) and the shm header frame (0xB9); senders decompose frame
#: values and stamp version 1 for v1 peers, so rolling restarts stay safe.
WIRE_VERSION = 2

DELIVERY_MAGIC = 0xB7
ACK_MAGIC = 0xB8
SHM_MAGIC = 0xB9

_CRC_CASTAGNOLI = 0  # flags bit 0 clear: CRC32C via the native layer
_CRC_ZLIB = 1        # flags bit 0 set: stdlib zlib.crc32

# Slot tags. New tags append; decoders reject unknown tags loudly (the
# version byte, not trailing tolerance, is the binary compat mechanism).
_T_NONE = 0
_T_FALSE = 1
_T_TRUE = 2
_T_I64 = 3
_T_F64 = 4
_T_STR = 5
_T_BYTES = 6
_T_NDARRAY = 7
_T_LIST = 8
_T_JSON = 9
_T_FRAME = 10  # wire v2: RecordFrame body (runtime/frames.py layout)

_I64_MIN = -(1 << 63)
_I64_MAX = (1 << 63) - 1

_pack_u16 = struct.Struct("<H").pack
_pack_u32 = struct.Struct("<I").pack
_pack_u64 = struct.Struct("<Q").pack
_pack_i64 = struct.Struct("<q").pack
_pack_f64 = struct.Struct("<d").pack
_pack_task = struct.Struct("<I").pack
_u16 = struct.Struct("<H")
_u32 = struct.Struct("<I")
_u64 = struct.Struct("<Q")
_i64 = struct.Struct("<q")
_f64 = struct.Struct("<d")
_origin_fix = struct.Struct("<IQ")
_ack_rec = struct.Struct("<BQQ")
# task u32 | edge_id u64 | age f64 | n_anchors u16, packed contiguously
# ("<" = no alignment padding) — one struct call for the fixed header.
_tuple_fix = struct.Struct("<IQdH")

# Ack op codes <-> the JSON envelope's op strings.
_ACK_OPS = ("xor", "anc", "ake", "fail")
_ACK_CODE = {op: i for i, op in enumerate(_ACK_OPS)}


class WireError(ValueError):
    """A binary frame failed validation (magic, version, CRC, structure).

    Raised instead of returning partial data: the gRPC handler surfaces it
    as a failed RPC, the sender's retry/backoff logic kicks in, and any
    tuples lost with the frame are replayed by their pending trees.
    """


def _frame_crc(flags: int, body) -> int:
    if flags & 1:
        return zlib.crc32(body) & 0xFFFFFFFF
    return crc32c(bytes(body))


# ---------------------------------------------------------------------------
# value slots


def _enc_str(out: List[bytes], s: str) -> None:
    b = s.encode("utf-8", "surrogatepass")
    out.append(b"\x05" + _pack_u32(len(b)))
    out.append(b)


def _enc_value(out: List[bytes], v) -> None:
    # bool before int: bool is an int subclass.
    if v is None:
        out.append(b"\x00")
    elif v is False:
        out.append(b"\x01")
    elif v is True:
        out.append(b"\x02")
    elif isinstance(v, str):
        _enc_str(out, v)
    elif isinstance(v, int) and not isinstance(v, bool):
        if _I64_MIN <= v <= _I64_MAX:
            out.append(b"\x03" + _pack_i64(v))
        else:  # arbitrary-precision stragglers ride the JSON slot
            b = str(v).encode("ascii")
            out.append(b"\x09" + _pack_u32(len(b)))
            out.append(b)
    elif isinstance(v, float):
        out.append(b"\x04" + _pack_f64(v))
    elif isinstance(v, (bytes, bytearray, memoryview)):
        b = bytes(v) if not isinstance(v, bytes) else v
        out.append(b"\x06" + _pack_u32(len(b)))
        out.append(b)
    elif isinstance(v, np.ndarray):
        from storm_tpu.serve.marshal import encode_tensor
        b = encode_tensor(np.ascontiguousarray(v))
        out.append(b"\x07" + _pack_u32(len(b)))
        out.append(b)
    elif isinstance(v, RecordFrame):
        # Record frames append as REFERENCES (header + per-record
        # buffers, runtime/frames.py) — the only whole-frame copy is the
        # seal join (or the shm segment write, which replaces it).
        out.append(b"\x0a" + _pack_u32(v.encoded_nbytes()))
        out.extend(v.encode_parts())
    elif isinstance(v, (list, tuple)):
        out.append(b"\x08" + _pack_u32(len(v)))
        for item in v:
            _enc_value(out, item)
    elif isinstance(v, np.bool_):
        out.append(b"\x02" if v else b"\x01")
    elif isinstance(v, np.integer):
        out.append(b"\x03" + _pack_i64(int(v)))
    elif isinstance(v, np.floating):
        out.append(b"\x04" + _pack_f64(float(v)))
    else:
        # Dicts and other JSON-able exotica. json.dumps raising TypeError
        # here is the loud equivalent of the JSON envelope's behaviour.
        b = json.dumps(v, separators=(",", ":")).encode("utf-8")
        out.append(b"\x09" + _pack_u32(len(b)))
        out.append(b)


def _dec_value(buf: memoryview, pos: int, end: int):
    if pos >= end:
        raise WireError("truncated frame: value slot past end")
    tag = buf[pos]
    pos += 1
    if tag == _T_NONE:
        return None, pos
    if tag == _T_FALSE:
        return False, pos
    if tag == _T_TRUE:
        return True, pos
    if tag == _T_I64:
        if pos + 8 > end:
            raise WireError("truncated frame: i64 slot")
        return _i64.unpack_from(buf, pos)[0], pos + 8
    if tag == _T_F64:
        if pos + 8 > end:
            raise WireError("truncated frame: f64 slot")
        return _f64.unpack_from(buf, pos)[0], pos + 8
    if tag in (_T_STR, _T_BYTES, _T_NDARRAY, _T_JSON, _T_FRAME):
        if pos + 4 > end:
            raise WireError("truncated frame: slot length")
        (n,) = _u32.unpack_from(buf, pos)
        pos += 4
        if pos + n > end:
            raise WireError("truncated frame: slot payload")
        raw = buf[pos:pos + n]
        pos += n
        if tag == _T_STR:
            return str(raw, "utf-8", "surrogatepass"), pos
        if tag == _T_BYTES:
            return bytes(raw), pos
        if tag == _T_NDARRAY:
            from storm_tpu.serve.marshal import decode_tensor
            return decode_tensor(raw), pos
        if tag == _T_FRAME:
            # Zero-copy: the frame's records are memoryview slices over
            # the received buffer (or the mapped shm segment).
            try:
                return RecordFrame.from_buffer(raw), pos
            except ValueError as exc:
                raise WireError(f"bad record-frame slot: {exc}") from None
        try:
            return json.loads(bytes(raw)), pos
        except ValueError as exc:
            raise WireError(f"bad JSON slot: {exc}") from None
    if tag == _T_LIST:
        if pos + 4 > end:
            raise WireError("truncated frame: list count")
        (n,) = _u32.unpack_from(buf, pos)
        pos += 4
        items = [None] * n
        for i in range(n):
            items[i], pos = _dec_value(buf, pos, end)
        return items, pos
    raise WireError(f"unknown value slot tag {tag}")


# ---------------------------------------------------------------------------
# names / tuple headers


#: Length-prefixed encodings of header names (streams, component ids,
#: field names). These are topology-static and repeat on every tuple, so
#: memoizing the encode+prefix turns ~6 utf-8 encodes per tuple into dict
#: hits. Bounded: a pathological dynamic-name producer stops inserting at
#: the cap instead of leaking.
_NAME_CACHE: dict = {}
_NAME_CACHE_MAX = 1024


def _name_bytes(s: str) -> bytes:
    b = _NAME_CACHE.get(s)
    if b is None:
        raw = s.encode("utf-8", "surrogatepass")
        if len(raw) > 0xFFFF:
            raise WireError(f"name too long for wire header: {len(raw)} bytes")
        b = _pack_u16(len(raw)) + raw
        if len(_NAME_CACHE) < _NAME_CACHE_MAX:
            _NAME_CACHE[s] = b
    return b


def _enc_name(out: List[bytes], s: str) -> None:
    out.append(_name_bytes(s))


def _dec_name(buf: memoryview, pos: int, end: int) -> Tup[str, int]:
    if pos + 2 > end:
        raise WireError("truncated frame: name length")
    (n,) = _u16.unpack_from(buf, pos)
    pos += 2
    if pos + n > end:
        raise WireError("truncated frame: name payload")
    return str(buf[pos:pos + n], "utf-8", "surrogatepass"), pos + n


def _enc_tuple(out: List[bytes], t: Tuple, now: float,
               version: int = WIRE_VERSION) -> None:
    # The whole header concatenates into ONE parts-list entry: a tuple is
    # ~8 tiny pieces (memoized names + a combined struct pack), and one
    # bytes concat beats 15+ list appends — fewer allocations means less
    # GC churn on the send loop, which shows up as latency jitter at
    # steady state on busy hosts.
    anchors = t.anchors
    head = (_name_bytes(t.stream)
            + _name_bytes(t.source_component)
            + _tuple_fix.pack(t.source_task, t.edge_id, now - t.root_ts,
                              len(anchors)))
    if anchors:
        head += b"".join(map(_pack_u64, anchors))

    origins = t.origins
    head += _pack_u16(len(origins))
    for topic, partition, next_offset in origins:
        head += _name_bytes(topic) + _origin_fix.pack(partition, next_offset)

    trace = t.trace
    tb = trace.to_bytes() if trace is not None else None
    if tb is not None and len(tb) == 24:
        head += b"\x01" + tb
    else:
        head += b"\x00"

    fields = t.fields
    head += _pack_u16(len(fields))
    for f in fields:
        head += _name_bytes(f)

    values = t.values
    if len(values) > 0xFFFF:
        raise WireError(f"tuple arity too large for wire: {len(values)}")
    out.append(head + _pack_u16(len(values)))
    for v in values:
        if version < 2 and isinstance(v, RecordFrame):
            # v1 peer: no frame slot on its decoder — decompose to the
            # list-of-bytes shape the legacy chunk path used (copies,
            # but only during a mixed-version rolling restart).
            v = v.tolist()
        _enc_value(out, v)


def _dec_tuple(buf: memoryview, pos: int, end: int, now: float):
    stream, pos = _dec_name(buf, pos, end)
    source_component, pos = _dec_name(buf, pos, end)
    if pos + 22 > end:
        raise WireError("truncated frame: tuple fixed header")
    source_task, edge_id, age, n = _tuple_fix.unpack_from(buf, pos)
    pos += 22
    if pos + 8 * n > end:
        raise WireError("truncated frame: anchors")
    anchors = frozenset(
        _u64.unpack_from(buf, pos + 8 * i)[0] for i in range(n))
    pos += 8 * n

    if pos + 2 > end:
        raise WireError("truncated frame: origin count")
    (n,) = _u16.unpack_from(buf, pos)
    pos += 2
    origins = []
    for _ in range(n):
        topic, pos = _dec_name(buf, pos, end)
        if pos + 12 > end:
            raise WireError("truncated frame: origin record")
        partition, next_offset = _origin_fix.unpack_from(buf, pos)
        pos += 12
        origins.append((topic, partition, next_offset))

    if pos >= end:
        raise WireError("truncated frame: trace flag")
    has_trace = buf[pos]
    pos += 1
    trace = None
    if has_trace:
        if pos + 24 > end:
            raise WireError("truncated frame: trace context")
        trace = TraceContext.from_bytes(bytes(buf[pos:pos + 24]))
        pos += 24

    if pos + 2 > end:
        raise WireError("truncated frame: field count")
    (n,) = _u16.unpack_from(buf, pos)
    pos += 2
    fields = [None] * n
    for i in range(n):
        fields[i], pos = _dec_name(buf, pos, end)

    if pos + 2 > end:
        raise WireError("truncated frame: value count")
    (n,) = _u16.unpack_from(buf, pos)
    pos += 2
    values = [None] * n
    for i in range(n):
        values[i], pos = _dec_value(buf, pos, end)

    t = Tuple(
        values=values,
        fields=tuple(fields),
        source_component=source_component,
        source_task=source_task,
        stream=stream,
        edge_id=edge_id,
        anchors=anchors,
        root_ts=now - age,
        origins=frozenset(origins),
        trace=trace,
    )
    return t, pos


# ---------------------------------------------------------------------------
# frames


def _open_frame(magic: int, count: int,
                version: int = WIRE_VERSION) -> Tup[List[bytes], int]:
    flags = _CRC_CASTAGNOLI if native_available() else _CRC_ZLIB
    return [bytes((magic, version, flags, 0)), _pack_u32(count)], flags


def _seal_frame(out: List[bytes], flags: int) -> bytes:
    body = b"".join(out)
    return body + _pack_u32(_frame_crc(flags, body))


def _check_frame(payload, magic: int) -> Tup[memoryview, int]:
    """Validate magic/version/CRC; return (body view, payload count)."""
    buf = memoryview(payload)
    if len(buf) < 12:
        raise WireError(f"frame too short: {len(buf)} bytes")
    if buf[0] != magic:
        raise WireError(f"bad magic 0x{buf[0]:02X} (want 0x{magic:02X})")
    if buf[1] > WIRE_VERSION:
        raise WireError(
            f"wire version {buf[1]} newer than supported {WIRE_VERSION}")
    flags = buf[2]
    (want,) = _u32.unpack_from(buf, len(buf) - 4)
    got = _frame_crc(flags, buf[:-4])
    if got != want:
        raise WireError(
            f"frame CRC mismatch: computed 0x{got:08X}, header 0x{want:08X}")
    (count,) = _u32.unpack_from(buf, 4)
    return buf, count


def encode_delivery_parts(deliveries: Sequence[Tup[str, int, Tuple]],
                          now: Optional[float] = None,
                          version: int = WIRE_VERSION
                          ) -> Tup[List[bytes], int]:
    """The deliveries frame as an UNSEALED parts list ``(parts, flags)``.

    For transports that write the frame themselves instead of joining it
    — the shm lane writes the parts sequentially into a shared-memory
    segment, making that single write the only whole-frame copy (its
    ``shm_transport`` ledger hop; no ``wire_encode`` bytes are charged
    here because no join happened). No CRC trailer: the shm header
    frame's own CRC is the lane's integrity check."""
    if now is None:
        now = time.perf_counter()
    if not isinstance(deliveries, (list, tuple)):
        deliveries = list(deliveries)
    out, flags = _open_frame(DELIVERY_MAGIC, len(deliveries), version)
    append = out.append
    for component, task, t in deliveries:
        _enc_name(out, component)
        append(_pack_task(task))
        _enc_tuple(out, t, now, version)
    _copyledger.record("wire_encode", 0, copies=0, allocs=0,
                       records=len(deliveries))
    return out, flags


def encode_deliveries(deliveries: Sequence[Tup[str, int, Tuple]],
                      now: Optional[float] = None,
                      version: int = WIRE_VERSION) -> bytes:
    """Encode ``[(component, task, tuple), ...]`` as one binary frame.

    ``version`` is the NEGOTIATED peer version: frames are stamped with
    it and v2-only value shapes (record frames) are decomposed for v1
    peers, so a mixed-version mesh keeps decoding."""
    if now is None:
        now = time.perf_counter()
    if not isinstance(deliveries, (list, tuple)):
        deliveries = list(deliveries)
    out, flags = _open_frame(DELIVERY_MAGIC, len(deliveries), version)
    append = out.append
    for component, task, t in deliveries:
        _enc_name(out, component)
        append(_pack_task(task))
        _enc_tuple(out, t, now, version)
    frame = _seal_frame(out, flags)
    # Copy ledger: the seal's parts-list join is the one full-frame copy
    # of the encode (slot encodes append views/bytes into the list).
    _copyledger.record("wire_encode", len(frame), copies=1, allocs=1,
                       records=len(deliveries))
    return frame


def _dec_deliveries(buf: memoryview, pos: int, end: int, count: int,
                    now: float) -> List[Tup[str, int, Tuple]]:
    deliveries = [None] * count
    for i in range(count):
        component, pos = _dec_name(buf, pos, end)
        if pos + 4 > end:
            raise WireError("truncated frame: delivery task")
        (task,) = _u32.unpack_from(buf, pos)
        pos += 4
        t, pos = _dec_tuple(buf, pos, end, now)
        deliveries[i] = (component, task, t)
    if pos != end:
        raise WireError(
            f"frame has {end - pos} trailing bytes after {count} deliveries")
    return deliveries


def decode_deliveries(payload,
                      now: Optional[float] = None
                      ) -> List[Tup[str, int, Tuple]]:
    """Decode a binary deliveries frame back to ``[(component, task, t)]``.

    Raises :class:`WireError` on any corruption; never returns partial
    results.
    """
    if now is None:
        now = time.perf_counter()
    buf, count = _check_frame(payload, DELIVERY_MAGIC)
    end = len(buf) - 4
    deliveries = _dec_deliveries(buf, 8, end, count, now)
    # Copy ledger: decoding materializes str/bytes slots out of the frame
    # view (ndarray slots stay zero-copy views — serve/marshal reports
    # those itself), so one decode pass over the frame counts as one copy.
    _copyledger.record("wire_decode", len(buf), copies=1,
                       allocs=count, records=count)
    return deliveries


def decode_deliveries_view(buf,
                           now: Optional[float] = None
                           ) -> List[Tup[str, int, Tuple]]:
    """Decode an UNSEALED deliveries frame over a mapped shm segment.

    No CRC trailer to verify (the shm header frame's CRC already passed,
    and a local segment has no network path to rot on); record-frame and
    ndarray slots stay zero-copy views over the segment, which is what
    the ``wire_decode`` hop's zeros assert."""
    if now is None:
        now = time.perf_counter()
    buf = memoryview(buf)
    if len(buf) < 8:
        raise WireError(f"shm frame body too short: {len(buf)} bytes")
    if buf[0] != DELIVERY_MAGIC:
        raise WireError(
            f"bad magic 0x{buf[0]:02X} in shm segment "
            f"(want 0x{DELIVERY_MAGIC:02X})")
    if buf[1] > WIRE_VERSION:
        raise WireError(
            f"wire version {buf[1]} newer than supported {WIRE_VERSION}")
    (count,) = _u32.unpack_from(buf, 4)
    deliveries = _dec_deliveries(buf, 8, len(buf), count, now)
    _copyledger.record("wire_decode", 0, copies=0,
                       allocs=count, records=count)
    return deliveries


def encode_shm_header(name: str, offset: int, length: int) -> bytes:
    """The 0xB9 header frame pointing a co-located peer at a segment.

    CRC covers the HEADER only — the body never touched the network."""
    out, flags = _open_frame(SHM_MAGIC, 0)
    # _open_frame's count slot is unused for shm headers (always 0); the
    # layout keeps the common 8-byte prefix so _check_frame applies.
    out.append(_name_bytes(name))
    out.append(struct.pack("<QQ", offset, length))
    return _seal_frame(out, flags)


def decode_shm_header(payload) -> Tup[str, int, int]:
    """Validate + decode a 0xB9 header -> ``(segment name, offset,
    length)``. Raises :class:`WireError` on magic/version/CRC/structure
    mismatch — a corrupt header must never attach a segment."""
    buf, _count = _check_frame(payload, SHM_MAGIC)
    end = len(buf) - 4
    name, pos = _dec_name(buf, 8, end)
    if pos + 16 != end:
        raise WireError(
            f"shm header length mismatch: {end - pos} trailing bytes")
    offset, length = struct.unpack_from("<QQ", buf, pos)
    return name, offset, length


def encode_acks(acks: Sequence[Tup[str, int, int]]) -> bytes:
    """Encode ``[(op, root_id, edge_id), ...]`` as fixed-width records."""
    if not isinstance(acks, (list, tuple)):
        acks = list(acks)
    out, flags = _open_frame(ACK_MAGIC, len(acks))
    pack = _ack_rec.pack
    code = _ACK_CODE
    append = out.append
    for op, root_id, edge_id in acks:
        append(pack(code[op], root_id, edge_id))
    return _seal_frame(out, flags)


def decode_acks(payload) -> List[Tup[str, int, int]]:
    """Decode a binary ack frame back to ``[(op, root, edge)]`` triples.

    Unknown op codes are dropped (same forward-compat stance as the JSON
    decoder); structural corruption raises :class:`WireError`.
    """
    buf, count = _check_frame(payload, ACK_MAGIC)
    end = len(buf) - 4
    if 8 + 17 * count != end:
        raise WireError(
            f"ack frame length mismatch: {end - 8} bytes for {count} records")
    ops = _ACK_OPS
    n_ops = len(ops)
    unpack = _ack_rec.unpack_from
    acks = []
    for i in range(count):
        op, root, edge = unpack(buf, 8 + 17 * i)
        if op < n_ops:
            acks.append((ops[op], root, edge))
    return acks
