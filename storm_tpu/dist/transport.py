"""Wire envelopes + gRPC plumbing for the distributed runtime.

Raw-bytes gRPC (no protoc codegen, same pattern as storm_tpu/serve): three
methods on service ``storm_tpu.Dist``:

- ``Deliver`` — a batch of tuples for components hosted on the receiving
  worker. The RPC returns only after every tuple is enqueued into its
  executor inbox, so bounded-inbox backpressure propagates across hosts.
- ``Ack`` — a batch of ledger ops (xor / fail_root) routed to the worker
  whose spout owns the tuple tree (id's top byte, tuples.owner_of).
- ``Control`` — controller -> worker RPCs: submit / start / metrics /
  drain / kill / ping, JSON in, JSON out.

Envelope notes: ids are 64-bit and JSON numbers lose integer precision past
2^53, so ids travel as decimal strings. ``root_ts`` is a local
``perf_counter`` value with a per-process epoch, so it crosses the wire as
*age* (sender_now - root_ts) and is rebased on arrival — e2e latency
histograms on remote workers stay meaningful (minus network transit, which
is part of what they should measure anyway).

Two wire formats share these RPCs. The default is the binary frame codec
in :mod:`storm_tpu.dist.wire` (tagged value slots, raw ``bytes`` allowed,
CRC-protected, traceparent in the frame header); this module keeps the
JSON envelope as the negotiated fallback for mixed-version clusters. ``decode_deliveries``/``decode_acks`` below
auto-detect the format from the first payload byte (JSON arrays start with
``[`` = 0x5B; binary frames with 0xB7/0xB8; shared-memory segment headers
with 0xB9), so a receiver accepts any of them regardless of what its own
sender half negotiated.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple as Tup

import grpc

from storm_tpu.dist import wire
from storm_tpu.dist.wire import WIRE_VERSION
from storm_tpu.obs import copyledger as _copyledger
from storm_tpu.resilience.retry import (RETRYABLE_BROAD, RETRYABLE_NARROW,
                                        RetryPolicy, _rpc_code, is_fatal_rpc)
from storm_tpu.runtime.tracing import TraceContext
from storm_tpu.runtime.tuples import Tuple

SERVICE = "storm_tpu.Dist"

_BIN_DELIVER = bytes((wire.DELIVERY_MAGIC,))
_BIN_ACK = bytes((wire.ACK_MAGIC,))
_BIN_SHM = bytes((wire.SHM_MAGIC,))

# Receiver half of the shared-memory lane: one process-wide LRU of
# attached segments (storm_tpu.dist.shm.SegmentCache), built lazily so
# importing this module never touches /dev/shm.
_segments = None


def _segment_cache():
    global _segments
    if _segments is None:
        from storm_tpu.dist import shm as _shm_lane

        _segments = _shm_lane.SegmentCache()
    return _segments

#: Shared-secret control-plane auth (VERDICT r4 missing #4): when set, the
#: controller exports this env var to its workers, every RPC carries the
#: token as metadata, and workers reject mismatches as UNAUTHENTICATED.
from storm_tpu.config import CONTROL_TOKEN_ENV as TOKEN_ENV
from storm_tpu.config import env_control_token as _env_token

_TOKEN_MD_KEY = "x-storm-tpu-token"

_OPTS = [
    ("grpc.max_receive_message_length", 64 * 1024 * 1024),
    ("grpc.max_send_message_length", 64 * 1024 * 1024),
]


# ---- tuple envelope ----------------------------------------------------------


def encode_tuple(t: Tuple, now: float) -> list:
    return [
        list(t.values),
        list(t.fields),
        t.stream,
        t.source_component,
        t.source_task,
        str(t.edge_id),
        [str(a) for a in t.anchors],
        now - t.root_ts,  # age, rebased on arrival
        # Source-log provenance (exactly-once offsets): without it a
        # transactional sink placed on ANOTHER worker would see empty
        # origins and silently never commit offsets. Log offsets are
        # sequential positions (nowhere near 2^53), so plain JSON ints
        # are lossless — unlike the random 64-bit ids above.
        [[tp, p, off] for tp, p, off in t.origins],
        # Distributed-trace context as a W3C traceparent string (None for
        # the unsampled common case) — trailing element per the versioning
        # contract in decode_tuple, so pre-tracing receivers ignore it.
        t.trace.traceparent() if t.trace is not None else None,
    ]


def decode_tuple(enc: list, now: float) -> Tuple:
    # Tolerant unpack: a worker built from a pre-origins checkout ships an
    # 8-element envelope — degrade to empty origins (EOS disabled for that
    # sender's tuples) instead of erroring the whole Deliver RPC and
    # wedging every tree from it into timeout/replay.
    #
    # VERSIONING CONTRACT (a round-3 review, low): from this version on, receivers
    # ignore unknown TRAILING envelope elements (the enc[:8] + indexed-
    # optional pattern below) and unknown ack-op names are dropped, so
    # adding fields/ops stays rolling-restart safe FORWARD. The guarantee
    # does not reach backward: pre-origins receivers hard-unpack 8
    # elements and treat unknown ack ops as fail_root — upgrading ACROSS
    # that boundary must be all-at-once (stop every worker, then restart).
    values, fields, stream, src, src_task, edge, anchors, age = enc[:8]
    origins = enc[8] if len(enc) > 8 else []
    tp_hdr = enc[9] if len(enc) > 9 else None
    return Tuple(
        values=values,
        fields=tuple(fields),
        source_component=src,
        source_task=src_task,
        stream=stream,
        edge_id=int(edge),
        anchors=frozenset(int(a) for a in anchors),
        root_ts=now - age,
        origins=frozenset((tp, p, off) for tp, p, off in origins),
        # from_traceparent returns None on malformed/absent input, so a
        # garbled header degrades to "unsampled" rather than failing the RPC.
        trace=TraceContext.from_traceparent(tp_hdr) if tp_hdr else None,
    )


def encode_deliveries(deliveries: Iterable[Tup[str, int, Tuple]]) -> bytes:
    """deliveries: (component_id, task_index, tuple) triples (JSON wire).

    ``now`` is sampled once per batch and threaded through; the hot loop
    pre-sizes the output list and binds the encoder locally rather than
    re-deriving per-tuple state each iteration.
    """
    now = time.perf_counter()
    if not isinstance(deliveries, (list, tuple)):
        deliveries = list(deliveries)
    enc = encode_tuple  # local bind: skip the global lookup per tuple
    out: list = [None] * len(deliveries)
    try:
        for j, (c, i, t) in enumerate(deliveries):
            out[j] = [c, i, enc(t, now)]
        payload = json.dumps(out).encode("utf-8")
        # Copy ledger: the JSON wire serializes every value into the
        # envelope (dumps) and then re-encodes the whole string to bytes
        # — two full-payload passes, the cost the binary wire removes.
        _copyledger.record("wire_encode", len(payload), copies=2,
                           allocs=2, records=len(deliveries))
        return payload
    except TypeError as e:
        # The likeliest non-JSON value is a raw-scheme (bytes) payload.
        raise TypeError(
            "tuple values must be JSON-serializable to cross the JSON "
            "inter-worker wire; spout scheme='raw' (bytes values) needs "
            "the binary wire (topology.wire_format='binary', the default)"
            " or topology.spout_scheme='string' under dist-run"
        ) from e


def decode_deliveries(payload: bytes) -> List[Tup[str, int, Tuple]]:
    """Decode a Deliver payload, auto-detecting the wire format.

    Binary frames (magic 0xB7) route to :mod:`storm_tpu.dist.wire`; JSON
    arrays (leading ``[``) use the envelope above. Receivers therefore
    accept both formats unconditionally — negotiation only shapes what the
    sender emits.
    """
    if payload[:1] == _BIN_DELIVER:
        return wire.decode_deliveries(payload, time.perf_counter())
    if payload[:1] == _BIN_SHM:
        # Shared-memory lane: the payload is only a CRC-protected header
        # naming a segment on THIS host; the frame body is decoded as
        # zero-copy views over the mapping. Attach/range failures become
        # WireError so the caller's corruption accounting (and the
        # sender's leave-to-replay handling) applies unchanged.
        name, offset, length = wire.decode_shm_header(payload)
        try:
            body = _segment_cache().view(name, offset, length)
        except (OSError, ValueError, RuntimeError) as e:
            raise wire.WireError(
                f"shm segment {name!r} unavailable: {e}") from e
        return wire.decode_deliveries_view(body, time.perf_counter())
    now = time.perf_counter()
    out = [
        (c, i, decode_tuple(enc, now)) for c, i, enc in json.loads(payload)
    ]
    # Copy ledger: json.loads materializes every value out of the payload
    # — one full-payload parse/copy pass on the JSON wire.
    _copyledger.record("wire_decode", len(payload), copies=1,
                       allocs=len(out), records=len(out))
    return out


def encode_acks(ops: Iterable[Tup[str, int, int]]) -> bytes:
    """ops: ('xor'|'fail', root_id, edge_id) triples (JSON wire)."""
    return json.dumps([[op, str(r), str(e)] for op, r, e in ops]).encode("utf-8")


def decode_acks(payload: bytes) -> List[Tup[str, int, int]]:
    """Decode an Ack payload, auto-detecting binary (0xB8) vs JSON."""
    if payload[:1] == _BIN_ACK:
        return wire.decode_acks(payload)
    return [(op, int(r), int(e)) for op, r, e in json.loads(payload)]


# ---- client ------------------------------------------------------------------


class WorkerClient:
    """Channel to one worker's Dist service. ``token=None`` reads
    STORM_TPU_CONTROL_TOKEN (the controller's export); a non-empty token
    rides every RPC as metadata.

    RPCs ride a deadline-budgeted retry policy
    (:class:`storm_tpu.resilience.RetryPolicy`): Control and Ack retry
    the broad transient-code set, Deliver retries UNAVAILABLE only (a
    timed-out Deliver may already be enqueued — re-sending it would
    double-deliver, so it is left to ledger-timeout replay). Fatal codes
    (UNAUTHENTICATED, INVALID_ARGUMENT, ...) never retry. ``retry=None``
    builds the default policy; pass an ``attempts=1`` policy to restore
    one-shot semantics."""

    def __init__(self, target: str, token: Optional[str] = None,
                 retry: Optional["RetryPolicy"] = None) -> None:
        self.target = target
        if token is None:
            token = _env_token()
        self._md = ((_TOKEN_MD_KEY, token),) if token else None
        self._channel = grpc.insecure_channel(target, options=_OPTS)
        self._deliver = self._channel.unary_unary(f"/{SERVICE}/Deliver")
        self._ack = self._channel.unary_unary(f"/{SERVICE}/Ack")
        self._control = self._channel.unary_unary(f"/{SERVICE}/Control")
        self.retry = RetryPolicy() if retry is None else retry

    def deliver(self, payload: bytes, timeout: float = 60.0,
                traceparent: Optional[str] = None) -> None:
        """``traceparent`` (first sampled tuple of the batch) rides as W3C
        gRPC metadata so proxies/interceptors that only see headers — not
        the opaque envelope — can still correlate the RPC to a trace."""
        md = self._md or ()
        if traceparent:
            md = md + (("traceparent", traceparent),)
        self.retry.call_sync(
            lambda t: self._deliver(payload, timeout=t, metadata=md or None),
            op_timeout=timeout, codes=RETRYABLE_NARROW)

    def ack(self, payload: bytes, timeout: float = 60.0) -> None:
        self.retry.call_sync(
            lambda t: self._ack(payload, timeout=t, metadata=self._md),
            op_timeout=timeout, codes=RETRYABLE_BROAD)

    def control(self, cmd: str, timeout: float = 120.0, **kwargs: Any) -> Dict:
        req = json.dumps({"cmd": cmd, **kwargs}).encode("utf-8")
        resp = json.loads(self.retry.call_sync(
            lambda t: self._control(req, timeout=t, metadata=self._md),
            op_timeout=timeout, codes=RETRYABLE_BROAD))
        if resp.get("error"):
            raise RuntimeError(f"{self.target} {cmd}: {resp['error']}")
        return resp

    def probe(self, cmd: str = "ping", timeout: float = 3.0,
              **kwargs: Any) -> Dict:
        """One-shot control RPC with NO retry/backoff: liveness checks
        must answer "is it there right now", and the broad retry policy
        under :meth:`control` would stretch a dead peer into tens of
        seconds of backoff. Used by the controller's reattach probe."""
        req = json.dumps({"cmd": cmd, **kwargs}).encode("utf-8")
        resp = json.loads(
            self._control(req, timeout=timeout, metadata=self._md))
        if resp.get("error"):
            raise RuntimeError(f"{self.target} {cmd}: {resp['error']}")
        return resp

    def wait_ready(self, timeout: float = 30.0) -> None:
        """Poll ping until the worker answers — but classify failures: a
        worker that is UP and rejecting us (bad control token ->
        UNAUTHENTICATED, protocol mismatch -> INVALID_ARGUMENT) will
        never become ready, so waiting out the full timeout just hides
        the real error for 30 s. Fail fast on those; keep polling only
        on connectivity-shaped failures."""
        deadline = time.monotonic() + timeout
        while True:
            try:
                # codes=frozenset(): this loop IS the retry policy;
                # stacking the client's backoff under it would stretch
                # the poll period.
                resp = json.loads(self.retry.call_sync(
                    lambda t: self._control(
                        json.dumps({"cmd": "ping"}).encode("utf-8"),
                        timeout=t, metadata=self._md),
                    op_timeout=2.0, codes=frozenset()))
                if resp.get("error"):  # answered but unhealthy: keep polling
                    raise RuntimeError(resp["error"])
                return
            except Exception as e:
                if is_fatal_rpc(e):
                    raise RuntimeError(
                        f"worker {self.target} rejected the handshake "
                        f"({_rpc_code(e)}): check the control token / "
                        "version skew") from e
                if time.monotonic() > deadline:
                    raise TimeoutError(f"worker {self.target} never became ready")
                time.sleep(0.1)

    def close(self) -> None:
        self._channel.close()


class DistHandler(grpc.GenericRpcHandler):
    """Routes the three methods to a worker's callbacks.

    ``token=None`` reads STORM_TPU_CONTROL_TOKEN (exported by the spawning
    controller); with a non-empty token every method — Control AND the
    Deliver/Ack data path — requires matching metadata, and mismatches are
    rejected UNAUTHENTICATED with a log line."""

    def __init__(self, deliver_fn, ack_fn, control_fn,
                 token: Optional[str] = None) -> None:
        if token is None:
            token = _env_token()
        if token:
            deliver_fn = self._guarded(deliver_fn, token, "Deliver")
            ack_fn = self._guarded(ack_fn, token, "Ack")
            control_fn = self._guarded(control_fn, token, "Control")
        self._methods = {
            f"/{SERVICE}/Deliver": deliver_fn,
            f"/{SERVICE}/Ack": ack_fn,
            f"/{SERVICE}/Control": control_fn,
        }

    @staticmethod
    def _guarded(fn, token: str, method: str):
        import hmac
        import logging

        log = logging.getLogger("storm_tpu.dist.transport")

        def wrapped(request, context):
            md = dict(context.invocation_metadata() or ())
            got = md.get(_TOKEN_MD_KEY, "")
            if isinstance(got, str):  # bytes: compare_digest rejects
                got = got.encode("utf-8", "surrogateescape")  # non-ASCII str
            if not hmac.compare_digest(got, token.encode("utf-8")):
                peer = context.peer()
                log.warning("rejected unauthenticated %s from %s",
                            method, peer)
                context.abort(grpc.StatusCode.UNAUTHENTICATED,
                              "missing or invalid control token")
            return fn(request, context)

        return wrapped

    def service(self, call_details):
        fn = self._methods.get(call_details.method)
        if fn is None:
            return None
        return grpc.unary_unary_rpc_method_handler(fn)
