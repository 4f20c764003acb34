"""The JSON wire contract: ``{"instances": ...}`` in, ``{"predictions": ...}`` out.

Reproduces the reference's I/O schema exactly (reference README.md:22-34;
data/InstObj.java:8 — a single ``float[][][][] instances`` field; and
data/PredObj.java:9 — a single ``float[][] predictions`` field) but fixes its
quirks (SURVEY.md §7 "Quirks ... NOT to reproduce"):

- the reference hard-codes the output shape ``float[1][10]``
  (InferenceBolt.java:86); here shapes come from the decoded payload and the
  model's metadata;
- the reference swallows parse errors, emits ``null`` and still acks
  (InferenceBolt.java:92-99); here a malformed payload raises
  :class:`SchemaError`, which the inference operator converts into a
  dead-letter record — never a silent ``null``.

Decoding is the per-tuple hot path (the reference's Jackson parse,
InferenceBolt.java:76). Decoding dispatches to the native C++ parser
(:mod:`storm_tpu.native`) when the shared library is built, with a
NumPy fallback.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import numpy as np


class SchemaError(ValueError):
    """A payload that does not satisfy the wire contract."""


@dataclass(frozen=True)
class Instances:
    """Decoded input record: a batch of instances as one dense array.

    The reference fixes rank 4 (NHWC image batches, InstObj.java:8) and
    documents other ranks as the extension point (reference README.md:17-18).
    We accept any rank >= 2 where axis 0 is the batch axis.
    """

    data: np.ndarray  # float32, shape (N, ...)
    # Arrival timestamp (perf_counter seconds) for Kafka->Kafka latency metrics.
    ts: float = 0.0
    # True when ``data`` is a zero-copy view over the payload buffer
    # (Arrow tensor fast path): the decode hop cost nothing, and the
    # ledger must say so (bytes=0, copies=0) instead of charging the
    # array size the JSON path would have allocated.
    view: bool = False

    @property
    def batch_size(self) -> int:
        return int(self.data.shape[0])


@dataclass(frozen=True)
class Predictions:
    """Decoded/encodable output record: ``(N, K)`` class scores."""

    data: np.ndarray  # float32, shape (N, K)

    @property
    def batch_size(self) -> int:
        return int(self.data.shape[0])


@dataclass(frozen=True)
class DeadLetter:
    """A poisoned input routed to the dead-letter stream instead of the
    reference's emit-``null``-and-ack behavior (InferenceBolt.java:92-99)."""

    payload: str
    error: str
    stage: str = "decode"

    def to_json(self) -> str:
        return json.dumps(
            {"error": self.error, "stage": self.stage, "payload": self.payload[:4096]}
        )


@dataclass(frozen=True)
class Overloaded:
    """A typed rejection emitted when load shedding drops an admitted
    record at the inference operator (QosConfig, storm_tpu.qos): the
    client gets an immediate, parseable answer instead of a timeout.
    Distinguishable from :class:`DeadLetter` (malformed input) and from
    predictions (``"overloaded"`` key instead of ``"predictions"``)."""

    lane: str = ""
    tenant: str = ""
    shed_level: int = 0

    def to_json(self) -> str:
        return json.dumps({
            "overloaded": True,
            "lane": self.lane,
            "tenant": self.tenant,
            "shed_level": self.shed_level,
        })


def _to_dense_f32(obj: Any) -> np.ndarray:
    """Nested lists -> dense float32 ndarray, rejecting ragged/non-numeric."""
    try:
        arr = np.asarray(obj, dtype=np.float32)
    except (ValueError, TypeError) as e:
        raise SchemaError(f"instances is ragged or non-numeric: {e}") from e
    if arr.dtype != np.float32:  # pragma: no cover - asarray coerces
        arr = arr.astype(np.float32)
    return arr


def decode_instances(payload: str | bytes, *, ts: float = 0.0) -> Instances:
    """Parse a ``{"instances": [[[[...]]]]}`` JSON payload.

    Mirrors ``objectMapper.readValue(..., InstObj.class)`` +
    ``instObj.getInstances()`` (InferenceBolt.java:76-77), producing a dense
    float32 array. Raises :class:`SchemaError` on any contract violation.
    """
    # Fastest path: Arrow IPC tensor payload (batch-frame data plane).
    # An encapsulated Arrow message leads with the 0xFFFFFFFF
    # continuation marker — no JSON document can start with 0xFF — so
    # one byte discriminates, and the decode is a zero-copy view over
    # the payload buffer (``Instances.view=True`` tells the ledger the
    # parse hop cost nothing).
    if isinstance(payload, (bytes, bytearray, memoryview)) and \
            len(payload) >= 1 and payload[0] == 0xFF:
        from storm_tpu.serve.marshal import decode_tensor

        try:
            arr = decode_tensor(payload)
        except Exception as e:
            raise SchemaError(f"payload is not a valid tensor frame: {e}") \
                from e
        view = True
        if arr.dtype != np.float32:
            arr = arr.astype(np.float32)  # correctness path, not hot
            view = False
        if arr.ndim < 2:
            raise SchemaError(
                "instances must have rank >= 2 (batch axis + features); "
                f"got rank {arr.ndim}")
        if arr.shape[0] == 0:
            raise SchemaError("instances batch is empty")
        return Instances(data=arr, ts=ts, view=view)

    # Fast path: native C++ parser (built lazily; falls back transparently).
    # bytes go to the native parser as-is — no utf-8 decode/encode round
    # trip on the hot path; the parser validates the JSON structurally.
    from storm_tpu.native import parse_instances_native

    if isinstance(payload, memoryview):
        # JSON records arriving as frame views: the parser wants a
        # contiguous bytes object; this materialization is the same copy
        # the per-record path always paid.
        payload = bytes(payload)
    arr = parse_instances_native(payload)
    if arr is None:
        if isinstance(payload, bytes):
            try:
                payload = payload.decode("utf-8")
            except UnicodeDecodeError as e:
                raise SchemaError(f"payload is not UTF-8: {e}") from e
        try:
            obj = json.loads(payload)
        except json.JSONDecodeError as e:
            raise SchemaError(f"payload is not valid JSON: {e}") from e
        if not isinstance(obj, dict) or "instances" not in obj:
            raise SchemaError('payload missing "instances" key')
        arr = _to_dense_f32(obj["instances"])

    if arr.ndim < 2:
        raise SchemaError(
            f"instances must have rank >= 2 (batch axis + features); got rank {arr.ndim}"
        )
    if arr.shape[0] == 0:
        raise SchemaError("instances batch is empty")
    return Instances(data=arr, ts=ts)


def encode_predictions(preds: Predictions | np.ndarray) -> str:
    """Serialize predictions to the ``{"predictions": [[...]]}`` wire form.

    Mirrors ``predObj.setPredictions(prob); writeValueAsString(predObj)``
    (InferenceBolt.java:89-91).
    """
    arr = preds.data if isinstance(preds, Predictions) else np.asarray(preds)
    if arr.ndim == 1:
        arr = arr[None, :]

    # Fast path: native C++ serializer (falls back transparently).
    from storm_tpu.native import format_predictions_native

    if arr.ndim == 2 and arr.dtype in (np.float32, np.float64):
        s = format_predictions_native(arr)
        if s is not None:
            return s
    return json.dumps({"predictions": arr.astype(np.float64).round(7).tolist()})


def decode_predictions(payload: str | bytes) -> Predictions:
    """Parse a ``{"predictions": ...}`` payload (used by tests and clients)."""
    if isinstance(payload, bytes):
        payload = payload.decode("utf-8")
    try:
        obj = json.loads(payload)
    except json.JSONDecodeError as e:
        raise SchemaError(f"payload is not valid JSON: {e}") from e
    if not isinstance(obj, dict) or "predictions" not in obj:
        raise SchemaError('payload missing "predictions" key')
    arr = _to_dense_f32(obj["predictions"])
    if arr.ndim == 1:
        arr = arr[None, :]
    return Predictions(data=arr)
