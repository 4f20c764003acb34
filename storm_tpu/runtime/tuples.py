"""Tuples and ack identities.

The unit of data flow, equivalent to Storm's ``Tuple`` (consumed at
InferenceBolt.java:70-71 via ``tuple.getString(0)``; produced via
``new Values(outputJson)`` at :98). Carries the XOR ack identity used by the
at-least-once ledger (:mod:`storm_tpu.runtime.acker`): every tuple edge has a
random 64-bit ``edge_id``; a tuple anchored to one or more root (spout)
tuples propagates their ``anchors`` set, exactly like Storm's anchoring model
that the reference relies on (SURVEY.md §2.5, §5.3).
"""

from __future__ import annotations

import os
import random
from dataclasses import dataclass, field
from typing import Any, FrozenSet, Optional, Sequence

# Ids only need uniqueness + uniform mixing for the XOR ledger (Storm uses
# plain Random too); a process-seeded Mersenne Twister is ~50x faster than
# secrets.randbits' per-call urandom syscall, which showed up in the emit
# hot path (new_id is called once per delivery edge).
_rng = random.Random(int.from_bytes(os.urandom(16), "big"))
_randbits = _rng.getrandbits

# Multi-host routing (storm_tpu.dist): the top 8 bits of every id carry the
# index of the worker process that generated it, so any worker receiving a
# tuple can route acks for its root back to the ledger owner without a
# lookup table. Single-process runtimes keep tag 0 and never consult it.
_worker_tag = 0


def set_worker_tag(index: int) -> None:
    """Stamp ids from this process with a worker index (0..255)."""
    global _worker_tag
    if not 0 <= index < 256:
        raise ValueError(f"worker index {index} out of range 0..255")
    _worker_tag = index << 56


def owner_of(ident: int) -> int:
    """The worker index that generated (and owns the ledger entry for) an id."""
    return ident >> 56


def new_id() -> int:
    """Random non-zero worker-tagged 64-bit id (zero = acker 'complete')."""
    while True:
        v = _randbits(56)
        if v:
            return _worker_tag | v


class Values(list):
    """An emitted value list, mirroring Storm's ``Values`` for familiarity."""


def merge_offsets(dst: dict, items) -> dict:
    """Max-wins merge of ``(key, offset)`` pairs into ``dst`` — THE offset
    fold of the exactly-once chain (origins union, ``send_offsets``
    staging, the transactional sink's commit). One implementation so the
    accounting can never diverge between sites."""
    for k, off in items:
        if off > dst.get(k, -1):
            dst[k] = off
    return dst


from functools import lru_cache


@lru_cache(maxsize=1024)
def _field_index(fields: tuple) -> dict:
    return {name: i for i, name in enumerate(fields)}


@dataclass
class Tuple:
    values: Sequence[Any]
    fields: Sequence[str]
    source_component: str
    source_task: int = 0
    stream: str = "default"
    edge_id: int = 0
    anchors: FrozenSet[int] = frozenset()
    # perf_counter timestamp when the root entered the topology; flows with
    # the tuple for end-to-end latency metrics.
    root_ts: float = 0.0
    # Source-log provenance: ``(topic, partition, next_offset)`` triples
    # identifying the ingest records this tuple derives from (next_offset =
    # the offset to COMMIT, i.e. last consumed + 1). Spouts stamp it;
    # anchored emits union it downstream — so a transactional sink can
    # commit the consumed offsets inside its producer transaction (KIP-98
    # consume-transform-produce exactly-once).
    origins: FrozenSet[tuple] = frozenset()
    # Distributed-trace context (tracing.TraceContext) — None unless this
    # record was sampled, so the tracing-off hot path pays only the field.
    trace: Optional[Any] = None
    # Its root's row of the record log in the making (obs/profile.py
    # RecordRow): the one object the spout made, so that every site on the
    # record's way stamps the same row. None while the profiler is off, over
    # the dist wire, and from a spout that makes none.
    record: Optional[Any] = None

    def __getitem__(self, i: int) -> Any:
        return self.values[i]

    def __len__(self) -> int:
        return len(self.values)

    _MISSING = object()

    def get(self, name: str, default: Any = _MISSING) -> Any:
        """Field access by declared name (Storm's ``getValueByField``).

        O(1): the field->index map is cached per distinct fields tuple
        (fields objects are shared across every tuple of a stream), and
        this is on the per-tuple hot path (groupings, sink mapping).
        A ``default`` makes missing fields non-fatal (Storm's ``contains``
        + get in one call) — used by passthrough plumbing fed by streams
        that don't declare the field.
        """
        idx = _field_index(tuple(self.fields)).get(name)
        if idx is None:
            if default is not Tuple._MISSING:
                return default
            raise KeyError(
                f"no field {name!r} in stream from {self.source_component} "
                f"(fields: {list(self.fields)})"
            )
        return self.values[idx]

    def get_string(self, i: int) -> str:
        """Storm's ``tuple.getString(i)`` (InferenceBolt.java:71)."""
        return str(self.values[i])


class TickTuple(Tuple):
    """Periodic timer tuple, equivalent to Storm's tick tuples that the
    reference's KafkaBolt filters via ``BaseTickTupleAwareRichBolt``
    (KafkaBolt.java:36)."""

    def __init__(self) -> None:
        super().__init__(
            values=(), fields=(), source_component="__system", stream="__tick"
        )


def is_tick(t: Tuple) -> bool:
    return t.stream == "__tick"
