"""In-process broker with Kafka semantics: topics, partitions, offsets,
consumer-group commits.

Plays two roles (SURVEY.md §4 build obligation):

- the *fake broker* for topology-level tests — what the reference never had
  (it could only be tested against real Kafka + a real Storm cluster);
- the default transport for single-host deployments where Kafka isn't
  wanted.

Thread-safe: external load generators (bench harness, gRPC ingest) produce
from other threads while the asyncio runtime consumes.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple


@dataclass(frozen=True)
class Record:
    topic: str
    partition: int
    offset: int
    key: Optional[bytes]
    value: bytes
    timestamp: float


class MemoryBroker:
    """Append-only per-partition logs + consumer-group offset store."""

    def __init__(self, default_partitions: int = 4) -> None:
        self._lock = threading.Lock()
        self._logs: Dict[Tuple[str, int], List[Record]] = {}
        self._partitions: Dict[str, int] = {}
        self._committed: Dict[Tuple[str, str, int], int] = {}  # (group, topic, part)
        self.default_partitions = default_partitions
        self._rr: Dict[str, int] = {}

    # ---- admin ---------------------------------------------------------------

    def create_topic(self, topic: str, partitions: Optional[int] = None) -> None:
        with self._lock:
            self._ensure(topic, partitions)

    def _ensure(self, topic: str, partitions: Optional[int] = None) -> None:
        if topic not in self._partitions:
            n = partitions or self.default_partitions
            self._partitions[topic] = n
            for p in range(n):
                self._logs[(topic, p)] = []
            self._rr[topic] = 0

    def partitions_for(self, topic: str) -> int:
        with self._lock:
            self._ensure(topic)
            return self._partitions[topic]

    # ---- producing -----------------------------------------------------------

    def produce(
        self,
        topic: str,
        value: bytes | str,
        key: Optional[bytes | str] = None,
        partition: Optional[int] = None,
    ) -> Tuple[int, int]:
        """Append a record; returns (partition, offset).

        Partitioning mirrors Kafka's default: hash of key when present,
        round-robin otherwise.
        """
        with self._lock:
            return self._produce_locked(topic, value, key, partition)

    def _produce_locked(self, topic, value, key=None, partition=None):
        if isinstance(value, str):
            value = value.encode("utf-8")
        if isinstance(key, str):
            key = key.encode("utf-8")
        self._ensure(topic)
        n = self._partitions[topic]
        if partition is None:
            if key is not None:
                partition = hash(key) % n
            else:
                partition = self._rr[topic] % n
                self._rr[topic] += 1
        log = self._logs[(topic, partition)]
        rec = Record(topic, partition, len(log), key, value, time.time())
        log.append(rec)
        return partition, rec.offset

    def txn(self, txn_id: str) -> "MemoryTxn":
        """A transaction handle (buffer + atomic commit); same surface as
        ``KafkaWireBroker.txn``."""
        return MemoryTxn(self, txn_id)

    # ---- fetching ------------------------------------------------------------

    def fetch(
        self, topic: str, partition: int, offset: int, max_records: int = 512
    ) -> List[Record]:
        with self._lock:
            self._ensure(topic)
            log = self._logs[(topic, partition)]
            if offset < 0:
                offset = 0
            return log[offset : offset + max_records]

    def earliest_offset(self, topic: str, partition: int) -> int:
        return 0

    def latest_offset(self, topic: str, partition: int) -> int:
        """Offset one past the last record (Kafka's 'log end offset')."""
        with self._lock:
            self._ensure(topic)
            return len(self._logs[(topic, partition)])

    # ---- consumer-group offsets ----------------------------------------------

    def commit(self, group: str, topic: str, partition: int, offset: int) -> None:
        with self._lock:
            self._committed[(group, topic, partition)] = offset

    def committed(self, group: str, topic: str, partition: int) -> Optional[int]:
        with self._lock:
            return self._committed.get((group, topic, partition))

    # ---- test/bench conveniences ---------------------------------------------

    def drain_topic(self, topic: str) -> List[Record]:
        """All records across partitions in offset order (tests only)."""
        with self._lock:
            self._ensure(topic)
            out: List[Record] = []
            for p in range(self._partitions[topic]):
                out.extend(self._logs[(topic, p)])
            return sorted(out, key=lambda r: (r.timestamp, r.partition, r.offset))

    def topic_size(self, topic: str) -> int:
        with self._lock:
            self._ensure(topic)
            return sum(
                len(self._logs[(topic, p)]) for p in range(self._partitions[topic])
            )


class MemoryTxn:
    """Transaction handle over :class:`MemoryBroker`: produced records
    buffer locally and append atomically (under the broker lock) at
    commit — read-committed visibility, same surface as the Kafka-backed
    ``KafkaWireBroker.txn``. Abort drops the buffer."""

    def __init__(self, broker: "MemoryBroker", txn_id: str) -> None:
        self._broker = broker
        self.txn_id = txn_id
        self._pending: List[tuple] = []
        self._offsets: Dict[str, Dict[Tuple[str, int], int]] = {}
        self._open = False

    def begin(self) -> None:
        self._pending.clear()
        self._offsets.clear()
        self._open = True

    def produce(self, topic: str, value, key=None, partition=None) -> None:
        assert self._open, "begin() first"
        self._pending.append((topic, value, key, partition))

    def send_offsets(self, group: str,
                     offsets: "Dict[Tuple[str, int], int]") -> None:
        """Stage consumer-group offsets to commit atomically with the
        records (same surface as ``KafkaTxn.send_offsets``)."""
        assert self._open, "begin() first"
        from storm_tpu.runtime.tuples import merge_offsets

        merge_offsets(self._offsets.setdefault(group, {}), offsets.items())

    def commit(self) -> None:
        assert self._open, "begin() first"
        from storm_tpu.runtime.tuples import merge_offsets

        self._open = False
        with self._broker._lock:
            # all-or-nothing under the broker lock: no fetch interleaves,
            # and staged offsets land with the records (never without them)
            for topic, value, key, partition in self._pending:
                self._broker._produce_locked(topic, value, key, partition)
            for group, offs in self._offsets.items():
                merge_offsets(
                    self._broker._committed,
                    (((group, t, p), off) for (t, p), off in offs.items()))
        self._pending.clear()
        self._offsets.clear()

    def abort(self) -> None:
        self._open = False
        self._pending.clear()
        self._offsets.clear()
