"""The per-operator-task micro-batcher of the ``continuous=False`` path.

NOT the default path any more (ISSUE 26): ``InferenceBolt`` submits each
decoded record to the one queue of the engine it shares
(:mod:`storm_tpu.infer.continuous`), and a batch is cut there when the
engine's ring has a free slot. This module stays for ``continuous=False``
and its tests until ROADMAP Design 1 deletes it; ``Batch``/``BatchItem``
are also what the lane batcher and the cascade router pass around.

The reference runs one ``session.run`` per Kafka record at batch 1
(InferenceBolt.java:80-86, SURVEY.md §3.3 "no micro-batching, no cross-tuple
amortization") — the single biggest performance defect to fix for TPU, where
throughput comes from large MXU-friendly batches. Policy (BatchConfig):
dispatch when ``max_batch`` instances are waiting OR the oldest instance has
waited ``max_wait_ms``. With four tasks each holding ``max_inflight`` batches
ahead of a two-slot ring, "the oldest has waited" is true of every record
under any load, so a batch's size is fixed about twelve device steps before
it runs: buckets two thirds empty under a backlog, a standing half second at
100 records/s (PERF.md §6, PR 26).

Pure accumulation logic, no asyncio here (the operator owns timing/tasks):
easy to unit-test, like the reference's mkProducer seam philosophy.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, List, Optional, Tuple

import numpy as np

from storm_tpu.config import BatchConfig


@dataclass
class BatchItem:
    payload: Any  # opaque per-record context (the runtime tuple)
    data: np.ndarray  # (n_i, *instance_shape)
    ts: float  # deadline clock: root (append) time when known
    # batcher-entry time (always perf_counter-now at add): what the
    # batch-wait stage of the latency decomposition is measured from, and
    # the start of a sampled record's queue_wait trace span (the operator's
    # _trace_batch — batcher entry to device dispatch).
    enq: float = 0.0
    # QoS priority lane (None outside QoS mode). Carried so the EDF lane
    # batcher (storm_tpu.qos.lanes) and per-lane metrics can attribute the
    # item without re-deriving it from the tuple.
    lane: Optional[str] = None


@dataclass
class Batch:
    items: List[BatchItem]
    size: int  # total instances

    def stack(self) -> np.ndarray:
        return np.concatenate([it.data for it in self.items], axis=0)

    def parts(self) -> List[np.ndarray]:
        """Per-item arrays for the engine's split-phase ``dispatch``: the
        engine stages them straight into its pooled padded buffer with one
        fused write, so no concatenated intermediate ever exists."""
        return [it.data for it in self.items]

    def split(self, out: np.ndarray) -> List[Tuple[Any, np.ndarray]]:
        """Slice a (size, K) result back per item."""
        res = []
        ofs = 0
        for it in self.items:
            n = it.data.shape[0]
            res.append((it.payload, out[ofs : ofs + n]))
            ofs += n
        return res


class MicroBatcher:
    def __init__(self, cfg: BatchConfig) -> None:
        self.cfg = cfg
        self._items: List[BatchItem] = []
        self._count = 0

    def __len__(self) -> int:
        return self._count

    @property
    def oldest_ts(self) -> Optional[float]:
        return self._items[0].ts if self._items else None

    def stats(self) -> dict:
        """Depth/age summary, key-parity with ``LaneBatcher.stats`` and
        the queue half of ``ContinuousBatcher.stats`` — the obs edge
        watermarks read every batching mode through one shape. Age is
        measured from batcher *entry* (``enq``), not the deadline clock:
        it answers "how long has work sat here", not "how late is it"."""
        now = time.perf_counter()
        oldest = self._items[0].enq if self._items else None
        return {
            "kind": "fifo",
            "pending_rows": self._count,
            "depth": len(self._items),
            "oldest_ms": (round(max(0.0, (now - oldest) * 1e3), 3)
                          if oldest is not None else 0.0),
            "pending_by_lane": {},
        }

    def add(self, payload: Any, data: np.ndarray, ts: Optional[float] = None) -> Optional[Batch]:
        """Add one record (n_i instances). Returns a ready Batch when the
        max_batch threshold is reached, else None.

        A record that would overshoot max_batch first flushes the pending
        batch and starts a new one, so no emitted batch exceeds max_batch
        (a single record larger than max_batch still forms its own
        oversized batch — the engine pads per-shape rather than crash)."""
        n = data.shape[0]
        flushed: Optional[Batch] = None
        if self._count and self._count + n > self.cfg.max_batch:
            flushed = self._take()
        now = time.perf_counter()
        self._items.append(
            BatchItem(payload, data, ts if ts is not None else now, now)
        )
        self._count += n
        if self._count >= self.cfg.max_batch:
            if flushed is None:
                return self._take()
            # Rare: both the old batch flushed AND the new record alone
            # reaches max_batch. ``add`` still returns one batch, but the
            # new full one must NOT sit until the deadline — the caller
            # drains it immediately via ``take_ready()``.
        return flushed

    def take_ready(self) -> Optional[Batch]:
        """Drain a pending batch that already reached max_batch (the
        two-batches-in-one-add case above). Call in a loop after every
        ``add`` that returned a batch; returns None when nothing full is
        parked."""
        if self._count >= self.cfg.max_batch:
            return self._take()
        return None

    def take_if_due(self, now: Optional[float] = None) -> Optional[Batch]:
        """Returns the pending batch if the oldest record exceeded the
        deadline, else None."""
        if not self._items:
            return None
        now = now if now is not None else time.perf_counter()
        if (now - self._items[0].ts) * 1e3 >= self.cfg.max_wait_ms:
            return self._take()
        return None

    def take_all(self) -> Optional[Batch]:
        return self._take() if self._items else None

    def _take(self) -> Batch:
        b = Batch(self._items, self._count)
        self._items = []
        self._count = 0
        return b
