"""Ingest spout: the KafkaSpout equivalent.

Reproduces the reference's consumer semantics as *policy*, not hard-coding
(MainTopology.java:95-106, SURVEY.md §2.1 KafkaSpout row):

- ``policy='latest'`` + ``max_behind=0``: start at the log end, ignore
  committed offsets, drop any backlog — the reference's deliberate
  freshness-over-completeness configuration (``ignoreZkOffsets=true``,
  ``startOffsetTime=LatestTime``, ``maxOffsetBehind=0``,
  MainTopology.java:101-103);
- ``policy='resume'``: commit offsets on ack and resume from the committed
  position — the recovery mode the reference lacked (SURVEY.md §5.4);
- ``policy='earliest'``: replay the full log.

At-least-once: each record is emitted with ``msg_id=(partition, offset)``;
failed/timed-out trees are re-emitted from a replay queue before new fetches
(unless the freshness policy says they are already too stale to matter).

Partitions are assigned to spout tasks round-robin by task index, like
Kafka's consumer-group assignment across the reference's 2 spout executors.
"""

from __future__ import annotations

import asyncio
import collections
import logging
import re
import threading
import time
import uuid
from typing import Any, Deque, Dict, Optional, Tuple

from storm_tpu.config import OffsetsConfig
from storm_tpu.connectors.memory import MemoryBroker, Record
from storm_tpu.obs import copyledger as _copyledger
from storm_tpu.obs import profile as _profile
from storm_tpu.runtime.base import Spout, TopologyContext, OutputCollector
from storm_tpu.runtime.tracing import NOT_SAMPLED
from storm_tpu.runtime.tuples import Values

log = logging.getLogger("storm_tpu.spout")


def parse_seek_position(s):
    """"earliest" | "latest" | integer string -> seek position.
    Raises ValueError on anything else (shared by the HTTP route and the
    ctl CLI so both reject malformed positions identically)."""
    if s in ("earliest", "latest"):
        return s
    if isinstance(s, int):
        return s
    if isinstance(s, str) and re.fullmatch(r"-?[0-9]+", s):
        return int(s)
    raise ValueError(
        f"seek position must be earliest|latest|<int>, got {s!r}")


class BrokerSpout(Spout):
    def __init__(
        self,
        broker: MemoryBroker,
        topic: str,
        offsets: Optional[OffsetsConfig] = None,
        fetch_size: int = 256,
        chunk: int = 0,
        scheme: str = "string",
        qos=None,
        frames: bool = False,
    ) -> None:
        self.broker = broker
        self.topic = topic
        self.offsets_cfg = offsets or OffsetsConfig()
        self.fetch_size = fetch_size
        # QosConfig (config.py) or None. When enabled, each record is
        # classified from its broker key (``tenant:lane``) and run through
        # the spout-edge admission controller (storm_tpu.qos.admission);
        # the lane rides downstream as the declared ``qos_lane`` field.
        # A ctor arg (not read from context.config at open()) because
        # declare_output_fields() runs at topology build/validation time.
        self.qos = qos if (qos is not None and qos.enabled) else None
        # chunk > 1: emit up to `chunk` consecutive records as ONE tuple
        # (value = list of payloads). Same wire contract, one ledger entry
        # and one executor hop per chunk instead of per record — the
        # per-record asyncio overhead is the host-side throughput cap at
        # high message rates. Failure granularity becomes the chunk.
        self.chunk = chunk
        # Tuple-value scheme, Storm's StringScheme vs RawScheme
        # (MainTopology.java:100 picks StringScheme): "string" decodes each
        # record to str (full compat: every component, the JSON dist
        # wire). "raw" emits the broker bytes untouched — the JSON decoder
        # parses bytes natively, so the hot path skips a bytes->str->bytes
        # round trip (~20us/record on a 12KB payload), and under dist-run
        # the binary wire (TopologyConfig.wire_format="binary", the
        # default) carries the bytes across workers without re-encoding.
        # Not valid with components that JSON-serialize tuple values or
        # with wire_format="json" across worker boundaries.
        if scheme not in ("string", "raw"):
            raise ValueError(f"unknown spout scheme {scheme!r}")
        self.scheme = scheme
        # frames=True: chunks travel as ONE RecordFrame tuple value (a
        # reference move — the ``batch_route`` ledger hop) instead of a
        # list of N payload objects. Raw bytes only: the string scheme's
        # per-record decode is exactly the copy frames exist to avoid.
        if frames and scheme != "raw":
            raise ValueError(
                "spout frames need scheme='raw' (record frames carry "
                "broker bytes by reference; the string scheme decodes "
                "per record). Set topology.spout_scheme='raw' or disable "
                "topology.spout_frames.")
        self.frames = bool(frames)

    def clone(self) -> "BrokerSpout":
        """Per-task instance sharing the broker handle (the broker is a
        shared external resource, not per-task state)."""
        return type(self)(self.broker, self.topic, self.offsets_cfg,
                          self.fetch_size, self.chunk, self.scheme,
                          self.qos, self.frames)

    def declare_output_fields(self):
        if self.qos is not None:
            return {"default": ("message", "qos_lane")}
        return {"default": ("message",)}

    def open(self, context: TopologyContext, collector: OutputCollector) -> None:
        super().open(context, collector)
        cfg = self.offsets_cfg
        # Cached once: _mint_trace runs per emitted record, so the tracer
        # lookup must not be a per-record getattr chain.
        self._tracer = getattr(context, "tracer", None)
        # QoS admission (per task; the configured tenant rate is split
        # across spout tasks inside the controller).
        if self.qos is not None:
            from storm_tpu.qos.admission import AdmissionController

            self._admission = AdmissionController(
                self.qos, parallelism=context.parallelism,
                metrics=context.metrics)
        else:
            self._admission = None
        # Network-backed brokers (KafkaWireBroker) set blocking=True: their
        # fetches/commits run on worker threads, never on the event loop.
        self._blocking = bool(getattr(self.broker, "blocking", False))
        # Random group per run mirrors the reference's UUID consumer id
        # (MainTopology.java:98-99) unless the user pins one for resume.
        self.group = cfg.group_id or f"storm-tpu-{uuid.uuid4()}"
        self._membership = None
        self._last_hb = 0.0
        if getattr(cfg, "group_protocol", False):
            client = getattr(self.broker, "client", None)
            if client is None:
                raise ValueError(
                    "offsets.group_protocol needs a wire-protocol broker "
                    "(KafkaWireBroker); the memory broker has no coordinator")
            from storm_tpu.connectors.kafka_protocol import GroupMembership

            self._membership = GroupMembership(client, self.group, [self.topic])
            self.my_partitions: list = []  # assigned on first poll (off-loop)
        else:
            n_parts = self.broker.partitions_for(self.topic)
            self.my_partitions = [
                p for p in range(n_parts)
                if p % context.parallelism == context.task_index
            ]
        self.positions: Dict[int, int] = {}
        self._seek = None  # pending request_seek position
        self.pending: Dict[Tuple[int, int], Record] = {}
        self.replay: Deque[Record] = collections.deque()
        self.dropped = 0
        self._rr = 0
        # Blocking-broker machinery: strong refs to background tasks (asyncio
        # holds tasks weakly), per-partition committed high-water marks (so
        # commits are monotonic without a network read), and a lock making
        # check+commit atomic across worker threads.
        self._bg: set = set()
        self._commit_hwm: Dict[int, int] = {}
        self._commit_lock = threading.Lock()
        # policy='txn' (offsets committed by the transactional sink):
        # per-partition ORDERED delivery — at most one outstanding entry
        # (record or chunk) per partition, fetched only after the previous
        # one's tuple tree completes. Without it, an earlier offset still
        # in flight while a later one commits, followed by a crash, would
        # resume past the unprocessed record (silent loss). This is Kafka
        # Streams' per-partition processing model; cross-partition
        # parallelism and chunking carry the throughput.
        self._txn_mode = cfg.policy == "txn"
        if self._txn_mode and max(1, self.chunk) < 16:
            # Measured, not a guess: exactly-once delivery is ordered
            # depth-1 per partition, so each entry pays a commit+ack
            # round trip. The sink's tree-closure trigger commits a held
            # entry the moment it closes (no txn_ms deadline wait), which
            # keeps the cost bounded — measured ~4x at chunk=1, ~1.6x at
            # chunk=4, FREE at chunk >= 16 (a CPU-host run of an earlier
            # round; no ledger line). The 16 gate assumes the benched shape
            # (4 partitions, txn_batch 64); the true free point is
            # chunk >= txn_batch/partitions, which the spout cannot
            # compute (txn_batch lives on the sink) — hence a fixed,
            # bench-calibrated threshold and the formula in the message.
            log.warning(
                "offsets.policy='txn' with spout chunk %d: exactly-once "
                "delivers one gated entry per partition at a time; "
                "entries this small cost ~1.6-4x throughput (measured; "
                "free at chunk >= txn_batch/partitions, typically 16). "
                "Raise topology.spout_chunk — see "
                "docs/OPERATIONS.md#exactly-once.", max(1, self.chunk))
        self._part_inflight: Dict[int, int] = {}
        for p in self.my_partitions:
            self.positions[p] = self._initial_position(p)

    def _initial_position(self, p: int) -> int:
        """Starting offset for a newly-owned partition, honoring the policy
        INCLUDING the startup freshness clamp (Storm's maxOffsetBehind that
        the reference sets to 0, MainTopology.java:103) — applied the same
        whether the partition came from static assignment or a group
        rebalance handoff."""
        cfg = self.offsets_cfg
        if cfg.policy == "latest":
            return self.broker.latest_offset(self.topic, p)
        if cfg.policy == "earliest":
            return self.broker.earliest_offset(self.topic, p)
        committed = self.broker.committed(self.group, self.topic, p)
        pos = (committed if committed is not None
               else self.broker.earliest_offset(self.topic, p))
        if cfg.max_behind is not None:
            latest = self.broker.latest_offset(self.topic, p)
            if latest - pos > cfg.max_behind:
                self.dropped += latest - cfg.max_behind - pos
                pos = latest - cfg.max_behind
        return pos

    # ---- Spout API -----------------------------------------------------------

    def _apply_assignment(self, parts: "list[tuple]") -> None:
        """Adopt a group assignment: (re)position newly-owned partitions per
        the offsets policy; drop replay entries for revoked ones (another
        member owns them now — at-least-once tolerates the handoff)."""
        owned = sorted(p for t, p in parts if t == self.topic)
        revoked = set(self.my_partitions) - set(owned)
        self.my_partitions = owned
        if revoked:
            keep = []
            for entry in self.replay:
                recs = entry if isinstance(entry, list) else [entry]
                if recs[0].partition not in revoked:
                    keep.append(entry)
            self.replay = collections.deque(keep)
        for p in owned:
            if p not in self.positions:
                self.positions[p] = self._initial_position(p)
        for p in revoked:
            self.positions.pop(p, None)
            # a revoked partition's in-flight bookkeeping must not block
            # it forever if a later rebalance hands it back
            self._part_inflight.pop(p, None)

    async def _group_poll(self) -> None:
        """Join on first use; heartbeat ~1/s; rejoin on rebalance."""
        m = self._membership
        now = time.monotonic()
        if m.generation < 0:
            parts = await asyncio.to_thread(m.join)
            # off-loop: position resolution does per-partition offset RPCs
            await asyncio.to_thread(self._apply_assignment, parts)
            self._last_hb = now
            return
        if now - self._last_hb < 1.0:
            return
        self._last_hb = now
        ok = await asyncio.to_thread(m.heartbeat)
        if not ok:
            parts = await asyncio.to_thread(m.join)
            await asyncio.to_thread(self._apply_assignment, parts)

    def request_seek(self, position) -> None:
        """Reposition every owned partition at the next poll (the live
        replay/backfill op — impossible in the reference, whose spout
        pins start-at-latest and ignores stored offsets,
        MainTopology.java:101-103). ``position``: ``"earliest"`` |
        ``"latest"`` | absolute offset (int >= 0) | negative int = that
        many records behind latest. Queued replays are discarded;
        in-flight tuples still complete, so seeking backward duplicates
        their records (the at-least-once direction)."""
        if position not in ("earliest", "latest") and not isinstance(position, int):
            raise ValueError(f"bad seek position {position!r}")
        self._seek = position

    def _apply_seek(self, position) -> None:
        self.replay.clear()
        if self._txn_mode:
            # Discarded replay entries will never ack, so their in-flight
            # counts must not keep gating fetches (permanent partition
            # stall). Entries still in self.pending WILL complete — rebase
            # the counters on those alone.
            counts: Dict[int, int] = {}
            for mid in self.pending:
                pp, _ = self._msg_part_off(mid)
                counts[pp] = counts.get(pp, 0) + 1
            self._part_inflight = counts
        for p in self.my_partitions:
            if position == "earliest":
                pos = self.broker.earliest_offset(self.topic, p)
            elif position == "latest":
                pos = self.broker.latest_offset(self.topic, p)
            elif position < 0:
                pos = max(self.broker.earliest_offset(self.topic, p),
                          self.broker.latest_offset(self.topic, p) + position)
            else:
                # Clamp to the log's [earliest, latest]: an out-of-range
                # absolute offset would wedge wire brokers in a permanent
                # fetch-error loop.
                pos = max(self.broker.earliest_offset(self.topic, p),
                          min(position,
                              self.broker.latest_offset(self.topic, p)))
            self.positions[p] = pos

    def ingress_lag(self) -> dict:
        """How far this task's cursor trails the broker's high-water mark,
        summed over owned partitions — the obs edge watermarks' *ingress*
        row (EdgeLagTracker), i.e. the lag Storm/Burrow would chart for the
        consumer group. Blocking (wire) brokers answer offset queries with
        a network round trip that must not run on the event loop, so for
        them ``records_behind`` is None (unknown), not 0 — callers must
        treat None as "no data", never "caught up"."""
        if self._blocking:
            return {"records_behind": None,
                    "partitions": len(self.my_partitions)}
        behind = 0
        for p in self.my_partitions:
            pos = self.positions.get(p)
            if pos is None:
                continue
            behind += max(0, self.broker.latest_offset(self.topic, p) - pos)
        return {"records_behind": behind,
                "partitions": len(self.my_partitions)}

    async def next_tuple(self) -> bool:
        if self._membership is not None:
            await self._group_poll()
        if self._seek is not None:
            position, self._seek = self._seek, None
            if self._blocking:
                await asyncio.to_thread(self._apply_seek, position)
            else:
                self._apply_seek(position)
            return True
        # Replays first: failed trees take priority over new data.
        if self.replay:
            entry = self.replay.popleft()
            if isinstance(entry, list):
                await self._emit_chunk(entry)
            else:
                await self._emit(entry)
            return True
        if not self.my_partitions:
            return False
        # Round-robin over owned partitions.
        for _ in range(len(self.my_partitions)):
            p = self.my_partitions[self._rr % len(self.my_partitions)]
            self._rr += 1
            if self._txn_mode and self._part_inflight.get(p, 0):
                continue  # ordered delivery: previous entry still open
            pos = self.positions[p]
            # txn mode: one ENTRY per fetch (the chunk, or one record) so
            # exactly one tuple tree per partition is ever outstanding.
            size = (max(1, self.chunk) if self._txn_mode
                    else self.fetch_size)
            if self._blocking:
                records = await asyncio.to_thread(
                    self.broker.fetch, self.topic, p, pos, size
                )
            else:
                records = self.broker.fetch(self.topic, p, pos, size)
            if not records:
                continue
            # the record log's t_polled: the fetch has returned
            polled = time.time() if _profile.enabled() else None
            records = list(records)
            last_off = records[-1].offset
            if self._admission is not None:
                records = self._admit_records(records)
                if not records:
                    # Whole fetch throttled/shed: the cursor still
                    # advances — dropping with progress IS the admission
                    # policy (same shape as the max_behind freshness drop).
                    self.positions[p] = last_off + 1
                    return True
            # Emit FIRST, advance the cursor after: an exception mid-loop
            # (executor catches and retries next_tuple) must re-fetch the
            # unemitted tail — duplicates are the safe direction for
            # at-least-once; a skipped record is not.
            # txn mode counts AFTER each successful emit: incrementing
            # before an emit that then raises would gate the partition on
            # an ack that never comes (the executor's retry re-fetches the
            # unemitted entry, which must not find the gate closed).
            if self.chunk > 1:
                # One full-size fetch (one broker round trip), sliced into
                # chunk tuples — NOT one fetch per chunk, which would
                # multiply network fetches for blocking brokers.
                for i in range(0, len(records), self.chunk):
                    # Under QoS a chunk must be lane-homogeneous (one tuple
                    # carries one qos_lane value), so the slice is split by
                    # lane; without QoS the slice ships whole.
                    for group in self._lane_groups(records[i : i + self.chunk]):
                        await self._emit_chunk(group, polled)
                        if self._txn_mode:
                            self._part_inflight[p] = \
                                self._part_inflight.get(p, 0) + 1
            else:
                for rec in records:
                    await self._emit(rec, polled)
                    if self._txn_mode:
                        self._part_inflight[p] = \
                            self._part_inflight.get(p, 0) + 1
            self.positions[p] = last_off + 1
            return True
        return False

    # ---- QoS admission -------------------------------------------------------

    def _admit_records(self, records: "list[Record]") -> "list[Record]":
        """Run each fetched record through the admission controller;
        non-admitted records are dropped (their offsets are skipped by the
        cursor advance in next_tuple) and counted by the controller."""
        admitted = []
        for rec in records:
            tenant, lane = self._admission.classify(rec.key, self.topic)
            ok, _reason = self._admission.admit(tenant, lane)
            if ok:
                admitted.append(rec)
            else:
                self.dropped += 1
        return admitted

    def _lane_of(self, rec: Record) -> Optional[str]:
        if self._admission is None:
            return None
        return self._admission.classify(rec.key, self.topic)[1]

    def _lane_groups(self, records: "list[Record]"):
        """Split one chunk slice into lane-homogeneous groups, highest
        priority first (classification is deterministic from the record
        key, so replayed chunks re-derive the same lane)."""
        if self._admission is None:
            yield records
            return
        groups: Dict[str, list] = {}
        for rec in records:
            groups.setdefault(self._lane_of(rec), []).append(rec)
        for lane in sorted(groups, key=self.qos.lane_index):
            yield groups[lane]

    def _append_root_ts(self, rec: Record) -> float:
        """E2E ingress clock = broker APPEND time, not spout-emit time.

        The north-star metric is Kafka-append -> Kafka-deliver (BASELINE.md);
        starting the clock at spout emit hides broker-side queueing — e.g.
        when ``max_spout_pending`` throttles fetches, records age in the log
        invisibly. ``Record.timestamp`` is wall-clock (epoch seconds, both
        MemoryBroker and the Kafka wire client); the latency histograms run
        on ``perf_counter``, so rebase append time onto the perf basis.
        Clamped to ``now`` so a producer with a skewed-forward clock can't
        produce negative latency, and to age 0 when the record carries no
        real timestamp (Kafka baseTimestamp=-1 sentinel decodes to ts<=0,
        which would otherwise read as an epoch-scale age and poison the
        e2e histograms)."""
        now_perf = time.perf_counter()
        if rec.timestamp <= 0:
            return now_perf
        age = time.time() - rec.timestamp
        return now_perf - max(age, 0.0)

    def _scheme_value(self, value: bytes):
        if self.scheme == "raw":
            return value
        return value.decode("utf-8", "replace")

    def _mint_trace(self, root_ts: float, partition: int, offset: int,
                    records: int = 1):
        """Sampling decision + rich ingress span for one root emit.

        Returns a TraceContext, or NOT_SAMPLED so the collector knows the
        roll already happened (and missed) — keeping the effective rate at
        the configured value. The ingress span starts at broker-append
        time, so it shows broker-side queueing too."""
        tracer = self._tracer
        if tracer is None or not tracer.active:
            return NOT_SAMPLED
        ctx = tracer.maybe_trace()
        if ctx is None:
            return NOT_SAMPLED
        attrs = {"topic": self.topic, "partition": partition,
                 "offset": offset}
        if records > 1:
            attrs["records"] = records
        tracer.record(ctx, "ingress", self.context.component_id,
                      root_ts, time.perf_counter(), attrs=attrs)
        return ctx

    def _ledger_ingest(self, records: "list[Record]") -> None:
        """Copy-ledger ingress hops, one call per emit: raw payload bytes
        as they arrived (the amplification denominator — arrival is not a
        copy) and, under the "string" scheme, the bytes->str conversion
        pass that copies every payload."""
        if not _copyledger.active():
            return
        payload = sum(len(r.value) for r in records)
        comp = self.context.component_id
        _copyledger.record("spout_ingest", payload, copies=0, allocs=0,
                           records=len(records), engine=comp)
        if self.scheme != "raw":
            _copyledger.record("spout_scheme", payload,
                               copies=len(records), allocs=len(records),
                               records=len(records), engine=comp)

    async def _emit_chunk(self, records: "list[Record]",
                          polled: Optional[float] = None) -> None:
        first, last = records[0], records[-1]
        msg_id = ("c", first.partition, first.offset, last.offset)
        self.pending[msg_id] = records
        root_ts = self._append_root_ts(first)
        # one row of the record log a tuple: the first record's append
        row = _profile.new_record_row(first.timestamp, polled, len(records))
        self._ledger_ingest(records)
        if self.frames:
            # Batch ingress (the zero-copy path): the whole chunk rides
            # as ONE RecordFrame value — routing moves a reference, not N
            # payload objects. Replay rebuilds the frame from the same
            # pending records, so exactly-once is byte-identical on retry.
            from storm_tpu.runtime.frames import RecordFrame

            frame = RecordFrame([r.value for r in records])
            if _copyledger.active():
                _copyledger.record(
                    "batch_route", 0, copies=0, allocs=1,
                    records=len(records), engine=self.context.component_id)
            vals = [frame]
        else:
            vals = [[self._scheme_value(r.value) for r in records]]
        if self.qos is not None:
            # Chunks are lane-homogeneous (next_tuple groups by lane), so
            # the first record's lane speaks for the whole tuple.
            vals.append(self._lane_of(first))
        await self.collector.emit(
            Values(vals),
            msg_id=msg_id,
            # Oldest record in the chunk: its queueing is the one that counts.
            root_ts=root_ts,
            origins=frozenset(
                {(self.topic, first.partition, last.offset + 1)}),
            trace=self._mint_trace(root_ts, first.partition, first.offset,
                                   len(records)),
            record=row,
        )
        if row is not None:
            row.t_emitted = time.time()

    async def _emit(self, rec: Record,
                    polled: Optional[float] = None) -> None:
        msg_id = (rec.partition, rec.offset)
        self.pending[msg_id] = rec
        root_ts = self._append_root_ts(rec)
        # the record log's row: the broker's own stamp, the fetch's return
        # (now for a replay), and below the emit's return
        row = _profile.new_record_row(rec.timestamp, polled)
        self._ledger_ingest([rec])
        vals = [self._scheme_value(rec.value)]
        if self.qos is not None:
            vals.append(self._lane_of(rec))
        await self.collector.emit(
            Values(vals),
            msg_id=msg_id,
            root_ts=root_ts,
            origins=frozenset({(self.topic, rec.partition, rec.offset + 1)}),
            trace=self._mint_trace(root_ts, rec.partition, rec.offset),
            record=row,
        )
        if row is not None:
            row.t_emitted = time.time()

    @staticmethod
    def _msg_part_off(msg_id) -> Tuple[int, int]:
        """(partition, last offset) for record or chunk msg ids."""
        if msg_id[0] == "c":
            return msg_id[1], msg_id[3]
        return msg_id

    def ack(self, msg_id: Any) -> None:
        self.pending.pop(msg_id, None)
        if self._txn_mode:
            # Entry complete (its offsets committed in the sink's txn):
            # the partition may fetch its next entry. fail() deliberately
            # does NOT decrement — a failed entry stays outstanding through
            # the replay queue until its re-emission acks, keeping the
            # partition's delivery strictly ordered.
            p, _ = self._msg_part_off(msg_id)
            n = self._part_inflight.get(p, 0)
            if n > 0:
                self._part_inflight[p] = n - 1
        if self.offsets_cfg.policy == "resume":
            p, off = self._msg_part_off(msg_id)
            if self._membership is not None and p not in self.my_partitions:
                return  # revoked mid-flight: the new owner commits now
            # Commit the contiguous low-water mark for this partition —
            # including failed records awaiting replay, or a restart would
            # skip them and break the resume policy's at-least-once promise.
            open_offs = []
            for mid in self.pending:
                pp, _ = self._msg_part_off(mid)
                if pp == p:
                    # first open offset of the entry, chunk or record
                    open_offs.append(mid[2] if mid[0] == "c" else mid[1])
            for entry in self.replay:
                recs = entry if isinstance(entry, list) else [entry]
                open_offs += [r.offset for r in recs if r.partition == p]
            low = min(open_offs) if open_offs else off + 1
            if self._blocking:
                # Commit off-loop; ack() runs in ledger-callback (sync)
                # context. Strong ref kept in _bg (create_task results are
                # weakly referenced and could be GC'd before running).
                self._spawn_bg(asyncio.to_thread(self._commit_blocking, p, low))
            else:
                prev = self.broker.committed(self.group, self.topic, p)
                if prev is None or low > prev:
                    self.broker.commit(self.group, self.topic, p, low)

    def close(self) -> None:
        if getattr(self, "_membership", None) is not None:
            try:
                self._membership.leave()  # rebalance survivors promptly
            except Exception:
                pass

    def _spawn_bg(self, coro) -> None:
        task = asyncio.get_event_loop().create_task(coro)
        self._bg.add(task)
        task.add_done_callback(self._bg.discard)

    def _commit_blocking(self, p: int, low: int) -> None:
        # The lock makes check+commit atomic across to_thread workers, and
        # the local high-water mark keeps the committed offset monotonic
        # (two racing commits must never regress the group offset).
        with self._commit_lock:
            hwm = self._commit_hwm.get(p, -1)
            if low <= hwm:
                return
            self.broker.commit(self.group, self.topic, p, low)
            self._commit_hwm[p] = low

    def fail(self, msg_id: Any) -> None:
        entry = self.pending.pop(msg_id, None)
        if entry is None:
            return
        rec0 = entry[0] if isinstance(entry, list) else entry
        if self._membership is not None and \
                rec0.partition not in self.my_partitions:
            return  # revoked mid-flight: the new owner serves it now
        # Queue for replay FIRST, unconditionally: between here and a (possibly
        # asynchronous) staleness verdict the record must be visible to ack()'s
        # low-water commit scan, or a concurrent ack on a later offset would
        # commit past it and a restart would skip it. Staleness then *removes*
        # it — the conservative direction for at-least-once.
        self.replay.append(entry)
        max_behind = self.offsets_cfg.max_behind
        if max_behind is None:
            return
        # Staleness is judged by the entry's newest record (conservative for
        # chunks: the whole chunk stays if its tail is still fresh).
        rec = entry[-1] if isinstance(entry, list) else entry
        if self._blocking:
            # The staleness check is a network round-trip; fail() runs in
            # sync ledger-callback context on the loop, so decide off-loop.
            self._spawn_bg(self._fail_check_blocking(entry, max_behind))
            return
        self._drop_if_stale(entry, self.broker.latest_offset(self.topic, rec.partition), max_behind)

    async def _fail_check_blocking(self, entry, max_behind: int) -> None:
        rec = entry[-1] if isinstance(entry, list) else entry
        try:
            latest = await asyncio.to_thread(
                self.broker.latest_offset, self.topic, rec.partition
            )
        except Exception:
            # Broker unreachable: leave the record queued for replay rather
            # than guessing staleness — losing it would break at-least-once.
            return
        self._drop_if_stale(entry, latest, max_behind)

    def _drop_if_stale(self, entry, latest: int, max_behind: int) -> None:
        rec = entry[-1] if isinstance(entry, list) else entry
        if latest - rec.offset > max_behind:
            try:
                self.replay.remove(entry)
            except ValueError:
                return  # already picked up for replay — let it ride
            # Too stale to replay under the freshness policy.
            n = len(entry) if isinstance(entry, list) else 1
            self.dropped += n
            self.context.metrics.counter(
                self.context.component_id, "dropped_stale"
            ).inc(n)
