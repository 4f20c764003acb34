"""Operator API: Spout / Bolt / OutputCollector / TopologyContext.

Mirrors the surface the reference programs against (``BaseRichBolt``,
``OutputCollector``, ``TopologyContext`` — InferenceBolt.java:25,38-41,
KafkaBolt.java:84) with two deliberate changes for the asyncio runtime:

- ``execute``/``next_tuple`` are coroutines, because emitting into a bounded
  downstream inbox is a backpressure point (Storm blocks a thread; we await);
- uncaught exceptions in ``execute`` fail the input tuple and keep the
  executor alive (Storm kills the worker; the reference swallowed errors and
  acked anyway — InferenceBolt.java:92-99 — which we do NOT reproduce).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterable, List, Optional, Sequence

from storm_tpu.obs import copyledger as _copyledger
from storm_tpu.obs.profile import end_record
from storm_tpu.runtime.groupings import DirectGrouping
from storm_tpu.runtime.tracing import NOT_SAMPLED
from storm_tpu.runtime.tuples import Tuple, Values, merge_offsets, new_id


class TopologyContext:
    """What an operator instance knows about itself and its surroundings."""

    def __init__(
        self,
        component_id: str,
        task_index: int,
        parallelism: int,
        config: Any,
        metrics: "Any" = None,
        *,
        tracer: "Any" = None,
        flight: "Any" = None,
    ) -> None:
        self.component_id = component_id
        self.task_index = task_index
        self.parallelism = parallelism
        self.config = config
        self.metrics = metrics
        # Distributed tracing + flight recorder (runtime/tracing.py); None
        # outside a full runtime (unit-constructed contexts).
        self.tracer = tracer
        self.flight = flight

    def __repr__(self) -> str:  # pragma: no cover
        return f"<TopologyContext {self.component_id}[{self.task_index}/{self.parallelism}]>"


class OutputCollector:
    """Routes emits, maintains ack/anchor bookkeeping.

    Equivalent of Storm's ``OutputCollector``/``SpoutOutputCollector``
    (used at InferenceBolt.java:98-99, KafkaBolt.java:134-154).
    """

    def __init__(self, runtime: "Any", component_id: str, task_index: int) -> None:
        self._rt = runtime
        self.component_id = component_id
        self.task_index = task_index
        self._out_fields: Dict[str, Sequence[str]] = {"default": ("message",)}
        # Per-tuple hot path: resolve the registry dicts once, not per call.
        self._m_emitted = runtime.metrics.counter(component_id, "emitted")
        self._m_acked = runtime.metrics.counter(component_id, "acked")
        self._m_failed = runtime.metrics.counter(component_id, "failed")
        self._tracer = getattr(runtime, "tracer", None)

    def set_output_fields(self, fields: Dict[str, Sequence[str]]) -> None:
        self._out_fields = fields

    # ---- emitting ------------------------------------------------------------

    async def emit(
        self,
        values: Sequence[Any],
        *,
        stream: str = "default",
        anchors: Optional[Iterable[Tuple]] = None,
        msg_id: Any = None,
        root_ts: Optional[float] = None,
        origins: Optional[frozenset] = None,
        direct_task: Optional[int] = None,
        trace: Any = None,
        record: Any = None,
    ) -> int:
        """Emit a tuple downstream. Returns the number of deliveries.

        Bolt usage: ``await collector.emit(Values(out), anchors=[in_tuple])``.
        Spout usage: ``await collector.emit(Values(x), msg_id=offset)`` —
        a non-None ``msg_id`` opens an at-least-once ledger entry whose
        completion/failure is reported back to the spout.

        ``direct_task`` (normally via :meth:`emit_direct`) delivers only to
        subscriptions using ``DirectGrouping``, at that instance index.

        ``record``: the root's row of the record log (a spout's; see
        ``Tuple.record``). An anchored emit takes its anchors'; ``False``
        says this output carries none (a dead letter: its record's row has
        ended).
        """
        fields = self._out_fields.get(stream, ("message",))
        subs = self._rt.router.subscriptions(self.component_id, stream)

        roots: frozenset
        ts = root_ts if root_ts is not None else time.perf_counter()
        if anchors:
            anchor_list = list(anchors)
            roots = frozenset().union(*(a.anchors for a in anchor_list))
            if anchor_list and root_ts is None:
                ts = min(a.root_ts for a in anchor_list)
            if trace is None:
                # Trace context follows anchoring, like root_ts/origins.
                # Attribute reads only — no allocation when nothing is
                # sampled (the overwhelmingly common case).
                for a in anchor_list:
                    if a.trace is not None:
                        trace = a.trace
                        break
            if record is None:
                # The record log's row follows anchoring too: of several
                # anchors' the one appended first, as root_ts is the least.
                for a in anchor_list:
                    r = a.record
                    if r is not None and (record is None
                                          or r.t_append < record.t_append):
                        record = r
            if origins is None and any(a.origins for a in anchor_list):
                # Provenance follows anchoring: a derived tuple carries the
                # source-log positions of everything it was computed from.
                # Folded to the per-(topic, partition) MAX here, not a raw
                # union — an aggregating bolt anchored to N inputs must
                # carry O(partitions) triples, not O(N) (only the maximum
                # is ever consumed, by the transactional sink's offsets
                # commit).
                acc: dict = {}
                for a in anchor_list:
                    merge_offsets(acc, (((src_t, src_p), off)
                                        for (src_t, src_p, off) in a.origins))
                origins = frozenset(
                    (src_t, src_p, off) for (src_t, src_p), off in acc.items())
        else:
            roots = frozenset()
        origin_set = origins if origins is not None else frozenset()

        probe = Tuple(
            values=list(values),
            fields=fields,
            source_component=self.component_id,
            source_task=self.task_index,
            stream=stream,
            root_ts=ts,
        )

        deliveries: List[Any] = []  # (inbox, )
        for grouping, group in subs:
            if direct_task is not None:
                # emit_direct: only direct-grouped consumers, at the named
                # instance (Storm's emitDirect/directGrouping contract —
                # an out-of-range task is a producer bug, not a wrap).
                if isinstance(grouping, DirectGrouping):
                    if not 0 <= direct_task < len(group.inboxes):
                        raise ValueError(
                            f"emit_direct task {direct_task} out of range "
                            f"for {len(group.inboxes)}-instance consumer")
                    deliveries.append(group.inboxes[direct_task])
            else:
                for idx in grouping.choose(probe):
                    deliveries.append(group.inboxes[idx])

        root_id = None
        if msg_id is not None:
            if not deliveries:
                # No subscribers: complete immediately (Storm acks these).
                self._rt.spout_done(self.component_id, self.task_index, msg_id, True, ts)
                return 0
            root_id = new_id()
            self._rt.ledger.init_root(
                root_id,
                msg_id,
                self._rt.spout_done_cb(self.component_id, self.task_index),
                ts,
            )
            roots = frozenset((root_id,))
            if trace is None and self._tracer is not None and self._tracer.active:
                # Sampling fallback for spouts that don't mint their own
                # context (BrokerSpout does, and passes ``trace=``; a miss
                # there arrives as NOT_SAMPLED so the rate isn't doubled):
                # give every sampled root at least a generic ingress span.
                trace = self._tracer.maybe_trace()
                if trace is not None:
                    self._tracer.record(
                        trace, "ingress", self.component_id,
                        ts, time.perf_counter())
        if trace is NOT_SAMPLED:
            trace = None

        # XOR every new edge into the ledger BEFORE the first (possibly
        # yielding) queue put — otherwise a fast consumer could zero the
        # ledger while later deliveries of the same emit are still pending.
        edges = [new_id() for _ in deliveries]
        for edge in edges:
            for r in roots:
                self._rt.ledger.anchor(r, edge)
        n = 0
        for inbox, edge in zip(deliveries, edges):
            t = Tuple(
                # Fresh list per delivery: fan-out targets must never share
                # one mutable values object across executor instances.
                values=list(probe.values),
                fields=fields,
                source_component=self.component_id,
                source_task=self.task_index,
                stream=stream,
                edge_id=edge,
                anchors=roots,
                root_ts=ts,
                origins=origin_set,
                trace=trace,
                record=record or None,
            )
            await inbox.put(t)
            n += 1
        self._m_emitted.inc(n)
        if n and _copyledger.active():
            # Routing moves references, not payloads: bytes=0 is the
            # point of the row. Allocations are the probe tuple plus one
            # fresh Tuple (and values list) per delivery.
            _copyledger.record("tuple_route", 0, copies=0, allocs=n + 1,
                               records=n, engine=self.component_id)
        return n

    async def emit_direct(
        self,
        task: int,
        values: Sequence[Any],
        *,
        stream: str = "default",
        anchors: Optional[Iterable[Tuple]] = None,
        msg_id: Any = None,
        root_ts: Optional[float] = None,
    ) -> int:
        """Emit to instance ``task`` of every direct-grouped subscriber
        (Storm's ``emitDirect``; consumers subscribe with
        ``direct_grouping``)."""
        return await self.emit(
            values, stream=stream, anchors=anchors, msg_id=msg_id,
            root_ts=root_ts, direct_task=task,
        )

    # ---- acking --------------------------------------------------------------

    def ack(self, t: Tuple) -> None:
        """Mark the input tuple consumed (InferenceBolt.java:99)."""
        for r in t.anchors:
            self._rt.ledger.ack_edge(r, t.edge_id)
        self._m_acked.inc()

    def fail(self, t: Tuple) -> None:
        """Fail the input tuple's roots -> spout replay (KafkaBolt.java:137)."""
        for r in t.anchors:
            self._rt.ledger.fail_root(r)
        self._m_failed.inc()
        if t.record is not None:
            # its tree replays under a new row of the record log
            end_record(t.record, "failed")

    def report_error(self, err: BaseException) -> None:
        self._rt.report_error(self.component_id, self.task_index, err)

    @property
    def ledger(self):
        """The runtime's ack ledger (AckLedger in-process, RoutedLedger in
        dist workers). Exposed for the EOS sink's tree-shape queries
        (outstanding/watch); normal bolts never need it."""
        return self._rt.ledger


class Component:
    """Shared declarations for spouts and bolts."""

    #: stream name -> field names. Default mirrors the reference's single
    #: ``"message"`` field (InferenceBolt.java:104, KafkaBolt mapper default).
    def declare_output_fields(self) -> Dict[str, Sequence[str]]:
        return {"default": ("message",)}


class Spout(Component):
    def open(self, context: TopologyContext, collector: OutputCollector) -> None:
        self.context = context
        self.collector = collector

    async def next_tuple(self) -> bool:
        """Emit zero or more tuples; return True if anything was emitted
        (False lets the executor back off briefly)."""
        raise NotImplementedError

    def ack(self, msg_id: Any) -> None:
        """Tuple tree for ``msg_id`` fully processed."""

    def fail(self, msg_id: Any) -> None:
        """Tuple tree failed or timed out; replayable spouts re-emit."""

    def close(self) -> None:
        pass

    async def activate(self) -> None:
        pass

    async def deactivate(self) -> None:
        pass


class Bolt(Component):
    def prepare(self, context: TopologyContext, collector: OutputCollector) -> None:
        """One-time init per executor (InferenceBolt.java:44-62 loads the
        model here). Heavy state belongs here, not in __init__: the topology
        builder deep-copies the instance per task."""
        self.context = context
        self.collector = collector

    async def execute(self, t: Tuple) -> None:
        raise NotImplementedError

    async def tick(self) -> None:
        """Periodic timer callback (tick tuples, KafkaBolt.java:36)."""

    async def flush(self) -> None:
        """Drain hook: awaited by the executor after the last tuple during a
        graceful stop, before ``cleanup``. Bolts with deferred work (pending
        micro-batches, in-flight producer sends) settle it here."""

    def cleanup(self) -> None:
        """Graceful shutdown (KafkaBolt.java:175-177 closes the producer)."""
