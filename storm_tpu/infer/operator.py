"""The inference operator — the heart of the system (reference
InferenceBolt.java, SURVEY.md §3.3).

Per-tuple flow, redesigned for the async device boundary:

1. decode the ``{"instances": ...}`` payload (native C++ parser when built;
   the reference's Jackson parse, InferenceBolt.java:76);
2. validate against the model's input shape — a mismatch or parse failure
   emits a :class:`DeadLetter` on the ``dead_letter`` stream and acks
   (the reference emitted ``null`` and acked, :92-99 — poison input should
   never wedge the stream, but it should also never masquerade as output);
3. submit the decoded rows to the one queue of the shared
   :class:`InferenceEngine` (:mod:`storm_tpu.infer.continuous`), where a
   batch is cut when the engine's ring has a free slot, on the queue's own
   thread — the event loop keeps consuming while the TPU computes (the
   reference blocked its executor thread in ``session.run`` at batch 1);
4. when the batch returns, emit one ``{"predictions": ...}`` tuple per
   input record (records of one ``RecordFrame`` that rode one batch share
   one), anchored, and ack — acks are *deferred* until the device
   round-trip completes, preserving at-least-once across the async boundary
   (SURVEY.md §7 "Hard parts").

Failures inside the device call fail every tuple in the batch -> spout
replay (the reference swallowed inference errors)."""

from __future__ import annotations

import asyncio
import functools
import time
from typing import Optional, Sequence, Set

import numpy as np

from storm_tpu.api.schema import (
    DeadLetter, Overloaded, SchemaError, decode_instances, encode_predictions)
from storm_tpu.cascade.policy import CascadeConfig
from storm_tpu.cascade.router import CascadeRouter, Escalated
from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
from storm_tpu.infer.continuous import continuous_for
from storm_tpu.infer.engine import InferenceEngine, shared_engine
from storm_tpu.obs import copyledger as _copyledger
from storm_tpu.obs.profile import end_record
from storm_tpu.runtime.base import Bolt, OutputCollector, TopologyContext
from storm_tpu.runtime.frames import RecordFrame
from storm_tpu.runtime.tracing import NOT_SAMPLED, span
from storm_tpu.runtime.tuples import Tuple, Values


class _ChunkHandle:
    """Ref-counted completion for a chunked input tuple (BrokerSpout
    ``chunk=N``): N records share one upstream tuple; it is acked when every
    record completes, failed (once) if any record's batch fails. Poison
    records dead-letter individually and count as completed — one bad record
    must not replay the whole chunk forever."""

    __slots__ = ("tuple", "remaining", "failed", "frame")

    def __init__(self, t: Tuple, n: int, frame: bool = False) -> None:
        self.tuple = t
        self.remaining = n
        self.failed = False
        # frame=True: the chunk arrived as a RecordFrame (batch-native
        # ingress) — egress coalesces this handle's records into ONE
        # predictions payload per device batch (see _emit_groups).
        self.frame = frame

    def done(self, ok: bool, collector: OutputCollector) -> None:
        self.failed |= not ok
        self.remaining -= 1
        if self.remaining == 0:
            (collector.fail if self.failed else collector.ack)(self.tuple)


# What an engine's queue asks of a payload this operator submitted. Module
# functions, not closures: the queue lives as long as its engine and must
# not keep a bolt of a topology that is gone.

def _trace_of(payload):
    return InferenceBolt._anchor_of(payload).trace


def _link_of(payload):
    return payload.link_span if isinstance(payload, Escalated) else None


class InferenceBolt(Bolt):
    # Flipped by execute() on the first raw-scheme payload: predictions
    # then leave as utf-8 bytes.
    _bytes_egress = False

    def __init__(
        self,
        model: Optional[ModelConfig] = None,
        batch: Optional[BatchConfig] = None,
        sharding: Optional[ShardingConfig] = None,
        engine: Optional[InferenceEngine] = None,
        warmup: bool = True,
        passthrough: Sequence[str] = (),
        qos=None,
        cascade: Optional[CascadeConfig] = None,
    ) -> None:
        self.model_cfg = model or ModelConfig()
        self.batch_cfg = batch or BatchConfig()
        self.sharding_cfg = sharding or ShardingConfig()
        self._engine = engine
        self._warmup = warmup
        # Input fields copied verbatim onto every output tuple (both
        # streams): how a record's ``qos_lane`` reaches the sink.
        self.passthrough = tuple(passthrough)
        # QosConfig (config.py) or None. When enabled: the engine's queue
        # orders lanes earliest-deadline-first instead of FIFO, and
        # shed-eligible tuples are degraded/rejected while the shed level
        # (gauge ("qos", "shed_level")) is raised.
        self.qos = qos if (qos is not None and qos.enabled) else None
        # CascadeConfig (cascade/policy.py) or None: confidence-gated
        # tiered serving — records enter at tier 0 and only the
        # low-confidence residue escalates toward the flagship.
        self.cascade = cascade if (cascade is not None
                                   and cascade.enabled) else None

    @property
    def opens_device(self) -> bool:
        """True when ``prepare`` builds an in-process engine, so the
        process hosting this component opens the accelerator. The dist
        controller's one-process-per-chip placement rule reads it."""
        return self._engine is None

    def clone(self) -> "InferenceBolt":
        return InferenceBolt(
            self.model_cfg, self.batch_cfg, self.sharding_cfg, self._engine,
            self._warmup, self.passthrough, self.qos, self.cascade
        )

    def declare_output_fields(self):
        fields = ("message",) + self.passthrough
        return {"default": fields, "dead_letter": fields}

    def _extras(self, t: Tuple):
        # Default-tolerant: a stream that doesn't carry a passthrough field
        # (e.g. two spouts with different fields sharing this bolt) yields
        # None rather than poisoning the whole batch with a KeyError.
        return [t.get(f, None) for f in self.passthrough]

    def prewarm(self) -> None:
        """Build + warm the engine OFF the event loop, before this replica
        receives any traffic — called by ``rebalance`` on a worker thread
        when scaling out (warm scale-up: a cold compile must neither block
        the loop nor ride on live tuples). ``prepare`` then finds the
        engine already built and skips the in-loop warmup. Idempotent: the
        process-level engine cache makes repeat calls cheap. An engine
        injected at construction (the NullEngine bench path) is kept, not
        replaced — same contract as prepare()."""
        from storm_tpu.obs.profile import ensure_installed

        ensure_installed()  # before the cold compiles, as in prepare()
        self._engine = self._engine or shared_engine(
            self.model_cfg, self.sharding_cfg, self.batch_cfg)
        if self._warmup:
            self._engine.warmup()
        # Cascade tiers compile here too (the QoS degrade tier included —
        # its whole purpose is serving SHED traffic at peak overload, the
        # one moment an XLA compile on the hot path is least affordable).
        # prepare() then finds them in the process cache already warm.
        cas = self._cascade_cfg()
        if cas is not None:
            probe = CascadeRouter(cas, qos=self.qos)
            for i in range(len(cas.tiers)):
                mc = probe.tier_model(i, self.model_cfg)
                if mc is self.model_cfg:
                    continue  # the flagship engine, warmed above
                eng = shared_engine(mc, self.sharding_cfg, self.batch_cfg)
                if self._warmup:
                    eng.warmup()
        self._prewarmed = True

    def _cascade_cfg(self) -> Optional[CascadeConfig]:
        """The effective cascade: the explicit config when given, else a
        synthesized two-tier shed-only cascade for ``qos.degrade_model``
        (a cascade whose tier 0 serves pinned shed traffic through its
        engine's queue like any other)."""
        if self.cascade is not None:
            return self.cascade
        if self.qos is not None and self.qos.degrade_model:
            return CascadeConfig(
                enabled=True,
                tiers=(self.qos.degrade_model, self.model_cfg.name),
                thresholds=(0.0,), shed_only=True)
        return None

    def prepare(self, context: TopologyContext, collector: OutputCollector) -> None:
        super().prepare(context, collector)
        # Cost profiler (storm_tpu/obs): point the engine layer's profile
        # sink at the process ProfileStore BEFORE any engine builds or
        # warms up, so warmup's cold compiles land in the per-shape
        # compile table. Idempotent, near-free per batch.
        from storm_tpu.obs.profile import ensure_installed

        ensure_installed()
        _copyledger.ensure_installed()  # byte-side twin, same lifecycle
        # Shared across operator tasks: params live once in HBM; the mesh is
        # the parallelism (vs. the reference's per-bolt model replica).
        self.engine = self._engine or shared_engine(
            self.model_cfg, self.sharding_cfg, self.batch_cfg
        )
        prewarmed = getattr(self, "_prewarmed", False)
        if self._warmup and not prewarmed:
            self.engine.warmup()
        # Cascade (explicit config, or synthesized from qos.degrade_model):
        # one shared engine per tier. The operator keeps owning tasks, acks
        # and the row bound — max_inflight bounds the task's outstanding
        # rows ACROSS tiers.
        cas = self._cascade_cfg()
        if cas is not None:
            self._router = CascadeRouter(cas, qos=self.qos)
            self._router.build(
                self.model_cfg,
                build_engine=lambda mc: shared_engine(
                    mc, self.sharding_cfg, self.batch_cfg),
                flagship=self.engine,
                warmup=self._warmup and not prewarmed)
        else:
            self._router = None
        self._inflight: Set[asyncio.Task] = set()
        m = context.metrics
        cid = context.component_id
        self._m_dead = m.counter(cid, "dead_lettered")
        # Stage 1 of the latency decomposition (broker append -> bolt
        # arrival); the queue observes the batching and device stages
        # under this component id (ContinuousBatcher.bind).
        self._m_ingest = m.histogram(cid, "ingest_lag_ms")
        if self._router is not None:
            self._router.bind_metrics(m, cid)
        # QoS: the shed level is read per tuple, so cache the gauge (the
        # LoadShedController publishes through the same registry). The
        # degrade path lives in the cascade: qos.degrade_model synthesizes
        # a shed-only cascade whose tier 0 serves pinned shed traffic,
        # batched like any other.
        if self.qos is not None:
            self._shed_gauge = m.gauge("qos", "shed_level")
            self._m_shed = m.counter(cid, "shed_rejected")
            self._m_degraded = m.counter(cid, "shed_degraded")
        # Distributed tracing + flight recorder (runtime/tracing.py).
        self._tracer = getattr(context, "tracer", None)
        self._flight = getattr(context, "flight", None)
        if self._flight is not None:
            # Cold XLA compiles ride the hot path (a new bucket shape) —
            # exactly the latency cliff a post-mortem needs to see.
            hook = (
                lambda shape, ms, cid=cid, fl=self._flight: fl.event(
                    "xla_compile", component=cid, batch_shape=shape,
                    compile_ms=round(ms, 1)))
            self.engine.on_compile = hook
            if self._router is not None:
                for rt in self._router.tiers:
                    try:
                        rt.engine.on_compile = hook
                    except AttributeError:
                        pass  # slotted test double
        # Engine quarantine -> replacement (batch.watchdog_trips): the
        # watchdog quarantines on the fetch thread; this hook records it
        # and rebuilds a fresh shared engine on a background thread (the
        # quarantined one was evicted from the cache), swapping it in once
        # warmed. Until then dispatch raises EngineQuarantined, those
        # batches fail, and their sources replay — fail-and-replay, never
        # wedge.
        self._m_quarantined = m.gauge(cid, "engine_quarantined")
        self._m_wd_trips = m.counter(cid, "watchdog_trips")
        try:
            self.engine.on_quarantine = self._engine_quarantined
        except AttributeError:
            pass  # slotted test double
        # Batch formation lives OFF this task, in the one queue of each
        # engine it shares — every replica, the serve path and cascade
        # residues co-batch there, and a batch is cut from the queue when
        # the engine's ring has a free slot (infer/continuous.py). Shed and
        # lane classification stay here.
        self._cbs = {}
        self._cb_notify = {}
        if self._router is not None:
            for rt in self._router.tiers:
                self._bind_queue(rt.index, rt.engine)
        else:
            self._bind_queue(None, self.engine)
        # Per-task backpressure: the queue owns batching, so the task
        # bounds its outstanding ROWS, at max_inflight * max_batch.
        self._cb_cap = (max(1, self.batch_cfg.max_inflight)
                        * max(1, self.batch_cfg.max_batch))
        self._cb_rows = 0
        self._cb_room = asyncio.Event()
        self._cb_room.set()
        self._cb_source = f"{cid}#{context.task_index}"
        self._loop = None  # the event loop, known from the first record

    def _bind_queue(self, tier: Optional[int], engine) -> None:
        """Aim this task at ``engine``'s continuous queue (``tier`` None:
        the one engine of a bolt without a cascade) and take the queue's
        observability over for this topology."""
        cb = continuous_for(engine, self.batch_cfg, self.qos)
        cb.bind(self.context.metrics, self.context.component_id,
                tracer=self._tracer, flight=self._flight,
                trace_of=_trace_of, link_of=_link_of,
                span_name=("device_execute" if tier is None
                           else f"cascade_tier{tier}"))
        self._cbs[tier] = cb
        self._cb_notify.setdefault(
            tier, functools.partial(self._on_batch, tier))

    # ---- quarantine -> replacement -------------------------------------------

    def _engine_quarantined(self, trips: int) -> None:
        """Engine watchdog callback (fires ONCE, on the fetch thread):
        record the quarantine, then prewarm a replacement off-thread and
        swap it in. Batches dispatched in between fail fast
        (EngineQuarantined) and their sources replay."""
        import threading

        self._m_quarantined.set(1)
        self._m_wd_trips.inc(trips)
        if self._flight is not None:
            self._flight.event(
                "engine_quarantined", component=self.context.component_id,
                model=self.model_cfg.name, trips=trips)
        old = self.engine

        def rebuild() -> None:
            try:
                # The quarantined engine was evicted from the shared
                # cache, so this builds (and warms) a genuinely fresh one.
                eng = shared_engine(
                    self.model_cfg, self.sharding_cfg, self.batch_cfg)
                if self._warmup:
                    eng.warmup()
                try:
                    eng.on_compile = old.on_compile
                    eng.on_quarantine = self._engine_quarantined
                except AttributeError:
                    pass
                self.engine = eng
                # Re-aim at the replacement's queue (a queue holds the
                # engine it dispatches to).
                if None in self._cbs:
                    self._bind_queue(None, eng)
                self._m_quarantined.set(0)
                if self._flight is not None:
                    self._flight.event(
                        "engine_replaced",
                        component=self.context.component_id,
                        model=self.model_cfg.name)
            except Exception:
                import logging

                logging.getLogger(__name__).exception(
                    "replacement engine build failed; component stays "
                    "quarantined (batches fail fast and replay)")

        threading.Thread(target=rebuild, name="engine-replace",
                         daemon=True).start()

    # ---- ingest --------------------------------------------------------------

    # Batch items are a raw Tuple (one record per tuple), a _ChunkHandle
    # (chunked ingestion), or either wrapped in Escalated while riding a
    # cascade escalation tier. These two helpers are the only places that
    # distinguish them — completion always unwraps to the ORIGINAL tuple,
    # so deferred acks and replay are tier-blind (exactly-once preserved).

    @staticmethod
    def _anchor_of(item) -> Tuple:
        if isinstance(item, Escalated):
            item = item.payload
        return item.tuple if isinstance(item, _ChunkHandle) else item

    def _complete(self, item, ok: bool) -> None:
        if isinstance(item, Escalated):
            item = item.payload
        if isinstance(item, _ChunkHandle):
            item.done(ok, self.collector)
        elif ok:
            self.collector.ack(item)
        else:
            self.collector.fail(item)

    @staticmethod
    def _egress_groups(emit):
        """Partition an emit list into frame egress groups, order
        preserved: consecutive-or-not members of the same frame
        ``_ChunkHandle`` coalesce under it; everything else stays a
        singleton keyed ``None``. Returns ``[(handle|None, [(item,
        preds), ...]), ...]``."""
        out = []
        index = {}
        for item, preds in emit:
            base = item.payload if isinstance(item, Escalated) else item
            if isinstance(base, _ChunkHandle) and base.frame:
                i = index.get(id(base))
                if i is None:
                    index[id(base)] = len(out)
                    out.append((base, [(item, preds)]))
                else:
                    out[i][1].append((item, preds))
            else:
                out.append((None, [(item, preds)]))
        return out

    def _decode_checked(self, payload, root_ts):
        """Decode + shape-validate one record (raises SchemaError)."""
        with span(self.context.metrics, self.context.component_id, "decode"):
            inst = decode_instances(payload, ts=root_ts)
        if tuple(inst.data.shape[1:]) != self.engine.input_shape:
            raise SchemaError(
                f"instance shape {tuple(inst.data.shape[1:])} != model "
                f"input {self.engine.input_shape}"
            )
        if _copyledger.active():
            # Copy ledger: the parse writes a fresh float32 array — the
            # per-record tax the zero-copy path takes away. Bytes
            # are the array produced; the JSON text length rides in the
            # spout rows (scheme/ingest), not here. On the tensor-view
            # fast path nothing was written (the array is a view over
            # the payload buffer): the row stays, the zeros prove it.
            if inst.view:
                _copyledger.record("json_decode", 0, copies=0, allocs=0,
                                   records=1,
                                   engine=self.context.component_id)
            else:
                _copyledger.record("json_decode", inst.data.nbytes, copies=1,
                                   allocs=1, records=1,
                                   engine=self.context.component_id)
        return inst

    def _encode_ledgered(self, preds, records: int = 1):
        """``encode_predictions`` + the copy-ledger ``json_encode`` hop:
        the serialization writes one fresh payload per emit.

        Raw-scheme topologies (``_bytes_egress``) get the payload as
        utf-8 BYTES: the sink produces those bytes verbatim, so the
        legacy ``sink_encode`` re-encode hop (which duplicated every
        payload byte) disappears from the path. String
        topologies keep the str contract (the JSON dist wire
        cannot carry bytes)."""
        msg = encode_predictions(preds)
        if self._bytes_egress:
            payload = msg.encode("utf-8")
            if _copyledger.active():
                _copyledger.record("json_encode", len(payload), copies=1,
                                   allocs=1, records=records,
                                   engine=self.context.component_id)
            return payload
        if _copyledger.active():
            _copyledger.record("json_encode", len(msg), copies=1, allocs=1,
                               records=records,
                               engine=self.context.component_id)
        return msg

    async def _emit_dead_letter(self, anchor: Tuple, payload, error: str) -> None:
        self._m_dead.inc()
        if isinstance(payload, memoryview):
            # frame-record views: materialize before the envelope (also
            # releases the view's hold on its wire/shm backing buffer)
            payload = bytes(payload)
        if isinstance(payload, (bytes, bytearray)):
            # raw-scheme tuples: the DLQ envelope is JSON, so carry the
            # payload as text, not a bytes repr
            payload = payload.decode("utf-8", "replace")
        dl = DeadLetter(payload=str(payload), error=error)
        rec = anchor.record
        if rec is not None:
            # the record's row of the record log ends here; the dead letter
            # is another sink's delivery and carries none
            end_record(rec, "dead_lettered")
        await self.collector.emit(
            Values([dl.to_json(), *self._extras(anchor)]),
            stream="dead_letter", anchors=[anchor], record=False,
        )

    async def execute(self, t: Tuple) -> None:
        rec = t.record
        if rec is not None:
            rec.t_exec = time.time()
        if t.root_ts:
            # Stage 1 of the decomposition: broker append -> bolt arrival
            # (broker queueing + spout fetch/decode + inter-operator hop).
            self._m_ingest.observe((time.perf_counter() - t.root_ts) * 1e3)
        payload = t.get("message")
        if not self._bytes_egress and isinstance(
                payload, (bytes, bytearray, memoryview, RecordFrame)):
            # Raw-scheme ingress observed: predictions leave as utf-8
            # bytes so the sink produces them verbatim (no sink_encode
            # re-copy). Sticky for the bolt's lifetime — a topology's
            # scheme is uniform.
            self._bytes_egress = True
        lane = t.get("qos_lane", None) if self.qos is not None else None
        level = int(self._shed_gauge.value) if self.qos is not None else 0
        if level > 0 and self.qos.shed_eligible(lane, level):
            if self._router is None:
                # Shed BEFORE decode: with no cascade to degrade onto, the
                # whole point is spending nothing on traffic we will not
                # serve at full fidelity.
                await self._shed_tuple(t, payload, lane, level)
                return
            # Cascade degrade: the record serves at tier 0 — pinned there
            # by decide_item(), batched like any other — so fall through
            # to the regular ingest path.
            n = (len(payload)
                 if isinstance(payload, (list, tuple, RecordFrame)) else 1)
            self._m_degraded.inc(n)
            if self._flight is not None:
                self._flight.event(
                    "shed_degrade", throttle_s=1.0,
                    component=self.context.component_id,
                    lane=lane, level=level, records=n)
        entry = (self._router.entry_tier(lane, level)
                 if self._router is not None else None)
        if isinstance(payload, (list, tuple, RecordFrame)):
            await self._execute_chunk(t, payload, lane, entry)
            return
        try:
            inst = self._decode_checked(payload, t.root_ts)
        except SchemaError as e:
            await self._dead_letter(t, payload, str(e))
            return
        if rec is not None:
            rec.t_parsed = time.time()
        await self._submit_record(t, inst.data, t.root_ts or None, lane,
                                  entry)

    async def _execute_chunk(self, t: Tuple, payloads, lane=None,
                             entry=None) -> None:
        # frame_egress=False keeps the one-output-message-per-record
        # contract for frame ingress: the handle is marked non-frame so
        # egress never coalesces (zero-copy ingress/decode is unaffected).
        handle = _ChunkHandle(t, len(payloads),
                              frame=(isinstance(payloads, RecordFrame)
                                     and getattr(self.batch_cfg,
                                                 "frame_egress", True)))
        for payload in payloads:
            try:
                inst = self._decode_checked(payload, t.root_ts)
            except SchemaError as e:
                # Dead-letter the record, keep the chunk alive: anchored to
                # the chunk tuple, completed as handled.
                await self._emit_dead_letter(t, payload, str(e))
                handle.done(True, self.collector)
                continue
            if t.record is not None:
                t.record.t_parsed = time.time()
            await self._submit_record(handle, inst.data, t.root_ts or None,
                                      lane, entry)

    # ---- the engine's queue --------------------------------------------------

    async def _submit_record(self, item, data, ts, lane, entry) -> None:
        """Hand one record to the queue of its entry engine (a cascade
        tier's when a router is active); the queue calls back once a
        device batch with this task's records of it (``_on_batch``).
        Backpressure is row-counted per task (``max_inflight * max_batch``
        outstanding rows); the engine's pipeline ring stays the
        device-side bound."""
        while self._cb_rows >= self._cb_cap:
            self._cb_room.clear()
            await self._cb_room.wait()
        if self._loop is None:
            self._loop = asyncio.get_running_loop()
        tenant = (self._anchor_of(item).get("qos_tenant", None)
                  if self.qos is not None else None)
        self._enqueue(entry, data, item, ts, lane, tenant)

    def _enqueue(self, tier, data, item, ts, lane, tenant) -> None:
        """Count the rows as this task's and submit them; never waits (an
        escalation to the next tier must not park behind the row bound its
        own completion frees)."""
        self._cb_rows += int(data.shape[0])
        rec = self._anchor_of(item).record
        if rec is not None:
            # before the submit: the cut may come before it returns
            rec.t_enq = time.time()
        self._cbs[tier].submit(
            data, payload=item, ts=ts, lane=lane, tenant=tenant,
            source=self._cb_source, notify=self._cb_notify[tier])

    def _on_batch(self, tier, members) -> None:
        """The queue's callback, on the thread that finished the device
        batch: hand this task's records of that batch to the event loop
        as one group."""
        try:
            self._loop.call_soon_threadsafe(self._spawn_group, tier, members)
        except RuntimeError:
            pass  # loop closed under a shutdown: nobody is left to emit to

    def _spawn_group(self, tier, members) -> None:
        now = None
        for sub in members:
            rec = self._anchor_of(sub.payload).record
            if rec is not None:
                # the loop has the group: its step's key, and t_egress
                now = now or time.time()
                rec.t_egress = now
                if sub.step is not None:
                    rec.engine = sub.step["engine"]
                    rec.step = sub.step["step"]
        task = self._loop.create_task(self._finish_group(tier, members))
        self._inflight.add(task)
        task.add_done_callback(self._inflight.discard)

    async def _finish_group(self, tier, members) -> None:
        """Emit + complete this task's records of one device batch. A
        record a cascade tier is unsure of goes on to the next tier's
        queue and completes from that batch's group. A queue/device
        failure at ANY tier fails the ORIGINAL tuple (``_complete``
        unwraps ``Escalated``), so the record replays from tier 0 —
        at-least-once, each source failing its own tuples only."""
        emit = []
        try:
            reported = False
            for sub in members:
                item = sub.payload
                try:
                    preds = sub.future.result()
                    if tier is not None:
                        preds = self._decide_record(sub, preds, tier)
                        if preds is None:
                            continue  # escalated
                    emit.append((item, preds))
                except Exception as e:
                    if not reported:
                        self.collector.report_error(e)
                        reported = True
                    self._complete(item, False)
            await self._emit_groups(emit)
        finally:
            self._cb_rows -= sum(sub.rows for sub in members)
            if self._cb_rows < self._cb_cap:
                self._cb_room.set()

    def _decide_record(self, sub, out, tier: int):
        """One record's cascade decision after ``tier`` served it: its
        merged predictions, or None once its unsure rows are on their way
        to the next tier's queue."""
        level = int(self._shed_gauge.value) if self.qos is not None else 0
        merged, residue, info = self._router.decide_item(
            sub.payload, sub.data, out, sub.lane, tier, level, ts=sub.ts)
        if residue is None:
            return merged
        wrapper = residue.payload
        # Chain the trace: the next tier's queue_wait span links back to
        # the span of the batch that escalated this row.
        wrapper.link_span = sub.batch_span
        if self._flight is not None:
            self._flight.event(
                "cascade_escalation", throttle_s=1.0,
                component=self.context.component_id,
                tier=tier, model=self._router.tiers[tier].name,
                escalation_rate=round(
                    self._router.escalation_rate(), 4), **info)
        self._enqueue(tier + 1, residue.data, wrapper, residue.ts,
                      residue.lane, sub.tenant)
        return None

    async def _dead_letter(self, t: Tuple, payload: str, error: str) -> None:
        """Poison input: route to the dead-letter stream and ack (replaying
        a parse failure can never succeed; the reference's emit-null-and-ack
        at InferenceBolt.java:92-99 is the anti-pattern this replaces)."""
        await self._emit_dead_letter(t, payload, error)
        self.collector.ack(t)

    # ---- QoS shedding --------------------------------------------------------

    async def _shed_tuple(self, t: Tuple, payload, lane, level: int) -> None:
        """Typed rejection for a shed-eligible tuple while the shed level
        is raised and no cascade exists: answer immediately with an
        ``Overloaded`` record — the client gets a parseable response *now*
        instead of a timeout, and the tuple acks (shedding must never
        trigger replay: replaying rejected load is more load). Graceful
        degradation lives in the cascade: a configured ``qos.degrade_model``
        pins shed traffic to cascade tier 0, so this path is reject-only."""
        payloads = (payload
                    if isinstance(payload, (list, tuple, RecordFrame))
                    else [payload])
        msg = Overloaded(lane=lane or "", shed_level=level).to_json()
        for _ in payloads:
            await self.collector.emit(
                Values([msg, *self._extras(t)]), anchors=[t])
        self._m_shed.inc(len(payloads))
        if self._flight is not None:
            self._flight.event(
                "shed_reject", throttle_s=1.0,
                component=self.context.component_id,
                lane=lane, level=level, records=len(payloads))
        ctx = t.trace
        if (ctx is not None and ctx is not NOT_SAMPLED
                and self._tracer is not None and self._tracer.active):
            now = time.perf_counter()
            self._tracer.record(
                ctx, "qos_shed", self.context.component_id,
                t.root_ts or now, now,
                attrs={"lane": lane or "", "level": level,
                       "action": "reject"})
        self.collector.ack(t)

    async def _emit_groups(self, emit) -> None:
        """Batch egress: records that arrived together as a
        RecordFrame and rode one device batch leave together — their
        predictions concatenate into ONE payload per (frame, device
        batch), killing the per-record json_encode fan-out (r19 zero-copy
        plan). Other items keep the one-payload-per-record contract. A
        payload that cannot be encoded or emitted fails its own records."""
        for handle, group in self._egress_groups(emit):
            try:
                anchor = (self._anchor_of(group[0][0]) if handle is None
                          else handle.tuple)
                preds = (group[0][1] if len(group) == 1 else
                         np.concatenate([p for _, p in group], axis=0))
                with span(self.context.metrics, self.context.component_id,
                          "encode"):
                    msg = self._encode_ledgered(preds, records=len(group))
                rec = anchor.record
                if rec is not None:
                    rec.t_encoded = time.time()
                    # a group's records leave as one output
                    rec.left -= len(group) - 1
                await self.collector.emit(
                    Values([msg, *self._extras(anchor)]), anchors=[anchor])
            except Exception as e:
                self.collector.report_error(e)
                for item, _ in group:
                    self._complete(item, False)
            else:
                for item, _ in group:
                    self._complete(item, True)

    async def swap_model(self, model_cfg: ModelConfig) -> None:
        """Zero-downtime model swap (the reference ships its model inside
        the application jar, InferenceBolt.java:49-57 — redeploying means a
        full topology restart; here a new checkpoint/model goes live under
        traffic). The new engine is built and warmed on a worker thread,
        then the reference is switched atomically: batches already in
        flight finish on the old engine, later batches use the new one.
        The old engine stays in the process cache for instant rollback
        (swap back) at the cost of its HBM footprint.

        Swapping to a different ``input_shape`` may fail-and-replay tuples
        decoded under the old shape that reach the new engine's queue —
        at-least-once delivery covers them."""

        def build() -> InferenceEngine:
            eng = shared_engine(model_cfg, self.sharding_cfg, self.batch_cfg)
            eng.warmup()
            return eng

        old_engine = self.engine
        new_engine = await asyncio.to_thread(build)
        if self._router is not None:
            # The cascade tier serving the flagship follows the swap (the
            # tiers sharing the old engine object by identity — normally
            # just the last one), and so does its queue.
            for rt in self._router.tiers:
                if rt.engine is old_engine:
                    rt.engine = new_engine
                    rt.model_cfg = model_cfg
                    self._bind_queue(rt.index, new_engine)
        else:
            # Rows already queued finish on the old engine's queue; later
            # records go to the new engine's.
            self._bind_queue(None, new_engine)
        self.engine = new_engine
        self.model_cfg = model_cfg

    async def flush(self) -> None:
        """Drain: force the engine queues to dispatch and wait until none
        of this task's rows is outstanding and every group has been
        emitted, so a graceful stop never strands undecoded acks. Re-flush
        on a short period: a record escalating mid-drain enqueues into a
        LATER tier's queue after its flush already drained."""
        while self._cb_rows or self._inflight:
            for cb in set(self._cbs.values()):
                cb.flush()
            if self._inflight:
                await asyncio.wait(list(self._inflight), timeout=0.05)
            else:
                await asyncio.sleep(0.005)
