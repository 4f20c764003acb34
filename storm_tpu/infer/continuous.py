"""Per-engine batching: one slot-level queue feeds the device.

Where every batch is formed: each replica of an inference bolt, the gRPC
serve worker, ``DecodeBolt`` and cascade escalation residues all
``submit`` rows into ONE queue per shared engine, and a dedicated
dispatcher thread cuts a batch from it when the device is about to take it
(extending the split-phase ring of :mod:`storm_tpu.infer.engine`) — late
binding: a batch's size is decided when the device can take it, from
everything that has arrived by then, whatever operator task it arrived
through (BatchGen, PAPERS.md, argues batch formation must be decoupled
from operator topology and run continuously at the device; PERF.md §6,
PR 26 and PR 33 have the chip's numbers).

Dispatch rule (work-conserving slot refill, cut as late as the device
allows):

- ``max_batch`` rows pending  -> dispatch (the ring provides backpressure);
- a ring slot is free AND at least one batch is already in flight -> hold
  the cut until the running step is about to end, then dispatch everything
  that has arrived (the freed-slot refill — batches size themselves to
  whatever coalesced while the device worked, exactly BatchGen's
  continuous former). A batch cut the moment the slot frees would lie
  staged behind the whole step that has just begun, and every record that
  arrives during that step would miss it. The cut is at *expected end −
  lead*: the work in flight ends at the start of the running step (the
  later of its launch and the end of the batch before it) plus
  ``engine.step_ms`` of each batch in flight; the lead is the longest of
  the last ``_LEADS`` cuts of the bucket this cut would take, each read
  from the moment the rule meant to cut to the batch's hand-over to the
  device (the dispatcher waking late, formation, staging, ``device_put``,
  launch), over cuts that did not park on the ring. Where a reading is
  missing (a bucket's first step or first cut, an engine without
  ``step_ms``, a ring of one slot) or the time left is already under the
  lead (a launch-bound model, an overdue step) the cut is at once;
- the device is fully idle -> ``eager`` dispatches on arrival, otherwise
  the oldest row ages to ``max_wait_ms`` (trickle traffic waits that long
  for company, no longer).

How many rows a batch takes: ``max_batch``, or — where the engine's
measured step time (``engine.step_ms``, the least step seen) grows
with the bucket — the largest FULL bucket under the pending rows, when
serving them in full buckets takes no longer than one step padded up to the
next bucket (:meth:`ContinuousBatcher._rows_to_take_locked`). A model whose
step costs the same whatever its bucket takes ``max_batch`` and pads.

Fairness: rows queue per ``tenant:lane`` key, batch formation orders keys
earliest-deadline-first (lane deadlines from
:class:`~storm_tpu.config.QosConfig`, so a fresh high-priority record
preempts queued best-effort), takes rows weighted-round-robin across keys
(weight = lane priority), and a key passed over
``BatchConfig.starvation_rounds`` consecutive formations is promoted to the
front of the next batch regardless of deadline order.

Exactly-once is preserved PER SOURCE: ``submit`` returns a handle whose
future resolves to that record's own row slice — when a coalesced batch
fails, every member future gets the exception and each source fails/replays
its own tuples independently; nothing is shared but the device round trip.
"""

from __future__ import annotations

import inspect
import logging
import threading
import time
import weakref
from collections import OrderedDict, deque
from concurrent.futures import Future
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from storm_tpu.config import BatchConfig, QosConfig
from storm_tpu.runtime.tracing import DEVICE_SUBSTAGES

logger = logging.getLogger(__name__)

# How many cuts of a bucket the lead of the hold looks back over (the module
# docstring's dispatch rule): the longest of them is the lead, so about one
# cut in ``_LEADS`` + 1 takes longer than the rule allowed for.
_LEADS = 16


class Submission:
    """One submitted record inside the continuous queue.

    ``future`` resolves (on the engine's fetch thread) to this record's
    ``(n, K)`` prediction rows — or to the exception that failed the
    coalesced batch it rode in. ``batch_span`` carries the shared device
    span id of the batch that served it (None untraced), so a cascade
    escalation can link the next tier's spans back. ``notify``, when given,
    is called once per device batch (after the futures resolve, on the
    thread that finished the batch) with the list of the submissions of
    that batch that share it, in batch order — how a source learns which
    of its records rode one batch together."""

    __slots__ = ("data", "payload", "ts", "enq", "lane", "tenant", "source",
                 "deadline", "future", "batch_span", "notify", "step")

    def __init__(self, data, payload, ts: float, enq: float,
                 lane: Optional[str], tenant: Optional[str], source: str,
                 deadline: float, notify: Optional[Callable] = None) -> None:
        self.data = data
        self.payload = payload
        self.ts = ts
        self.enq = enq
        self.lane = lane
        self.tenant = tenant
        self.source = source
        self.deadline = deadline
        self.future: Future = Future()
        self.batch_span: Optional[str] = None
        self.notify = notify
        # the step log's row of the batch that took it (None: no step log)
        self.step: Optional[dict] = None

    @property
    def rows(self) -> int:
        return int(self.data.shape[0])


def _drain_ms(rows: int, steps: Dict[int, float]) -> float:
    """Least device time to serve ``rows`` in steps of the measured
    buckets: at each point one step padded up to the next bucket, or the
    largest full bucket now and the rest after it, whichever is less."""
    if rows <= 0:
        return 0.0
    top = max(steps)
    if rows >= top:
        return steps[top] + _drain_ms(rows - top, steps)
    pad = steps[min(b for b in steps if b >= rows)]
    lo = max((b for b in steps if b <= rows), default=None)
    if lo is None:
        return pad
    return min(pad, steps[lo] + _drain_ms(rows - lo, steps))


class ContinuousBatcher:
    """One continuous batch former per shared engine.

    Thread-safe ``submit`` from any thread (event loop, gRPC handlers,
    completion callbacks); a single dispatcher thread owns batch formation
    and ``engine.dispatch`` (so per-engine dispatch order is total), and
    the engine's fetch thread resolves member futures via a done-callback.
    The engine is held weakly — the process engine cache must stay able to
    evict idle engines; a dead engine fails pending submissions."""

    def __init__(self, engine, cfg: BatchConfig,
                 qos: Optional[QosConfig] = None) -> None:
        self.cfg = cfg
        self.qos = qos if (qos is not None and qos.enabled) else None
        self._engine_ref = weakref.ref(engine)
        self.engine_name = getattr(
            getattr(engine, "model_cfg", None), "name",
            type(engine).__name__)
        # Ring capacity: how many batches the engine keeps in flight. The
        # dispatcher mirrors it with _inflight so "a slot just freed" is a
        # local decision; engine.dispatch's own ring acquire stays the hard
        # bound (an engine without a ring serializes at capacity 1).
        self.capacity = max(1, int(getattr(engine, "ring_capacity",
                                           getattr(engine, "pipeline_depth",
                                                   1)) or 1))
        # Whether ``engine.dispatch`` takes the queue's moments for the step
        # log (``obs/profile.py new_step_row``); a test double's may not.
        dispatch = getattr(engine, "dispatch", None)
        self._logs_steps = dispatch is not None and "queued" in \
            inspect.signature(dispatch).parameters
        self._cond = threading.Condition()
        # tenant:lane key -> FIFO of Submissions (deadlines monotone per key)
        self._queues: "OrderedDict[tuple, deque]" = OrderedDict()
        self._skipped: Dict[tuple, int] = {}
        self._pending_rows = 0
        self._inflight = 0
        self._force = False  # flush(): dispatch regardless of deadline
        # ---- the hold before the cut (module docstring) ----
        # (handle, launched) of each batch on the device, in launch order;
        # when the first of them began its step; and per padded bucket the
        # last cuts' milliseconds from "meant to cut" to "on the device".
        self._flying: deque = deque()
        self._began = 0.0
        self._leads: Dict[int, deque] = {}
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        # ---- stats (read by the qos UI route / tests) ----
        self.batches = 0
        self.rows_dispatched = 0
        self.fair_rows: Dict[tuple, int] = {}
        self.fair_starved: Dict[tuple, int] = {}
        self.last_batch: Optional[dict] = None
        self._fills: deque = deque(maxlen=256)
        # ---- observability bindings (first binder wins) ----
        self._metrics = None
        self._cid: Optional[str] = None
        self._tracer = None
        self._flight = None
        self._trace_of: Optional[Callable] = None
        self._link_of: Optional[Callable] = None
        self._span_name = "device_execute"
        self._m: Dict[str, object] = {}

    # ---- binding -------------------------------------------------------------

    def bind(self, metrics, component_id: str, tracer=None, flight=None,
             trace_of: Optional[Callable] = None,
             link_of: Optional[Callable] = None,
             span_name: str = "device_execute") -> None:
        """Attach the observability surfaces. The latest binder wins:
        replicas sharing one engine bind the same registry and component
        id, so among them the call is idempotent and the queue's metrics
        land once; a topology submitted later in the same process (the
        engine cache outlives a topology) takes the queue over from one
        that is gone."""
        with self._cond:
            self._metrics = metrics
            self._cid = component_id
            self._tracer = tracer
            self._flight = flight
            self._trace_of = trace_of
            self._link_of = link_of
            self._span_name = span_name
            m, cid = metrics, component_id
            self._m = {
                "batch_size": m.histogram(cid, "batch_size"),
                "batch_fill": m.histogram(cid, "batch_fill"),
                "device_ms": m.histogram(cid, "device_ms"),
                "batch_wait": m.histogram(cid, "batch_wait_ms"),
                "disp_wait": m.histogram(cid, "dispatch_wait_ms"),
                "cut_hold": m.histogram(cid, "cut_hold_ms"),
                "cuts_late": m.counter(cid, "cuts_late"),
                "infer": m.counter(cid, "instances_inferred"),
                "coalesced": m.counter(cid, "coalesced_sources"),
                "substage": {key: m.histogram(cid, key)
                             for key, _ in DEVICE_SUBSTAGES},
            }

    # ---- submission ----------------------------------------------------------

    def _key(self, tenant: Optional[str], lane: Optional[str]) -> tuple:
        if self.qos is not None:
            lane = lane if lane in self.qos.lanes else self.qos.default_lane
        return (tenant or "default", lane or "default")

    def _deadline_ms(self, lane: Optional[str]) -> float:
        if self.qos is not None:
            return self.qos.deadline_for(lane)
        return self.cfg.max_wait_ms

    def submit(self, data: np.ndarray, payload=None,
               ts: Optional[float] = None, lane: Optional[str] = None,
               tenant: Optional[str] = None,
               source: str = "anon",
               notify: Optional[Callable] = None) -> Submission:
        """Enqueue one record's rows; returns a :class:`Submission` whose
        future resolves to this record's own prediction slice. Never
        blocks — per-source backpressure (``max_inflight``) is the
        caller's contract, the engine ring is the device-side bound."""
        now = time.perf_counter()
        base = ts if ts is not None else now
        sub = Submission(
            data, payload, base, now, lane, tenant, source,
            base + self._deadline_ms(lane) / 1e3, notify)
        with self._cond:
            if self._closed:
                raise RuntimeError("continuous batcher is closed")
            self._queues.setdefault(
                self._key(tenant, lane), deque()).append(sub)
            self._pending_rows += sub.rows
            self._ensure_thread_locked()
            if (self._inflight < self.capacity
                    or self._pending_rows >= self.cfg.max_batch):
                # Otherwise the dispatcher sleeps until a slot frees
                # (_loop's untimed wait) and one more row changes nothing:
                # under a backlog that spares a wake-up per record. A
                # dispatcher that holds a cut wakes, finds the hold not
                # over and the batch not full, and goes on holding.
                self._cond.notify_all()
        return sub

    def configure(self, cfg: BatchConfig,
                  qos: Optional[QosConfig] = None) -> None:
        """Take the formation policy of the latest caller of
        :func:`continuous_for` (deadline, eagerness, starvation bound; the
        buckets and ``max_batch`` are the engine's identity and cannot
        differ). A caller without QoS leaves the lanes of one with."""
        with self._cond:
            self.cfg = cfg
            if qos is not None and qos.enabled:
                self.qos = qos
            self._cond.notify_all()

    def flush(self) -> None:
        """Force-dispatch everything pending (graceful drain): the force
        flag sticks until the queue empties, so a flush moves multiple
        max_batch-sized batches if that much is queued."""
        with self._cond:
            self._force = True
            self._cond.notify_all()

    def close(self) -> None:
        """No more submissions; what is pending is dispatched at once (a
        hold is released like ``flush`` releases it) and the dispatcher
        thread ends."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def __len__(self) -> int:
        return self._pending_rows

    @property
    def inflight(self) -> int:
        return self._inflight

    # ---- dispatcher thread ---------------------------------------------------

    def _ensure_thread_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name=f"storm-tpu-contbatch-{self.engine_name}")
            self._thread.start()

    def _oldest_enq_locked(self) -> float:
        return min(q[0].enq for q in self._queues.values() if q)

    def _loop(self) -> None:
        while True:
            with self._cond:
                # the hold before this cut: since when, and until when the
                # last timed wait meant to sleep
                held_from = wake_at = None
                while True:
                    if self._pending_rows == 0:
                        if self._closed:
                            return
                        self._force = False
                        self._cond.wait()
                        continue
                    now = time.perf_counter()
                    full = self._pending_rows >= self.cfg.max_batch
                    slot_free = self._inflight < self.capacity
                    if full or self._force or self._closed:
                        # full/forced batches may dispatch with every slot
                        # busy: engine.dispatch parks on the ring — that IS
                        # the backpressure, and the park happens on this
                        # thread, never the event loop.
                        break
                    if slot_free and (self._inflight > 0
                                      or held_from is not None):
                        # The refill: hold the cut until the running step
                        # is about to end. A record's arrival wakes this
                        # thread and changes nothing until the batch is
                        # full; a step that ended meanwhile ends the hold.
                        cut_at = self._cut_at_locked()
                        if cut_at is None or cut_at <= now:
                            break
                        if held_from is None:
                            held_from = now
                        wake_at = cut_at
                        self._cond.wait(timeout=cut_at - now)
                        continue
                    due = (now - self._oldest_enq_locked()) * 1e3 >= \
                        self.cfg.max_wait_ms
                    if slot_free and (self.cfg.eager or due):
                        break
                    if slot_free:
                        # Idle + non-eager: age toward the deadline.
                        wait_s = self.cfg.max_wait_ms / 1e3 - (
                            now - self._oldest_enq_locked())
                        self._cond.wait(timeout=max(wait_s, 1e-4))
                    else:
                        # Every slot busy and not enough rows to force a
                        # park: wait for the next slot-free notify.
                        self._cond.wait()
                # When the rule meant to cut (a timed wait that ran out
                # meant its target, not the moment this thread got to
                # run); None for a cut that parks on the ring.
                meant = None
                if slot_free:
                    meant = now if wake_at is None else min(now, wake_at)
                hold_ms = 0.0 if held_from is None else (now - held_from) * 1e3
                items = self._form_locked()
                self._inflight += 1
            self._dispatch(items, meant, hold_ms)

    def _cut_at_locked(self) -> Optional[float]:
        """When to cut so that the batch reaches the device as the work in
        flight ends (the module docstring's *expected end − lead*); None
        where a reading is missing, and the cut is then at once."""
        if not self._flying:
            return None
        engine = self._engine_ref()
        steps = getattr(engine, "step_ms", None) or {}
        rows = min(self._pending_rows, self._rows_to_take_locked())
        leads = self._leads.get(
            getattr(engine, "pad_batch", self.cfg.bucket_for)(rows))
        if not leads:
            return None
        end = self._began
        for handle, _ in self._flying:
            step = steps.get(handle.padded)
            if step is None:
                return None
            end += step / 1e3
        return end - max(leads) / 1e3

    # ---- batch formation (EDF + weighted round-robin + starvation bound) -----

    def _lane_weight(self, key: tuple) -> int:
        if self.qos is None:
            return 1
        # Higher-priority lanes draw proportionally more rows per pass:
        # weight = n_lanes - lane_index (highest lane = n, lowest = 1).
        return len(self.qos.lanes) - self.qos.lane_index(key[1])

    def _rows_to_take_locked(self) -> int:
        """The row limit of the next batch: ``max_batch``, or the largest
        FULL bucket under the pending rows where cutting there serves them
        sooner than padding them up to the next bucket. Which it is
        depends on how the engine's step time grows with the bucket, and
        the engine measures that (``step_ms``: the least step seen of each
        bucket that traffic has used): where a step
        costs about the same per padded row (a large model), 9 rows padded
        to 32 cost four 8-row steps and serve one, and the long step
        gathers the next over-full batch; where a step costs the same
        whatever its bucket (a launch-bound model), padding is free and
        two steps are twice one. Where either bucket is unmeasured the
        batch pads, which is how a bucket gets its first reading."""
        max_rows = max(1, self.cfg.max_batch)
        pending = self._pending_rows
        if pending >= max_rows:
            return max_rows
        # a copy: the engine's fetch thread writes the original
        steps = dict(getattr(self._engine_ref(), "step_ms", None) or ())
        if not steps:
            return max_rows
        lo = max((b for b in steps if b <= pending), default=None)
        hi = min((b for b in steps if b >= pending), default=None)
        if lo is None or hi is None or lo == hi:
            return max_rows
        if steps[lo] + _drain_ms(pending - lo, steps) <= steps[hi]:
            # no row finishes later than under the padded step, and the
            # first ``lo`` finish sooner
            return lo
        return max_rows

    def _form_locked(self) -> List[Submission]:
        """Take up to ``max_batch`` rows across keys (fewer where
        :meth:`_rows_to_take_locked` cuts at a full bucket). Key order: starved
        keys first (passed over >= starvation_rounds formations, most
        starved first), then earliest head-of-line deadline — EDF across
        tenants and lanes, so a fresh high-priority record preempts
        queued best-effort ones.
        Within the order, rows are taken weighted-round-robin so one
        flooding key cannot monopolize a batch while others wait."""
        max_rows = self._rows_to_take_locked()
        rounds = max(1, int(getattr(self.cfg, "starvation_rounds", 4)))
        keys = [k for k, q in self._queues.items() if q]
        starved = sorted(
            (k for k in keys if self._skipped.get(k, 0) >= rounds),
            key=lambda k: -self._skipped.get(k, 0))
        rest = sorted((k for k in keys if k not in starved),
                      key=lambda k: self._queues[k][0].deadline)
        order = starved + rest
        for k in starved:
            self.fair_starved[k] = self.fair_starved.get(k, 0) + 1
            if self._metrics is not None and self.qos is not None:
                self._metrics.counter(
                    "qos", f"fair_starved_{k[0]}_{k[1]}").inc()
        items: List[Submission] = []
        size = 0
        capped = False
        while not capped:
            progressed = False
            for k in order:
                q = self._queues[k]
                for _ in range(self._lane_weight(k)):
                    if not q:
                        break
                    n = q[0].rows
                    if items and size + n > max_rows:
                        # Leftovers stay pending (an oversized single
                        # record still ships alone — the engine pads per
                        # shape rather than crash).
                        capped = True
                        break
                    items.append(q.popleft())
                    size += n
                    progressed = True
                    if size >= max_rows:
                        capped = True
                        break
                if capped:
                    break
            if not progressed:
                break
        self._pending_rows -= size
        contributed: Dict[tuple, int] = {}
        for it in items:
            k = self._key(it.tenant, it.lane)
            contributed[k] = contributed.get(k, 0) + it.rows
        for k, n in contributed.items():
            self._skipped[k] = 0
            self.fair_rows[k] = self.fair_rows.get(k, 0) + n
            if self._metrics is not None and self.qos is not None:
                self._metrics.counter(
                    "qos", f"fair_rows_{k[0]}_{k[1]}").inc(n)
        for k in keys:
            if k not in contributed and self._queues.get(k):
                self._skipped[k] = self._skipped.get(k, 0) + 1
        if self._pending_rows == 0:
            self._force = False
        return items

    # ---- device round trip ---------------------------------------------------

    def _dispatch(self, items: List[Submission], meant: Optional[float],
                  hold_ms: float) -> None:
        """Runs on the dispatcher thread. ``engine.dispatch`` may park on
        the pipeline ring — bounded, and exactly the backpressure the
        split-phase engine defines. Every path (success, engine failure,
        evicted engine) funnels into :meth:`_finish`, which owns the
        single slot decrement. ``meant`` is when the rule meant to cut
        (None: the cut parks on the ring, and says nothing of the lead),
        ``hold_ms`` how long the cut was held for the running step."""
        t0 = time.perf_counter()
        if self._m:
            self._m["cut_hold"].observe(hold_ms)
            for it in items:
                self._m["batch_wait"].observe((t0 - it.enq) * 1e3)
        try:
            engine = self._engine_ref()
            if engine is None:
                raise RuntimeError(
                    f"engine {self.engine_name!r} was evicted with rows "
                    "queued")
            dispatch = getattr(engine, "dispatch", None)
            if dispatch is None:
                # predict-only engines (plain test doubles): serialized.
                parts = [it.data for it in items]
                x = parts[0] if len(parts) == 1 else np.concatenate(parts)
                out = engine.predict(x)
                self._finish(items, out, None, None, t0,
                             time.perf_counter())
                return
            parts = [it.data for it in items]
            if self._logs_steps:
                # the step log's clock is ``time.time()``: this row's
                # offset, read at its cut
                wall = time.time() - time.perf_counter()
                handle = dispatch(parts, queued={
                    "t_first_enq": min(it.enq for it in items) + wall,
                    "t_cut": t0 + wall,
                    "sources": len({it.source for it in items})})
            else:
                handle = dispatch(parts)
        except BaseException as e:  # noqa: BLE001 - fail ONLY this batch
            self._finish(items, None, e, None, t0, time.perf_counter())
            return
        t1 = time.perf_counter()
        with self._cond:
            # Nothing ahead of it on the device: its step begins now, and
            # a cut that was held for the step before it came too late.
            idle = not self._flying
            late = idle and hold_ms > 0.0
            if idle:
                self._began = t1
            self._flying.append((handle, t1))
            if meant is not None:
                self._leads.setdefault(
                    handle.padded, deque(maxlen=_LEADS)).append(
                        (t1 - meant) * 1e3)
        if self._m:
            # Slot wait: time parked on the engine ring.
            self._m["disp_wait"].observe((t1 - t0) * 1e3)
            if late:
                self._m["cuts_late"].inc()
        handle.future.add_done_callback(
            lambda f, its=items, h=handle, a=t0, b=t1:
            self._on_done(its, f, h, a, b))

    def _on_done(self, items: List[Submission], fut: Future, handle,
                 t_form: float, t_disp: float) -> None:
        """Engine fetch-thread callback: free the mirrored slot FIRST (the
        dispatcher can refill while we slice results), then resolve every
        member future."""
        exc = fut.exception()
        out = None if exc is not None else fut.result()
        self._finish(items, out, exc, handle, t_form, time.perf_counter(),
                     t_disp)

    def _finish(self, items, out, exc, handle, t_form, t_done,
                t_disp=None) -> None:
        with self._cond:
            self._inflight -= 1
            if handle is not None:
                # The batch behind it began its step when this one's
                # result was ready (the fetch thread then copied it out),
                # or at its own launch where that came later.
                first = self._flying[0][0] is handle
                self._flying.remove((handle, t_disp))
                if first and self._flying:
                    ready = t_done - (getattr(handle, "timings", None)
                                      or {}).get("d2h_ms", 0.0) / 1e3
                    self._began = max(ready, self._flying[0][1])
            self._cond.notify_all()
        rows = sum(it.rows for it in items)
        t_disp = t_disp if t_disp is not None else t_form
        row = getattr(handle, "step", None)
        for it in items:
            it.step = row
        if exc is not None:
            # Exactly-once per source: every member record fails with the
            # batch's exception and each source replays ITS OWN tuples.
            for it in items:
                it.future.set_exception(exc)
            self._notify(items)
            return
        padded = rows
        if handle is not None:
            padded = int(getattr(handle, "padded", rows) or rows)
        fill = rows / max(padded, 1)
        sources = {it.source for it in items}
        self.batches += 1
        self.rows_dispatched += rows
        self._fills.append(fill)
        self.last_batch = {
            "rows": rows, "padded": padded, "fill": round(fill, 4),
            "records": len(items), "sources": sorted(sources)}
        batch_span = None
        if self._tracer is not None and self._tracer.active:
            batch_span = self._trace(items, t_disp, t_done, handle, fill,
                                     len(sources))
        if batch_span is not None:
            for it in items:
                it.batch_span = batch_span
        if self._m:
            self._m["batch_size"].observe(rows)
            self._m["batch_fill"].observe(fill)
            self._m["device_ms"].observe((t_done - t_disp) * 1e3)
            self._m["infer"].inc(rows)
            self._m["coalesced"].inc(len(sources))
            # Device steps by padded bucket: with batch_size's count, the
            # share of steps each program of the ladder ran.
            self._metrics.counter(self._cid, f"steps_bucket_{padded}").inc()
            timings = getattr(handle, "timings", None) if handle else None
            if timings:
                for key, _ in DEVICE_SUBSTAGES:
                    if key in timings:
                        self._m["substage"][key].observe(timings[key])
            aux = getattr(handle, "aux", None) if handle else None
            if aux:
                self._observe_aux(aux)
        if self._flight is not None:
            self._flight.event(
                "batch_formed", throttle_s=1.0,
                component=self._cid or "continuous",
                size=rows, records=len(items),
                fill=round(fill, 3), sources=len(sources),
                device_ms=round((t_done - t_disp) * 1e3, 3))
        ofs = 0
        for it in items:
            n = it.rows
            it.future.set_result(out[ofs:ofs + n])
            ofs += n
        if row is not None:
            row["t_resolved"] = time.time()
        self._notify(items)

    def _observe_aux(self, aux: dict) -> None:
        """What the model counted on the device during this step (its
        ``new_state["aux"]``, fetched with the predictions), into the
        registry, by the model's own reader (``ModelDef.observe_aux``): what
        the counts are called and mean is the business of the op that
        counts them."""
        model = getattr(self._engine_ref(), "model", None)
        reader = getattr(model, "observe_aux", None)
        if reader is not None:
            reader(self._metrics, self._cid, aux)

    @staticmethod
    def _notify(items: List[Submission]) -> None:
        """Tell each source which of its records rode this batch together
        (``Submission.notify``), once a source, in batch order."""
        groups: Dict[int, List[Submission]] = {}
        for it in items:
            if it.notify is not None:
                groups.setdefault(id(it.notify), []).append(it)
        for members in groups.values():
            try:
                members[0].notify(members)
            except Exception:  # one source's callback must not cost another's
                logger.exception("continuous batch notify failed")

    def _trace(self, items, t0, t1, handle, fill, n_sources):
        """Span bookkeeping for one device round trip: a ``queue_wait``
        span per SAMPLED record (queue entry -> device start) and ONE
        shared device span (``span_name``), same span id in every
        participating trace and linked to all member record spans, so
        the fan-in of N records into one batch is first-class in the
        trace. Returns the shared span's id (None when no member record
        is sampled) for escalation links."""
        tracer = self._tracer
        cid = self._cid or "continuous"
        traced = []
        for it in items:
            ctx = self._trace_of(it.payload) if self._trace_of else None
            if ctx is not None:
                # Escalated records link back to the span of the tier
                # that escalated them (link_of), chaining the journey.
                back = self._link_of(it.payload) if self._link_of else None
                traced.append((it, ctx, tracer.record(
                    ctx, "queue_wait", cid, it.enq or t0, t0,
                    links=(back,) if back else ())))
        if not traced:
            return None
        batch_span = tracer.new_span_id()
        links = tuple(qid for _, _, qid in traced)
        attrs = {"batch_size": sum(it.rows for it in items),
                 "records": len(items), "fill": round(fill, 3),
                 "sources": n_sources}
        row = getattr(handle, "step", None)
        if row is not None:  # the step log's row of this batch
            attrs["step"] = row["step"]
        timings = getattr(handle, "timings", None) if handle else None
        if timings:
            for key, _ in DEVICE_SUBSTAGES:
                if key in timings:
                    attrs[key] = round(timings[key], 3)
        for _, ctx, qid in traced:
            tracer.record(ctx, self._span_name, cid, t0, t1,
                          span_id=batch_span, parent_id=qid,
                          links=links, attrs=attrs)
        return batch_span

    # ---- introspection -------------------------------------------------------

    def fill_median(self) -> Optional[float]:
        if not self._fills:
            return None
        return float(np.median(list(self._fills)))

    def stats(self) -> dict:
        """Fairness + fill summary for the qos UI route."""
        with self._cond:
            pending = {f"{k[0]}:{k[1]}": sum(s.rows for s in q)
                       for k, q in self._queues.items() if q}
            # Queue-age occupancy signal for the observatory: how long the
            # oldest queued record has been waiting (0 when idle).
            oldest_ms = 0.0
            if any(q for q in self._queues.values()):
                oldest_ms = max(
                    0.0,
                    (time.perf_counter() - self._oldest_enq_locked()) * 1e3)
            leads = {b: round(max(d), 3)
                     for b, d in sorted(self._leads.items()) if d}
        med = self.fill_median()
        return {
            "engine": self.engine_name,
            "capacity": self.capacity,
            "inflight": self._inflight,
            "pending_rows": self._pending_rows,
            "oldest_ms": round(oldest_ms, 3),
            "pending_by_key": pending,
            "batches": self.batches,
            "rows": self.rows_dispatched,
            "batch_fill_p50": None if med is None else round(med, 4),
            "lead_ms": leads,
            "fair_rows": {f"{k[0]}:{k[1]}": v
                          for k, v in self.fair_rows.items()},
            "fair_starved": {f"{k[0]}:{k[1]}": v
                             for k, v in self.fair_starved.items()},
            "last_batch": self.last_batch,
        }


# ---- one queue per live engine -------------------------------------------------

# The queue lives ON the engine it serves (attribute ``_continuous_queue``),
# so replicas, the serve path, and cascade tiers sharing an engine (via the
# shared_engine cache) get the SAME queue — that identity is what makes them
# co-batch. The queue holds its engine weakly, so the cache's orphan-refcount
# eviction keeps working, and the engine's finalizer closes the queue. A
# finalizer runs wherever the collector does — on any thread, inside any
# allocation — so no lock guards this: ``close`` takes only the queue's own
# re-entrant condition, and each step on ``_LIVE`` (weak references to the
# queues, for ``registry_stats``) is one atomic set operation.
_LIVE: set = set()


def continuous_for(engine, cfg: BatchConfig,
                   qos: Optional[QosConfig] = None) -> ContinuousBatcher:
    """The engine's continuous queue, created on first use. All sources
    sharing an engine share one formation policy, like they share its
    buckets: the latest caller's (:meth:`ContinuousBatcher.configure`) —
    the engine cache outlives a topology, and the next one's deadline
    must not be the last one's."""
    # no batch over what the engine's model lets a step hold
    cfg = cfg.clipped(getattr(engine, "max_rows", None))
    cb = vars(engine).get("_continuous_queue")
    if cb is None:
        new = ContinuousBatcher(engine, cfg, qos)
        cb = vars(engine).setdefault("_continuous_queue", new)
        if cb is new:  # this caller won the race to create it
            _LIVE.add(weakref.ref(new, _LIVE.discard))
            weakref.finalize(engine, new.close)
            return cb
    cb.configure(cfg, qos)
    return cb


def registry_stats() -> List[dict]:
    """Stats for every live continuous queue (the qos UI route)."""
    cbs = [ref() for ref in tuple(_LIVE)]
    return [cb.stats() for cb in cbs
            if cb is not None and cb._engine_ref() is not None]


def _reset_registry() -> None:
    """Test hook: close every queue and detach it from its engine."""
    for ref in tuple(_LIVE):
        cb = ref()
        engine = None if cb is None else cb._engine_ref()
        if engine is not None:
            del engine._continuous_queue
            cb.close()
    _LIVE.clear()
