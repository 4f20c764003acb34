"""Executors: one asyncio task per operator instance.

The runtime equivalent of Storm's executor threads (SURVEY.md §1 layer 1).
Each bolt instance owns a bounded inbox queue — the backpressure point that
replaces Storm's Disruptor queues — and each spout instance runs a pull loop
gated on ``max_spout_pending`` (Storm's ``topology.max.spout.pending``).
Single ownership per instance: no shared mutable state between executors,
which is what makes the reference's mutable-POJO-reuse hazard
(InferenceBolt.java:34-35, SURVEY.md §5.2) structurally impossible here.
"""

from __future__ import annotations

import asyncio
import copy
import logging
import time
import traceback
from typing import Any, Optional

from storm_tpu.obs.profile import setup_span
from storm_tpu.runtime.base import Bolt, OutputCollector, Spout, TopologyContext
from storm_tpu.runtime.tuples import TickTuple, Tuple, is_tick

log = logging.getLogger("storm_tpu.executor")

_STOP = object()  # inbox sentinel
_CKPT = object()  # checkpoint sentinel: snapshot between tuples


class BoltExecutor:
    def __init__(
        self,
        runtime: Any,
        component_id: str,
        task_index: int,
        bolt: Bolt,
        inbox_capacity: int,
        tick_interval_s: float = 0.0,
        inbox: Optional[asyncio.Queue] = None,
    ) -> None:
        self.rt = runtime
        self.component_id = component_id
        self.task_index = task_index
        self.bolt = bolt
        # A supervisor restart hands over the previous executor's inbox so
        # upstream routing tables stay valid across the swap.
        self.inbox: asyncio.Queue = inbox if inbox is not None else asyncio.Queue(
            maxsize=inbox_capacity
        )
        self.tick_interval_s = tick_interval_s
        # Per-executor stats (Storm UI's per-executor table): plain ints
        # updated on the owning loop, read by the stats route.
        self.n_executed = 0
        self.exec_ms_total = 0.0
        self.n_errors = 0
        # Busy/idle wall-time split (Storm UI's "capacity" input, consumed
        # by obs/capacity.CapacityTracker as windowed deltas): seconds in
        # execute/tick vs blocked on the inbox vs the final drain flush.
        # ``clock`` is injectable so tests drive the split without sleeps;
        # set it before start() — _run binds it locally.
        self.clock = time.perf_counter
        self.busy_s = 0.0
        self.wait_s = 0.0
        self.flush_s = 0.0
        self._task: Optional[asyncio.Task] = None
        self._tick_task: Optional[asyncio.Task] = None
        self._ckpt_task: Optional[asyncio.Task] = None
        self._stateful = False
        self.collector = OutputCollector(runtime, component_id, task_index)
        self.collector.set_output_fields(bolt.declare_output_fields())

    def start(self) -> None:
        ctx = TopologyContext(
            self.component_id,
            self.task_index,
            self.rt.parallelism_of(self.component_id),
            self.rt.config,
            self.rt.metrics,
            tracer=getattr(self.rt, "tracer", None),
            flight=getattr(self.rt, "flight", None),
        )
        with setup_span("component.prepare", component=self.component_id,
                        task=self.task_index):
            self.bolt.prepare(ctx, self.collector)
        self._init_state()
        self._task = asyncio.create_task(
            self._run(), name=f"{self.component_id}[{self.task_index}]"
        )
        if self.tick_interval_s > 0:
            self._tick_task = asyncio.create_task(
                self._ticker(self.tick_interval_s))
        ckpt = self.rt.config.topology.checkpoint_interval_s
        if self._stateful and ckpt > 0:
            self._ckpt_task = asyncio.create_task(
                self._ticker(ckpt, payload=_CKPT)
            )

    def _init_state(self) -> None:
        """Restore + hand state to a StatefulBolt (Storm's prepare ->
        initState ordering): a replacement executor (supervisor sweep,
        rebalance, recovered worker) resumes from the last checkpoint."""
        from storm_tpu.runtime.state import KeyValueState, StatefulBolt

        self._stateful = isinstance(self.bolt, StatefulBolt)
        self._state_version = 0
        if not self._stateful:
            return
        got = self.rt.state_backend.load(self.component_id, self.task_index)
        if got is not None:
            self._state_version, snap = got
            state = KeyValueState(snap)
        else:
            state = KeyValueState()
        self._state = state
        self.bolt.init_state(state)
        # Synchronous-checkpoint hook: a bolt that commits progress (the
        # decode bolt's watermark) persists state BEFORE acking, so an ack
        # can never outrun the snapshot it depends on (exactly-once across
        # crashes).
        self.bolt.checkpoint_now = self._checkpoint

    def _checkpoint(self) -> None:
        if not self._state.dirty:
            return
        self.bolt.pre_checkpoint()
        self._state_version += 1
        self.rt.state_backend.save(
            self.component_id, self.task_index,
            self._state_version, self._state.snapshot(),
        )
        self._state.dirty = False
        self.rt.metrics.counter(self.component_id, "checkpoints").inc()

    async def _ticker(self, interval: float, payload: Any = None) -> None:
        while True:
            await asyncio.sleep(interval)
            # Non-blocking: a full inbox skips the tick rather than stalling.
            try:
                self.inbox.put_nowait(payload if payload is not None else TickTuple())
            except asyncio.QueueFull:
                pass

    async def _run(self) -> None:
        m = self.rt.metrics
        executed = m.counter(self.component_id, "executed")
        exec_ms = m.histogram(self.component_id, "execute_ms")
        tracer = getattr(self.rt, "tracer", None)
        clock = self.clock
        while True:
            w0 = clock()
            item = await self.inbox.get()
            self.wait_s += clock() - w0
            if item is _STOP:
                break
            if item is _CKPT:
                try:
                    self._checkpoint()
                except Exception as e:
                    self.n_errors += 1
                    self.rt.report_error(self.component_id, self.task_index, e)
                continue
            t: Tuple = item
            try:
                if is_tick(t):
                    t0 = clock()
                    try:
                        await self.bolt.tick()
                    finally:
                        self.busy_s += clock() - t0
                else:
                    executed.inc()
                    self.n_executed += 1
                    t0 = clock()
                    try:
                        await self.bolt.execute(t)
                    finally:
                        # Count time for failed executes too, or a failing
                        # bolt reports a misleadingly low average.
                        t1 = clock()
                        dt_ms = (t1 - t0) * 1e3
                        exec_ms.observe(dt_ms)
                        self.exec_ms_total += dt_ms
                        self.busy_s += t1 - t0
                        if t.trace is not None and tracer is not None:
                            tracer.record(t.trace, "execute",
                                          self.component_id, t0, t1)
            except asyncio.CancelledError:
                raise
            except Exception as e:  # fail the tuple, keep the executor alive
                self.n_errors += 1
                self.rt.report_error(self.component_id, self.task_index, e)
                if not is_tick(t):
                    self.collector.fail(t)

    async def stop(self, drain: bool) -> None:
        if self._tick_task:
            self._tick_task.cancel()
        if self._ckpt_task:
            self._ckpt_task.cancel()
        if self._task is None:
            return
        if drain:
            try:
                # Bounded: if the run loop already died with a full inbox,
                # the sentinel can never land, and an unbounded put would
                # park stop() forever — while rebalance holds the
                # cluster-wide rebalance lock.
                await asyncio.wait_for(self.inbox.put(_STOP), timeout=30.0)
            except asyncio.TimeoutError:  # pragma: no cover
                self._task.cancel()
            try:
                await asyncio.wait_for(self._task, timeout=30.0)
            except asyncio.TimeoutError:  # pragma: no cover
                self._task.cancel()
            f0 = self.clock()
            try:
                # Settle deferred work (pending batches, in-flight sends)
                # before cleanup closes resources under it.
                await asyncio.wait_for(self.bolt.flush(), timeout=30.0)
            except Exception as e:
                log.warning("flush error in %s: %s", self.component_id, e)
            finally:
                self.flush_s += self.clock() - f0
            if self._stateful:
                # Final checkpoint: a graceful stop must not lose the tail
                # of state updates since the last periodic snapshot.
                try:
                    self._checkpoint()
                except Exception as e:
                    log.warning("final checkpoint of %s failed: %s",
                                self.component_id, e)
        else:
            self._task.cancel()
        try:
            await self._task
        except (asyncio.CancelledError, Exception):
            pass
        try:
            self.bolt.cleanup()
        except Exception as e:  # pragma: no cover
            log.warning("cleanup error in %s: %s", self.component_id, e)


class SpoutExecutor:
    def __init__(
        self,
        runtime: Any,
        component_id: str,
        task_index: int,
        spout: Spout,
        max_pending: int,
    ) -> None:
        self.rt = runtime
        self.component_id = component_id
        self.task_index = task_index
        self.spout = spout
        self.max_pending = max_pending
        self.inflight = 0
        # Per-executor stats (see BoltExecutor)
        self.n_acked = 0
        self.n_failed = 0
        self.n_errors = 0
        # Busy/idle split (see BoltExecutor): emitting polls are busy;
        # pending-slot waits, idle backoff, and empty polls are wait.
        # flush_s exists only for surface parity with bolts.
        self.clock = time.perf_counter
        self.busy_s = 0.0
        self.wait_s = 0.0
        self.flush_s = 0.0
        self._slot = asyncio.Event()
        self._slot.set()
        self._task: Optional[asyncio.Task] = None
        self._active = True
        self.collector = OutputCollector(runtime, component_id, task_index)
        self.collector.set_output_fields(spout.declare_output_fields())

    def on_done(self, msg_id: Any, ok: bool, root_ts: float) -> None:
        """Ledger callback: tuple tree for msg_id completed or failed."""
        self.inflight -= 1
        if self.inflight < self.max_pending:
            self._slot.set()
        m = self.rt.metrics
        if ok:
            m.counter(self.component_id, "tree_acked").inc()
            self.n_acked += 1
            self.spout.ack(msg_id)
        else:
            m.counter(self.component_id, "tree_failed").inc()
            self.n_failed += 1
            self.spout.fail(msg_id)

    def track(self) -> None:
        """Called by the runtime when this spout opens a ledger entry."""
        self.inflight += 1
        if self.inflight >= self.max_pending:
            self._slot.clear()

    def start(self) -> None:
        ctx = TopologyContext(
            self.component_id,
            self.task_index,
            self.rt.parallelism_of(self.component_id),
            self.rt.config,
            self.rt.metrics,
            tracer=getattr(self.rt, "tracer", None),
            flight=getattr(self.rt, "flight", None),
        )
        with setup_span("component.prepare", component=self.component_id,
                        task=self.task_index):
            self.spout.open(ctx, self.collector)
        self._task = asyncio.create_task(
            self._run(), name=f"{self.component_id}[{self.task_index}]"
        )

    async def _run(self) -> None:
        idle_backoff = 0.001
        clock = self.clock
        while True:
            w0 = clock()
            await self._slot.wait()
            if not self._active:
                await asyncio.sleep(0.05)
                self.wait_s += clock() - w0
                continue
            self.wait_s += clock() - w0
            b0 = clock()
            try:
                emitted = await self.spout.next_tuple()
            except asyncio.CancelledError:
                raise
            except Exception as e:
                self.n_errors += 1
                self.rt.report_error(self.component_id, self.task_index, e)
                emitted = False
            finally:
                dt = clock() - b0
            if not emitted:
                # An empty poll is idle time, not work: a drained spout
                # keeps calling next_tuple yet must read capacity ~0.
                self.wait_s += dt
                s0 = clock()
                await asyncio.sleep(idle_backoff)
                self.wait_s += clock() - s0
                idle_backoff = min(idle_backoff * 2, 0.05)
            else:
                self.busy_s += dt
                idle_backoff = 0.001

    async def stop(self) -> None:
        if self._task is None:
            return
        self._task.cancel()
        try:
            await self._task
        except (asyncio.CancelledError, Exception):
            pass
        try:
            self.spout.close()
        except Exception as e:  # pragma: no cover
            log.warning("close error in %s: %s", self.component_id, e)


def clone_component(obj: Any) -> Any:
    """Per-task instance from the prototype the user handed the builder.

    Storm gets per-executor instances by serialize/deserialize of the
    submitted bolt; we deep-copy. Components may define ``clone()`` to
    customize (e.g., to share a read-only model artifact)."""
    if hasattr(obj, "clone"):
        return obj.clone()
    return copy.deepcopy(obj)
