"""The in-process cluster: routing, lifecycle, rebalance, at-least-once sweep.

Fills two roles from the reference stack (SURVEY.md §1):

- Storm's cluster runtime (layer 1): executor scheduling, tuple transport,
  ack/replay, supervision — here an asyncio runtime with bounded queues;
- the ``LocalCluster`` test harness the reference never used (SURVEY.md §4
  notes it tested only by running on a real cluster for an hour) — here the
  *primary* way topologies run in tests.

Also provides what the reference lacked: runtime ``rebalance`` (elastic
parallelism — the reference's scaling knob is a compile-time constant,
MainTopology.java:25-28), graceful drain instead of the fixed
sleep-1h-then-hard-kill driver (MainTopology.java:71-77).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple as Tup

from storm_tpu.config import Config
from storm_tpu.obs.profile import (
    profile_store,
    setup_line,
    setup_span,
    setup_summary,
)
from storm_tpu.runtime.acker import AckLedger
from storm_tpu.runtime.executor import BoltExecutor, SpoutExecutor, clone_component
from storm_tpu.runtime.metrics import MetricsRegistry
from storm_tpu.runtime.topology import Topology

log = logging.getLogger("storm_tpu.cluster")


class TargetGroup:
    """Mutable set of inboxes for one downstream component (mutable so
    rebalance can swap instances under live producers)."""

    def __init__(self, component_id: str) -> None:
        self.component_id = component_id
        self.inboxes: List[asyncio.Queue] = []


class Router:
    def __init__(self) -> None:
        self._subs: Dict[Tup[str, str], List[Tup[Any, TargetGroup]]] = {}

    def add(self, source: str, stream: str, grouping: Any, group: TargetGroup) -> None:
        grouping.prepare(len(group.inboxes))
        self._subs.setdefault((source, stream), []).append((grouping, group))

    def subscriptions(self, source: str, stream: str) -> List[Tup[Any, TargetGroup]]:
        return self._subs.get((source, stream), [])

    def reprepare(self, component_id: str) -> None:
        for subs in self._subs.values():
            for grouping, group in subs:
                if group.component_id == component_id:
                    grouping.prepare(len(group.inboxes))

    def edges(self):
        """(source, stream, TargetGroup) rows — the observatory's
        read-only view of the routing table (obs/capacity.EdgeLagTracker
        derives per-edge depth/growth watermarks from the target
        inboxes). One row per subscription; consumers dedupe by
        (source, stream, dst) if two groupings share an edge."""
        for (source, stream), subs in list(self._subs.items()):
            for _grouping, group in subs:
                yield source, stream, group


class TopologyRuntime:
    """Everything live for one submitted topology."""

    def __init__(self, name: str, topology: Topology, config: Config) -> None:
        self.name = name
        self.topology = topology
        self.config = config
        self.metrics = MetricsRegistry()
        from storm_tpu.runtime.state import make_backend

        self.state_backend = make_backend(config.topology.state_dir)
        from storm_tpu.runtime.tracing import FlightRecorder, Tracer

        tr = getattr(config, "tracing", None)
        self.tracer = Tracer(
            sample_rate=getattr(tr, "sample_rate", 0.0),
            store_capacity=getattr(tr, "store_capacity", 256),
        )
        self.flight = FlightRecorder(
            path=getattr(tr, "flight_path", ""),
            capacity=getattr(tr, "flight_capacity", 512),
            max_bytes=getattr(tr, "flight_max_bytes", 4 * 1024 * 1024),
            max_files=getattr(tr, "flight_max_files", 3),
        )
        self.ledger = AckLedger(timeout_s=config.topology.message_timeout_s)
        self.router = Router()
        self.groups: Dict[str, TargetGroup] = {}
        self.bolt_execs: Dict[str, List[BoltExecutor]] = {}
        self.spout_execs: Dict[str, List[SpoutExecutor]] = {}
        self.errors: List[Tup[str, int, BaseException]] = []
        self._sweeper: Optional[asyncio.Task] = None
        self._error_cb: Optional[Callable] = None
        self._consumer_tasks: List[asyncio.Task] = []
        self._consumers: List[Any] = []
        # rebalance grows suspend at the prewarm await; without the lock,
        # a concurrent rebalance for the same component would observe the
        # same executor count and over-grow / collide on task_index.
        self._rebalance_lock = asyncio.Lock()

    # ---- wiring --------------------------------------------------------------

    def _make_executors(self) -> None:
        tcfg = self.config.topology
        for spec in self.topology.specs.values():
            group = TargetGroup(spec.component_id)
            self.groups[spec.component_id] = group
            if spec.is_spout:
                execs = [
                    SpoutExecutor(
                        self,
                        spec.component_id,
                        i,
                        clone_component(spec.obj),
                        tcfg.max_spout_pending,
                    )
                    for i in range(spec.parallelism)
                ]
                self.spout_execs[spec.component_id] = execs
            else:
                execs = [
                    BoltExecutor(
                        self,
                        spec.component_id,
                        i,
                        clone_component(spec.obj),
                        tcfg.inbox_capacity,
                        tcfg.tick_interval_s,
                    )
                    for i in range(spec.parallelism)
                ]
                self.bolt_execs[spec.component_id] = execs
                group.inboxes = [e.inbox for e in execs]
        for spec in self.topology.specs.values():
            for sub in spec.inputs:
                self.router.add(
                    sub.source, sub.stream, sub.grouping, self.groups[spec.component_id]
                )

    async def start(self) -> None:
        self._make_executors()
        # Bolts first (downstream ready before data flows), then spouts.
        for execs in self.bolt_execs.values():
            for e in execs:
                e.start()
        for execs in self.spout_execs.values():
            for e in execs:
                e.start()
        self._sweeper = asyncio.create_task(self._sweep_loop())

    async def _sweep_loop(self) -> None:
        interval = max(0.25, min(1.0, self.config.topology.message_timeout_s / 4))
        prev_counts: Dict[str, int] = {}
        prev_t = time.monotonic()
        while True:
            await asyncio.sleep(interval)
            n = self.ledger.sweep()
            if n:
                log.warning("%s: %d tuple trees timed out", self.name, n)
                self.flight.event("tree_timeout", topology=self.name, trees=n)
            self._supervise()
            # Backpressure visibility: queued tuples per bolt component
            # (Storm UI's capacity/queue columns; the autoscaler's other
            # signal besides latency).
            for cid, execs in self.bolt_execs.items():
                self.metrics.gauge(cid, "inbox_depth").set(
                    sum(e.inbox.qsize() for e in execs)
                )
            # Throughput visibility (Storm UI's rate columns): counter
            # deltas per sweep -> executed/sec for bolts, acked trees/sec
            # for spouts.
            now = time.monotonic()
            dt = max(1e-6, now - prev_t)
            prev_t = now
            def rate_of(cid: str, counter_name: str) -> float:
                cur = self.metrics.counter(cid, counter_name).value
                rate = (cur - prev_counts.get(cid, cur)) / dt
                prev_counts[cid] = cur
                return round(rate, 3)

            # Gauge names spelled literally at the call site so the
            # metric-name registry (OBS001) picks them up.
            for cid in self.bolt_execs:
                self.metrics.gauge(cid, "execute_rate").set(
                    rate_of(cid, "executed"))
            for cid in self.spout_execs:
                self.metrics.gauge(cid, "ack_rate").set(
                    rate_of(cid, "tree_acked"))

    def _supervise(self) -> None:
        """Storm-supervisor analog: an executor task that died (bug in
        framework code — user exceptions are caught in the loop) is replaced
        with a fresh component clone on the same inbox."""
        tcfg = self.config.topology

        def replace(cid, i, execs, old, make_fresh, dispose):
            exc = old._task.exception()
            log.error("executor %s[%d] died (%r); restarting", cid, i, exc)
            self.metrics.counter(cid, "executor_restarts").inc()
            self.flight.event("executor_restart", topology=self.name,
                              component=cid, task=i, error=repr(exc))
            try:
                dispose()  # release the crashed component's resources
            except Exception as ce:
                log.warning("cleanup of dead %s[%d] failed: %s", cid, i, ce)
            fresh = make_fresh(clone_component(self.topology.specs[cid].obj))
            execs[i] = fresh
            fresh.start()
            return fresh

        def died(e) -> bool:
            return e._task is not None and e._task.done() and not e._task.cancelled()

        for cid, execs in self.bolt_execs.items():
            for i, e in enumerate(execs):
                if died(e):
                    if e._tick_task is not None:
                        e._tick_task.cancel()  # or the old ticker keeps feeding the inbox
                    if e._ckpt_task is not None:
                        e._ckpt_task.cancel()  # same for the checkpoint ticker

                    replace(
                        cid, i, execs, e,
                        lambda proto, e=e, cid=cid, i=i: BoltExecutor(
                            self, cid, i, proto,
                            tcfg.inbox_capacity, tcfg.tick_interval_s, inbox=e.inbox,
                        ),
                        e.bolt.cleanup,
                    )
        for cid, execs in self.spout_execs.items():
            for i, e in enumerate(execs):
                if died(e):
                    fresh = replace(
                        cid, i, execs, e,
                        lambda proto, cid=cid, i=i: SpoutExecutor(
                            self, cid, i, proto, tcfg.max_spout_pending
                        ),
                        e.spout.close,
                    )
                    # Preserve deactivation: a drain in progress must not be
                    # resurrected into an emitting spout.
                    fresh._active = e._active

    def health(self) -> Dict[str, Any]:
        """Liveness snapshot: executor task states + in-flight counts."""
        comps: Dict[str, Any] = {}
        for cid, execs in {**self.bolt_execs, **self.spout_execs}.items():
            comps[cid] = {
                "tasks": len(execs),
                "alive": sum(
                    1 for e in execs if e._task is not None and not e._task.done()
                ),
            }
        return {
            "topology": self.name,
            "inflight_trees": self.ledger.inflight,
            "components": comps,
        }

    # ---- runtime services (used by collectors/executors) ---------------------

    def parallelism_of(self, component_id: str) -> int:
        if component_id in self.bolt_execs:
            return len(self.bolt_execs[component_id])
        if component_id in self.spout_execs:
            return len(self.spout_execs[component_id])
        return self.topology.specs[component_id].parallelism

    def spout_done_cb(self, component_id: str, task_index: int):
        ex = self.spout_execs[component_id][task_index]
        ex.track()
        return ex.on_done

    def spout_done(self, component_id: str, task_index: int, msg_id, ok: bool, ts: float) -> None:
        """Completion for roots that never entered the ledger (emit with no
        subscribers). Keeps tree_acked/tree_failed accounting consistent with
        the ledger path without touching the executor's inflight gate."""
        ex = self.spout_execs[component_id][task_index]
        self.metrics.counter(component_id, "tree_acked" if ok else "tree_failed").inc()
        (ex.spout.ack if ok else ex.spout.fail)(msg_id)

    def report_error(self, component_id: str, task_index: int, err: BaseException) -> None:
        self.errors.append((component_id, task_index, err))
        self.metrics.counter(component_id, "errors").inc()
        log.error(
            "error in %s[%d]: %r", component_id, task_index, err, exc_info=err
        )
        if self._error_cb is not None:
            self._error_cb(component_id, task_index, err)

    # ---- lifecycle -----------------------------------------------------------

    async def deactivate(self) -> None:
        """Stop spouts pulling; in-flight tuples keep flowing (Storm's
        'deactivate' — first phase of a graceful drain)."""
        for execs in self.spout_execs.values():
            for e in execs:
                e._active = False
                await e.spout.deactivate()

    async def activate(self) -> None:
        """Resume spouts after a deactivate (Storm's 'activate' — the other
        half of the pair; the executor loop polls ``_active``)."""
        for execs in self.spout_execs.values():
            for e in execs:
                e._active = True
                await e.spout.activate()

    async def drain(self, timeout_s: float = 30.0) -> bool:
        """Wait for all in-flight tuple trees and inboxes to empty."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            busy = self.ledger.inflight > 0 or any(
                not e.inbox.empty()
                for execs in self.bolt_execs.values()
                for e in execs
            )
            if not busy:
                return True
            await asyncio.sleep(0.01)
        return False

    # ---- metrics consumers (Storm's IMetricsConsumer, SURVEY.md §5.5) -------

    def add_metrics_consumer(self, consumer, interval_s: float = 10.0) -> None:
        """Publish a metrics snapshot to ``consumer.handle(topology, ts,
        snapshot)`` every ``interval_s`` seconds until the topology dies
        (Storm's ``Config.registerMetricsConsumer`` equivalent)."""
        self._consumers.append(consumer)

        async def pump() -> None:
            while True:
                await asyncio.sleep(interval_s)
                try:
                    consumer.handle(self.name, time.time(), self.metrics.snapshot())
                except Exception:
                    log.exception("metrics consumer %r failed", consumer)

        self._consumer_tasks.append(asyncio.get_running_loop().create_task(pump()))

    async def kill(self, wait_secs: float = 0.0) -> None:
        """Kill the topology. ``wait_secs`` mirrors Storm's KillOptions
        (the reference sets wait_secs=0 for a hard kill,
        MainTopology.java:74-76); >0 deactivates and drains first."""
        if wait_secs > 0:
            await self.deactivate()
            await self.drain(timeout_s=wait_secs)
        for task in self._consumer_tasks:
            task.cancel()
        for consumer in self._consumers:
            # final snapshot so short-lived topologies still record once; a
            # failing last handle() must not leak the consumer's resources
            try:
                consumer.handle(self.name, time.time(), self.metrics.snapshot())
            except Exception:
                log.exception("metrics consumer %r final handle failed", consumer)
            finally:
                try:
                    consumer.close()
                except Exception:
                    log.exception("metrics consumer %r close failed", consumer)
        self._consumer_tasks.clear()
        self._consumers.clear()
        if self._sweeper:
            self._sweeper.cancel()
        for execs in self.spout_execs.values():
            for e in execs:
                await e.stop()
        # Drain-stop bolts so queued tuples finish when killing gracefully.
        for execs in self.bolt_execs.values():
            for e in execs:
                await e.stop(drain=wait_secs > 0)
        self.flight.close()

    # ---- elasticity ----------------------------------------------------------

    async def swap_model(self, component_id: str, overrides: dict,
                         tasks: Optional[list] = None):
        """Live model swap on an inference component: apply field
        ``overrides`` (e.g. ``{"checkpoint": "/models/v2"}``) to its
        current ModelConfig and roll every instance onto the new engine
        under traffic. Returns the new config.

        ``tasks=[i, ...]`` swaps only those instances — a canary: compare
        the canary tasks' `component_stats` rows (avg_execute_ms, errors,
        and the per-task ``model`` descriptor) against the rest, then
        swap the remainder or roll the canary back. Canary swaps leave
        the prototype untouched, so rebalance-added executors keep the
        majority model."""
        import dataclasses as _dc

        execs = self.bolt_execs.get(component_id)
        if execs is None:
            raise KeyError(component_id)
        swappable = [e for e in execs if hasattr(e.bolt, "swap_model")]
        if not swappable:
            raise TypeError(f"component {component_id!r} has no model to swap")
        # Base on the PROTOTYPE config, not a live instance: after a canary,
        # instance configs diverge, and deriving from the canaried task
        # would silently promote its fields into every later swap.
        proto = self.topology.specs[component_id].obj
        base = proto.model_cfg if hasattr(proto, "model_cfg") \
            else swappable[0].bolt.model_cfg
        new_cfg = _dc.replace(base, **overrides)
        if tasks is not None:
            if not tasks:
                raise ValueError("tasks must be a non-empty list")
            chosen = [e for e in swappable if e.task_index in set(tasks)]
            missing = set(tasks) - {e.task_index for e in chosen}
            if missing:
                raise KeyError(
                    f"no swappable task(s) {sorted(missing)} in "
                    f"{component_id!r}")
            for e in chosen:
                await e.bolt.swap_model(new_cfg)
            return new_cfg
        # Update the prototype FIRST: executors cloned by a rebalance that
        # interleaves with the (slow, awaiting) engine builds below must
        # pick up the new model, not the submit-time one.
        if hasattr(proto, "model_cfg"):
            proto.model_cfg = new_cfg
        # First call builds+warms the engine (shared per process); the rest
        # just switch references. Re-scan until stable: a rebalance during
        # an await may have added instances cloned before the proto update.
        while True:
            pending = [
                e for e in self.bolt_execs.get(component_id, ())
                if hasattr(e.bolt, "swap_model")
                and e.bolt.model_cfg is not new_cfg
            ]
            if not pending:
                return new_cfg
            for e in pending:
                await e.bolt.swap_model(new_cfg)

    def component_stats(self, component_id: str) -> list:
        """Per-executor stats for one component (Storm UI's executor
        table): task index, executed/avg-latency for bolts, in-flight and
        acked/failed trees for spouts."""
        if component_id in self.bolt_execs:
            def model_of(e):
                cfg = getattr(e.bolt, "model_cfg", None)
                if cfg is None:
                    return None
                # Compact version descriptor for canary comparison.
                parts = [cfg.name]
                if cfg.checkpoint:
                    parts.append(cfg.checkpoint)
                if cfg.seed:
                    parts.append(f"seed={cfg.seed}")
                if getattr(cfg, "weights", "float") != "float":
                    parts.append(cfg.weights)
                return ":".join(parts)

            return [
                {
                    "task": e.task_index,
                    "executed": e.n_executed,
                    "avg_execute_ms": round(
                        e.exec_ms_total / e.n_executed, 3)
                    if e.n_executed else None,
                    "errors": e.n_errors,
                    "inbox_depth": e.inbox.qsize(),
                    **({"model": m} if (m := model_of(e)) else {}),
                }
                for e in self.bolt_execs[component_id]
            ]
        if component_id in self.spout_execs:
            return [
                {
                    "task": e.task_index,
                    "acked": e.n_acked,
                    "failed": e.n_failed,
                    "errors": e.n_errors,
                    "inflight": e.inflight,
                }
                for e in self.spout_execs[component_id]
            ]
        raise KeyError(component_id)

    async def seek(self, component_id: str, position) -> int:
        """Reposition a spout component's consumption (replay/backfill).
        Returns the number of instances repositioned."""
        execs = self.spout_execs.get(component_id)
        if execs is None:
            raise KeyError(component_id)
        seekable = [e for e in execs if hasattr(e.spout, "request_seek")]
        if not seekable:
            raise TypeError(f"component {component_id!r} is not seekable")
        for e in seekable:
            e.spout.request_seek(position)
        return len(seekable)

    async def rebalance(self, component_id: str, parallelism: int) -> None:
        """Change a component's parallelism live — the framework op the
        reference's README frames as 'rebuild with more bolts'
        (README.md:13-14; SURVEY.md §2.4 elastic row)."""
        if parallelism < 1:
            raise ValueError("parallelism must be >= 1")
        async with self._rebalance_lock:
            await self._rebalance_locked(component_id, parallelism)

    async def _rebalance_locked(self, component_id: str,
                                parallelism: int) -> None:
        tcfg = self.config.topology
        proto = self.topology.specs[component_id].obj
        if component_id in self.bolt_execs:
            execs = self.bolt_execs[component_id]
            added: list = []
            try:
                while len(execs) < parallelism:
                    clone = clone_component(proto)
                    # Warm scale-up (VERDICT r3 weak #3): build/warm the
                    # replica's expensive state (engine compile, checkpoint
                    # load) on a worker thread BEFORE it joins the routing
                    # table — a cold prepare on the event loop would stall
                    # every executor in the process, and a cold replica
                    # fielding live traffic injects its compile time into
                    # the latency the scale-up exists to reduce.
                    prewarm = getattr(clone, "prewarm", None)
                    if prewarm is not None:
                        await asyncio.to_thread(prewarm)
                    e = BoltExecutor(
                        self,
                        component_id,
                        len(execs),
                        clone,
                        tcfg.inbox_capacity,
                        tcfg.tick_interval_s,
                    )
                    # append before start so prepare() sees the grown
                    # parallelism (parallelism_of == len(execs) — the EOS
                    # sink's parallelism-1 guard depends on it)...
                    execs.append(e)
                    added.append(e)
                    e.start()
            except BaseException:
                # ...and a prepare() raise rolls back EVERY executor this
                # call added — a half-registered, never-started executor
                # left in bolt_execs would swallow routed tuples forever.
                for e in reversed(added):
                    if e in execs:
                        execs.remove(e)
                    await e.stop(drain=False)
                raise
            removed = []
            while len(execs) > parallelism:
                removed.append(execs.pop())
            self.groups[component_id].inboxes = [e.inbox for e in execs]
            self.router.reprepare(component_id)
            for e in removed:
                await e.stop(drain=True)
        elif component_id in self.spout_execs:
            execs = self.spout_execs[component_id]
            # New tasks inherit the component's activation state: a grow
            # during a deactivate/drain must not start an emitting spout
            # (same invariant _supervise preserves on restart).
            active = all(e._active for e in execs) if execs else True
            while len(execs) < parallelism:
                e = SpoutExecutor(
                    self,
                    component_id,
                    len(execs),
                    clone_component(proto),
                    tcfg.max_spout_pending,
                )
                e._active = active
                execs.append(e)
                e.start()
            while len(execs) > parallelism:
                await execs.pop().stop()
        else:
            raise KeyError(component_id)
        self.topology.specs[component_id].parallelism = parallelism


class AsyncLocalCluster:
    """Async-native cluster API (use inside an event loop / async tests)."""

    def __init__(self) -> None:
        self._topologies: Dict[str, TopologyRuntime] = {}

    async def submit(self, name: str, config: Config, topology: Topology) -> TopologyRuntime:
        if name in self._topologies:
            raise ValueError(f"topology {name!r} already running")
        topology.validate()
        rt = TopologyRuntime(name, topology, config)
        self._topologies[name] = rt
        with setup_span("topology.submit", topology=name) as span:
            await rt.start()
        if span.span is not None:  # the set-up log is on
            log.info("%s: %s", name, setup_line(setup_summary(
                profile_store().setup(), span.span)))
        return rt

    def runtime(self, name: str) -> TopologyRuntime:
        return self._topologies[name]

    @property
    def runtimes(self) -> Dict[str, TopologyRuntime]:
        """Live topologies by name (read-only view for the UI server)."""
        return dict(self._topologies)

    async def kill(self, name: str, wait_secs: float = 0.0) -> None:
        # pop-with-default: a UI-initiated kill may race the daemon's own
        # shutdown (or a second kill request); killing twice is a no-op.
        rt = self._topologies.pop(name, None)
        if rt is not None:
            await rt.kill(wait_secs)

    async def shutdown(self) -> None:
        for name in list(self._topologies):
            await self.kill(name, wait_secs=0.0)


class LocalCluster:
    """Synchronous facade over :class:`AsyncLocalCluster`, running its own
    event loop in a background thread — the drop-in equivalent of Storm's
    ``LocalCluster`` for scripts and notebooks."""

    def __init__(self) -> None:
        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self._loop.run_forever, name="storm-tpu-cluster", daemon=True
        )
        self._thread.start()
        self._cluster = AsyncLocalCluster()

    def _run(self, coro, timeout: Optional[float] = None):
        return asyncio.run_coroutine_threadsafe(coro, self._loop).result(timeout)

    def submit_topology(self, name: str, config: Config, topology: Topology) -> None:
        self._run(self._cluster.submit(name, config, topology))

    def kill_topology(self, name: str, wait_secs: float = 0.0) -> None:
        self._run(self._cluster.kill(name, wait_secs))

    def rebalance(self, name: str, component_id: str, parallelism: int) -> None:
        self._run(self._cluster.runtime(name).rebalance(component_id, parallelism))

    def deactivate(self, name: str) -> None:
        self._run(self._cluster.runtime(name).deactivate())

    def activate(self, name: str) -> None:
        self._run(self._cluster.runtime(name).activate())

    def drain(self, name: str, timeout_s: float = 30.0) -> bool:
        return self._run(self._cluster.runtime(name).drain(timeout_s))

    def metrics(self, name: str) -> Dict[str, Dict[str, object]]:
        # Marshal onto the loop thread: snapshot() iterates dicts the
        # executors mutate there.
        async def snap():
            return self._cluster.runtime(name).metrics.snapshot()

        return self._run(snap())

    def reset_histogram(self, name: str, component: str, metric: str) -> None:
        """Clear one histogram's reservoir (bench harness: drop calibration
        traffic so the measured window starts clean)."""
        async def reset():
            self._cluster.runtime(name).metrics.histogram(
                component, metric).reset()

        self._run(reset())

    def errors(self, name: str) -> List[Tup[str, int, BaseException]]:
        async def errs():
            return list(self._cluster.runtime(name).errors)

        return self._run(errs())

    def shutdown(self) -> None:
        # Idempotent: callers wrap work in try/finally shutdown AND call it
        # on the happy path; the second call must not touch the dead loop.
        if getattr(self, "_closed", False):
            return
        self._closed = True
        self._run(self._cluster.shutdown())
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=10)
        self._loop.close()

    def __enter__(self) -> "LocalCluster":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
