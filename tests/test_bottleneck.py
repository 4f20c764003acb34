"""Bottleneck observatory (round-12 tentpole).

Unit coverage for the three measurement layers — executor busy/wait/flush
wall-time accounting (fake clock, no sleeps), the windowed CapacityTracker
and EdgeLagTracker, and the BottleneckAttributor's fused verdict — plus
the dist merge (controller ``merge_utilization``), the batcher depth/age
stats parity, spout ingress lag, and the autoscaler's capacity signal.
The end-to-end claim (the attributor names an induced limiter in both an
inference-bound and a spout-bound topology) was seen in a CPU-host run of
round 12 and is not re-measured here.
"""

from __future__ import annotations

import asyncio
from types import SimpleNamespace

import numpy as np
import pytest

from storm_tpu.config import BatchConfig, Config, ObsConfig, QosConfig
from storm_tpu.obs.bottleneck import BottleneckAttributor
from storm_tpu.obs.capacity import (
    CapacityTracker,
    EdgeLagTracker,
    utilization_snapshot,
)
from storm_tpu.runtime.metrics import MetricsRegistry


class FakeFlight:
    def __init__(self) -> None:
        self.events = []

    def event(self, kind, **fields):
        fields.pop("throttle_s", None)
        self.events.append({"kind": kind, **fields})

    def close(self) -> None:  # cluster.shutdown closes the real recorder
        pass


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t


def _fake_exec(task_index=0, busy=0.0, wait=0.0, flush=0.0, **extra):
    return SimpleNamespace(task_index=task_index, busy_s=busy, wait_s=wait,
                           flush_s=flush, **extra)


class FakeQueue:
    def __init__(self, n: int = 0) -> None:
        self.n = n

    def qsize(self) -> int:
        return self.n


class FakeRouter:
    """Router stand-in: ``edges()`` yields (src, stream, group) like
    ``runtime.cluster.Router.edges``."""

    def __init__(self, edges) -> None:
        self._edges = edges

    def edges(self):
        yield from self._edges


def _edge(src, dst, depth, stream="default"):
    group = SimpleNamespace(component_id=dst, inboxes=[FakeQueue(depth)])
    return src, stream, group


# ---- executor wall-time accounting (fake clock, no sleeps) -------------------


def test_bolt_executor_splits_wait_and_busy(run):
    from storm_tpu.runtime.base import Bolt
    from storm_tpu.runtime.executor import _STOP, BoltExecutor
    from storm_tpu.runtime.tuples import Tuple

    clock = FakeClock()

    class SlowBolt(Bolt):
        async def execute(self, t):
            clock.t += 3.0  # 3 fake-seconds of "work" per tuple

        async def flush(self):
            clock.t += 2.0

    rt = SimpleNamespace(metrics=MetricsRegistry(), tracer=None,
                         report_error=lambda *a: None)
    ex = BoltExecutor(rt, "b", 0, SlowBolt(), inbox_capacity=8)
    ex.clock = clock
    ex._stateful = False  # start() was skipped; _run/stop only need these

    async def go():
        ex._task = asyncio.get_event_loop().create_task(ex._run())
        for _ in range(2):
            await ex.inbox.put(Tuple(("x",), ("message",), "s"))
        # Let the loop drain both tuples and block on the empty inbox,
        # then advance the clock across the idle gap: that gap is wait.
        while ex.busy_s < 6.0:
            await asyncio.sleep(0)
        await asyncio.sleep(0)
        clock.t += 5.0
        await ex.stop(drain=True)

    run(go())
    assert ex.busy_s == pytest.approx(6.0)  # 2 tuples x 3s
    assert ex.wait_s == pytest.approx(5.0)  # the idle gap
    assert ex.flush_s == pytest.approx(2.0)  # drain flush


def test_spout_executor_counts_empty_polls_as_wait(run):
    from storm_tpu.runtime.base import Spout
    from storm_tpu.runtime.executor import SpoutExecutor

    clock = FakeClock()

    class PollSpout(Spout):
        def __init__(self) -> None:
            self.polls = 0

        async def next_tuple(self) -> bool:
            self.polls += 1
            clock.t += 1.0  # every poll costs 1 fake-second
            return self.polls <= 3  # 3 productive, then drained

    rt = SimpleNamespace(metrics=MetricsRegistry(), tracer=None,
                         report_error=lambda *a: None,
                         config=Config())
    spout = PollSpout()
    ex = SpoutExecutor(rt, "s", 0, spout, max_pending=0)
    ex.clock = clock

    async def go():
        ex._task = asyncio.get_event_loop().create_task(ex._run())
        while spout.polls < 6:
            await asyncio.sleep(0)
        ex._task.cancel()
        try:
            await ex._task
        except asyncio.CancelledError:
            pass

    run(go())
    # Emitting polls are busy; empty polls are idle time (a drained spout
    # keeps polling yet must read capacity ~0), as are backoff sleeps.
    assert ex.busy_s == pytest.approx(3.0)
    assert ex.wait_s >= 3.0


# ---- CapacityTracker ---------------------------------------------------------


def test_capacity_tracker_windows_per_key():
    clock = FakeClock()
    e = _fake_exec(busy=1.0, wait=1.0)
    rt = SimpleNamespace(metrics=MetricsRegistry(),
                         bolt_execs={"b": [e]}, spout_execs={})
    tr = CapacityTracker(rt, clock=clock)

    assert tr.sample(key="a") == {}  # first call primes: zero-length window

    e.busy_s += 8.0
    e.wait_s += 2.0
    clock.t += 10.0
    row = tr.sample(key="a")["b"]
    assert row["capacity"] == pytest.approx(0.8)
    assert row["busy_frac"] == pytest.approx(0.8)
    assert row["wait_frac"] == pytest.approx(0.2)
    assert row["dt_s"] == pytest.approx(10.0)
    # publish=True set the Storm-UI gauges
    assert rt.metrics.gauge("b", "capacity").value == pytest.approx(0.8)
    # Named cursors: key "z" never sampled before, so its window spans the
    # whole lifetime — key "a"'s read did not steal the delta.
    assert tr.sample(key="z") == {}
    clock.t += 1.0
    assert tr.sample(key="z")["b"]["busy_s"] == pytest.approx(0.0)


def test_capacity_tracker_sums_tasks_and_drops_removed():
    clock = FakeClock()
    e0, e1 = _fake_exec(0), _fake_exec(1)
    rt = SimpleNamespace(metrics=MetricsRegistry(),
                         bolt_execs={"b": [e0, e1]}, spout_execs={})
    tr = CapacityTracker(rt, clock=clock)
    tr.sample()
    e0.busy_s += 10.0
    e1.busy_s += 5.0
    clock.t += 10.0
    row = tr.sample()["b"]
    assert row["tasks"] == 2
    # capacity normalizes over tasks*window: (10+5) / (2*10)
    assert row["capacity"] == pytest.approx(0.75)

    rt.bolt_execs["b"] = [e0]  # rebalance removed task 1
    clock.t += 10.0
    assert tr.sample()["b"]["tasks"] == 1


# ---- EdgeLagTracker ----------------------------------------------------------


def test_edge_lag_growth_and_ingress_rows():
    clock = FakeClock()
    edge = _edge("spout", "bolt", depth=10)
    spout = SimpleNamespace(ingress_lag=lambda: {
        "records_behind": 100, "partitions": 4})
    rt = SimpleNamespace(
        metrics=MetricsRegistry(), router=FakeRouter([edge]),
        bolt_execs={},
        spout_execs={"spout": [_fake_exec(spout=spout)]})
    tr = EdgeLagTracker(rt, clock=clock)

    out = tr.sample()
    assert out["edges"][0]["depth"] == 10
    assert out["edges"][0]["growth_per_s"] is None  # first sample: no slope
    assert out["ingress"][0]["records_behind"] == 100
    assert out["transport"] == {}  # single-host: no peer senders

    edge[2].inboxes[0].n = 30
    clock.t += 2.0
    out = tr.sample()
    assert out["edges"][0]["growth_per_s"] == pytest.approx(10.0)
    assert rt.metrics.gauge(
        "obs", "edge_depth_spout->bolt").value == pytest.approx(30.0)


def test_transport_depths_reads_peer_senders():
    from storm_tpu.obs.capacity import transport_depths

    rt = SimpleNamespace(senders={1: SimpleNamespace(queue=FakeQueue(5)),
                                  2: SimpleNamespace(queue=FakeQueue(0))})
    assert transport_depths(rt) == {"peer_1": 5, "peer_2": 0}


# ---- BottleneckAttributor ----------------------------------------------------


def _attributor_rig(edges, bolt_execs, spout_execs):
    clock = FakeClock()
    rt = SimpleNamespace(metrics=MetricsRegistry(), flight=FakeFlight(),
                         router=FakeRouter(edges),
                         bolt_execs=bolt_execs, spout_execs=spout_execs)
    cfg = ObsConfig()
    cap = CapacityTracker(rt, clock=clock)
    lag = EdgeLagTracker(rt, clock=clock)
    return rt, clock, BottleneckAttributor(rt, cfg, cap, lag, clock=clock)


def test_attributor_names_the_slowed_component():
    """An artificially saturated bolt with a growing inbound edge must be
    named leader over a busier-looking upstream that is merely loaded."""
    slow, up = _fake_exec(), _fake_exec()
    edge = _edge("upstream", "slow-bolt", depth=10)
    rt, clock, bn = _attributor_rig(
        [edge], {"slow-bolt": [slow], "upstream": [up]}, {})

    v = bn.step()  # primes every cursor
    assert v["leader"] is None and v["ranked"] == []

    clock.t += 10.0
    slow.busy_s += 9.5
    slow.wait_s += 0.5
    up.busy_s += 7.0
    up.wait_s += 3.0
    edge[2].inboxes[0].n = 200  # inbound backlog grew 19 rows/s
    v = bn.step()

    assert v["leader"] == "slow-bolt"
    assert v["ranked"][0]["component"] == "slow-bolt"
    assert v["ranked"][0]["score"] > v["ranked"][1]["score"]
    assert any("inflow growing" in r for r in v["ranked"][0]["reasons"])
    ev = [e for e in rt.flight.events if e["kind"] == "bottleneck_shift"]
    assert len(ev) == 1 and ev[0]["component"] == "slow-bolt"
    assert ev[0]["previous"] is None
    assert rt.metrics.gauge(
        "obs", "bottleneck_score_slow-bolt").value == v["ranked"][0]["score"]

    # Stable leader: no second shift event while the verdict holds.
    clock.t += 10.0
    slow.busy_s += 9.0
    up.busy_s += 5.0
    bn.step()
    assert len([e for e in rt.flight.events
                if e["kind"] == "bottleneck_shift"]) == 1


def test_attributor_idle_topology_names_nobody():
    idle = _fake_exec()
    rt, clock, bn = _attributor_rig(
        [_edge("s", "b", 0)], {"b": [idle]}, {})
    bn.step()
    clock.t += 10.0
    idle.wait_s += 10.0
    v = bn.step()
    assert v["leader"] is None  # busy 0 < bottleneck_min_score
    assert v["ranked"][0]["score"] < bn.cfg.bottleneck_min_score
    assert rt.flight.events == []


def test_attributor_spout_ingress_boost_is_capacity_qualified():
    """Growing broker backlog boosts a near-capacity spout, but not a
    throttled (mostly waiting) one — downstream pressure also grows the
    backlog, so ingress slope alone must not name the spout."""
    behind = {"n": 0}
    spout_obj = SimpleNamespace(
        ingress_lag=lambda: {"records_behind": behind["n"], "partitions": 1})
    for busy, boosted in ((9.0, True), (2.0, False)):
        sp = _fake_exec(spout=spout_obj)
        behind["n"] = 0
        rt, clock, bn = _attributor_rig([], {}, {"kafka-spout": [sp]})
        bn.step()
        clock.t += 10.0
        sp.busy_s += busy
        sp.wait_s += 10.0 - busy
        behind["n"] = 500
        v = bn.step()
        row = v["ranked"][0]
        boost = any("ingress lag growing" in r for r in row["reasons"])
        assert boost is boosted, (busy, row)


def test_critical_path_decomposes_windowed_means():
    rt, clock, bn = _attributor_rig([], {}, {})
    m = rt.metrics

    def feed():
        for _ in range(10):
            m.histogram("inference-bolt", "batch_wait_ms").observe(2.0)
            m.histogram("inference-bolt", "device_ms").observe(6.0)
            m.histogram("inference-bolt", "compute_ms").observe(5.0)
            m.histogram("kafka-bolt", "e2e_latency_ms").observe(10.0)

    feed()
    cp = bn.critical_path()  # first read primes the named cursors
    assert cp["records"] == 0 and cp["e2e_mean_ms"] is None
    feed()
    cp = bn.critical_path()
    assert cp["records"] == 10
    assert cp["e2e_mean_ms"] == pytest.approx(10.0)
    assert cp["stages"]["device"]["mean_ms"] == pytest.approx(6.0)
    assert cp["stages"]["device"]["substages_ms"]["compute"] == pytest.approx(5.0)
    assert cp["device_frac"] == pytest.approx(0.6)
    assert cp["stages"]["queue_wait_batch"]["frac_of_e2e"] == pytest.approx(0.2)
    # remainder = e2e - (batch_wait + device); substages don't double-count
    assert cp["stages"]["other_wire_routing_sink"]["mean_ms"] == pytest.approx(2.0)


# ---- dist merge --------------------------------------------------------------


def _worker_snap(components, transport=None):
    return {"components": components, "transport": transport or {}}


def test_merge_utilization_sums_seconds_across_workers():
    from storm_tpu.dist.controller import merge_utilization

    per_worker = {
        0: _worker_snap({"inference-bolt": {
            "component": "inference-bolt", "tasks": 1, "busy_s": 8.0,
            "wait_s": 2.0, "flush_s": 0.0, "dt_s": 10.0}}),
        1: _worker_snap({"inference-bolt": {
            "component": "inference-bolt", "tasks": 1, "busy_s": 4.0,
            "wait_s": 6.0, "flush_s": 0.0, "dt_s": 10.0},
            "kafka-spout": {
            "component": "kafka-spout", "tasks": 1, "busy_s": 1.0,
            "wait_s": 9.0, "flush_s": 0.0, "dt_s": 10.0}},
            transport={"peer_0": 3}),
    }
    merged = merge_utilization(per_worker)
    inf = merged["inference-bolt"]
    # raw seconds add, dt takes the max, capacity re-derived from totals:
    # (8+4) / (2 tasks * 10s) = 0.6
    assert inf["tasks"] == 2
    assert inf["busy_s"] == pytest.approx(12.0)
    assert inf["dt_s"] == pytest.approx(10.0)
    assert inf["capacity"] == pytest.approx(0.6)
    assert inf["busy_frac"] == pytest.approx(12.0 / 20.0)
    assert inf["workers"] == [0, 1]
    assert merged["kafka-spout"]["workers"] == [1]


def test_dist_cluster_utilization_merges_and_threads_key():
    from storm_tpu.dist.controller import DistCluster

    calls = []

    class FakeClient:
        def __init__(self, idx):
            self.idx = idx

        def control(self, cmd, **kw):
            calls.append((self.idx, cmd, kw))
            return {"index": self.idx, "utilization": _worker_snap({
                "b": {"component": "b", "tasks": 1, "busy_s": 5.0,
                      "wait_s": 5.0, "flush_s": 0.0, "dt_s": 10.0}})}

    dc = DistCluster.__new__(DistCluster)
    dc.clients = [FakeClient(0), FakeClient(1)]
    out = dc.utilization(key="bench")
    assert calls == [(0, "utilization", {"key": "bench"}),
                     (1, "utilization", {"key": "bench"})]
    assert set(out["workers"]) == {0, 1}
    assert out["components"]["b"]["capacity"] == pytest.approx(0.5)


def test_utilization_snapshot_caches_tracker_on_runtime():
    rt = SimpleNamespace(metrics=MetricsRegistry(),
                         bolt_execs={"b": [_fake_exec(busy=1.0)]},
                         spout_execs={})
    out = utilization_snapshot(rt)
    assert out["components"] == {}  # first call primes
    tr = rt._capacity_tracker
    rt.bolt_execs["b"][0].busy_s += 1.0
    out = utilization_snapshot(rt)
    assert rt._capacity_tracker is tr  # cursor survives across calls
    assert "b" in out["components"]


# ---- the engine queue's stats: what obs/ and the /qos route read -------------


def test_engine_queue_stats_carry_the_keys_obs_reads():
    from storm_tpu.infer.continuous import ContinuousBatcher

    class _Engine:
        ring_capacity = 2

    engine = _Engine()  # the queue holds its engine weakly
    cb = ContinuousBatcher(
        engine, BatchConfig(max_batch=64, max_wait_ms=1000.0),
        QosConfig(enabled=True))
    cb._ensure_thread_locked = lambda: None  # nothing dispatches here
    st = cb.stats()
    # Observatory.occupancy joins on "engine" and reads pending_rows and
    # oldest_ms; the /qos route shows the rest
    assert {"engine", "capacity", "inflight", "pending_rows", "oldest_ms",
            "pending_by_key", "batches", "rows", "batch_fill_p50",
            "fair_rows", "fair_starved", "last_batch"} <= set(st)
    assert st["pending_rows"] == 0 and st["oldest_ms"] == 0.0
    assert st["engine"] == "_Engine" and st["capacity"] == 2

    cb.submit(np.zeros((2, 4), np.float32), lane="high", tenant="gold")
    cb.submit(np.zeros((3, 4), np.float32))  # default tenant and lane
    st = cb.stats()
    assert st["pending_rows"] == 5 and st["oldest_ms"] >= 0.0
    assert st["pending_by_key"] == {"gold:high": 2, "default:normal": 3}


# ---- spout ingress lag -------------------------------------------------------


def _bare_spout(blocking, positions, latest):
    from storm_tpu.connectors.spout import BrokerSpout

    sp = BrokerSpout.__new__(BrokerSpout)
    sp.topic = "t"
    sp._blocking = blocking
    sp.my_partitions = sorted(positions)
    sp.positions = dict(positions)
    sp.broker = SimpleNamespace(
        latest_offset=lambda topic, p: latest[p])
    return sp


def test_ingress_lag_sums_owned_partitions():
    sp = _bare_spout(False, {0: 10, 1: 40}, {0: 25, 1: 40})
    assert sp.ingress_lag() == {"records_behind": 15, "partitions": 2}


def test_ingress_lag_blocking_broker_is_unknown_not_zero():
    sp = _bare_spout(True, {0: 0}, {0: 10**6})
    assert sp.ingress_lag() == {"records_behind": None, "partitions": 1}


# ---- autoscaler capacity signal ----------------------------------------------


def test_autoscaler_scales_the_named_bottleneck(run):
    """Leader==policy component at capacity scales up with NO latency or
    inbox signal; a verdict naming some other component does not."""
    from storm_tpu.runtime import Bolt, TopologyBuilder
    from storm_tpu.runtime.autoscale import AutoscalePolicy, Autoscaler
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    class IdleBolt(Bolt):
        async def execute(self, t):
            self.collector.ack(t)

    def verdict(leader, capacity=0.97):
        return {"leader": leader, "ranked": [
            {"component": leader, "capacity": capacity, "score": 1.2}]}

    async def go():
        from tests.test_runtime import ListSpout

        cluster = AsyncLocalCluster()
        tb = TopologyBuilder()
        tb.set_spout("s", ListSpout([]), 1)
        tb.set_bolt("inference-bolt", IdleBolt(), 1).shuffle_grouping("s")
        rt = await cluster.submit("t", Config(), tb.build())
        rt.flight = FakeFlight()
        scaler = Autoscaler(rt, AutoscalePolicy(max_parallelism=3))
        scaler.bottleneck = SimpleNamespace(
            cfg=ObsConfig(), last_verdict=verdict("kafka-spout"))

        r_other = [await scaler.step(), await scaler.step()]
        scaler.bottleneck.last_verdict = verdict("inference-bolt")
        r_named = [await scaler.step(), await scaler.step()]
        par = rt.parallelism_of("inference-bolt")
        events = list(rt.flight.events)
        await cluster.shutdown()
        return r_other, r_named, par, events

    r_other, r_named, par, events = run(go())
    assert r_other == [None, None]  # another component's saturation: no-op
    assert r_named == [None, 2]  # two hot intervals -> scale the bottleneck
    assert par == 2
    ev = [e for e in events if e["kind"] == "autoscale_decision"]
    assert ev and ev[-1]["direction"] == "up"
    assert ev[-1]["capacity"] == pytest.approx(0.97)
    assert ev[-1]["bottleneck"] is True
