"""Supervision, health, spans, multi-model co-residency (BASELINE config 5)."""

import asyncio
import json

import numpy as np
import pytest

from storm_tpu.config import (
    BatchConfig,
    Config,
    ModelConfig,
    OffsetsConfig,
    ShardingConfig,
)
from storm_tpu.connectors import BrokerSink, BrokerSpout, MemoryBroker
from storm_tpu.infer import InferenceBolt
from storm_tpu.runtime import Bolt, TopologyBuilder
from storm_tpu.runtime.cluster import AsyncLocalCluster
from storm_tpu.runtime.tracing import span
from storm_tpu.runtime.metrics import MetricsRegistry


def test_span_records_histogram():
    m = MetricsRegistry()
    with span(m, "comp", "decode"):
        pass
    snap = m.snapshot()
    assert snap["comp"]["decode_ms"]["count"] == 1


def test_supervisor_restarts_dead_executor(run):
    """Kill an executor task behind the runtime's back; the supervisor
    replaces it and the topology keeps delivering."""
    from tests.test_runtime import CaptureBolt, ListSpout, settle

    CaptureBolt.seen = None

    async def go():
        cfg = Config()
        cfg.topology.message_timeout_s = 2.0  # fast sweep loop
        cluster = AsyncLocalCluster()
        tb = TopologyBuilder()
        spout = ListSpout([f"m{i}" for i in range(6)])
        tb.set_spout("s", spout, 1)
        tb.set_bolt("c", CaptureBolt(), 1).shuffle_grouping("s")
        rt = await cluster.submit("t", cfg, tb.build())
        await settle(rt, "s", 6)
        # simulate a framework-level crash
        rt.bolt_execs["c"][0]._task.cancel()  # cancelled -> NOT restarted
        await asyncio.sleep(0.1)
        victim = rt.bolt_execs["c"][0]
        victim._task = asyncio.get_event_loop().create_task(_boom())
        await asyncio.sleep(0.05)
        for _ in range(100):
            await asyncio.sleep(0.05)
            if rt.metrics.counter("c", "executor_restarts").value:
                break
        restarted = rt.bolt_execs["c"][0] is not victim
        health = rt.health()
        await cluster.shutdown()
        return restarted, health

    async def _boom():
        raise RuntimeError("framework bug")

    restarted, health = run(go(), timeout=30)
    assert restarted
    assert health["components"]["c"]["alive"] == 1


def test_multi_model_topology_shares_process(run):
    """Two models co-resident (BASELINE config 5): MNIST + CIFAR topics
    routed to different InferenceBolts, separate engines, one runtime."""

    async def go():
        broker = MemoryBroker(default_partitions=1)
        cfg = Config()
        off = OffsetsConfig(policy="earliest", max_behind=None)
        bat = BatchConfig(max_batch=4, max_wait_ms=10, buckets=(4,))
        shard = ShardingConfig(data_parallel=0)

        tb = TopologyBuilder()
        tb.set_spout("mnist-in", BrokerSpout(broker, "mnist", off), 1)
        tb.set_spout("cifar-in", BrokerSpout(broker, "cifar", off), 1)
        tb.set_bolt(
            "mnist-bolt",
            InferenceBolt(
                ModelConfig(name="lenet5", dtype="float32", input_shape=(28, 28, 1)),
                bat, shard, warmup=False,
            ),
            1,
        ).shuffle_grouping("mnist-in")
        tb.set_bolt(
            "cifar-bolt",
            InferenceBolt(
                ModelConfig(name="resnet20", dtype="float32", input_shape=(32, 32, 3)),
                bat, shard, warmup=False,
            ),
            1,
        ).shuffle_grouping("cifar-in")
        tb.set_bolt("mnist-out", BrokerSink(broker, "mnist-preds", cfg.sink), 1)\
            .shuffle_grouping("mnist-bolt")
        tb.set_bolt("cifar-out", BrokerSink(broker, "cifar-preds", cfg.sink), 1)\
            .shuffle_grouping("cifar-bolt")

        cluster = AsyncLocalCluster()
        rt = await cluster.submit("multi", cfg, tb.build())
        rng = np.random.RandomState(0)
        for _ in range(4):
            broker.produce("mnist", json.dumps(
                {"instances": rng.rand(1, 28, 28, 1).tolist()}))
            broker.produce("cifar", json.dumps(
                {"instances": rng.rand(1, 32, 32, 3).tolist()}))
        deadline = asyncio.get_event_loop().time() + 90
        while asyncio.get_event_loop().time() < deadline:
            if (broker.topic_size("mnist-preds") >= 4
                    and broker.topic_size("cifar-preds") >= 4):
                break
            await asyncio.sleep(0.05)
        res = (broker.drain_topic("mnist-preds"), broker.drain_topic("cifar-preds"))
        await cluster.shutdown()
        return res

    mnist, cifar = run(go(), timeout=120)
    assert len(mnist) == 4 and len(cifar) == 4
    assert len(json.loads(mnist[0].value)["predictions"][0]) == 10
    assert len(json.loads(cifar[0].value)["predictions"][0]) == 10


# ---- distributed tracing (per-record spans, flight recorder) -----------------


def test_traceparent_roundtrip():
    from storm_tpu.runtime.tracing import TraceContext

    ctx = TraceContext("ab" * 16, "cd" * 8)
    hdr = ctx.traceparent()
    assert hdr == f"00-{'ab' * 16}-{'cd' * 8}-01"
    back = TraceContext.from_traceparent(hdr)
    assert back.trace_id == ctx.trace_id and back.span_id == ctx.span_id


@pytest.mark.parametrize("bad", [
    None, "", "00-short-cdcd-01", "no-dashes",
    f"00-{'zz' * 16}-{'cd' * 8}-01",  # non-hex
    f"00-{'ab' * 16}-{'cd' * 8}",     # missing flags
    42,
])
def test_a_malformed_traceparent_is_none(bad):
    from storm_tpu.runtime.tracing import TraceContext

    assert TraceContext.from_traceparent(bad) is None


def test_tracer_sampling_gates_allocation():
    from storm_tpu.runtime.tracing import Tracer

    off = Tracer(sample_rate=0.0)
    assert not off.active
    assert all(off.maybe_trace() is None for _ in range(50))
    on = Tracer(sample_rate=1.0)
    ctx = on.maybe_trace()
    assert ctx is not None
    sid = on.record(ctx, "ingress", "spout", 0.0, 0.001)
    on.finish(ctx, 1.0)
    [rec] = on.store.recent(5)
    assert rec["trace_id"] == ctx.trace_id
    assert rec["spans"][0]["span_id"] == sid
    assert rec["duration_ms"] == 1.0


def test_trace_store_bounds_open_and_done():
    from storm_tpu.runtime.tracing import Span, TraceStore

    store = TraceStore(capacity=4)
    # done ring: deque(maxlen=capacity)
    for i in range(10):
        tid = f"{i:032x}"
        store.add_span(tid, Span("s", "c", f"{i:016x}", None, 0.0, 1.0))
        store.finish(tid, 1.0)
    assert store.stats()["done"] == 4
    # open map: abandoned records evicted oldest-first past 4x capacity
    for i in range(100, 100 + 40):
        store.open(f"{i:032x}")
    st = store.stats()
    assert st["open"] == 16  # 4x capacity
    assert st["dropped"] == 40 - 16
    # open slices are renderable (dist workers that never see the sink)
    assert len(store.open_records(5)) == 5


def test_flight_recorder_ring_throttle_and_rotation(tmp_path):
    import json as _json

    from storm_tpu.runtime.tracing import FlightRecorder

    path = str(tmp_path / "flight.jsonl")
    fr = FlightRecorder(path=path, capacity=16, max_bytes=4096, max_files=3)
    try:
        assert fr.event("batch_formed", size=4)
        # same-kind throttle window suppresses the repeat
        assert fr.event("slo_breach", throttle_s=60.0, e2e_ms=9.0)
        assert not fr.event("slo_breach", throttle_s=60.0, e2e_ms=9.1)
        # ring is bounded at capacity
        for i in range(200):
            fr.event("spam", i=i)
        tail = fr.tail(1000)
        assert len(tail) == 16
        assert tail[-1]["kind"] == "spam" and tail[-1]["i"] == 199
    finally:
        fr.close()
    # rotation happened (200 events * ~40B > 4096) and is bounded
    import os

    assert os.path.exists(path) and os.path.exists(path + ".1")
    assert not os.path.exists(path + f".{3}")
    # every surviving line is valid JSONL
    for line in open(path):
        ev = _json.loads(line)
        assert "ts" in ev and "kind" in ev


def test_flight_recorder_survives_bad_path():
    from storm_tpu.runtime.tracing import FlightRecorder

    fr = FlightRecorder(path="/nonexistent-dir-zz/flight.jsonl")
    assert fr.event("still_works", n=1)  # ring keeps working, no raise
    assert fr.tail(5)[-1]["kind"] == "still_works"
    fr.close()


def test_e2e_trace_spans_links_and_exemplar(run):
    """Acceptance path: one record's trace contains ingress, queue_wait,
    device_execute (linked to the shared batch span) and egress spans with
    a consistent trace id, and that id rides the e2e latency histogram as
    an OpenMetrics exemplar on /metrics."""

    async def go():
        from storm_tpu.runtime.ui import UIServer

        broker = MemoryBroker(default_partitions=1)
        cfg = Config()
        cfg.tracing.sample_rate = 1.0
        off = OffsetsConfig(policy="earliest", max_behind=None)
        bat = BatchConfig(max_batch=4, max_wait_ms=10, buckets=(4,))
        shard = ShardingConfig(data_parallel=0)

        tb = TopologyBuilder()
        tb.set_spout("in", BrokerSpout(broker, "mnist", off), 1)
        tb.set_bolt(
            "infer",
            InferenceBolt(
                ModelConfig(name="lenet5", dtype="float32",
                            input_shape=(28, 28, 1)),
                bat, shard, warmup=False,
            ),
            1,
        ).shuffle_grouping("in")
        tb.set_bolt("out", BrokerSink(broker, "preds", cfg.sink), 1)\
            .shuffle_grouping("infer")

        cluster = AsyncLocalCluster()
        rt = await cluster.submit("t", cfg, tb.build())
        rng = np.random.RandomState(0)
        for _ in range(4):
            broker.produce("mnist", json.dumps(
                {"instances": rng.rand(1, 28, 28, 1).tolist()}))
        deadline = asyncio.get_event_loop().time() + 90
        while asyncio.get_event_loop().time() < deadline:
            if broker.topic_size("preds") >= 4:
                break
            await asyncio.sleep(0.05)
        assert broker.topic_size("preds") >= 4
        # let the last egress/finish land
        for _ in range(100):
            if len(rt.tracer.store.recent(10)) >= 4:
                break
            await asyncio.sleep(0.05)
        traces = rt.tracer.store.recent(10)

        ui = await UIServer(cluster, port=0).start()
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", ui.port)
            writer.write(b"GET /metrics HTTP/1.1\r\nHost: x\r\n"
                         b"Connection: close\r\n\r\n")
            await writer.drain()
            metrics_raw = await reader.read()
            writer.close()
            reader, writer = await asyncio.open_connection("127.0.0.1", ui.port)
            writer.write(b"GET /api/v1/topology/t/traces?n=5 HTTP/1.1\r\n"
                         b"Host: x\r\nConnection: close\r\n\r\n")
            await writer.drain()
            traces_raw = await reader.read()
            writer.close()
        finally:
            await ui.stop()
            flight = rt.flight.tail(50)
            await cluster.shutdown()
        return traces, metrics_raw, traces_raw, flight

    traces, metrics_raw, traces_raw, flight = run(go(), timeout=120)
    assert len(traces) >= 4

    # every trace carries the full span tree under ONE trace id
    batch_span_ids = set()
    for rec in traces:
        by_name = {}
        for s in rec["spans"]:
            by_name.setdefault(s["name"], s)
        for name in ("ingress", "queue_wait", "device_execute", "egress"):
            assert name in by_name, (name, sorted(by_name))
        dev = by_name["device_execute"]
        qw = by_name["queue_wait"]
        # fan-in: the device span is parented on THIS record's queue_wait
        # and links every member record's queue_wait span
        assert dev["parent_id"] == qw["span_id"]
        assert qw["span_id"] in dev["links"]
        assert dev["attrs"]["batch_size"] >= 1
        assert by_name["ingress"]["attrs"]["topic"] == "mnist"
        assert rec["duration_ms"] is not None
        batch_span_ids.add(dev["span_id"])
    # records batched together share ONE device-execution span id
    assert len(batch_span_ids) < len(traces)

    # exemplar: a sampled trace id rides the sink's e2e histogram
    body = metrics_raw.partition(b"\r\n\r\n")[2].decode()
    count_line = next(
        l for l in body.splitlines()
        if l.startswith("storm_tpu_e2e_latency_ms_count")
        and 'component="out"' in l)
    assert "# {trace_id=" in count_line
    exemplar_tid = count_line.split('trace_id="')[1].split('"')[0]
    assert exemplar_tid in {r["trace_id"] for r in traces}

    # UI traces route serves the slowest view
    tbody = json.loads(traces_raw.partition(b"\r\n\r\n")[2])
    assert tbody["topology"] == "t"
    assert tbody["slowest"] and tbody["slowest"][0]["spans"]
    assert tbody["stats"]["done"] >= 4

    # flight recorder saw the batch forming
    assert any(ev["kind"] == "batch_formed" for ev in flight)


def test_sampling_off_attaches_no_trace(run):
    """tracing.sample_rate=0 (default): tuples carry trace=None end to end
    and the store stays empty — the hot path never touches the tracer."""
    from tests.test_runtime import CaptureBolt, ListSpout, settle

    CaptureBolt.seen = None

    async def go():
        cfg = Config()  # default: sampling off
        cluster = AsyncLocalCluster()
        tb = TopologyBuilder()
        tb.set_spout("s", ListSpout([f"m{i}" for i in range(5)]), 1)
        tb.set_bolt("c", CaptureBolt(), 1).shuffle_grouping("s")
        rt = await cluster.submit("t", cfg, tb.build())
        await settle(rt, "s", 5)
        assert not rt.tracer.active
        stats = rt.tracer.store.stats()
        await cluster.shutdown()
        return stats

    stats = run(go(), timeout=60)
    assert stats["open"] == 0 and stats["done"] == 0


def test_flight_event_warn_once_for_unregistered_names(caplog):
    """Runtime mirror of PRT003: an event name the generated protocol
    registry doesn't know warns exactly once; registered names never do."""
    import logging

    from storm_tpu.runtime import tracing
    from storm_tpu.runtime.tracing import FlightRecorder

    fr = FlightRecorder()
    try:
        tracing._event_names_checked.discard("zz_not_in_registry")
        with caplog.at_level(logging.WARNING, logger="storm_tpu.tracing"):
            fr.event("zz_not_in_registry", n=1)
            fr.event("zz_not_in_registry", n=2)  # second is silent
        hits = [r for r in caplog.records
                if "zz_not_in_registry" in r.getMessage()]
        assert len(hits) == 1
        assert "regen-protocol-registry" in hits[0].getMessage()
        caplog.clear()
        tracing._event_names_checked.discard("dist_worker_draining")
        with caplog.at_level(logging.WARNING, logger="storm_tpu.tracing"):
            fr.event("dist_worker_draining", worker=0)
        assert caplog.records == []
    finally:
        fr.close()
