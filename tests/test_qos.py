"""Admission control & QoS (storm_tpu/qos/, round-6 tentpole): token-bucket
tenant quotas + lane classification at the spout edge, earliest-deadline-
first batch formation (tests/test_continuous.py), the hysteresis load-shed controller,
shed-first/scale-second autoscaler coupling, and the typed ``Overloaded``
degradation path — unit-level on the qos package, then e2e through the
broker -> spout -> InferenceBolt -> sink slice, then the UI /qos route."""

import asyncio
import json
from types import SimpleNamespace

import numpy as np
import pytest

from storm_tpu.api.schema import decode_predictions
from storm_tpu.config import (
    BatchConfig, Config, ModelConfig, OffsetsConfig, QosConfig,
    ShardingConfig)
from storm_tpu.connectors import BrokerSink, BrokerSpout, MemoryBroker
from storm_tpu.infer import InferenceBolt
from storm_tpu.qos import (
    AdmissionController, LoadShedController, ShedPolicy, TokenBucket)
from storm_tpu.runtime import Bolt, Spout, TopologyBuilder, Values
from storm_tpu.runtime.autoscale import Autoscaler, AutoscalePolicy
from storm_tpu.runtime.cluster import AsyncLocalCluster
from storm_tpu.runtime.metrics import MetricsRegistry


# ---- token bucket ------------------------------------------------------------


def test_token_bucket_refill_is_continuous():
    b = TokenBucket(rate=10.0, burst=5.0, now=0.0)
    for _ in range(5):  # starts full: a fresh tenant gets its burst
        assert b.try_take(1.0, now=0.0)
    assert not b.try_take(1.0, now=0.0)
    # 0.5 s at 10/s refills 5 tokens, capped at burst.
    for _ in range(5):
        assert b.try_take(1.0, now=0.5)
    assert not b.try_take(1.0, now=0.5)
    # Refill never exceeds burst even after a long idle stretch.
    assert b.try_take(5.0, now=100.0)
    assert not b.try_take(1.0, now=100.0)


def test_token_bucket_burst_floor():
    # A tiny rate still admits at least one record per burst window.
    b = TokenBucket(rate=0.1, burst=0.01, now=0.0)
    assert b.burst == 1.0
    assert b.try_take(1.0, now=0.0)
    assert not b.try_take(1.0, now=0.0)


# ---- classification ----------------------------------------------------------


def test_classify_tenant_lane_key():
    ac = AdmissionController(QosConfig(enabled=True))
    assert ac.classify(b"gold:high") == ("gold", "high")
    assert ac.classify(b"free:best_effort") == ("free", "best_effort")
    # No lane / unknown lane -> default lane; no key -> topic as tenant.
    assert ac.classify(b"gold") == ("gold", "normal")
    assert ac.classify(b"gold:bogus") == ("gold", "normal")
    assert ac.classify(None, topic="clicks") == ("clicks", "normal")
    assert ac.classify(b"", topic="clicks") == ("clicks", "normal")
    assert ac.classify(b":high", topic="clicks") == ("clicks", "high")


def test_qos_config_lane_semantics():
    qos = QosConfig(enabled=True)
    assert qos.lane_index("high") == 0
    assert qos.lane_index("nonsense") == qos.lane_index("normal")
    assert qos.deadline_for("high") == 50.0
    assert qos.deadline_for("best_effort") == 1000.0
    assert qos.max_shed_level == 2
    # Level N sheds the N lowest-priority lanes; the top lane never sheds.
    assert not qos.shed_eligible("best_effort", 0)
    assert qos.shed_eligible("best_effort", 1)
    assert not qos.shed_eligible("normal", 1)
    assert qos.shed_eligible("normal", 2)
    assert not qos.shed_eligible("high", 2)
    assert not qos.shed_eligible("high", 99)  # clamped to max_shed_level
    # Per-tenant override beats the default rate.
    qos2 = QosConfig(enabled=True, tenant_rate=5.0,
                     tenant_rates={"gold": 50.0})
    assert qos2.rate_for("gold") == 50.0
    assert qos2.rate_for("anyone") == 5.0


def test_qos_config_validation():
    with pytest.raises(ValueError):
        QosConfig(lanes=("a", "a"))
    with pytest.raises(ValueError):
        QosConfig(lanes=("a", "b"), lane_deadline_ms=(1.0,))
    with pytest.raises(ValueError):
        QosConfig(default_lane="nope")


# ---- admission ---------------------------------------------------------------


def test_admit_throttles_over_quota_tenant():
    reg = MetricsRegistry()
    qos = QosConfig(enabled=True, tenant_rate=2.0, tenant_burst_s=1.0)
    ac = AdmissionController(qos, parallelism=1, metrics=reg)
    t0 = 100.0
    assert ac.admit("gold", "high", now=t0) == (True, "ok")
    assert ac.admit("gold", "high", now=t0) == (True, "ok")
    assert ac.admit("gold", "high", now=t0) == (False, "throttled")
    # A second into the future the bucket has refilled.
    assert ac.admit("gold", "high", now=t0 + 1.0) == (True, "ok")
    snap = reg.snapshot()["qos"]
    assert snap["admitted_gold"] == 3
    assert snap["throttled_gold"] == 1
    assert snap["admitted_lane_high"] == 3
    assert snap["throttled_lane_high"] == 1


def test_admit_splits_rate_across_spout_tasks():
    qos = QosConfig(enabled=True, tenant_rate=4.0, tenant_burst_s=1.0)
    ac = AdmissionController(qos, parallelism=2)
    t0 = 0.0
    assert ac.admit("gold", "normal", now=t0)[0]
    assert ac.admit("gold", "normal", now=t0)[0]
    # 4/s across 2 tasks = 2/s per task; the third local take fails.
    assert ac.admit("gold", "normal", now=t0) == (False, "throttled")


def test_admit_unlimited_tenant_never_throttles():
    ac = AdmissionController(QosConfig(enabled=True, tenant_rate=0.0))
    for _ in range(100):
        assert ac.admit("anyone", "normal", now=0.0) == (True, "ok")


def test_admit_sheds_lanes_at_raised_level():
    reg = MetricsRegistry()
    ac = AdmissionController(QosConfig(enabled=True), metrics=reg)
    reg.gauge("qos", "shed_level").set(1.0)
    assert ac.admit("free", "best_effort", now=0.0) == (False, "shed")
    assert ac.admit("gold", "high", now=0.0) == (True, "ok")
    assert ac.admit("gold", "normal", now=0.0) == (True, "ok")
    reg.gauge("qos", "shed_level").set(2.0)
    assert ac.admit("gold", "normal", now=0.0) == (False, "shed")
    assert ac.admit("gold", "high", now=0.0) == (True, "ok")
    snap = reg.snapshot()["qos"]
    assert snap["shed_free"] == 1
    assert snap["shed_gold"] == 1
    assert snap["shed_lane_best_effort"] == 1
    assert snap["shed_lane_normal"] == 1


# ---- load-shed controller ----------------------------------------------------


def _shed_rig(**kw):
    reg = MetricsRegistry()
    rt = SimpleNamespace(metrics=reg, bolt_execs={}, flight=None)
    pol = ShedPolicy(interval_s=1.0, breach_rate=1.0, hot_steps=2,
                     calm_steps=2, max_level=2, **kw)
    return reg, rt, LoadShedController(rt, pol)


def test_shed_controller_hysteresis_round_trip():
    reg, rt, ctl = _shed_rig()
    assert rt.qos is ctl  # exposed for the UI /qos route
    assert reg.gauge("qos", "shed_level").value == 0.0
    breaches = reg.counter("kafka-bolt", "slo_breaches")

    assert ctl.step() is None  # first step: no breach baseline yet
    breaches.inc(5)
    assert ctl.step() is None  # hot x1 — below hot_steps
    breaches.inc(5)
    assert ctl.step() == 1     # hot x2 -> shed one lane
    assert ctl.level == 1
    assert reg.gauge("qos", "shed_level").value == 1.0
    assert ctl.decisions == [("shed", 0, 1)]
    assert reg.snapshot()["qos"]["shed_decisions"] == 1

    # Signals go quiet: calm_steps consecutive calm intervals restore.
    assert ctl.step() is None
    assert ctl.step() == 0
    assert ctl.level == 0
    assert reg.gauge("qos", "shed_level").value == 0.0
    assert ctl.decisions[-1] == ("restore", 1, 0)


def test_shed_controller_caps_at_max_level():
    reg, rt, ctl = _shed_rig()
    breaches = reg.counter("kafka-bolt", "slo_breaches")
    ctl.step()
    for _ in range(12):  # relentless heat
        breaches.inc(10)
        ctl.step()
    assert ctl.level == 2  # max_level: the top lane is never shed
    assert reg.gauge("qos", "shed_level").value == 2.0


def test_shed_controller_middling_signals_reset_both_streaks():
    # 1 breach/interval on a 1.0/s threshold is NOT > 1.0 (never hot) and
    # not < 0.5 (never calm): both streaks reset, no decision ever fires.
    reg, rt, ctl = _shed_rig()
    breaches = reg.counter("kafka-bolt", "slo_breaches")
    ctl.step()
    for _ in range(8):
        breaches.inc(1)
        assert ctl.step() is None
    assert ctl.level == 0 and ctl.decisions == []


def test_shed_controller_inbox_signal():
    # The signal counts queued RECORDS (round 20): a parked frame tuple
    # contributes its row count, a plain tuple contributes 1.
    reg = MetricsRegistry()
    frame = SimpleNamespace(values=[list(range(45))])
    queued = [frame] + [SimpleNamespace(values=["rec"]) for _ in range(45)]
    full = SimpleNamespace(
        inbox=SimpleNamespace(_queue=queued, maxsize=100))
    rt = SimpleNamespace(metrics=reg,
                         bolt_execs={"inference-bolt": [full]}, flight=None)
    ctl = LoadShedController(rt, ShedPolicy(hot_steps=2, calm_steps=2))
    assert ctl.step() is None
    assert ctl.step() == 1  # 45-row frame + 45 tuples = 90% > 50%, two hot steps


def test_shed_policy_from_qos():
    qos = QosConfig(enabled=True, shed_interval_s=0.25, shed_breach_rate=3.0,
                    shed_hot_steps=4, shed_calm_steps=9)
    pol = ShedPolicy.from_qos(qos, component="mnist-inference",
                              latency_source="mnist-sink")
    assert pol.component == "mnist-inference"
    assert pol.latency_source == "mnist-sink"
    assert pol.interval_s == 0.25
    assert pol.breach_rate == 3.0
    assert pol.hot_steps == 4 and pol.calm_steps == 9
    assert pol.max_level == qos.max_shed_level == 2


# ---- shed-first / scale-second -----------------------------------------------


def _hot_autoscaler_rig(shedder):
    reg = MetricsRegistry()
    for _ in range(20):  # p50 far above high_ms: permanently hot
        reg.histogram("kafka-bolt", "e2e_latency_ms").observe(500.0)
    calls = []

    async def rebalance(component, n):
        calls.append((component, n))

    rt = SimpleNamespace(metrics=reg, bolt_execs={}, flight=None,
                         parallelism_of=lambda c: 1, rebalance=rebalance)
    sc = Autoscaler(rt, AutoscalePolicy(high_ms=100.0, interval_s=0.1),
                    shedder=shedder)
    return sc, calls


def test_autoscaler_defers_one_interval_while_shedder_calm(run):
    async def go():
        shedder = SimpleNamespace(level=0)
        sc, calls = _hot_autoscaler_rig(shedder)
        assert await sc.step() is None   # hot x1
        assert await sc.step() is None   # hot x2 but DEFERRED (level 0)
        assert calls == []
        assert await sc.step() == 2      # deferral spent: scale up
        assert calls == [("inference-bolt", 2)]
        assert sc.decisions == [("up", 1, 2)]

    run(go())


def test_autoscaler_scales_immediately_once_shedding_active(run):
    async def go():
        shedder = SimpleNamespace(level=1)
        sc, calls = _hot_autoscaler_rig(shedder)
        assert await sc.step() is None   # hot x1
        assert await sc.step() == 2      # shedder already reacted: no defer
        assert calls == [("inference-bolt", 2)]

    run(go())


def test_autoscaler_without_shedder_keeps_old_behavior(run):
    async def go():
        sc, calls = _hot_autoscaler_rig(None)
        assert await sc.step() is None
        assert await sc.step() == 2
        assert calls == [("inference-bolt", 2)]

    run(go())


# ---- e2e: broker -> spout -> operator -> sink with QoS -----------------------


def _payload(n=1, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 28, 28, 1).astype(np.float32)
    return json.dumps({"instances": x.tolist()})


async def _run_qos_e2e(keys, shed_level=0.0, spout_qos=True, n_expect=None):
    broker = MemoryBroker(default_partitions=2)
    cfg = Config()
    qos = QosConfig(enabled=True)
    model_cfg = ModelConfig(name="lenet5", dtype="float32",
                            input_shape=(28, 28, 1))
    batch_cfg = BatchConfig(max_batch=8, max_wait_ms=20, buckets=(8,))
    shard_cfg = ShardingConfig(data_parallel=0)

    tb = TopologyBuilder()
    tb.set_spout(
        "kafka-spout",
        BrokerSpout(broker, "input",
                    OffsetsConfig(policy="earliest", max_behind=None),
                    qos=qos if spout_qos else None),
        parallelism=1,
    )
    tb.set_bolt(
        "inference-bolt",
        InferenceBolt(model_cfg, batch_cfg, shard_cfg, warmup=False,
                      passthrough=("qos_lane",) if spout_qos else (),
                      qos=qos),
        parallelism=1,
    ).shuffle_grouping("kafka-spout")
    tb.set_bolt("kafka-bolt", BrokerSink(broker, "output", cfg.sink),
                parallelism=1).shuffle_grouping("inference-bolt")
    tb.set_bolt("dlq-bolt", BrokerSink(broker, "dead-letter", cfg.sink),
                parallelism=1)\
        .shuffle_grouping("inference-bolt", stream="dead_letter")

    cluster = AsyncLocalCluster()
    rt = await cluster.submit("qos-e2e", cfg, tb.build())
    if shed_level:
        # Normally the LoadShedController moves this gauge; pinning it
        # makes the shed paths deterministic under test.
        rt.metrics.gauge("qos", "shed_level").set(float(shed_level))

    for i, key in enumerate(keys):
        broker.produce("input", _payload(n=1, seed=i), key=key)

    total = len(keys) if n_expect is None else n_expect
    deadline = asyncio.get_event_loop().time() + 60
    while asyncio.get_event_loop().time() < deadline:
        done = broker.topic_size("output") + broker.topic_size("dead-letter")
        if done >= total:
            break
        await asyncio.sleep(0.05)
    await rt.drain(timeout_s=30)
    snap = rt.metrics.snapshot()
    outs = broker.drain_topic("output")
    dlq = broker.drain_topic("dead-letter")
    await cluster.shutdown()
    return outs, dlq, snap


def test_e2e_lane_field_and_per_lane_latency(run):
    keys = [b"gold:high"] * 3 + [b"free:best_effort"] * 3
    outs, dlq, snap = run(_run_qos_e2e(keys), timeout=120)
    assert len(outs) == 6 and len(dlq) == 0
    for r in outs:
        preds = decode_predictions(r.value)
        assert preds.data.shape == (1, 10)
    # Spout-edge admission accounting, by tenant and by lane.
    q = snap["qos"]
    assert q["admitted_gold"] == 3 and q["admitted_free"] == 3
    assert q["admitted_lane_high"] == 3
    assert q["admitted_lane_best_effort"] == 3
    # The lane rode the tuple (spout passthrough) all the way to the sink:
    # per-lane e2e histograms exist alongside the pooled one.
    sink = snap["kafka-bolt"]
    assert sink["e2e_latency_ms_high"]["count"] == 3
    assert sink["e2e_latency_ms_best_effort"]["count"] == 3
    assert sink["e2e_latency_ms"]["count"] == 6
    assert snap["kafka-spout"]["tree_acked"] == 6


def test_e2e_edge_shed_drops_best_effort_keeps_high(run):
    keys = [b"free:best_effort"] * 3 + [b"gold:high"] * 3
    outs, dlq, snap = run(
        _run_qos_e2e(keys, shed_level=1.0, n_expect=3), timeout=120)
    # Best-effort was dropped AT THE SPOUT (cursor advanced, no replay);
    # high-priority traffic was served untouched.
    assert len(outs) == 3 and len(dlq) == 0
    for r in outs:
        assert decode_predictions(r.value).data.shape == (1, 10)
    q = snap["qos"]
    assert q["shed_free"] == 3
    assert q["shed_lane_best_effort"] == 3
    assert q["admitted_gold"] == 3
    assert snap["kafka-spout"]["tree_acked"] == 3  # only admitted records
    assert snap["kafka-bolt"]["e2e_latency_ms_high"]["count"] == 3


def test_e2e_operator_shed_answers_overloaded(run):
    # Spout QoS off (no edge shedding) so records REACH the operator, which
    # must answer each with a typed Overloaded record — ack, never replay.
    keys = [None] * 4
    outs, dlq, snap = run(
        _run_qos_e2e(keys, shed_level=2.0, spout_qos=False), timeout=120)
    assert len(outs) == 4 and len(dlq) == 0
    for r in outs:
        msg = json.loads(r.value)
        assert msg["overloaded"] is True
        assert msg["shed_level"] == 2
    assert snap["inference-bolt"]["shed_rejected"] == 4
    assert snap["inference-bolt"].get("instances_inferred", 0) == 0
    assert snap["kafka-spout"]["tree_acked"] == 4


# ---- UI /qos route -----------------------------------------------------------


class _TrickleSpout(Spout):
    def open(self, context, collector):
        super().open(context, collector)
        self.n = 0

    async def next_tuple(self):
        await asyncio.sleep(0.01)
        await self.collector.emit(Values([self.n]), msg_id=self.n)
        self.n += 1
        return True

    def ack(self, msg_id):
        pass

    def fail(self, msg_id):
        pass


class _EchoBolt(Bolt):
    async def execute(self, t):
        self.collector.ack(t)


async def _http_get(port, path):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(
        (f"GET {path} HTTP/1.1\r\nHost: localhost\r\n"
         f"Content-Length: 0\r\nConnection: close\r\n\r\n").encode())
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), json.loads(body)


def test_ui_qos_route_serves_shed_state(run):
    from storm_tpu.runtime.ui import UIServer

    async def go():
        tb = TopologyBuilder()
        tb.set_spout("spout", _TrickleSpout(), parallelism=1)
        tb.set_bolt("echo", _EchoBolt(), parallelism=1)\
            .shuffle_grouping("spout")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("demo", Config(), tb.build())
        ui = await UIServer(cluster, port=0).start()
        try:
            ctl = LoadShedController(rt, ShedPolicy())
            ctl._set_level(1, "shed", {"inbox_frac": 0.9,
                                       "wait_p95_ms": 0.0,
                                       "breach_rate": 3.0})
            st, body = await _http_get(
                ui.port, "/api/v1/topology/demo/qos")
            assert st == 200
            assert body["topology"] == "demo"
            assert body["shed_level"] == 1
            assert body["decisions"] == [
                {"direction": "shed", "from": 0, "to": 1}]
            assert body["qos"]["shed_level"] == 1.0
            assert body["qos"]["shed_decisions"] == 1
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)
