"""Connector tests: memory broker semantics, spout offset policies,
sink ack modes (reference KafkaSpout config MainTopology.java:95-106 and
KafkaBolt.java:116-166)."""

import asyncio

import pytest

from storm_tpu.config import Config, OffsetsConfig, SinkConfig
from storm_tpu.connectors import BrokerSink, BrokerSpout, MemoryBroker
from storm_tpu.connectors.sink import Producer
from storm_tpu.runtime import TopologyBuilder
from storm_tpu.runtime.cluster import AsyncLocalCluster


# ---- broker ------------------------------------------------------------------


def test_broker_produce_fetch_offsets():
    b = MemoryBroker(default_partitions=2)
    for i in range(10):
        b.produce("t", f"v{i}")
    assert b.topic_size("t") == 10
    total = sum(len(b.fetch("t", p, 0, 100)) for p in range(2))
    assert total == 10
    assert b.latest_offset("t", 0) + b.latest_offset("t", 1) == 10


def test_broker_key_partition_affinity():
    b = MemoryBroker(default_partitions=4)
    parts = {b.produce("t", "v", key="samekey")[0] for _ in range(10)}
    assert len(parts) == 1


def test_broker_commit_roundtrip():
    b = MemoryBroker()
    assert b.committed("g", "t", 0) is None
    b.commit("g", "t", 0, 7)
    assert b.committed("g", "t", 0) == 7


# ---- spout policies ----------------------------------------------------------


async def _spout_run(broker, offsets, produce_before, produce_after, wait=1.0):
    from tests.test_runtime import CaptureBolt

    CaptureBolt.seen = None
    for v in produce_before:
        broker.produce("in", v)
    cluster = AsyncLocalCluster()
    tb = TopologyBuilder()
    tb.set_spout("spout", BrokerSpout(broker, "in", offsets), 2)
    tb.set_bolt("cap", CaptureBolt(), 2).shuffle_grouping("spout")
    rt = await cluster.submit("t", Config(), tb.build())
    await asyncio.sleep(0.1)
    for v in produce_after:
        broker.produce("in", v)
    deadline = asyncio.get_event_loop().time() + wait
    while asyncio.get_event_loop().time() < deadline:
        if CaptureBolt.seen and len(CaptureBolt.seen) >= len(produce_after) + len(
            produce_before
        ):
            break
        await asyncio.sleep(0.02)
    await rt.drain(timeout_s=5)
    seen = sorted(m for _, m in (CaptureBolt.seen or []))
    await cluster.shutdown()
    return seen


def test_latest_policy_skips_backlog(run):
    """Reference semantics: start at log end — backlog invisible
    (MainTopology.java:101-103)."""
    broker = MemoryBroker(default_partitions=2)
    seen = run(
        _spout_run(
            broker,
            OffsetsConfig(policy="latest", max_behind=0),
            produce_before=["old1", "old2"],
            produce_after=["new1", "new2", "new3"],
        )
    )
    assert seen == ["new1", "new2", "new3"]


def test_earliest_policy_replays_backlog(run):
    broker = MemoryBroker(default_partitions=2)
    seen = run(
        _spout_run(
            broker,
            OffsetsConfig(policy="earliest", max_behind=None),
            produce_before=["a", "b"],
            produce_after=["c"],
        )
    )
    assert seen == ["a", "b", "c"]


def test_resume_policy_commits_and_resumes(run):
    broker = MemoryBroker(default_partitions=1)
    offsets = OffsetsConfig(policy="resume", max_behind=None, group_id="g1")
    seen1 = run(
        _spout_run(broker, offsets, produce_before=["a", "b"], produce_after=[])
    )
    assert seen1 == ["a", "b"]
    # Second run with same group resumes after committed offset.
    seen2 = run(
        _spout_run(broker, offsets, produce_before=[], produce_after=["c", "d"])
    )
    assert seen2 == ["c", "d"]
    assert broker.committed("g1", "in", 0) == 4


# ---- sink ack modes ----------------------------------------------------------


class FlakyProducer(Producer):
    """Fails the first N sends."""

    def __init__(self, broker, fail_first=0):
        self.broker = broker
        self.fail_first = fail_first
        self.sent = 0

    async def send(self, topic, value, key):
        if self.sent < self.fail_first:
            self.sent += 1
            raise IOError("delivery failed")
        self.sent += 1
        self.broker.produce(topic, value, key)


def _sink_with(broker, mode, fail_first=0):
    class TestSink(BrokerSink):
        def make_producer(self):  # the mkProducer test seam
            return FlakyProducer(broker, fail_first)

    return TestSink(broker, "out", SinkConfig(mode=mode))


async def _sink_run(broker, sink, items):
    from tests.test_runtime import ListSpout

    cluster = AsyncLocalCluster()
    tb = TopologyBuilder()
    spout = ListSpout(items)
    tb.set_spout("s", spout, 1)
    tb.set_bolt("sink", sink, 1).shuffle_grouping("s")
    rt = await cluster.submit("t", Config(), tb.build())
    deadline = asyncio.get_event_loop().time() + 5
    while asyncio.get_event_loop().time() < deadline:
        live = rt.spout_execs["s"][0].spout
        if len(live.acked) + len(live.failed) >= len(items):
            break
        await asyncio.sleep(0.01)
    await asyncio.sleep(0.05)  # let async send tasks settle
    live = rt.spout_execs["s"][0].spout
    res = (list(live.acked), list(live.failed))
    await cluster.shutdown()
    return res


@pytest.mark.parametrize("mode", ["async", "sync"])
def test_sink_delivery_ack(run, mode):
    broker = MemoryBroker()
    acked, failed = run(_sink_run(broker, _sink_with(broker, mode), ["a", "b"]))
    assert sorted(acked) == ["a", "b"] and failed == []
    assert broker.topic_size("out") == 2


@pytest.mark.parametrize("mode", ["async", "sync"])
def test_sink_delivery_failure_fails_tuple(run, mode):
    """Producer error -> tuple failed -> spout replay (KafkaBolt.java:137)."""
    broker = MemoryBroker()
    acked, failed = run(
        _sink_run(broker, _sink_with(broker, mode, fail_first=1), ["a"])
    )
    assert failed == ["a"] and acked == []
    assert broker.topic_size("out") == 0


def test_sink_fire_and_forget_acks_despite_failure(run):
    """fire-and-forget acks immediately, errors dropped (KafkaBolt.java:153-155)."""
    broker = MemoryBroker()
    acked, failed = run(
        _sink_run(broker, _sink_with(broker, "fire_and_forget", fail_first=1), ["a"])
    )
    assert acked == ["a"] and failed == []


def test_sink_null_topic_warns_and_acks(run):
    """None topic -> ack without send (KafkaBolt.java:156-159)."""
    broker = MemoryBroker()
    sink = BrokerSink(broker, None, SinkConfig(mode="sync"))
    acked, failed = run(_sink_run(broker, sink, ["a"]))
    assert acked == ["a"] and failed == []
    assert broker.topic_size("out") == 0


# ---- fail-path at-least-once invariants (blocking brokers) -------------------


class _SlowLatestBroker(MemoryBroker):
    """Blocking broker whose latest_offset waits on an event (simulating a
    network round-trip) or raises (simulating broker downtime)."""

    blocking = True

    def __init__(self):
        super().__init__(default_partitions=1)
        self.gate = asyncio.Event()
        self.raise_on_latest = False
        self._loop = None

    def latest_offset(self, topic, partition):
        if self.raise_on_latest:
            raise OSError("broker unreachable")
        if self._loop is not None:
            # Called from a to_thread worker: block until the test opens the gate.
            import concurrent.futures
            fut = asyncio.run_coroutine_threadsafe(self.gate.wait(), self._loop)
            fut.result(timeout=5)
        return super().latest_offset(topic, partition)


def _make_failing_spout(broker):
    """A BrokerSpout wired with the minimum context to exercise fail()."""
    from storm_tpu.runtime.metrics import MetricsRegistry

    spout = BrokerSpout(broker, "in", OffsetsConfig(policy="earliest", max_behind=0))

    class Ctx:
        parallelism = 1
        task_index = 0
        component_id = "spout"
        metrics = MetricsRegistry()

    class Coll:
        async def emit(self, *a, **k):
            return 1

    spout.open(Ctx(), Coll())
    return spout


def test_blocking_fail_keeps_record_visible_during_staleness_check(run):
    """While the async staleness check is in flight, the failed record must
    already sit in `replay` so ack()'s low-water commit scan sees it — a
    commit racing past an undecided failure would break at-least-once."""

    async def body():
        broker = _SlowLatestBroker()
        broker.produce("in", "v0")
        spout = _make_failing_spout(broker)
        broker._loop = asyncio.get_running_loop()
        rec = broker.fetch("in", 0, 0, 10)[0]
        spout.pending[(0, rec.offset)] = rec
        spout.fail((0, rec.offset))
        # Verdict still pending (gate closed): record must be in replay NOW.
        assert rec in spout.replay
        broker.produce("in", "fresh")  # makes offset 0 stale (max_behind=0)
        broker.gate.set()
        for _ in range(100):
            if rec not in spout.replay:
                break
            await asyncio.sleep(0.01)
        assert rec not in spout.replay  # stale verdict removed it
        assert spout.dropped == 1

    run(body())


def test_blocking_fail_broker_error_keeps_record_for_replay(run):
    """If the staleness probe raises (broker down), the record must stay
    queued for replay — never silently dropped."""

    async def body():
        broker = _SlowLatestBroker()
        broker.produce("in", "v0")
        spout = _make_failing_spout(broker)
        broker.raise_on_latest = True
        rec = broker.fetch("in", 0, 0, 10)[0]
        spout.pending[(0, rec.offset)] = rec
        spout.fail((0, rec.offset))
        await asyncio.sleep(0.05)  # let the background check run and raise
        assert rec in spout.replay
        assert spout.dropped == 0

    run(body())


# ---- consumer-group-protocol spout mode --------------------------------------


def test_spout_group_protocol_splits_partitions(run):
    """Two spout tasks with offsets.group_protocol=True get their partitions
    from JoinGroup/SyncGroup coordination instead of task-index modulo, and
    together consume everything exactly the static mode would."""
    import json as _json
    import sys as _sys

    _sys.path.insert(0, "tests")
    from kafka_stub import KafkaStubBroker
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker
    from storm_tpu.runtime import Bolt, TopologyBuilder
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    class Gather(Bolt):
        got = None

        def prepare(self, context, collector):
            super().prepare(context, collector)
            if Gather.got is None:
                Gather.got = []

        async def execute(self, t):
            Gather.got.append(t.get("message"))
            self.collector.ack(t)

    async def go():
        Gather.got = None
        stub = KafkaStubBroker(partitions=4)
        try:
            broker = KafkaWireBroker(f"127.0.0.1:{stub.port}")
            for i in range(12):
                broker.produce("gin", f"m{i}", key=str(i))

            cfg = Config()
            tb = TopologyBuilder()
            tb.set_spout(
                "spout",
                BrokerSpout(broker, "gin",
                            OffsetsConfig(policy="earliest", max_behind=None,
                                          group_id="gspout",
                                          group_protocol=True)),
                parallelism=2,
            )
            tb.set_bolt("gather", Gather(), parallelism=1)\
                .shuffle_grouping("spout")
            cluster = AsyncLocalCluster()
            rt = await cluster.submit("gp", cfg, tb.build())
            spouts = [e.spout for e in rt.spout_execs["spout"]]
            deadline = asyncio.get_event_loop().time() + 60
            while asyncio.get_event_loop().time() < deadline:
                # settle BOTH conditions: the rebalanced 2/2 split (the
                # second join races the first member's initial solo grab)
                # and full consumption
                split = sorted(len(s.my_partitions) for s in spouts)
                if split == [2, 2] and len(Gather.got or []) >= 12:
                    break
                await asyncio.sleep(0.1)
            assert sorted(len(s.my_partitions) for s in spouts) == [2, 2]
            owned = sorted(p for s in spouts for p in s.my_partitions)
            assert owned == [0, 1, 2, 3]
            await cluster.shutdown()
            # at-least-once across the handoff: partitions reassigned mid-run
            # are re-read from 'earliest' by their new owner (duplicates are
            # the correct policy outcome; nothing may be LOST)
            assert set(Gather.got) == {f"m{i}" for i in range(12)}
        finally:
            stub.close()

    run(go(), timeout=120)


def test_topology_over_scram_authenticated_broker(run):
    """Full spout -> bolt -> sink path over a SCRAM-authenticated wire
    broker, with the security dict built from BrokerConfig — the daemon's
    config surface. Every connection (spout fetch, sink produce, metadata)
    authenticates via the RFC 5802 exchange."""
    import sys as _sys

    _sys.path.insert(0, "tests")
    from kafka_stub import KafkaStubBroker
    from storm_tpu.config import BrokerConfig
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker
    from storm_tpu.runtime import Bolt

    class Echo(Bolt):
        async def execute(self, t):
            await self.collector.emit([t.get("message")], anchors=[t])
            self.collector.ack(t)

    async def go():
        stub = KafkaStubBroker(partitions=2)
        stub.sasl = ("svc", "scram-pw")
        stub.sasl_mechanism = "SCRAM-SHA-256"
        try:
            bcfg = BrokerConfig(
                kind="kafka", bootstrap=f"127.0.0.1:{stub.port}",
                security_protocol="SASL_PLAINTEXT",
                sasl_mechanism="SCRAM-SHA-256",
                sasl_username="svc", sasl_password="scram-pw")
            broker = KafkaWireBroker(bcfg.bootstrap,
                                     security=bcfg.security_dict())
            for i in range(6):
                broker.produce("sin", f"r{i}", key=str(i))
            cfg = Config()
            tb = TopologyBuilder()
            tb.set_spout("spout", BrokerSpout(
                broker, "sin",
                OffsetsConfig(policy="earliest", max_behind=None)),
                parallelism=1)
            tb.set_bolt("echo", Echo(), parallelism=1)\
                .shuffle_grouping("spout")
            tb.set_bolt("sink", BrokerSink(broker, "sout", cfg.sink),
                        parallelism=1).shuffle_grouping("echo")
            cluster = AsyncLocalCluster()
            rt = await cluster.submit("scram-topo", cfg, tb.build())
            got = set()
            deadline = asyncio.get_event_loop().time() + 60
            while asyncio.get_event_loop().time() < deadline:
                for p in range(2):
                    for rec in broker.client.fetch("sout", p, 0,
                                                   max_wait_ms=10):
                        got.add(rec.value.decode())
                if len(got) >= 6:
                    break
                await asyncio.sleep(0.1)
            assert got == {f"r{i}" for i in range(6)}
            await rt.drain(timeout_s=20)
            await cluster.shutdown()
        finally:
            stub.close()

    run(go(), timeout=120)


def test_spout_group_protocol_requires_wire_broker():
    from storm_tpu.runtime.base import OutputCollector

    broker = MemoryBroker()
    # group_protocol without a pinned group_id is itself a config error
    with pytest.raises(ValueError, match="group_id"):
        OffsetsConfig(group_protocol=True)
    spout = BrokerSpout(broker, "t",
                        OffsetsConfig(group_protocol=True, group_id="g"))

    class Ctx:
        task_index = 0
        parallelism = 1
        component_id = "s"
        config = None
        metrics = None

    with pytest.raises(ValueError, match="wire-protocol broker"):
        spout.open(Ctx(), None)


def test_spout_seek_replays_and_skips(run):
    """request_seek('earliest') reprocesses the log; seek('latest') skips
    backlog; negative seek replays the last N records."""
    import asyncio
    import json as _json

    from storm_tpu.config import Config
    from storm_tpu.connectors import BrokerSpout, MemoryBroker
    from storm_tpu.runtime import Bolt, TopologyBuilder
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    broker = MemoryBroker(default_partitions=2)
    for i in range(10):
        broker.produce("t", _json.dumps({"i": i}))

    class Count(Bolt):
        seen = []

        async def execute(self, t):
            Count.seen.append(t.values[0])
            self.collector.ack(t)

    async def settle_at(n, timeout=15.0):
        deadline = asyncio.get_event_loop().time() + timeout
        while asyncio.get_event_loop().time() < deadline:
            if len(Count.seen) >= n:
                await asyncio.sleep(0.3)  # let any extras surface
                return
            await asyncio.sleep(0.05)
        raise AssertionError(f"timed out at {len(Count.seen)}/{n}")

    async def go():
        from storm_tpu.connectors.spout import OffsetsConfig

        Count.seen = []
        tb = TopologyBuilder()
        tb.set_spout("s", BrokerSpout(
            broker, "t", OffsetsConfig(policy="earliest")), 1)
        tb.set_bolt("c", Count(), 1).shuffle_grouping("s")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("seek", Config(), tb.build())
        await settle_at(10)
        assert len(Count.seen) == 10

        # full replay
        n = await rt.seek("s", "earliest")
        assert n == 1
        await settle_at(20)
        assert len(Count.seen) == 20

        # seek latest: new backlog produced BEFORE the seek applies is
        # skipped once the spout repositions
        await rt.seek("s", "latest")
        await asyncio.sleep(0.3)
        before = len(Count.seen)
        broker.produce("t", _json.dumps({"i": 99}))
        await settle_at(before + 1)
        assert len(Count.seen) == before + 1

        # negative: replay ~last 2 per partition
        await rt.seek("s", -2)
        await asyncio.sleep(0.5)
        assert len(Count.seen) > before + 1

        # unknown / non-spout components error
        with pytest.raises(KeyError):
            await rt.seek("nope", "earliest")
        with pytest.raises(KeyError):
            await rt.seek("c", "earliest")
        await cluster.shutdown()

    run(go(), timeout=60)


def test_transactional_sink_commit_and_abort(run):
    """TransactionalSink: a failing commit aborts all-or-nothing (records
    never partially visible) and fails the tuples for replay; the replay
    commits in a new transaction and every record appears exactly once."""
    import asyncio
    import json as _json

    from storm_tpu.config import Config
    from storm_tpu.connectors import MemoryBroker, TransactionalBrokerSink
    from storm_tpu.runtime import TopologyBuilder
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    class FlakyTxn:
        """Fails the first commit, then delegates (deterministic chaos)."""

        def __init__(self, inner):
            self._inner = inner
            self.fail_next = 1

        def begin(self):
            self._inner.begin()

        def produce(self, *a, **kw):
            self._inner.produce(*a, **kw)

        def commit(self):
            if self.fail_next:
                self.fail_next -= 1
                self._inner.abort()
                raise RuntimeError("injected commit failure")
            self._inner.commit()

        def abort(self):
            self._inner.abort()

    class FlakyBroker(MemoryBroker):
        def txn(self, txn_id):
            return FlakyTxn(super().txn(txn_id))

    from storm_tpu.runtime import Spout, Values

    class ReplaySpout(Spout):
        def open(self, ctx, col):
            super().open(ctx, col)
            self.q = [f"m{i}" for i in range(6)] if ctx.task_index == 0 else []
            self.done = []

        async def next_tuple(self):
            if not self.q:
                return False
            m = self.q.pop(0)
            await self.collector.emit(Values([m]), msg_id=m)
            return True

        def ack(self, msg_id):
            self.done.append(msg_id)

        def fail(self, msg_id):
            self.q.append(msg_id)  # replay

    async def main():
        broker = FlakyBroker()
        tb = TopologyBuilder()
        tb.set_spout("s", ReplaySpout(), 1)
        from storm_tpu.config import SinkConfig

        tb.set_bolt("sink", TransactionalBrokerSink(
            broker, "out",
            SinkConfig(mode="transactional", txn_batch=3, txn_ms=30.0)), 1)\
            .shuffle_grouping("s")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("txn", Config(), tb.build())
        deadline = asyncio.get_event_loop().time() + 20
        while asyncio.get_event_loop().time() < deadline:
            if broker.topic_size("out") >= 6:
                break
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.3)
        recs = broker.drain_topic("out")
        vals = sorted(r.value.decode() for r in recs)
        assert vals == [f"m{i}" for i in range(6)], vals  # exactly once
        snap = rt.metrics.snapshot()
        assert snap["sink"]["txn_aborts"] == 1
        assert snap["sink"]["txn_commits"] >= 2
        await cluster.shutdown()

    run(main(), timeout=60)


def test_transactional_sink_rearms_deadline_after_own_flush(run):
    """Tuples that arrive WHILE a deadline-triggered flush is committing
    must get a fresh deadline timer: the flushing task is the deadline task
    itself (`.done()` is False), so the old re-arm check skipped them and
    they sat unacked until tree-timeout replay — the double-commit the
    re-arm exists to prevent. Regression for ADVICE r1 (sink.py:303)."""
    import time as _time

    from storm_tpu.config import Config, SinkConfig
    from storm_tpu.connectors import MemoryBroker, TransactionalBrokerSink
    from storm_tpu.runtime import Spout, TopologyBuilder, Values
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    class SlowTxn:
        def __init__(self, inner):
            self._inner = inner

        def begin(self):
            self._inner.begin()

        def produce(self, *a, **kw):
            self._inner.produce(*a, **kw)

        def commit(self):
            _time.sleep(0.25)  # commit in flight while tuple "b" arrives
            self._inner.commit()

        def abort(self):
            self._inner.abort()

    class SlowBroker(MemoryBroker):
        blocking = True  # sink runs txns on a worker thread

        def txn(self, txn_id):
            return SlowTxn(super().txn(txn_id))

    class TwoPhaseSpout(Spout):
        def open(self, ctx, col):
            super().open(ctx, col)
            self.plan = [("a", 0.0), ("b", 0.1)] if ctx.task_index == 0 else []
            self.t0 = _time.monotonic()
            self.acked, self.failed = [], []

        async def next_tuple(self):
            if not self.plan:
                return False
            m, at = self.plan[0]
            if _time.monotonic() - self.t0 < at:
                return False
            self.plan.pop(0)
            await self.collector.emit(Values([m]), msg_id=m)
            return True

        def ack(self, msg_id):
            self.acked.append(msg_id)

        def fail(self, msg_id):
            self.failed.append(msg_id)

    async def main():
        broker = SlowBroker()
        tb = TopologyBuilder()
        tb.set_spout("s", TwoPhaseSpout(), 1)
        # batch=100 so only the deadline (30ms) ever triggers a flush:
        # t=30ms flush("a") starts, commit blocks 250ms; t=100ms "b" arrives
        # mid-flush; the re-armed deadline must flush "b" ~30ms after.
        tb.set_bolt("sink", TransactionalBrokerSink(
            broker, "out",
            SinkConfig(mode="transactional", txn_batch=100, txn_ms=30.0)), 1)\
            .shuffle_grouping("s")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("txn-rearm", Config(), tb.build())
        spout = rt.spout_execs["s"][0].spout
        deadline = asyncio.get_event_loop().time() + 3.0
        while asyncio.get_event_loop().time() < deadline:
            if len(spout.acked) >= 2:
                break
            await asyncio.sleep(0.02)
        # Well under any tree timeout: both tuples committed+acked promptly.
        assert sorted(spout.acked) == ["a", "b"], (spout.acked, spout.failed)
        assert spout.failed == []
        recs = broker.drain_topic("out")
        assert sorted(r.value.decode() for r in recs) == ["a", "b"]
        await cluster.shutdown()

    run(main(), timeout=30)


def test_append_root_ts_clamps_future_timestamps():
    """A producer with a skewed-forward clock must not yield negative
    latency: the ingress clock clamps record age at 0."""
    import time as _time

    from storm_tpu.connectors.memory import Record
    from storm_tpu.connectors.spout import BrokerSpout

    spout = object.__new__(BrokerSpout)  # _append_root_ts reads no state
    now = _time.perf_counter()
    past = Record("t", 0, 0, None, b"v", _time.time() - 1.5)
    future = Record("t", 0, 1, None, b"v", _time.time() + 60.0)
    ts_past = spout._append_root_ts(past)
    ts_future = spout._append_root_ts(future)
    assert 1.3 <= now - ts_past <= 1.8  # ~1.5s of age preserved
    assert ts_future <= _time.perf_counter()  # clamped, never negative age

    # Kafka baseTimestamp=-1 sentinel (no producer timestamp) decodes to
    # ts<=0; the clock must fall back to age 0, not an epoch-scale age
    # that poisons the e2e histograms.
    sentinel = Record("t", 0, 2, None, b"v", -0.001)
    zero = Record("t", 0, 3, None, b"v", 0.0)
    for rec in (sentinel, zero):
        before = _time.perf_counter()
        ts = spout._append_root_ts(rec)
        assert before <= ts <= _time.perf_counter()  # age ~0


# ---- EOS fan-out: whole tree per transaction (ADVICE r3-high) ----------------


def _eos_fanout_harness(group: str, fan: int, violations: list):
    """Shared fixtures for the EOS fan-out tests: a broker whose
    transactions record, at every commit, (a) duplicate output values and
    (b) any committed source offset not fully covered by its tree's
    outputs in the topic — the two ways a split tree breaks exactly-once —
    plus the 1->fan splitter bolt that creates such trees."""
    from storm_tpu.runtime import Bolt, Values

    class RecTxn:
        def __init__(self, inner, broker):
            self._inner, self._broker = inner, broker

        def begin(self):
            self._inner.begin()

        def produce(self, *a, **kw):
            self._inner.produce(*a, **kw)

        def send_offsets(self, *a, **kw):
            self._inner.send_offsets(*a, **kw)

        def abort(self):
            self._inner.abort()

        def commit(self):
            self._inner.commit()
            out_vals = [r.value.decode()
                        for r in self._broker.drain_topic("out")]
            if len(out_vals) != len(set(out_vals)):
                violations.append(("dupes", sorted(out_vals)))
            uniq = set(out_vals)
            for p in range(2):
                k = self._broker.committed(group, "in", p)
                if k is None:
                    continue
                for rec in self._broker.fetch("in", p, 0, 100)[:k]:
                    v = rec.value.decode()
                    missing = [j for j in range(fan)
                               if f"{v}/{j}" not in uniq]
                    if missing:
                        violations.append((v, missing))

    class RecBroker(MemoryBroker):
        def txn(self, txn_id):
            return RecTxn(super().txn(txn_id), self)

    class SplitBolt(Bolt):
        async def execute(self, t):
            for j in range(fan):
                await self.collector.emit(
                    Values([f'{t.get("message")}/{j}']), anchors=[t])
            self.collector.ack(t)

    return RecBroker, SplitBolt


def test_eos_fanout_whole_tree_single_txn(run):
    """One spout entry fanning out to multiple sink tuples must commit ALL
    its outputs + its source offsets in ONE transaction even when txn_batch
    would split the tree (ADVICE r3-high, sink.py fold-on-first-sight).
    A recording txn asserts, at every commit, that a committed source
    offset is fully covered by its tree's outputs already in the topic —
    never an offset ahead of unproduced siblings."""
    from storm_tpu.connectors import TransactionalBrokerSink
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    G = "eos-fan"
    FAN = 3
    violations = []
    RecBroker, SplitBolt = _eos_fanout_harness(G, FAN, violations)

    async def main():
        broker = RecBroker(default_partitions=2)
        for i in range(8):
            broker.produce("in", f"r{i}", partition=i % 2)
        tb = TopologyBuilder()
        tb.set_spout("s", BrokerSpout(
            broker, "in",
            OffsetsConfig(policy="txn", group_id=G, max_behind=None)), 1)
        tb.set_bolt("mid", SplitBolt(), 1).shuffle_grouping("s")
        # txn_batch=2 < FAN: fold-on-first-sight would commit the entry's
        # offset in a transaction holding only part of its tree.
        tb.set_bolt("sink", TransactionalBrokerSink(
            broker, "out",
            SinkConfig(mode="transactional", txn_batch=2, txn_ms=20.0,
                       offsets_group=G)), 1).shuffle_grouping("mid")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("fan", Config(), tb.build())
        deadline = asyncio.get_event_loop().time() + 25
        while asyncio.get_event_loop().time() < deadline:
            if (broker.topic_size("out") >= 8 * FAN
                    and all(broker.committed(G, "in", p) == 4
                            for p in range(2))):
                break
            await asyncio.sleep(0.05)
        snap = rt.metrics.snapshot()
        await cluster.shutdown()
        assert violations == [], violations
        vals = sorted(r.value.decode() for r in broker.drain_topic("out"))
        assert vals == sorted(
            f"r{i}/{j}" for i in range(8) for j in range(FAN)), vals
        committed = {p: broker.committed(G, "in", p) for p in range(2)}
        assert committed == {0: 4, 1: 4}, committed
        # parking actually engaged (the batch boundary DID split the tree)
        assert snap["sink"]["txn_offsets_deferred"] > 0, snap["sink"]

    run(main(), timeout=60)


def test_eos_offsets_group_rejects_parallel_sink(run):
    """offsets_group + sink parallelism > 1 must fail loudly at prepare: a
    fan-out tree split across sink executors can close in neither (each
    sees live edges held by the other), so parked tuples would replay
    forever."""
    from storm_tpu.connectors import TransactionalBrokerSink
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    async def main():
        broker = MemoryBroker(default_partitions=2)
        tb = TopologyBuilder()
        tb.set_spout("s", BrokerSpout(
            broker, "in",
            OffsetsConfig(policy="txn", group_id="g", max_behind=None)), 1)
        tb.set_bolt("sink", TransactionalBrokerSink(
            broker, "out",
            SinkConfig(mode="transactional", offsets_group="g")),
            2).shuffle_grouping("s")
        cluster = AsyncLocalCluster()
        with pytest.raises(ValueError, match="parallelism 1"):
            await cluster.submit("fan2", Config(), tb.build())
        await cluster.shutdown()

    run(main(), timeout=30)


def test_eos_fanout_sibling_failure_no_partial_commit(run):
    """When one sibling of a fan-out tree fails mid-flight, the sink's
    parked siblings belong to a FAILED tree (ledger entry gone): they must
    be dropped, never produced or offset-committed — the replayed tree
    then commits whole. Guards the outstanding()==0 'gone means failed,
    not closed' distinction in _plan."""
    from storm_tpu.connectors import TransactionalBrokerSink
    from storm_tpu.runtime import Bolt, Values
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    G = "eos-fail"
    FAN = 3
    violations = []
    RecBroker, SplitBolt = _eos_fanout_harness(G, FAN, violations)

    class FlakyPass(Bolt):
        failed = False

        async def execute(self, t):
            v = t.get("message")
            if v.endswith("/1") and not FlakyPass.failed:
                FlakyPass.failed = True
                self.collector.fail(t)  # kills the whole tree
                return
            await self.collector.emit(Values([v]), anchors=[t])
            self.collector.ack(t)

    async def main():
        FlakyPass.failed = False
        broker = RecBroker(default_partitions=2)
        for i in range(4):
            broker.produce("in", f"r{i}", partition=i % 2)
        tb = TopologyBuilder()
        tb.set_spout("s", BrokerSpout(
            broker, "in",
            OffsetsConfig(policy="txn", group_id=G, max_behind=None)), 1)
        tb.set_bolt("split", SplitBolt(), 1).shuffle_grouping("s")
        tb.set_bolt("mid", FlakyPass(), 1).shuffle_grouping("split")
        tb.set_bolt("sink", TransactionalBrokerSink(
            broker, "out",
            SinkConfig(mode="transactional", txn_batch=2, txn_ms=20.0,
                       offsets_group=G)), 1).shuffle_grouping("mid")
        cluster = AsyncLocalCluster()
        await cluster.submit("fanfail", Config(), tb.build())
        deadline = asyncio.get_event_loop().time() + 25
        while asyncio.get_event_loop().time() < deadline:
            if (broker.topic_size("out") >= 4 * FAN
                    and all(broker.committed(G, "in", p) == 2
                            for p in range(2))):
                break
            await asyncio.sleep(0.05)
        await cluster.shutdown()
        assert violations == [], violations
        vals = sorted(r.value.decode() for r in broker.drain_topic("out"))
        assert vals == sorted(
            f"r{i}/{j}" for i in range(4) for j in range(FAN)), vals
        committed = {p: broker.committed(G, "in", p) for p in range(2)}
        assert committed == {0: 2, 1: 2}, committed

    run(main(), timeout=60)


def test_txn_small_chunk_warns(caplog):
    """offsets.policy='txn' below the measured 5x throughput cliff
    (chunk < 64, a CPU-host run of an earlier round) must warn
    loudly at open — the foot-gun is silent otherwise (VERDICT r3 #8)."""
    import logging

    from storm_tpu.runtime.metrics import MetricsRegistry

    class Ctx:
        parallelism = 1
        task_index = 0
        component_id = "spout"
        metrics = MetricsRegistry()

    class Coll:
        async def emit(self, *a, **k):
            return 1

    broker = MemoryBroker(default_partitions=2)
    with caplog.at_level(logging.WARNING, logger="storm_tpu.spout"):
        s = BrokerSpout(broker, "in",
                        OffsetsConfig(policy="txn", group_id="g",
                                      max_behind=None), chunk=4)
        s.open(Ctx(), Coll())
    assert any("spout_chunk" in r.message and "gated entry" in r.message
               for r in caplog.records), caplog.records

    # at or past the measured-free point: silent (on the spout's own
    # logger — caplog collects every logger's records, filter first)
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="storm_tpu.spout"):
        s2 = BrokerSpout(broker, "in2",
                         OffsetsConfig(policy="txn", group_id="g",
                                       max_behind=None), chunk=16)
        s2.open(Ctx(), Coll())
    assert not [r for r in caplog.records if r.name == "storm_tpu.spout"]


def test_eos_rebalance_to_parallel_sink_rolls_back(run):
    """Growing the offsets-committing sink past parallelism 1 must fail
    loudly AND leave the runtime intact: the rejected replica is rolled
    out of bolt_execs (a half-registered executor would swallow routed
    tuples forever) and the pipeline keeps flowing."""
    from storm_tpu.connectors import TransactionalBrokerSink
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    async def main():
        broker = MemoryBroker(default_partitions=2)
        for i in range(3):
            broker.produce("in", f"a{i}", partition=i % 2)
        tb = TopologyBuilder()
        tb.set_spout("s", BrokerSpout(
            broker, "in",
            OffsetsConfig(policy="txn", group_id="rb-g",
                          max_behind=None)), 1)
        tb.set_bolt("sink", TransactionalBrokerSink(
            broker, "out",
            SinkConfig(mode="transactional", txn_batch=2, txn_ms=20.0,
                       offsets_group="rb-g")), 1).shuffle_grouping("s")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("rb", Config(), tb.build())
        with pytest.raises(ValueError, match="parallelism 1"):
            await rt.rebalance("sink", 2)
        assert rt.parallelism_of("sink") == 1  # rolled back, not zombie
        for i in range(3, 6):
            broker.produce("in", f"a{i}", partition=i % 2)
        deadline = asyncio.get_event_loop().time() + 20
        while asyncio.get_event_loop().time() < deadline:
            if broker.topic_size("out") >= 6:
                break
            await asyncio.sleep(0.05)
        assert broker.topic_size("out") == 6  # still flowing after the raise
        await cluster.shutdown()

    run(main(), timeout=40)


def test_eos_tree_closure_commits_without_deadline_wait(run):
    """The tree-closure trigger: an entry whose tree is fully held must
    commit IMMEDIATELY, not after txn_ms/txn_batch — with a 30 s deadline
    and a huge batch, three single-record entries still flow in well
    under a second each (before the trigger, each gated entry waited the
    full deadline: measured 60 rec/s at chunk=1 on a 50 ms txn_ms)."""
    from storm_tpu.connectors import TransactionalBrokerSink
    from storm_tpu.runtime.cluster import AsyncLocalCluster
    from tests.test_runtime import PassBolt

    async def main():
        broker = MemoryBroker(default_partitions=1)
        for i in range(3):
            broker.produce("in", f"m{i}", partition=0)
        tb = TopologyBuilder()
        tb.set_spout("s", BrokerSpout(
            broker, "in",
            OffsetsConfig(policy="txn", group_id="cl-g",
                          max_behind=None)), 1)
        tb.set_bolt("mid", PassBolt(), 1).shuffle_grouping("s")
        # deadline and batch far beyond the test timeout: only the
        # closure trigger can commit these
        tb.set_bolt("sink", TransactionalBrokerSink(
            broker, "out",
            SinkConfig(mode="transactional", txn_batch=512,
                       txn_ms=30_000.0, offsets_group="cl-g")),
            1).shuffle_grouping("mid")
        cluster = AsyncLocalCluster()
        await cluster.submit("closure", Config(), tb.build())
        t0 = asyncio.get_event_loop().time()
        while asyncio.get_event_loop().time() - t0 < 10:
            if broker.topic_size("out") >= 3:
                break
            await asyncio.sleep(0.05)
        took = asyncio.get_event_loop().time() - t0
        await cluster.shutdown()
        assert broker.topic_size("out") == 3, broker.topic_size("out")
        assert took < 5.0, f"closure trigger too slow: {took:.1f}s"
        assert broker.committed("cl-g", "in", 0) == 3

    run(main(), timeout=40)
