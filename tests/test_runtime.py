"""Runtime-core tests: topology DSL, groupings, XOR acker, replay, rebalance.

Covers the Storm-layer semantics the reference inherits from storm-core
(SURVEY.md §1 layer 1, §2.5) using the in-process cluster the reference
never had (§4)."""

import asyncio

import pytest

import storm_tpu.runtime
from storm_tpu.config import Config
from storm_tpu.runtime import (
    Bolt,
    LocalCluster,
    Spout,
    TopologyBuilder,
    Tuple,
    Values,
)
from storm_tpu.runtime.acker import AckLedger
from storm_tpu.runtime.cluster import AsyncLocalCluster
from storm_tpu.runtime.tuples import new_id


class ListSpout(Spout):
    """Emits each item once; tracks acks/fails; replays failures once."""

    def __init__(self, items, replay_on_fail=False):
        self.items = list(items)
        self.replay_on_fail = replay_on_fail

    def open(self, context, collector):
        super().open(context, collector)
        self.queue = list(self.items) if context.task_index == 0 else []
        self.acked, self.failed = [], []

    async def next_tuple(self):
        if not self.queue:
            return False
        item = self.queue.pop(0)
        await self.collector.emit(Values([item]), msg_id=item)
        return True

    def ack(self, msg_id):
        self.acked.append(msg_id)

    def fail(self, msg_id):
        self.failed.append(msg_id)
        if self.replay_on_fail:
            self.queue.append(msg_id)
            self.replay_on_fail = False  # replay once only


class CaptureBolt(Bolt):
    seen = None  # class-level capture across deep-copied instances

    def prepare(self, context, collector):
        super().prepare(context, collector)
        if CaptureBolt.seen is None:
            CaptureBolt.seen = []

    async def execute(self, t):
        CaptureBolt.seen.append((self.context.task_index, t.get("message")))
        self.collector.ack(t)


class PassBolt(Bolt):
    async def execute(self, t):
        await self.collector.emit(Values([t.get("message")]), anchors=[t])
        self.collector.ack(t)


class FailOnceBolt(Bolt):
    failed_once = False

    async def execute(self, t):
        if not FailOnceBolt.failed_once:
            FailOnceBolt.failed_once = True
            self.collector.fail(t)
            return
        self.collector.ack(t)


class ExplodingBolt(Bolt):
    async def execute(self, t):
        raise RuntimeError("boom")


# ---- ledger unit tests -------------------------------------------------------


def test_ledger_basic_ack():
    led = AckLedger(timeout_s=0)
    done = []
    root = new_id()
    led.init_root(root, "m1", lambda m, ok, ts: done.append((m, ok)), 0.0)
    e1 = new_id()
    led.xor(root, e1)  # emit edge
    assert led.inflight == 1
    led.xor(root, e1)  # ack edge
    assert led.inflight == 0
    assert done == [("m1", True)]
    assert led.acked == 1


def test_ledger_multi_edge_tree():
    led = AckLedger(timeout_s=0)
    done = []
    root = new_id()
    led.init_root(root, "m", lambda m, ok, ts: done.append(ok), 0.0)
    e1, e2, e3 = new_id(), new_id(), new_id()
    led.xor(root, e1)          # spout -> boltA
    led.xor(root, e2)          # boltA emits child to boltB
    led.xor(root, e3)          # boltA emits child to boltC
    led.xor(root, e1)          # boltA acks input
    assert not done
    led.xor(root, e2)
    led.xor(root, e3)
    assert done == [True]


def test_ledger_fail_and_timeout():
    led = AckLedger(timeout_s=0.01)
    done = []
    r1, r2 = new_id(), new_id()
    led.init_root(r1, "a", lambda m, ok, ts: done.append((m, ok)), 0.0)
    led.xor(r1, new_id())
    led.fail_root(r1)
    assert done == [("a", False)]
    led.init_root(r2, "b", lambda m, ok, ts: done.append((m, ok)), 0.0)
    led.xor(r2, new_id())
    import time

    time.sleep(0.03)
    assert led.sweep() == 1
    assert done[-1] == ("b", False)


# ---- topology DSL ------------------------------------------------------------


def test_builder_validation():
    b = TopologyBuilder()
    b.set_spout("s", ListSpout([]), 1)
    b.set_bolt("x", CaptureBolt(), 1).shuffle_grouping("nope")
    with pytest.raises(ValueError):
        b.build()

    b2 = TopologyBuilder()
    b2.set_spout("s", ListSpout([]), 1)
    with pytest.raises(ValueError):
        b2.set_spout("s", ListSpout([]), 1)
    with pytest.raises(ValueError):
        b2.set_bolt("__sys", CaptureBolt(), 1)


# ---- end-to-end through the async cluster ------------------------------------


async def settle(rt, spout_id, n_items, timeout=10.0):
    """Wait until every spout-emitted tree completed (acked or failed)."""
    deadline = asyncio.get_event_loop().time() + timeout
    while asyncio.get_event_loop().time() < deadline:
        live = rt.spout_execs[spout_id][0].spout
        if len(live.acked) + len(live.failed) >= n_items:
            await rt.drain(timeout_s=timeout)
            return True
        await asyncio.sleep(0.01)
    return False


async def _run_simple(items, bolt, parallelism=2, cfg=None):
    cfg = cfg or Config()
    cluster = AsyncLocalCluster()
    b = TopologyBuilder()
    spout = ListSpout(items)
    b.set_spout("spout", spout, 1)
    b.set_bolt("bolt", bolt, parallelism).shuffle_grouping("spout")
    rt = await cluster.submit("t", cfg, b.build())
    ok = await settle(rt, "spout", len(items))
    # find the live spout instance to inspect acks
    live_spout = rt.spout_execs["spout"][0].spout
    await cluster.shutdown()
    return ok, live_spout, rt


def test_shuffle_delivers_all_and_acks(run):
    CaptureBolt.seen = None
    items = [f"m{i}" for i in range(50)]
    ok, spout, rt = run(_run_simple(items, CaptureBolt(), parallelism=3))
    assert ok
    assert sorted(m for _, m in CaptureBolt.seen) == sorted(items)
    assert sorted(spout.acked) == sorted(items)
    assert spout.failed == []
    # shuffle spreads across instances
    tasks = {t for t, _ in CaptureBolt.seen}
    assert len(tasks) == 3


def test_multi_hop_anchoring(run):
    """spout -> pass -> capture: tree acked only after both hops ack."""
    CaptureBolt.seen = None

    async def go():
        cluster = AsyncLocalCluster()
        b = TopologyBuilder()
        spout = ListSpout(["a", "b", "c"])
        b.set_spout("s", spout, 1)
        b.set_bolt("mid", PassBolt(), 2).shuffle_grouping("s")
        b.set_bolt("end", CaptureBolt(), 2).shuffle_grouping("mid")
        rt = await cluster.submit("t", Config(), b.build())
        assert await settle(rt, "s", 3)
        acked = list(rt.spout_execs["s"][0].spout.acked)
        await cluster.shutdown()
        return acked

    acked = run(go())
    assert sorted(acked) == ["a", "b", "c"]
    assert sorted(m for _, m in CaptureBolt.seen) == ["a", "b", "c"]


def test_explicit_fail_reaches_spout(run):
    FailOnceBolt.failed_once = False
    ok, spout, rt = run(_run_simple(["x"], FailOnceBolt(), parallelism=1))
    assert ok
    assert spout.failed == ["x"]


def test_uncaught_exception_fails_tuple(run):
    ok, spout, rt = run(_run_simple(["x", "y"], ExplodingBolt(), parallelism=1))
    assert ok
    assert sorted(spout.failed) == ["x", "y"]
    assert spout.acked == []
    assert len(rt.errors) == 2


def test_replay_after_fail(run):
    """Failed msg_id replayed by the spout completes on second attempt."""
    FailOnceBolt.failed_once = False

    async def go():
        cluster = AsyncLocalCluster()
        b = TopologyBuilder()
        spout = ListSpout(["r"], replay_on_fail=True)
        b.set_spout("s", spout, 1)
        b.set_bolt("f", FailOnceBolt(), 1).shuffle_grouping("s")
        rt = await cluster.submit("t", Config(), b.build())
        for _ in range(200):
            live = rt.spout_execs["s"][0].spout
            if live.acked:
                break
            await asyncio.sleep(0.02)
        live = rt.spout_execs["s"][0].spout
        res = (list(live.acked), list(live.failed))
        await cluster.shutdown()
        return res

    acked, failed = run(go())
    assert failed == ["r"]
    assert acked == ["r"]


def test_fields_grouping_affinity(run):
    """Same key always lands on the same task."""

    class KeySpout(ListSpout):
        def declare_output_fields(self):
            return {"default": ("message",)}

    CaptureBolt.seen = None

    async def go():
        cluster = AsyncLocalCluster()
        b = TopologyBuilder()
        items = [f"k{i % 4}" for i in range(40)]
        b.set_spout("s", KeySpout(items), 1)
        b.set_bolt("c", CaptureBolt(), 4).fields_grouping("s", "message")
        rt = await cluster.submit("t", Config(), b.build())
        assert await settle(rt, "s", 40)
        await cluster.shutdown()

    run(go())
    owner = {}
    for task, msg in CaptureBolt.seen:
        assert owner.setdefault(msg, task) == task


def test_rebalance_live(run):
    """Grow bolt parallelism mid-run; all tuples still delivered + acked."""
    CaptureBolt.seen = None

    async def go():
        cluster = AsyncLocalCluster()
        b = TopologyBuilder()
        spout = ListSpout([f"m{i}" for i in range(30)])
        b.set_spout("s", spout, 1)
        b.set_bolt("c", CaptureBolt(), 1).shuffle_grouping("s")
        rt = await cluster.submit("t", Config(), b.build())
        await asyncio.sleep(0.05)
        await rt.rebalance("c", 4)
        assert rt.parallelism_of("c") == 4
        assert await settle(rt, "s", 30)
        acked = list(rt.spout_execs["s"][0].spout.acked)
        await cluster.shutdown()
        return acked

    acked = run(go())
    assert len(acked) == 30
    assert len(CaptureBolt.seen) == 30


def test_deactivate_activate_pause_resume(run):
    """deactivate stops the spout pulling; activate resumes it; a spout
    grown while deactivated must come up paused (not emitting)."""
    CaptureBolt.seen = None

    async def go():
        cluster = AsyncLocalCluster()
        b = TopologyBuilder()
        n_items = 20000
        spout = ListSpout([f"m{i}" for i in range(n_items)])
        b.set_spout("s", spout, 1)
        b.set_bolt("c", CaptureBolt(), 1).shuffle_grouping("s")
        rt = await cluster.submit("t", Config(), b.build())
        await rt.deactivate()
        assert await rt.drain(timeout_s=30.0)
        spout = rt.spout_execs["s"][0].spout  # the live (cloned) instance
        paused_at = len(spout.acked)
        # while deactivated: grow the spout; the new task inherits paused
        await rt.rebalance("s", 2)
        assert all(not e._active for e in rt.spout_execs["s"])
        await asyncio.sleep(0.2)
        assert len(spout.acked) == paused_at  # nothing moved while paused
        await rt.activate()
        assert all(e._active for e in rt.spout_execs["s"])
        deadline = asyncio.get_event_loop().time() + 10
        while (asyncio.get_event_loop().time() < deadline
               and len(spout.acked) <= paused_at):
            await asyncio.sleep(0.01)
        resumed = len(spout.acked) > paused_at
        await cluster.shutdown()
        return paused_at, resumed

    paused_at, resumed = run(go())
    assert paused_at < 20000  # the pause bit mid-stream
    assert resumed


def test_sync_localcluster_facade():
    CaptureBolt.seen = None
    with LocalCluster() as cluster:
        b = TopologyBuilder()
        b.set_spout("s", ListSpout(["1", "2"]), 1)
        b.set_bolt("c", CaptureBolt(), 1).shuffle_grouping("s")
        cluster.submit_topology("t", Config(), b.build())
        import time

        for _ in range(500):
            snap = cluster.metrics("t")
            if snap.get("s", {}).get("tree_acked", 0) >= 2:
                break
            time.sleep(0.01)
        snap = cluster.metrics("t")
        assert snap["s"]["emitted"] == 2
        cluster.kill_topology("t")
    assert sorted(m for _, m in CaptureBolt.seen) == ["1", "2"]


def test_direct_grouping_emit_direct(run):
    """emit_direct(task, ...) reaches exactly the named instance of
    direct-grouped consumers (Storm's emitDirect contract); non-direct
    subscribers on the stream see nothing from direct emits."""
    CaptureBolt.seen = None

    class RouteBolt(Bolt):
        async def execute(self, t):
            # Route message "m<i>" to task i % 3 explicitly.
            i = int(t.values[0][1:])
            await self.collector.emit_direct(i % 3, Values(t.values),
                                             anchors=[t])
            self.collector.ack(t)

    async def go():
        cluster = AsyncLocalCluster()
        b = TopologyBuilder()
        spout = ListSpout([f"m{i}" for i in range(12)])
        b.set_spout("s", spout, 1)
        b.set_bolt("r", RouteBolt(), 1).shuffle_grouping("s")
        b.set_bolt("c", CaptureBolt(), 3).direct_grouping("r")
        rt = await cluster.submit("t", Config(), b.build())
        assert await settle(rt, "s", 12)
        await cluster.shutdown()

    run(go())
    assert len(CaptureBolt.seen) == 12
    for task, msg in CaptureBolt.seen:
        assert task == int(msg[1:]) % 3, (task, msg)


def test_none_and_custom_grouping(run):
    """none_grouping delivers everything; custom_grouping (a user Grouping
    subclass) steers tuples with its own choose()."""
    from storm_tpu.runtime import groupings as G

    CaptureBolt.seen = None

    class LastCharGrouping(G.Grouping):
        def choose(self, t):
            return (int(t.values[0][-1]) % self.n,)

    async def go():
        cluster = AsyncLocalCluster()
        b = TopologyBuilder()
        spout = ListSpout([f"m{i}" for i in range(10)])
        b.set_spout("s", spout, 1)
        b.set_bolt("p", PassBolt(), 2).none_grouping("s")
        b.set_bolt("c", CaptureBolt(), 2).custom_grouping("p", LastCharGrouping())
        rt = await cluster.submit("t", Config(), b.build())
        assert await settle(rt, "s", 10)
        await cluster.shutdown()

    run(go())
    assert len(CaptureBolt.seen) == 10
    for task, msg in CaptureBolt.seen:
        assert task == int(msg[-1]) % 2, (task, msg)


def test_partial_key_grouping_two_choices(run):
    """Every key lands on at most 2 instances (power-of-two-choices), and a
    heavily skewed key stream still spreads across instances — the balance
    FieldsGrouping can't give under skew."""
    CaptureBolt.seen = None

    class KeySpout(ListSpout):
        pass

    async def go():
        cluster = AsyncLocalCluster()
        b = TopologyBuilder()
        # 90% one hot key + a tail of others.
        items = ["hot"] * 36 + [f"k{i}" for i in range(4)]
        b.set_spout("s", KeySpout(items), 1)
        b.set_bolt("c", CaptureBolt(), 4).partial_key_grouping("s", "message")
        rt = await cluster.submit("t", Config(), b.build())
        assert await settle(rt, "s", 40)
        await cluster.shutdown()

    run(go())
    owners = {}
    for task, msg in CaptureBolt.seen:
        owners.setdefault(msg, set()).add(task)
    assert all(len(v) <= 2 for v in owners.values()), owners
    hot = owners["hot"]
    assert len(hot) == 2  # the skewed key used both its candidates


def test_stable_hash_groupings_cross_process_consistent():
    """FieldsGrouping/PartialKeyGrouping routing must not depend on the
    producer process's hash salt (dist mode: many producer workers)."""
    import os, pathlib, subprocess, sys

    root = str(pathlib.Path(__file__).resolve().parents[1])
    code = ("from storm_tpu.runtime.groupings import stable_hash;"
            "print(stable_hash(('user-42', 7)))")
    outs = {
        subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True,
                       env={**os.environ, "PYTHONPATH": root,
                            "PYTHONHASHSEED": str(seed)},
                       cwd=root).stdout.strip()
        for seed in (1, 2)
    }
    assert len(outs) == 1 and outs != {""}, outs


def test_ledger_live_edge_refcount_and_watch():
    """anchor/ack_edge maintain an exact outstanding-edge count alongside
    the XOR, and watch() fires on completion/failure — the queries the EOS
    sink's whole-tree-per-txn parking needs (ADVICE r3-high)."""
    led = AckLedger(timeout_s=0)
    root = new_id()
    led.init_root(root, "m", lambda *a: None, 0.0)
    e1, e2, e3 = new_id(), new_id(), new_id()
    led.anchor(root, e1)
    led.anchor(root, e2)
    assert led.outstanding(root) == 2
    led.ack_edge(root, e1)
    assert led.outstanding(root) == 1
    led.anchor(root, e3)
    assert led.outstanding(root) == 2
    fates = []
    assert led.watch(root, fates.append)
    led.ack_edge(root, e2)
    led.ack_edge(root, e3)
    assert led.outstanding(root) == 0  # gone == complete
    assert fates == [True]
    assert not led.watch(root, fates.append)  # entry gone -> not registered

    # failure path: watchers hear ok=False, count resets to 0
    r2 = new_id()
    led.init_root(r2, "m2", lambda *a: None, 0.0)
    led.anchor(r2, new_id())
    fates2 = []
    led.watch(r2, fates2.append)
    led.fail_root(r2)
    assert fates2 == [False]
    assert led.outstanding(r2) == 0


def test_ledger_tolerates_ack_before_anchor():
    """In dist topologies an edge's anchor (from the emitting worker) and
    ack (from the consuming worker) reach the root's owner over
    INDEPENDENT links and can arrive in either order. The refcount must
    never transiently dip — a dip could fake tree closure for the EOS
    sink (offsets committed past unproduced siblings) or fake tree death
    (spurious replays). Early acks park and cancel against their anchor."""
    done = []
    led = AckLedger(timeout_s=0)
    root = new_id()
    led.init_root(root, "m", lambda *a: done.append(a), 0.0)
    e_spout, e_fast, e_slow = new_id(), new_id(), new_id()
    led.anchor(root, e_spout)   # spout -> splitter delivery
    led.anchor(root, e_fast)    # splitter -> sink (fast link)
    # SLOW LINK: e_slow's anchor is delayed; its ack arrives first
    led.ack_edge(root, e_slow)
    assert led.outstanding(root) == 2  # no dip: parked, not subtracted
    led.ack_edge(root, e_spout)
    assert led.outstanding(root) == 1  # the sink's held tuple, correctly
    led.anchor(root, e_slow)    # delayed anchor lands: cancels the pair
    assert led.outstanding(root) == 1
    assert not done              # tree still open
    led.ack_edge(root, e_fast)
    assert led.outstanding(root) == 0
    assert done and done[0][1] is True  # completed exactly once


def test_merge_offsets_max_wins():
    from storm_tpu.runtime.tuples import merge_offsets

    dst = {("t", 0): 5}
    merge_offsets(dst, [(("t", 0), 3), (("t", 1), 7), (("t", 0), 9)])
    assert dst == {("t", 0): 9, ("t", 1): 7}


# ---- tick tuples (executor.py's ticker; the decode bolt's housekeeping) ------


class TickBolt(Bolt):
    """Counts its ticks and keeps what ``execute`` was handed. Class-level,
    as CaptureBolt: the builder deep-copies the instance per task."""

    tick_interval_s = 0.01  # no source of ticks: the topology's setting is
    ticks = 0
    executed = None
    gate = None  # an asyncio.Event to hold ``execute`` on, or None
    raising = False

    @classmethod
    def reset(cls, gate=None, raising=False):
        cls.ticks, cls.executed = 0, []
        cls.gate, cls.raising = gate, raising

    async def execute(self, t):
        TickBolt.executed.append(t)
        if TickBolt.gate is not None:
            await TickBolt.gate.wait()
        self.collector.ack(t)

    async def tick(self):
        TickBolt.ticks += 1
        if TickBolt.raising:
            raise RuntimeError("tick failed")


async def _ticked(items, tick_interval_s, inbox_capacity=4096):
    cfg = Config()
    cfg.topology.tick_interval_s = tick_interval_s
    cfg.topology.inbox_capacity = inbox_capacity
    cluster = AsyncLocalCluster()
    b = TopologyBuilder()
    b.set_spout("s", ListSpout(items), 1)
    b.set_bolt("t", TickBolt(), 1).shuffle_grouping("s")
    rt = await cluster.submit("ticks", cfg, b.build())
    return cluster, rt, rt.bolt_execs["t"][0]


async def until(cond, timeout=5.0):
    deadline = asyncio.get_event_loop().time() + timeout
    while not cond() and asyncio.get_event_loop().time() < deadline:
        await asyncio.sleep(0.005)
    return cond()


def test_a_bolt_is_ticked_at_the_topologys_interval(run):
    async def go():
        TickBolt.reset()
        cluster, rt, ex = await _ticked([], tick_interval_s=0.02)
        t0 = asyncio.get_event_loop().time()
        assert await until(lambda: TickBolt.ticks >= 3)
        took = asyncio.get_event_loop().time() - t0
        await cluster.shutdown()
        return took

    # three ticks need three intervals: the bolt's own 0.01 is not the clock
    assert run(go()) >= 0.05


def test_no_interval_no_ticks_whatever_the_bolt_says_of_itself(run):
    async def go():
        TickBolt.reset()
        cluster, rt, ex = await _ticked(["a"], tick_interval_s=0.0)
        assert await settle(rt, "s", 1)
        await asyncio.sleep(0.08)  # eight of the bolt's own 0.01
        assert ex._tick_task is None
        assert TickBolt.ticks == 0
        await cluster.shutdown()

    run(go())


def test_a_full_inbox_skips_the_tick_and_the_ticker_goes_on(run):
    async def go():
        gate = asyncio.Event()
        TickBolt.reset(gate=gate)
        # "a" is held in execute, "b" fills the inbox of one
        cluster, rt, ex = await _ticked(["a", "b"], 0.01, inbox_capacity=1)
        assert await until(lambda: ex.inbox.full() and TickBolt.executed)
        await asyncio.sleep(0.06)  # six intervals, each finding it full
        assert TickBolt.ticks == 0
        assert not ex._tick_task.done(), "the ticker stalled or died"
        gate.set()
        assert await until(lambda: TickBolt.ticks >= 2)
        assert [t.get("message") for t in TickBolt.executed] == ["a", "b"]
        await cluster.shutdown()

    run(go())


def test_a_tick_is_not_acked_not_counted_and_not_handed_to_execute(run):
    async def go():
        TickBolt.reset()
        cluster, rt, ex = await _ticked(["a", "b", "c"], tick_interval_s=0.01)
        assert await settle(rt, "s", 3)
        assert await until(lambda: TickBolt.ticks >= 3)
        spout = rt.spout_execs["s"][0].spout
        snap = rt.metrics.snapshot()["t"]
        await cluster.shutdown()
        return spout, snap, ex.n_executed, rt.ledger.inflight

    spout, snap, n_executed, inflight = run(go())
    assert [t.get("message") for t in TickBolt.executed] == ["a", "b", "c"]
    assert not any(t.stream == "__tick" for t in TickBolt.executed)
    assert snap["executed"] == 3 and n_executed == 3
    assert snap["execute_ms"]["count"] == 3
    assert sorted(spout.acked) == ["a", "b", "c"] and spout.failed == []
    assert inflight == 0


def test_a_tick_that_raises_is_reported_and_fails_no_tuple(run):
    async def go():
        TickBolt.reset(raising=True)
        cluster, rt, ex = await _ticked(["a"], tick_interval_s=0.01)
        assert await settle(rt, "s", 1)
        assert await until(lambda: TickBolt.ticks >= 2)
        spout = rt.spout_execs["s"][0].spout
        errors, n_errors = len(rt.errors), ex.n_errors
        alive = not ex._task.done()
        await cluster.shutdown()
        return spout, errors, n_errors, alive

    spout, errors, n_errors, alive = run(go())
    assert alive, "a failing tick must not end the executor"
    assert errors >= 2 and n_errors >= 2
    assert spout.acked == ["a"] and spout.failed == []


def test_the_ticker_is_cancelled_at_stop(run):
    async def go():
        TickBolt.reset()
        cluster, rt, ex = await _ticked([], tick_interval_s=0.01)
        assert await until(lambda: TickBolt.ticks >= 1)
        ticker = ex._tick_task
        await cluster.shutdown()
        await asyncio.sleep(0)  # let the cancellation land
        at_stop = TickBolt.ticks
        await asyncio.sleep(0.05)
        return ticker, at_stop

    ticker, at_stop = run(go())
    assert ticker.cancelled()
    assert TickBolt.ticks == at_stop


# ---- the package's own list of names ------------------------------------------


@pytest.mark.parametrize("name", storm_tpu.runtime.__all__)
def test_every_name_the_runtime_lists_is_there(name):
    assert getattr(storm_tpu.runtime, name).__module__.startswith(
        "storm_tpu.runtime.")
