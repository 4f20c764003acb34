"""Stateful-bolt tests: KeyValueState, checkpoint backends, restore across
supervisor restarts and across topology restarts (durable file backend).

The reference checkpoints nothing (SURVEY.md §5.4); this is the Storm
``IStatefulBolt``/``KeyValueState`` capability owned by the layer-1 runtime."""

import asyncio

import pytest

from storm_tpu.config import Config
from storm_tpu.runtime import (
    FileStateBackend,
    KeyValueState,
    MemoryStateBackend,
    StatefulBolt,
    TopologyBuilder,
    Values,
)
from storm_tpu.runtime.chaos import ChaosMonkey
from storm_tpu.runtime.cluster import AsyncLocalCluster

from test_runtime import ListSpout, until


class CountBolt(StatefulBolt):
    """Word-count: the canonical stateful operator."""

    async def execute(self, t):
        key = t.get("message")
        self.state.put(key, self.state.get(key, 0) + 1)
        self.collector.ack(t)


# ---- unit: state + backends --------------------------------------------------


def test_kv_state_basics():
    s = KeyValueState()
    assert not s.dirty
    s.put("a", 1)
    s.put("b", {"nested": [1, 2]})
    assert s.dirty
    assert s.get("a") == 1
    assert s.get("missing", 42) == 42
    assert "b" in s and len(s) == 2
    snap = s.snapshot()
    s.delete("a")
    assert "a" not in s
    assert snap["a"] == 1  # snapshot unaffected by later mutation
    restored = KeyValueState(snap)
    assert restored.get("a") == 1 and not restored.dirty


def test_memory_backend_roundtrip():
    b = MemoryStateBackend()
    assert b.load("c", 0) is None
    b.save("c", 0, 3, {"k": 1})
    assert b.load("c", 0) == (3, {"k": 1})
    b.save("c", 1, 1, {"other": True})
    assert b.load("c", 0) == (3, {"k": 1})  # tasks isolated


def test_file_backend_roundtrip(tmp_path):
    b = FileStateBackend(str(tmp_path))
    assert b.load("count-bolt", 2) is None
    b.save("count-bolt", 2, 1, {"x": [1, 2, 3]})
    b.save("count-bolt", 2, 2, {"x": [1, 2, 3, 4]})
    # fresh instance reads what a prior process wrote (durability)
    b2 = FileStateBackend(str(tmp_path))
    assert b2.load("count-bolt", 2) == (2, {"x": [1, 2, 3, 4]})
    # no stray tmp files from the atomic write
    assert all(not p.name.endswith(".tmp") for p in tmp_path.iterdir())


def test_file_backend_fsyncs_directory(tmp_path, monkeypatch):
    """save() must fsync the state DIRECTORY after os.replace: the rename
    is atomic but not durable, and losing the directory entry on a power
    cut would silently resurrect the previous checkpoint."""
    import os

    synced_inodes = set()
    real_fsync = os.fsync

    def spy_fsync(fd):
        synced_inodes.add(os.fstat(fd).st_ino)
        real_fsync(fd)

    monkeypatch.setattr(os, "fsync", spy_fsync)
    b = FileStateBackend(str(tmp_path))
    b.save("count-bolt", 0, 1, {"k": 1})
    assert tmp_path.stat().st_ino in synced_inodes


# ---- integration: checkpoint + restore ---------------------------------------


def _config(**topo):
    cfg = Config()
    cfg.topology.message_timeout_s = topo.pop("message_timeout_s", 2.0)
    cfg.topology.checkpoint_interval_s = topo.pop("checkpoint_interval_s", 0.05)
    for k, v in topo.items():
        setattr(cfg.topology, k, v)
    return cfg


def test_supervisor_restore_after_crash(run):
    """Crash the stateful bolt's executor mid-stream: the supervisor
    replaces it, the replacement restores the last checkpoint, and the
    in-flight tuple replays — counts end >= exact (at-least-once)."""

    async def scenario():
        items = ["a", "b", "a", "c", "a", "b"]
        spout = ListSpout(items, replay_on_fail=True)

        builder = TopologyBuilder()
        builder.set_spout("spout", spout, 1)
        builder.set_bolt("count", CountBolt(), 1).shuffle_grouping("spout")
        cfg = _config()

        cluster = AsyncLocalCluster()
        rt = await cluster.submit("stateful", cfg, builder.build())
        try:
            # Phase 1: everything counted and at least one checkpoint taken.
            for _ in range(400):
                sp = rt.spout_execs["spout"][0].spout
                if len(sp.acked) >= len(items) and \
                        rt.metrics.snapshot().get("count", {}).get("checkpoints", 0) >= 1:
                    break
                await asyncio.sleep(0.05)
            got = rt.state_backend.load("count", 0)
            assert got is not None
            version, snap = got
            assert sum(snap.values()) == len(items)
            assert snap["a"] == 3

            # Phase 2: chaos-kill the executor on its next tuple.
            ChaosMonkey(rt).crash_bolt("count", 0)
            rt.spout_execs["spout"][0].spout.queue.extend(["c", "b"])
            for _ in range(400):
                snap2 = rt.metrics.snapshot().get("count", {})
                if snap2.get("executor_restarts", 0) >= 1:
                    break
                await asyncio.sleep(0.05)
            assert rt.metrics.snapshot()["count"]["executor_restarts"] >= 1

            # Phase 3: replacement restored state; replayed + new tuples
            # land on top of it. At-least-once: counts >= exact.
            for _ in range(400):
                got = rt.state_backend.load("count", 0)
                if got and got[1].get("c", 0) >= 2 and got[1].get("b", 0) >= 3:
                    break
                await asyncio.sleep(0.05)
            version2, final = rt.state_backend.load("count", 0)
            assert version2 > version
            assert final["a"] >= 3 and final["b"] >= 3 and final["c"] >= 2
        finally:
            await cluster.shutdown()

    run(scenario(), timeout=60)


def test_durable_state_across_topology_restart(run, tmp_path):
    """File backend: a graceful kill checkpoints the tail; a new topology
    (fresh process-equivalent) resumes the counts."""

    async def scenario():
        cfg = _config(checkpoint_interval_s=30.0)  # only the final checkpoint
        cfg.topology.state_dir = str(tmp_path)

        async def run_once(items):
            builder = TopologyBuilder()
            builder.set_spout("spout", ListSpout(items), 1)
            builder.set_bolt("count", CountBolt(), 1).shuffle_grouping("spout")
            cluster = AsyncLocalCluster()
            rt = await cluster.submit("durable", cfg, builder.build())
            for _ in range(400):
                if len(rt.spout_execs["spout"][0].spout.acked) >= len(items):
                    break
                await asyncio.sleep(0.05)
            await cluster.kill("durable", wait_secs=5.0)  # graceful: checkpoints

        await run_once(["x", "y", "x"])
        await run_once(["y", "z"])

        got = FileStateBackend(str(tmp_path)).load("count", 0)
        assert got is not None
        _, counts = got
        assert counts == {"x": 2, "y": 2, "z": 1}

    run(scenario(), timeout=60)


def test_non_stateful_bolt_untouched(run):
    """Plain bolts: no state machinery, no checkpoint files, no counter."""

    async def scenario():
        from test_runtime import CaptureBolt

        CaptureBolt.seen = None
        builder = TopologyBuilder()
        builder.set_spout("spout", ListSpout(["m"]), 1)
        builder.set_bolt("cap", CaptureBolt(), 1).shuffle_grouping("spout")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("plain", _config(), builder.build())
        try:
            for _ in range(200):
                if CaptureBolt.seen:
                    break
                await asyncio.sleep(0.05)
            assert rt.state_backend.load("cap", 0) is None
            assert "checkpoints" not in rt.metrics.snapshot().get("cap", {})
        finally:
            await cluster.shutdown()

    run(scenario(), timeout=30)


# ---- the executor's checkpoint hook (what DecodeBolt stands on) --------------


async def _counting(cfg, items=(), bolt=None):
    builder = TopologyBuilder()
    builder.set_spout("spout", ListSpout(list(items)), 1)
    builder.set_bolt("count", bolt or CountBolt(), 1).shuffle_grouping("spout")
    cluster = AsyncLocalCluster()
    rt = await cluster.submit("hook", cfg, builder.build())
    return cluster, rt


def _checkpoints(rt):
    return rt.metrics.snapshot().get("count", {}).get("checkpoints", 0)


def test_a_stateful_bolt_is_checkpointed_on_the_interval(run):
    """No stop, no ``checkpoint_now``: the interval alone saves."""

    async def scenario():
        cluster, rt = await _counting(_config(checkpoint_interval_s=0.02),
                                      ["a", "b", "a"])
        try:
            assert await until(
                lambda: (rt.state_backend.load("count", 0) or (0, {}))[1]
                == {"a": 2, "b": 1})
            assert _checkpoints(rt) >= 1
            assert rt.bolt_execs["count"][0]._tick_task is None  # not a tick
        finally:
            await cluster.shutdown()

    run(scenario(), timeout=30)


class CommitBolt(CountBolt):
    """Persist, then ack: the decode bolt's order."""

    async def execute(self, t):
        key = t.get("message")
        self.state.put(key, self.state.get(key, 0) + 1)
        self.checkpoint_now()
        self.collector.ack(t)


def test_checkpoint_now_has_saved_before_the_ack_that_follows(run):
    async def scenario():
        # an interval no run reaches: every save here is checkpoint_now's
        cluster, rt = await _counting(_config(checkpoint_interval_s=30.0),
                                      bolt=CommitBolt())
        try:
            spout = rt.spout_execs["spout"][0].spout
            log = []
            save = rt.state_backend.save

            def spy_save(component, task, version, snap):
                save(component, task, version, snap)
                log.append(("saved", dict(snap)))

            rt.state_backend.save = spy_save
            spout.ack = lambda msg_id: log.append(("acked", msg_id))
            spout.queue.extend(["x", "y", "x"])
            assert await until(lambda: len(log) >= 6)
        finally:
            await cluster.shutdown()
        return log

    assert run(scenario(), timeout=30)[:6] == [
        ("saved", {"x": 1}), ("acked", "x"),
        ("saved", {"x": 1, "y": 1}), ("acked", "y"),
        ("saved", {"x": 2, "y": 1}), ("acked", "x"),
    ]


class FoldBolt(StatefulBolt):
    """Keeps an aggregate beside the state and folds it in when asked."""

    folds = 0

    def init_state(self, state):
        super().init_state(state)
        self.seen = list(state.get("seen", []))

    async def execute(self, t):
        self.seen.append(t.get("message"))
        self.state.put("n", len(self.seen))
        self.collector.ack(t)

    def pre_checkpoint(self):
        FoldBolt.folds += 1
        self.state.put("seen", list(self.seen))


def test_pre_checkpoint_folds_before_the_snapshot_and_a_clean_state_is_not_saved(run):
    async def scenario():
        FoldBolt.folds = 0
        cluster, rt = await _counting(_config(checkpoint_interval_s=0.01),
                                      ["a", "b", "c"], bolt=FoldBolt())
        try:
            assert await until(
                lambda: (rt.state_backend.load("count", 0) or (0, {}))[1]
                .get("n") == 3)
            version, snap = rt.state_backend.load("count", 0)
            # the snapshot holds what the hook put there on the way in
            assert snap == {"n": 3, "seen": ["a", "b", "c"]}
            folds, saves = FoldBolt.folds, _checkpoints(rt)
            await asyncio.sleep(0.06)  # six intervals over a clean state
            assert rt.state_backend.load("count", 0)[0] == version
            assert (FoldBolt.folds, _checkpoints(rt)) == (folds, saves)
            assert saves == version  # one version a save, none skipped
        finally:
            await cluster.shutdown()

    run(scenario(), timeout=30)


def test_a_replacement_executor_restores_the_last_version(run, tmp_path):
    """What a predecessor saved is what ``init_state`` hands over, and the
    numbering goes on from it."""

    async def scenario():
        FileStateBackend(str(tmp_path)).save(
            "count", 0, 1, {"n": 1, "seen": ["stale"]})
        FileStateBackend(str(tmp_path)).save(
            "count", 0, 7, {"n": 2, "seen": ["p", "q"]})
        cfg = _config(checkpoint_interval_s=0.01)
        cfg.topology.state_dir = str(tmp_path)
        cluster, rt = await _counting(cfg, ["r"], bolt=FoldBolt())
        try:
            assert await until(
                lambda: rt.state_backend.load("count", 0)[0] > 7)
            assert rt.state_backend.load("count", 0) == (
                8, {"n": 3, "seen": ["p", "q", "r"]})
        finally:
            await cluster.shutdown()

    run(scenario(), timeout=30)
