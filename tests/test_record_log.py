"""The record log (PR 54): one row a root tuple, stamped along the record's
way from the broker's append to the sink's produce and written where the
record ends, in the step log's store, on its clock and under its switch
(``obs/profile.py ProfileStore.records()``; docs/OPERATIONS.md, "Reading the
record log"). The tiny ViT through ``build_standard_topology`` on a
``LocalCluster`` over the ``MemoryBroker``."""

import asyncio
import json
import threading
import time

import numpy as np
import pytest

from storm_tpu.config import Config, ModelConfig
from storm_tpu.connectors import MemoryBroker
from storm_tpu.infer.continuous import _reset_registry
from storm_tpu.main import build_standard_topology
from storm_tpu.obs import profile
from storm_tpu.obs.profile import (RECORD_FIELDS, RECORD_INTERVALS,
                                   RECORD_LOG, RECORD_MOMENTS, RECORD_PATH,
                                   RecordRow, end_record, new_record_row,
                                   record_intervals, record_paths)
from storm_tpu.runtime.base import OutputCollector
from storm_tpu.runtime.cluster import LocalCluster
from storm_tpu.runtime.tuples import Tuple

# a stamp on ``perf_counter`` brought onto ``time.time()`` by an offset read
# beside it (the step's ``t_cut``) lies within this of one read directly
CLOCKS = 2e-4
POISON = '{"instances": "garbage"}'


@pytest.fixture(autouse=True)
def _fresh():
    _reset_registry()
    profile.set_enabled(True)
    profile.profile_store().reset()
    yield
    profile.set_enabled(True)
    _reset_registry()


def _payload(i):
    x = np.full((1, 32, 32, 3), (i % 11) / 11, np.float32)
    return json.dumps({"instances": x.round(3).tolist()})


def _serve(payloads, scheme="string", chunk=1, frames=False, mode="async",
           backlog=False):
    """Run ``payloads`` through the standard topology (``backlog``: all of
    them appended before it is submitted); returns the input and output
    topics' records and the dead letters."""
    cfg = Config()
    cfg.model = ModelConfig(name="vit_tiny", dtype="float32",
                            num_classes=10, input_shape=(32, 32, 3))
    cfg.offsets.policy, cfg.offsets.max_behind = "earliest", None
    cfg.topology.spout_scheme = scheme
    cfg.topology.spout_chunk = chunk
    cfg.topology.spout_frames = frames
    cfg.sink.mode = mode
    cfg.batch.buckets, cfg.batch.max_batch = (8,), 8
    broker = MemoryBroker(default_partitions=2)
    with LocalCluster() as cluster:
        if backlog:
            for p in payloads:
                broker.produce(cfg.broker.input_topic, p)
            payloads = []
        cluster.submit_topology("t", cfg, build_standard_topology(cfg, broker))
        for i, p in enumerate(payloads):
            broker.produce(cfg.broker.input_topic, p)
            if i % 5 == 4:
                time.sleep(0.02)
        def answered():  # a frame's records may leave as one output
            return sum(len(json.loads(o.value)["predictions"]) for o in
                       broker.drain_topic(cfg.broker.output_topic)) \
                + broker.topic_size(cfg.broker.dead_letter_topic)

        deadline = time.time() + 90
        while time.time() < deadline and \
                answered() < broker.topic_size(cfg.broker.input_topic):
            time.sleep(0.02)
        assert cluster.drain("t", timeout_s=30)
        assert not cluster.errors("t")
    return (broker.drain_topic(cfg.broker.input_topic),
            broker.drain_topic(cfg.broker.output_topic),
            broker.drain_topic(cfg.broker.dead_letter_topic))


def _logs():
    store = profile.profile_store()
    deadline = time.time() + 10  # a step's row lands after its result
    while True:
        rows, steps = store.records(), store.steps()
        keys = {(s["engine"], s["step"]) for s in steps}
        if all((r["engine"], r["step"]) in keys for r in rows
               if r["step"] is not None) or time.time() > deadline:
            return rows, steps
        time.sleep(0.01)


@pytest.mark.parametrize("scheme,mode", [("string", "async"), ("raw", "async"),
                                         ("string", "sync"),
                                         ("raw", "transactional")])
def test_every_delivered_record_has_one_row_that_tiles_its_latency(scheme,
                                                                   mode):
    n = 24
    inputs, outputs, dead = _serve([_payload(i) for i in range(n)],
                                   scheme=scheme, mode=mode)
    assert len(outputs) == n and not dead
    rows, steps = _logs()
    assert len(rows) == n
    assert all(tuple(r) == RECORD_FIELDS for r in rows)
    assert all(r["records"] == 1 and r["ended"] == "delivered" for r in rows)
    # t_append is the input record's broker timestamp as the broker stamped
    # it, t_produced the output record's to under a millisecond
    assert sorted(r["t_append"] for r in rows) == \
        sorted(rec.timestamp for rec in inputs)
    for r, out in zip(sorted(rows, key=lambda r: r["t_produced"]), outputs):
        if mode != "transactional":  # a commit stamps its batch after it
            assert 0 <= r["t_produced"] - out.timestamp < 1e-3
    # joined with its step's row, the moments never decrease and their
    # intervals add up to produce less append: no hole, no overlap
    paths = record_paths(rows, steps)
    for p in paths:
        moments = [p[m] for m in RECORD_PATH]
        assert None not in moments, p
        for (name, a, b) in RECORD_INTERVALS:
            assert p[b] - p[a] >= (-CLOCKS if "cut" in name else 0.0), \
                (name, p)
        total = sum(p[b] - p[a] for _, a, b in RECORD_INTERVALS)
        assert total == pytest.approx(p["t_produced"] - p["t_append"],
                                      abs=1e-9)
    # (engine, step) names the row of steps() whose rows count it
    taken = {}
    for r in rows:
        taken[(r["engine"], r["step"])] = \
            taken.get((r["engine"], r["step"]), 0) + 1
    by_key = {(s["engine"], s["step"]): s for s in steps}
    for key, count in taken.items():
        assert by_key[key]["rows"] == count
    # the snapshot an operator reads: every interval, and the slowest row
    snap = profile.profile_store().snapshot()["records"]
    assert snap["count"] == n
    assert set(snap["intervals"]) == {name for name, _, _ in RECORD_INTERVALS}
    assert snap["intervals"] == record_intervals(paths)
    slowest = max(paths, key=lambda p: p["t_produced"] - p["t_append"])
    assert snap["slowest"] == slowest
    json.dumps(snap)


def test_the_profile_command_prints_the_intervals_and_the_slowest_record(
        monkeypatch, capsys):
    import argparse
    import io
    import urllib.request

    from storm_tpu.main import _profile_cmd

    _serve([_payload(i) for i in range(8)])
    _logs()
    snap = profile.profile_store().snapshot()
    monkeypatch.setattr(
        urllib.request, "urlopen", lambda req, timeout=None: io.BytesIO(
            json.dumps({"profile": snap}).encode()))
    assert _profile_cmd(argparse.Namespace(
        url="http://localhost:1", topology="t", token=None, json=False)) == 0
    out = capsys.readouterr().out
    (way,) = [x for x in out.splitlines() if x.startswith("a record's way")]
    assert "over 8 logged" in way
    for name, _, _ in RECORD_INTERVALS:
        assert f" {name}=" in way
    (slow,) = [x for x in out.splitlines() if x.startswith("slowest record")]
    assert f"step vit_tiny #{snap['records']['slowest']['step']}" in slow
    assert "resolved->egress=" in slow and "step vit_tiny #" in out


@pytest.mark.parametrize("later", ["t_parsed", "t_enq", "step", "t_egress",
                                   "t_sink", "t_produced"])
def test_a_malformed_record_ends_dead_lettered_where_it_ends(later):
    payloads = [_payload(i) for i in range(6)]
    payloads[3] = POISON
    inputs, outputs, dead = _serve(payloads)
    assert len(outputs) == 5 and len(dead) == 1
    rows, _ = _logs()
    assert len(rows) == 6
    bad = [r for r in rows if r["ended"] != "delivered"]
    assert len(bad) == 1
    (bad,) = bad
    assert bad["ended"] == "dead_lettered"
    assert bad["t_append"] == inputs[3].timestamp
    assert bad["t_append"] <= bad["t_polled"] <= bad["t_emitted"] \
        <= bad["t_exec"]
    assert bad[later] is None


@pytest.mark.parametrize("frames", [False, True])
def test_a_chunked_tuple_has_one_row_with_its_records(frames):
    payloads = [_payload(i) for i in range(16)]
    payloads[5] = POISON
    inputs, outputs, dead = _serve(payloads, scheme="raw", chunk=4,
                                   frames=frames, backlog=True)
    assert len(dead) == 1
    rows, steps = _logs()
    # two partitions of eight records: four tuples of four
    assert len(rows) == 4
    assert all(r["records"] == 4 for r in rows)
    firsts = {rec.timestamp for rec in inputs if rec.offset % 4 == 0}
    assert {r["t_append"] for r in rows} == firsts
    # the chunk with the malformed record says so; its other records were
    # delivered, and the row was written at the last of them
    assert sorted(r["ended"] for r in rows) == \
        ["dead_lettered"] + ["delivered"] * 3
    last_out = max(out.timestamp for out in outputs)
    assert max(r["t_produced"] for r in rows) - last_out < 1e-3
    for p in record_paths(rows, steps):
        moments = [p[m] for m in RECORD_PATH]
        assert None not in moments
        assert all(b - a >= -CLOCKS for a, b in zip(moments, moments[1:]))


class _Probe:
    """A bolt that keeps what it is handed."""

    def __init__(self):
        self.seen = []

    def clone(self):
        return self

    def declare_output_fields(self):
        return {"default": ("message",)}

    def prepare(self, context, collector):
        self.collector = collector

    async def execute(self, t):
        self.seen.append(t)
        self.collector.ack(t)

    async def tick(self):
        pass

    async def flush(self):
        pass

    def cleanup(self):
        pass


@pytest.mark.parametrize("enabled", [True, False])
def test_the_switch_is_the_profilers(enabled):
    from storm_tpu.connectors import BrokerSpout
    from storm_tpu.config import OffsetsConfig
    from storm_tpu.runtime import TopologyBuilder

    profile.set_enabled(enabled)
    assert (new_record_row(1.0) is not None) == enabled
    broker = MemoryBroker(default_partitions=1)
    probe = _Probe()
    tb = TopologyBuilder()
    tb.set_spout("spout", BrokerSpout(
        broker, "in", OffsetsConfig(policy="earliest", max_behind=None)))
    tb.set_bolt("probe", probe).shuffle_grouping("spout")
    with LocalCluster() as cluster:
        cluster.submit_topology("t", Config(), tb.build())
        for i in range(5):
            broker.produce("in", f"r{i}")
        deadline = time.time() + 30
        while len(probe.seen) < 5 and time.time() < deadline:
            time.sleep(0.01)
    assert len(probe.seen) == 5
    stamps = [rec.timestamp for rec in broker.drain_topic("in")]
    if not enabled:
        assert all(t.record is None for t in probe.seen)
    else:
        assert [t.record.t_append for t in probe.seen] == stamps
        for t in probe.seen:
            assert t.record.t_append <= t.record.t_polled \
                <= t.record.t_emitted
    # no sink delivered and nothing failed: no row either way
    assert profile.profile_store().records() == []


def test_disabled_the_whole_path_logs_nothing():
    profile.set_enabled(False)
    _, outputs, _ = _serve([_payload(i) for i in range(8)])
    assert len(outputs) == 8
    store = profile.profile_store()
    assert store.records() == [] and store.steps() == []


def test_the_ring_keeps_record_log_rows_under_eight_threads():
    store = profile.profile_store()
    each = RECORD_LOG // 8 + 500

    def submit(k):
        for i in range(each):
            end_record(RecordRow(float(k), float(i)), "delivered")

    threads = [threading.Thread(target=submit, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rows = store.records()
    assert len(rows) == RECORD_LOG
    # the newest rows, none twice: what is left of a thread's is in its
    # order and ends with its last
    assert len({(r["t_append"], r["t_polled"]) for r in rows}) == RECORD_LOG
    for k in range(8):
        mine = [r["t_polled"] for r in rows if r["t_append"] == float(k)]
        assert mine == sorted(mine)
        assert not mine or mine[-1] == float(each - 1)
    store.reset()
    assert store.records() == []


@pytest.mark.parametrize("how,left,ended", [
    (["delivered"], 0, "delivered"),
    (["delivered", "delivered"], 0, "delivered"),
    (["dead_lettered", "delivered"], 0, "dead_lettered"),
    (["delivered", "failed"], 0, "failed"),
    (["delivered"] * 3, 0, "delivered"),  # one more than it has: once
])
def test_a_row_is_written_once_when_nothing_is_left(how, left, ended):
    rec = RecordRow(1.0, 2.0, records=min(len(how), 2))
    for h in how:
        end_record(rec, h)
    rows = profile.profile_store().records()
    assert len(rows) == 1
    assert rows[0]["ended"] == ended and rec.left == left


class _Inbox:
    def __init__(self):
        self.items = []

    async def put(self, t):
        self.items.append(t)


def _collector(inbox):
    from storm_tpu.runtime.groupings import ShuffleGrouping
    from storm_tpu.runtime.metrics import MetricsRegistry

    class _Group:
        inboxes = [inbox]

    grouping = ShuffleGrouping()
    grouping.prepare(1)

    class _Router:
        def subscriptions(self, component, stream):
            return [(grouping, _Group())]

    class _Ledger:
        def anchor(self, root, edge):
            pass

    class _Runtime:
        metrics = MetricsRegistry()
        router = _Router()
        ledger = _Ledger()
        tracer = None

    return OutputCollector(_Runtime(), "bolt", 0)


@pytest.mark.parametrize("order", [(0, 1), (1, 0)])
def test_an_output_of_two_inputs_keeps_the_older_row(order):
    older, newer = RecordRow(10.0, 10.5), RecordRow(11.0, 11.5)
    inputs = [Tuple(values=["a"], fields=("message",), source_component="s",
                    root_ts=5.0, record=older),
              Tuple(values=["b"], fields=("message",), source_component="s",
                    root_ts=4.0, record=newer)]
    inbox = _Inbox()
    collector = _collector(inbox)
    asyncio.run(collector.emit(["c"], anchors=[inputs[i] for i in order]))
    asyncio.run(collector.emit(["d"], anchors=inputs, record=False))
    asyncio.run(collector.emit(["e"], anchors=[
        Tuple(values=["x"], fields=("message",), source_component="s")]))
    out, detached, bare = inbox.items
    assert out.record is older and out.root_ts == 4.0
    assert detached.record is None and bare.record is None


def test_the_moments_are_the_documented_ones():
    assert RECORD_MOMENTS == (
        "t_append", "t_polled", "t_emitted", "t_exec", "t_parsed", "t_enq",
        "t_egress", "t_encoded", "t_sink", "t_produced")
    names = [name for name, _, _ in RECORD_INTERVALS]
    assert names[:6] == ["append->polled", "polled->emitted", "emitted->exec",
                         "exec->parsed", "parsed->enq", "enq->cut"]
    assert names[-4:] == ["resolved->egress", "egress->encoded",
                          "encoded->sink", "sink->produced"]
    # the step's own intervals lie between, under the step log's names
    assert set(names[6:-4]) < {n for n, _, _ in profile.STEP_INTERVALS}
    assert len(names) == len(RECORD_PATH) - 1
