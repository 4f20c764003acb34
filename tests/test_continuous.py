"""The engine's one queue, where every batch is formed.

Covers the slot-level queue itself (refill-on-free dispatch, idle
deadline aging and eager dispatch, the formation rules: ``max_batch``,
first in first out within a key, EDF + weighted round-robin across keys
with the starvation bound), a record's own rows coming back to it, the
cross-source guarantees (serve + topology traffic co-batching into ONE
dispatched batch, exactly-once per source when a coalesced batch fails),
the operator's row bound, the cascade integration (escalation residues
ride the next tier's queue, per-tier counters intact), the per-engine
registry lifecycle (identity, close-on-eviction), and the
batch_fill/coalesced_sources metrics.
"""

from __future__ import annotations

import asyncio
import gc
import json
import threading
import time
from collections import deque

import numpy as np
import pytest

from storm_tpu.cascade.policy import CascadeConfig
from storm_tpu.config import BatchConfig, Config, ModelConfig, QosConfig
from storm_tpu.infer.continuous import (
    ContinuousBatcher, Submission, _reset_registry, continuous_for,
    registry_stats)
from storm_tpu.infer.engine import InflightBatch
from storm_tpu.infer.operator import InferenceBolt
from storm_tpu.runtime.base import TopologyContext
from storm_tpu.runtime.metrics import MetricsRegistry
from storm_tpu.serve.worker import InferenceWorker

from tests.test_cascade import _cascade_bolt, _conf_payload, _argmaxes
from tests.test_pipeline import _Collector, _payload, _tuple

SHAPE = (28, 28, 1)


@pytest.fixture(autouse=True)
def _fresh_registry():
    _reset_registry()
    yield
    _reset_registry()


class _SlotEngine:
    """dispatch-protocol engine whose handles the TEST resolves — batch
    formation and slot accounting are exercised without device timing.
    ``pad_to`` mimics bucket padding so batch_fill < 1 is observable."""

    input_shape = SHAPE

    def __init__(self, capacity: int = 1, pad_to: int = 0) -> None:
        self.ring_capacity = capacity
        self.pad_to = pad_to
        self.handles = []
        self.sizes = []  # per dispatch: rows per part

    def warmup(self, buckets=None):
        pass

    def dispatch(self, parts):
        n = sum(int(p.shape[0]) for p in parts)
        h = InflightBatch(n, max(self.pad_to, n) if self.pad_to else n)
        h.timings = {}
        self.handles.append(h)
        self.sizes.append([int(p.shape[0]) for p in parts])
        return h


def _resolve(h, v=0.1):
    h.future.set_result(np.full((h.n, 10), v, np.float32))


async def _until(cond, timeout=5.0, msg="condition not met in time"):
    t0 = time.perf_counter()
    while not cond():
        if time.perf_counter() - t0 > timeout:
            raise AssertionError(msg)
        await asyncio.sleep(0.005)


def _bolt(engine, metrics=None, task_index=0, **batch_kw):
    bolt = InferenceBolt(
        ModelConfig(name="lenet5", dtype="float32", input_shape=SHAPE),
        BatchConfig(**batch_kw), engine=engine, warmup=False)
    ctx = TopologyContext("inference-bolt", task_index, 1, Config(),
                          metrics=metrics or MetricsRegistry())
    coll = _Collector()
    bolt.prepare(ctx, coll)
    return bolt, coll


def _rows(n=1, c=0.0):
    return np.full((n, *SHAPE), c, np.float32)


# ---- the queue: slot refill, deadline aging ----------------------------------


def test_slot_refill_on_free_dispatches_immediately():
    """The tentpole behavior: rows arriving while the device works are
    dispatched the MOMENT a slot frees — not at a deadline tick. With a
    10s deadline, only the refill path can explain the second batch.

    Since ISSUE 33 the refill cut is held until the running step is about
    to end where the queue can tell when that is; this engine has one slot
    and no ``step_ms``, so the rule falls back to the cut at once, and
    this test stays as it was (``tests/test_late_binding.py`` (f) has the
    hold)."""
    eng = _SlotEngine(capacity=1)
    cb = continuous_for(eng, BatchConfig(
        max_batch=8, buckets=(8,), max_wait_ms=10_000, eager=True))
    a = cb.submit(_rows(), source="s1")
    t0 = time.perf_counter()
    while len(eng.handles) < 1:
        assert time.perf_counter() - t0 < 5.0
        time.sleep(0.002)
    b = cb.submit(_rows(), source="s1")
    c = cb.submit(_rows(), source="s2")
    time.sleep(0.05)
    assert len(eng.handles) == 1, \
        "slot busy: later rows must coalesce, not dispatch"
    _resolve(eng.handles[0])
    t1 = time.perf_counter()
    while len(eng.handles) < 2:
        assert time.perf_counter() - t1 < 5.0, \
            "freed slot must refill well before the 10s deadline"
        time.sleep(0.002)
    assert eng.sizes[1] == [1, 1], \
        "both queued records ship in ONE refill batch"
    _resolve(eng.handles[1])
    assert a.future.result(timeout=5).shape == (1, 10)
    assert b.future.result(timeout=5).shape == (1, 10)
    assert c.future.result(timeout=5).shape == (1, 10)
    assert cb.last_batch["sources"] == ["s1", "s2"]


def test_idle_non_eager_ages_to_deadline():
    """Trickle traffic on an idle device waits ``max_wait_ms`` for
    company: no eager dispatch, the row ships at about the deadline."""
    eng = _SlotEngine(capacity=1)
    cb = continuous_for(eng, BatchConfig(
        max_batch=8, buckets=(8,), max_wait_ms=50.0, eager=False))
    sub = cb.submit(_rows(), source="s1")
    time.sleep(0.01)
    assert not eng.handles, "idle + non-eager must wait for the deadline"
    t0 = time.perf_counter()
    while not eng.handles:
        assert time.perf_counter() - t0 < 5.0
        time.sleep(0.002)
    _resolve(eng.handles[0])
    assert sub.future.result(timeout=5).shape == (1, 10)


def test_idle_eager_dispatches_on_arrival():
    """``eager=True``: an idle device takes the first row at once, a
    minute before its deadline, and alone (nothing else had arrived)."""
    eng = _SlotEngine(capacity=2)
    cb = continuous_for(eng, BatchConfig(
        max_batch=8, buckets=(8,), max_wait_ms=60_000, eager=True))
    sub = cb.submit(_rows(), source="s1")
    t0 = time.perf_counter()
    while not eng.handles:
        assert time.perf_counter() - t0 < 5.0, \
            "an idle eager device must not age the row to its deadline"
        time.sleep(0.002)
    assert eng.sizes[0] == [1]
    _resolve(eng.handles[0])
    assert sub.future.result(timeout=5).shape == (1, 10)


# ---- formation: fairness, starvation, preemption, max_batch ----------------------


def _manual_cb(cfg, qos=None):
    """A batcher whose dispatcher thread is never started — formation is
    driven directly so the test controls every round."""
    return ContinuousBatcher(_SlotEngine(), cfg, qos)


def _enqueue(cb, rows, lane, tenant, ts, source="s", payload=None):
    sub = Submission(
        _rows(rows), payload, ts, ts, lane, tenant, source,
        ts + cb._deadline_ms(lane) / 1e3)
    cb._queues.setdefault(cb._key(tenant, lane), deque()).append(sub)
    cb._pending_rows += sub.rows
    return sub


def test_weighted_round_robin_across_lanes():
    qos = QosConfig(enabled=True)
    cb = _manual_cb(BatchConfig(max_batch=4, buckets=(4,)), qos)
    t = time.perf_counter()
    for _ in range(4):
        _enqueue(cb, 1, "high", "gold", t)
    for _ in range(4):
        _enqueue(cb, 1, "best_effort", "brz", t)
    batch = cb._form_locked()
    lanes = [s.lane for s in batch]
    # high (weight 3) draws 3 rows per pass, best_effort (weight 1) one:
    # the flooded low lane still makes progress inside every batch.
    assert lanes == ["high", "high", "high", "best_effort"]


def test_tenant_fairness_starvation_bound():
    """A tenant:lane key passed over ``starvation_rounds`` formations is
    served FIRST in the next one — a flooding tenant cannot starve a
    same-lane competitor indefinitely."""
    qos = QosConfig(enabled=True)
    cb = _manual_cb(BatchConfig(max_batch=2, buckets=(2,),
                                starvation_rounds=2), qos)
    t = time.perf_counter()
    for _ in range(12):
        _enqueue(cb, 1, "normal", "flood", t)
    starved_sub = _enqueue(cb, 1, "normal", "quiet", t + 0.01)
    first = cb._form_locked()   # flood fills the batch, quiet skipped (1)
    second = cb._form_locked()  # skipped (2) -> starved
    third = cb._form_locked()   # starved key served first
    assert all(s.tenant == "flood" for s in first + second)
    assert third[0] is starved_sub, \
        "the starved key must lead the batch after the bound trips"
    assert cb.fair_starved.get(("quiet", "normal")) == 1
    assert cb.fair_rows[("quiet", "normal")] == 1
    assert cb.fair_rows[("flood", "normal")] == 5  # 2 + 2 + 1


def test_fresh_high_priority_record_preempts_queued_best_effort():
    """EDF across lanes: a high-priority record that arrives last leads
    the batch, ahead of the best-effort records queued before it, which
    keep their own arrival order."""
    qos = QosConfig(enabled=True)
    t = time.perf_counter()
    cb = _manual_cb(BatchConfig(max_batch=3, buckets=(3,)), qos)
    for name, lane in (("p0", "best_effort"), ("p1", "best_effort"),
                       ("p2", "high")):
        _enqueue(cb, 1, lane, None, t, payload=name)
    assert [s.payload for s in cb._form_locked()] == ["p2", "p0", "p1"]


@pytest.mark.parametrize("sizes,max_batch,batches", [
    # a record of more rows than max_batch still ships, alone
    ([20], 8, [[20]]),
    # no batch exceeds max_batch: a record that would overshoot waits
    ([6, 3, 20, 2], 8, [[6], [3], [20], [2]]),
    # rows beyond max_batch stay for the next batch
    ([1, 1, 1], 2, [[1, 1], [1]]),
    # first in, first out within one tenant:lane key
    ([2, 1, 3, 1, 1], 4, [[2, 1], [3, 1], [1]]),
], ids=["oversized_ships_alone", "never_over_max_batch",
        "leftovers_stay", "fifo_within_a_key"])
def test_formation_respects_max_batch_and_arrival_order(
        sizes, max_batch, batches):
    cb = _manual_cb(BatchConfig(max_batch=max_batch, buckets=(max_batch,)))
    t = time.perf_counter()
    for i, rows in enumerate(sizes):
        _enqueue(cb, rows, None, None, t, payload=i)
    formed = []
    while len(cb):
        formed.append(cb._form_locked())
    assert [[s.rows for s in b] for b in formed] == batches
    assert [s.payload for b in formed for s in b] == list(range(len(sizes)))


def test_records_of_unequal_rows_each_get_back_their_own_rows():
    """One device batch, three records of 3, 1 and 4 rows from two
    sources: every future resolves to exactly its record's slice of the
    batch's output, in the record's own row order."""
    eng = _SlotEngine(capacity=1)
    cb = continuous_for(eng, BatchConfig(
        max_batch=8, buckets=(8,), max_wait_ms=10_000, eager=True))
    hold = cb.submit(_rows(), source="hold")  # occupies the only slot
    t0 = time.perf_counter()
    while len(eng.handles) < 1:
        assert time.perf_counter() - t0 < 5.0
        time.sleep(0.002)
    subs = [cb.submit(_rows(n), source=src)
            for n, src in ((3, "s1"), (1, "s2"), (4, "s1"))]
    _resolve(eng.handles[0])
    while len(eng.handles) < 2:
        assert time.perf_counter() - t0 < 5.0
        time.sleep(0.002)
    assert eng.sizes[1] == [3, 1, 4]
    out = np.arange(8 * 10, dtype=np.float32).reshape(8, 10)
    eng.handles[1].future.set_result(out)
    np.testing.assert_array_equal(subs[0].future.result(timeout=5), out[0:3])
    np.testing.assert_array_equal(subs[1].future.result(timeout=5), out[3:4])
    np.testing.assert_array_equal(subs[2].future.result(timeout=5), out[4:8])
    hold.future.result(timeout=1)


# ---- cross-source guarantees -------------------------------------------------


def test_serve_and_topology_traffic_cobatch(run):
    """The acceptance-criteria assertion: ONE dispatched batch contains
    rows from both the gRPC serve path and a topology bolt."""
    async def go():
        eng = _SlotEngine(capacity=1)
        bolt, coll = _bolt(eng, max_batch=8, buckets=(8,),
                           max_wait_ms=10_000, eager=True)
        cb = bolt._cbs[None]
        warm = cb.submit(_rows(), source="warm")  # occupy the only slot
        await _until(lambda: len(eng.handles) == 1)
        await bolt.execute(_tuple(_payload()))
        serve = InferenceWorker(engine=eng, batch=bolt.batch_cfg, port=0)
        out_box = {}
        th = threading.Thread(
            target=lambda: out_box.setdefault(
                "out", serve._run_predict(_rows(1, 0.5))))
        th.start()
        await _until(lambda: len(cb) == 2,
                     msg="bolt + serve rows must both be queued")
        assert len(eng.handles) == 1
        _resolve(eng.handles[0])
        await _until(lambda: len(eng.handles) == 2)
        _resolve(eng.handles[1], v=0.2)
        th.join(timeout=5)
        assert out_box["out"].shape == (1, 10)
        assert np.allclose(out_box["out"], 0.2)
        await bolt.flush()
        assert len(coll.acked) == 1 and not coll.failed
        assert eng.sizes[1] == [1, 1]
        assert cb.last_batch["sources"] == ["inference-bolt#0", "serve"], \
            "one batch, two sources — serve and topology co-batch"
        m = bolt.context.metrics.snapshot()["inference-bolt"]
        assert m["coalesced_sources"] == 1 + 2  # warm batch + co-batch
        assert m["batch_fill"]["count"] == 2
        warm.future.result(timeout=1)

    run(go(), timeout=60)


def test_exactly_once_per_source_on_coalesced_batch_failure(run):
    """A coalesced batch fails -> every member future carries the
    exception and EACH source fails/replays its own tuples independently
    (the other source's collector is untouched by ours)."""
    async def go():
        eng = _SlotEngine(capacity=1)
        m = MetricsRegistry()
        b1, c1 = _bolt(eng, metrics=m, task_index=0, max_batch=8,
                       buckets=(8,), max_wait_ms=10_000, eager=True)
        b2, c2 = _bolt(eng, metrics=m, task_index=1, max_batch=8,
                       buckets=(8,), max_wait_ms=10_000, eager=True)
        assert b1._cbs[None] is b2._cbs[None], \
            "replicas sharing an engine share ONE queue"
        cb = b1._cbs[None]
        warm = cb.submit(_rows(), source="warm")
        await _until(lambda: len(eng.handles) == 1)
        t1, t2 = _tuple(_payload()), _tuple(_payload())
        await b1.execute(t1)
        await b2.execute(t2)
        await _until(lambda: len(cb) == 2)
        _resolve(eng.handles[0])
        await _until(lambda: len(eng.handles) == 2)
        assert eng.sizes[1] == [1, 1], "both sources coalesced"
        eng.handles[1].future.set_exception(RuntimeError("device fault"))
        await b1.flush()
        await b2.flush()
        assert [id(t) for t in c1.failed] == [id(t1)]
        assert [id(t) for t in c2.failed] == [id(t2)]
        assert not c1.acked and not c2.acked
        assert c1.errors and c2.errors
        # Replay: the same tuples run again and succeed. Later handles
        # may dispatch at any point, so resolve-as-they-appear.
        await b1.execute(t1)
        await b2.execute(t2)
        t0 = time.perf_counter()
        while not (c1.acked and c2.acked):
            for h in eng.handles:
                if not h.future.done():
                    _resolve(h)
            assert time.perf_counter() - t0 < 10.0, "replay did not ack"
            await asyncio.sleep(0.01)
        await b1.flush()
        await b2.flush()
        assert [id(t) for t in c1.acked] == [id(t1)]
        assert [id(t) for t in c2.acked] == [id(t2)]
        warm.future.result(timeout=1)

    run(go(), timeout=60)


# ---- the operator's row bound -------------------------------------------------


@pytest.mark.parametrize("how", ["finishes", "emit_raises", "emit_cancelled"])
def test_row_bound_parks_the_task_and_every_group_gives_its_rows_back(
        run, how):
    """A task with ``max_inflight * max_batch`` rows outstanding parks its
    next record and resumes when a group finishes — also when the group's
    emit raises or its task is cancelled mid-emit: the rows come back in a
    ``finally``, or the bound would shrink for good."""
    async def go():
        eng = _SlotEngine(capacity=1)
        bolt, coll = _bolt(eng, max_batch=2, buckets=(2,),
                           max_wait_ms=10_000, max_inflight=1)
        first = [_tuple(_payload()), _tuple(_payload())]
        for t in first:
            await bolt.execute(t)
        await _until(lambda: len(eng.handles) == 1)
        assert bolt._cb_rows == bolt._cb_cap == 2
        third = _tuple(_payload())
        parked = asyncio.get_running_loop().create_task(bolt.execute(third))
        await asyncio.sleep(0.05)
        assert not parked.done(), "at its bound the task must park"
        assert len(bolt._cbs[None]) == 0, "and submit nothing meanwhile"
        plain_emit = coll.emit
        entered = asyncio.Event()

        async def bad_emit(*a, **kw):
            entered.set()
            if how == "emit_raises":
                raise RuntimeError("collector fault")
            await asyncio.Event().wait()  # held until cancelled

        if how != "finishes":
            coll.emit = bad_emit
        _resolve(eng.handles[0])
        if how == "emit_cancelled":
            await asyncio.wait_for(entered.wait(), 5)
            (group,) = bolt._inflight
            group.cancel()
        await asyncio.wait_for(parked, 5)  # the freed rows let it in
        coll.emit = plain_emit
        assert bolt._cb_rows == 1, "only the third record is outstanding"
        flush = asyncio.get_running_loop().create_task(bolt.flush())
        await _until(lambda: len(eng.handles) == 2)
        _resolve(eng.handles[1])
        await flush
        assert bolt._cb_rows == 0 and not bolt._inflight
        done = {"finishes": (first + [third], []),
                "emit_raises": ([third], first),
                # neither acked nor failed: the tree times out and replays
                "emit_cancelled": ([third], [])}[how]
        assert [id(t) for t in coll.acked] == [id(t) for t in done[0]]
        assert [id(t) for t in coll.failed] == [id(t) for t in done[1]]

    run(go(), timeout=60)


# ---- cascade integration -----------------------------------------------------


def test_cascade_residue_rides_continuous_queue(run, monkeypatch):
    """Escalations enqueue into the NEXT tier's queue; accepts and
    escalations, per-tier counters and which-tier-answered argmaxes are
    those of tests/test_cascade.py's split test."""
    async def go():
        cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                            thresholds=(0.5,))
        bolt, coll, engines = _cascade_bolt(
            monkeypatch, cas, max_batch=4, max_wait_ms=10_000,
            max_inflight=4, eager=True)
        assert set(bolt._cbs) == {0, 1}
        for c in (0.9, 0.2, 0.9, 0.2):
            await bolt.execute(_tuple(_conf_payload(c)))
        await bolt.flush()
        assert sum(engines["lenet5"].calls) == 4
        assert sum(engines["resnet20"].calls) == 2, \
            "only the low-confidence residue reaches the flagship"
        assert len(coll.acked) == 4 and not coll.failed
        assert sorted(_argmaxes(coll)) == [0, 0, 1, 1]
        m = bolt.context.metrics.snapshot()["inference-bolt"]
        assert m["cascade_accepted_tier0"] == 2
        assert m["cascade_accepted_tier1"] == 2
        assert m["cascade_escalations"] == 2
        assert bolt._cbs[0].rows_dispatched == 4
        assert bolt._cbs[1].rows_dispatched == 2
        assert len(registry_stats()) == 2  # one queue per tier engine

    run(go(), timeout=60)


# ---- registry lifecycle ------------------------------------------------------


def test_registry_identity_and_close_on_eviction():
    eng = _SlotEngine()
    cfg = BatchConfig(max_batch=8, buckets=(8,))
    cb = continuous_for(eng, cfg)
    assert continuous_for(eng, cfg) is cb
    assert len(registry_stats()) == 1
    del eng
    gc.collect()
    assert registry_stats() == [], "evicted engine drops its queue"
    with pytest.raises(RuntimeError):
        cb.submit(_rows())


def test_engine_finalizer_inside_continuous_for_does_not_deadlock():
    """An engine's finalizer runs wherever the collector does — also on a
    thread that is in the middle of ``continuous_for`` for ANOTHER engine
    (building the queue allocates, and allocation lets the collector run).
    The finalizer must need nothing that thread holds: with a registry lock
    held around the build, this wedged the thread for good."""
    cfg = BatchConfig(max_batch=8, buckets=(8,))
    doomed = [_SlotEngine()]
    dead_cb = continuous_for(doomed[0], cfg)

    class _CollectingEngine(_SlotEngine):
        @property
        def ring_capacity(self):  # read while the queue is being built
            doomed.clear()  # last reference: the finalizer runs right here
            gc.collect()
            return 1

        @ring_capacity.setter
        def ring_capacity(self, value):
            pass

    eng = _CollectingEngine()
    got = []
    t = threading.Thread(
        target=lambda: got.append(continuous_for(eng, cfg)), daemon=True)
    t.start()
    t.join(5.0)
    assert got, "continuous_for deadlocked against an engine's finalizer"
    assert not doomed, "the hook ran: the doomed engine died mid-build"
    assert got[0] is continuous_for(eng, cfg)
    assert len(registry_stats()) == 1, "only the live engine's queue is left"
    with pytest.raises(RuntimeError):
        dead_cb.submit(_rows())


# ---- batch_fill / coalesced_sources ---------------------------------------------


def test_queue_observes_batch_fill():
    eng = _SlotEngine(pad_to=8)
    m = MetricsRegistry()
    cb = continuous_for(eng, BatchConfig(
        max_batch=8, buckets=(8,), max_wait_ms=10_000, eager=True))
    cb.bind(m, "engine")
    subs = [cb.submit(_rows(), source=f"s{i}") for i in range(3)]
    # Resolve handles as the dispatcher produces them: with a 1-slot
    # ring the 3 rows may split across dispatches, and the next one
    # only appears after the previous resolves.
    t0 = time.perf_counter()
    while not all(s.future.done() for s in subs):
        assert time.perf_counter() - t0 < 5.0
        for h in list(eng.handles):
            if not h.future.done():
                _resolve(h)
        time.sleep(0.002)
    for s in subs:
        s.future.result(timeout=5)
    snap = m.snapshot()["engine"]
    assert snap["batch_fill"]["count"] == len(eng.handles)
    total = sum(sum(sz) for sz in eng.sizes)
    assert total == 3
    assert snap["coalesced_sources"] >= len(eng.handles)
    assert cb.fill_median() is not None
