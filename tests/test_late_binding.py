"""Late binding (ISSUE 26): ``InferenceBolt`` hands each decoded record to
the ONE queue of the engine it shares, and a batch is cut from that queue
when the engine's ring has a free slot.

Everything runs on a fake engine with the real one's dispatch protocol: a
ring of two slots that ``dispatch`` parks on, one device that runs the
batches in ring order, each for the step time of its bucket (a few ms), and
``step_ms`` as the real engine's warm-up measures it. Counts are asserted,
never times: the tests of the hold before the cut (ISSUE 33) give the queue
a clock that only the test moves.
"""

from __future__ import annotations

import asyncio
import json
import queue
import statistics
import sys
import threading
import time

import numpy as np
import pytest

from storm_tpu.config import BatchConfig, Config, ModelConfig
from storm_tpu.infer import continuous
from storm_tpu.infer.continuous import _reset_registry, continuous_for
from storm_tpu.infer.engine import InflightBatch, StagingPool, _fetch_loop
from storm_tpu.infer.operator import InferenceBolt
from storm_tpu.runtime.base import TopologyContext
from storm_tpu.runtime.frames import RecordFrame
from storm_tpu.runtime.metrics import MetricsRegistry
from storm_tpu.runtime.tuples import Tuple
from storm_tpu.serve.marshal import encode_tensor

from tests.test_pipeline import _Collector

SHAPE = (28, 28, 1)
# a step that costs the same per padded row (a large model on the chip) and
# one that costs the same whatever its bucket (a launch-bound model)
GROWS = {8: 3.0, 32: 12.0}
FLAT = {8: 3.0, 32: 3.0}


@pytest.fixture(autouse=True)
def _fresh_queues():
    _reset_registry()
    yield
    _reset_registry()


class _RingEngine:
    """``tags`` of a step are the first pixel of each of its rows: a test
    writes a record's source or identity there and reads back which step
    served it."""

    input_shape = SHAPE

    def __init__(self, step_ms, capacity=2, fail_steps=(), manual=False):
        # manual: a step ends when the test says so (``finish_step``), so a
        # test can count in steps with no clock in it
        self._permits = threading.Semaphore(0) if manual else None
        self.step_ms = dict(step_ms)
        self.ring_capacity = capacity
        self.fail_steps = set(fail_steps)
        self.steps = []  # in ring order: {"rows", "padded", "tags"}
        self.finished = 0
        self.gate = threading.Event()  # closed: the device holds its step
        self.gate.set()
        self._ring = threading.BoundedSemaphore(capacity)
        self._enter = threading.Lock()
        self._q = queue.SimpleQueue()
        threading.Thread(target=self._device, daemon=True).start()

    def warmup(self, buckets=None):
        pass

    def finish_step(self):
        self._permits.release()

    def run_free(self):
        """From here on every step ends by itself (a test's last word, also
        when it fails: a batch left in the ring would hold a thread)."""
        permits, self._permits = self._permits, None
        if permits is not None:
            permits.release()
        self.gate.set()

    def dispatch(self, parts):
        rows = sum(int(p.shape[0]) for p in parts)
        padded = min((b for b in self.step_ms if b >= rows), default=rows)
        handle = InflightBatch(rows, padded)
        self._ring.acquire()
        with self._enter:
            index = len(self.steps)
            self.steps.append({
                "rows": rows, "padded": padded,
                "tags": [float(row[0, 0, 0]) for p in parts for row in p]})
        self._q.put((index, handle))
        return handle

    def _device(self):
        while True:
            index, handle = self._q.get()
            self.gate.wait()
            permits = self._permits
            if permits is not None:
                permits.acquire()
            else:
                time.sleep(self.step_ms.get(handle.padded, 2.0) / 1e3)
            self.finished += 1
            if index in self.fail_steps:
                handle.future.set_exception(
                    RuntimeError(f"device fault in step {index}"))
            else:
                handle.future.set_result(
                    np.full((handle.n, 10), 0.1, np.float32))
            self._ring.release()


def _bolts(engine, tasks, **batch_kw):
    """``tasks`` replicas of one bolt over one engine, as a topology makes
    them: one registry, one collector each."""
    metrics = MetricsRegistry()
    out = []
    for i in range(tasks):
        bolt = InferenceBolt(
            ModelConfig(name="lenet5", dtype="float32", input_shape=SHAPE),
            BatchConfig(**batch_kw), engine=engine, warmup=False)
        coll = _Collector()
        bolt.prepare(TopologyContext("inference-bolt", i, tasks, Config(),
                                     metrics=metrics), coll)
        out.append((bolt, coll))
    return out, metrics


def _record(tag):
    return Tuple(
        values=[json.dumps(
            {"instances": np.full((1, *SHAPE), tag, np.float32).tolist()})],
        fields=("message",), source_component="spout",
        root_ts=time.perf_counter())


async def _until(cond, timeout=10.0):
    t0 = time.perf_counter()
    while not cond():
        assert time.perf_counter() - t0 < timeout, "condition not met in time"
        await asyncio.sleep(0.002)


# ---- (a) a backlog from four sources fills every batch -----------------------


@pytest.mark.timeout(60)
def test_backlog_from_four_sources_forms_full_mixed_batches(run):
    """With >= 2 x max_batch rows outstanding from four bolt tasks, every
    batch after the first two is ``max_batch`` rows and carries rows of
    more than one task."""
    max_batch, n_batches = 32, 8

    async def go():
        eng = _RingEngine(GROWS)
        bolts, metrics = _bolts(eng, 4, max_batch=max_batch, buckets=(8, 32))
        eng.gate.clear()  # nothing finishes while the backlog is laid down
        try:
            for i in range(max_batch * n_batches):
                await bolts[i % 4][0].execute(_record(tag=i % 4))
            eng.gate.set()
            await asyncio.gather(*(b.flush() for b, _ in bolts))
        finally:
            eng.run_free()
        assert sum(len(c.acked) for _, c in bolts) == max_batch * n_batches
        assert not any(c.failed for _, c in bolts)
        total = max_batch * n_batches
        assert sum(s["rows"] for s in eng.steps) == total
        # The first two leave while the backlog is still being laid down
        # (the idle device's 5 ms deadline, then the free second slot);
        # from then on a step is full for as long as a full one is left.
        left, full = total, 0
        for k, step in enumerate(eng.steps):
            if k >= 2 and left >= max_batch:
                assert step["rows"] == max_batch
                assert len(set(step["tags"])) > 1, "one task's rows only"
                full += 1
            left -= step["rows"]
        assert full >= n_batches - 2
        m = metrics.snapshot()["inference-bolt"]
        assert m["batch_size"]["count"] == len(eng.steps)  # once a step
        assert m["coalesced_sources"] >= 4 * full
        assert m["steps_bucket_32"] >= full

    run(go(), timeout=50)


# ---- (b) how many formed batches a record waits behind -----------------------


@pytest.mark.timeout(90)
def test_batches_a_record_waits_behind(run):
    """Four rows arrive in every step, one at each of four bolt tasks:
    half of what 8-row steps can serve. A record's batch is cut when a
    ring slot frees, so ``ring_capacity`` formed batches (one running,
    one staged) run before it, however many tasks feed the queue (batches
    formed per task stood about twelve steps ahead of the ring: the half
    second of ``vit_g14.json_paced`` before PR 26, PERF.md §6). Time is
    counted in device steps, which the test ends by hand."""
    rounds, settle_s = 60, 0.004

    async def go():
        eng = _RingEngine(GROWS, manual=True)
        # a deadline well under a step, as the 5 ms are under the chip's
        # 28 ms step
        bolts, _ = _bolts(eng, 4, max_wait_ms=0.5)
        finished_at_arrival = []
        try:
            for r in range(rounds):
                for bolt, _ in bolts:  # arrivals during the running step
                    finished_at_arrival.append(eng.finished)
                    await bolt.execute(_record(tag=len(finished_at_arrival) - 1))
                await asyncio.sleep(settle_s)
                if r:  # the step that ran meanwhile ends
                    eng.finish_step()
                    await _until(lambda: eng.finished == r)
                    await asyncio.sleep(settle_s)
            eng.run_free()
            await asyncio.gather(*(b.flush() for b, _ in bolts))
        finally:
            eng.run_free()
        n = len(finished_at_arrival)
        assert sum(len(c.acked) for _, c in bolts) == n
        served = {int(tag): k for k, step in enumerate(eng.steps)
                  for tag in step["tags"]}
        # steps that had to end before the record's own could run
        ahead = [served[i] - finished_at_arrival[i] for i in range(n)]
        return ahead[n // 2:], eng  # the second half: the steady state

    ahead, eng = run(go(), timeout=80)
    assert statistics.median(ahead) <= eng.ring_capacity, ahead
    assert max(ahead) <= eng.ring_capacity + 1, ahead


# ---- (c) frame ingress keeps its coalesced egress ----------------------------


@pytest.mark.timeout(60)
def test_frame_records_of_one_batch_leave_as_one_payload(run):
    """Records that arrived in one ``RecordFrame`` and rode one device
    batch leave as ONE predictions payload (``batch.frame_egress``);
    records of other tuples in the same batch keep a payload each."""

    async def go():
        eng = _RingEngine(GROWS)
        ((bolt, coll),), _ = _bolts(eng, 1)
        frame = Tuple(
            values=[RecordFrame([
                encode_tensor(np.full((1, *SHAPE), 7.0, np.float32))
                for _ in range(5)])],
            fields=("message",), source_component="spout",
            root_ts=time.perf_counter())
        eng.gate.clear()
        try:
            for k in (1, 2):  # one running, one staged: the ring is full
                await bolt.execute(_record(tag=0))
                await _until(lambda: len(eng.steps) == k)
            await bolt.execute(frame)
            await bolt.execute(_record(tag=2))
            eng.gate.set()
            await bolt.flush()
        finally:
            eng.run_free()
        assert [s["rows"] for s in eng.steps] == [1, 1, 6]
        assert len(coll.acked) == 4 and not coll.failed
        rows = sorted(len(json.loads(msg)["predictions"])
                      for _, (msg, *_) in coll.emitted)
        assert rows == [1, 1, 1, 5], "the frame's five rows are one payload"

    run(go(), timeout=50)


# ---- (d) a failed batch fails each source's own tuples, which replay ---------


@pytest.mark.timeout(60)
def test_failed_batch_fails_and_replays_each_sources_own_tuples(run):
    async def go():
        eng = _RingEngine(GROWS, fail_steps={2})
        bolts, _ = _bolts(eng, 2)
        (b0, c0), (b1, c1) = bolts
        eng.gate.clear()
        try:
            warm = [_record(tag=0), _record(tag=0)]
            for k, t in enumerate(warm, 1):  # fill the ring
                await b0.execute(t)
                await _until(lambda: len(eng.steps) == k)
            mine, theirs = _record(tag=1), _record(tag=2)
            await b0.execute(mine)
            await b1.execute(theirs)
            eng.gate.set()
            await asyncio.gather(b0.flush(), b1.flush())
            assert eng.steps[2]["tags"] == [1.0, 2.0], "one batch, two tasks"
            assert [id(t) for t in c0.failed] == [id(mine)]
            assert [id(t) for t in c1.failed] == [id(theirs)]
            assert [id(t) for t in c0.acked] == [id(t) for t in warm]
            assert not c1.acked and c0.errors and c1.errors
            # the spout replays each failed tree to the task that had it
            await b0.execute(mine)
            await b1.execute(theirs)
            await asyncio.gather(b0.flush(), b1.flush())
        finally:
            eng.run_free()
        assert [id(t) for t in c0.acked] == [id(t) for t in warm + [mine]]
        assert [id(t) for t in c1.acked] == [id(theirs)]
        assert len(c0.failed) == 1 and len(c1.failed) == 1

    run(go(), timeout=50)


# ---- (e) the full-bucket cut, and the step times that decide it --------------


@pytest.mark.timeout(60)
@pytest.mark.parametrize("pending", [9, 20, 31])
@pytest.mark.parametrize("step_ms,expected", [(GROWS, 8), (FLAT, None)],
                         ids=["step_grows_with_bucket", "step_is_flat"])
def test_full_bucket_cut_follows_the_measured_step_times(
        run, pending, step_ms, expected):
    """9-31 pending rows against buckets 8 and 32: where a step's cost
    grows with its bucket the queue cuts the full 8-row batch and leaves
    the rest to the next (padding 9 rows to 32 costs four 8-row steps and
    the long step gathers the next over-full batch); where it is flat,
    one padded 32-row step serves them all."""

    async def go():
        eng = _RingEngine(step_ms)
        ((bolt, coll),), _ = _bolts(eng, 1, max_batch=32, buckets=(8, 32))
        eng.gate.clear()
        try:
            await bolt.execute(_record(tag=0))  # holds the device
            await _until(lambda: len(eng.steps) == 1)
            await bolt.execute(_record(tag=0))  # takes the second slot
            await _until(lambda: len(eng.steps) == 2)
            for _ in range(pending):  # both slots busy: these queue up
                await bolt.execute(_record(tag=1))
            eng.gate.set()
            await bolt.flush()
        finally:
            eng.run_free()
        assert len(coll.acked) == pending + 2 and not coll.failed
        return [s["rows"] for s in eng.steps[2:]]

    sizes = run(go(), timeout=50)
    assert sum(sizes) == pending
    if expected is None:
        assert sizes == [pending], "flat step time: one padded step"
    else:
        assert sizes[0] == expected, "the largest full bucket goes first"
        assert all(s <= expected for s in sizes)


@pytest.mark.timeout(60)
def test_fetch_thread_reads_the_step_of_each_bucket_without_the_queueing():
    """``engine.step_ms`` is the least seen of (ready - the later of the
    batch's own hand-over and the batch before becoming ready): a batch
    staged behind another reads as its own step, not step plus wait, and
    a program's slow first run is corrected by the next. A batch that was
    ready before the thread looked, and the one after it, are not read."""

    class _Out:  # becomes ready ``step_s`` after the batch before it did
        def __init__(self, step_s, n):
            self.step_s, self.n = step_s, n

        def is_ready(self):
            return self.step_s == 0.0

        def block_until_ready(self):
            time.sleep(self.step_s)
            return self

        def __array__(self, dtype=None, copy=None):
            return np.zeros((self.n, 10), np.float32)

    fetch_q = queue.SimpleQueue()
    ring = threading.BoundedSemaphore(8)
    step_ms = {}
    thread = threading.Thread(
        target=_fetch_loop, args=(fetch_q, ring, StagingPool(1), step_ms),
        daemon=True)
    launched = time.perf_counter()  # all four were launched before any ran
    handles = []
    # 0.0: ready before the fetch thread came to it (a stalled host), so
    # neither it nor the batch after it says how long a step takes
    for padded, step_s in ((8, 0.030), (32, 0.012), (8, 0.004), (32, 0.012),
                           (32, 0.0), (32, 0.001)):
        ring.acquire()
        handle = InflightBatch(padded, padded)
        handle._out = _Out(step_s, padded)
        handle._t_put = launched
        handles.append(handle)
        fetch_q.put(handle)
    thread.start()
    try:
        for handle in handles:
            handle.future.result(timeout=10)
    finally:
        fetch_q.put(None)
        thread.join(timeout=5)
    # the 8-row program's first run took 30 ms, its second 4: the least
    # stands; the 32-row steps waited behind 30 and 46 ms of other work
    # and still read as their own 12
    assert 4.0 <= step_ms[8] < 12.0
    assert 12.0 <= step_ms[32] < 24.0


# ---- (f) the hold before the cut (ISSUE 33) ----------------------------------

# One 8-row step lasts a minute of the test's clock, so that no real delay
# can run a hold out: a hold ends when the test moves the clock, or never.
MINUTE = {8: 60_000.0, 32: 60_000.0}


class _Clock:
    """Stands in for the ``time`` module in ``infer/continuous.py``."""

    def __init__(self):
        self.now = 1_000.0

    def perf_counter(self):
        return self.now


def _wait(cond, timeout=10.0):
    t0 = time.perf_counter()
    while not cond():
        assert time.perf_counter() - t0 < timeout, "condition not met in time"
        time.sleep(0.002)


def _row(tag, n=1):
    return np.full((n, *SHAPE), tag, np.float32)


def _held_queue(monkeypatch, step_ms=MINUTE, capacity=2, **batch_kw):
    """A queue whose first batch (one row, tag 0) is on the device and
    stays there until the test ends its step; the lead of bucket 8 is
    known from that cut (0 ms: the clock stood still meanwhile)."""
    clock = _Clock()
    monkeypatch.setattr(continuous, "time", clock)
    eng = _RingEngine(step_ms, capacity=capacity, manual=True)
    metrics = MetricsRegistry()
    batch_kw.setdefault("max_batch", 32)
    cb = continuous_for(eng, BatchConfig(
        buckets=(8, 32), eager=True, **batch_kw))
    cb.bind(metrics, "inference-bolt")
    first = cb.submit(_row(0))
    _wait(lambda: len(eng.steps) == 1 and (
        len(cb._flying) == 1 or capacity == 1))
    return clock, eng, cb, metrics, first


def _let_go(eng):
    """The test's last word: the steps left end by themselves, and in no
    time (``run_free`` lets each last its ``step_ms``)."""
    eng.step_ms = dict.fromkeys(eng.step_ms, 1.0)
    eng.run_free()


def _poke(cb):
    """What the timed wait's end does, for a clock that only the test
    moves: wake the dispatcher to look at the time again."""
    with cb._cond:
        cb._cond.notify_all()


def _hold_metrics(metrics):
    m = metrics.snapshot()["inference-bolt"]
    return m["cut_hold_ms"], m.get("cuts_late", 0)


@pytest.mark.timeout(60)
def test_refill_cut_is_held_until_the_running_step_is_about_to_end(
        monkeypatch):
    """A slot is free, a batch is on the device, its step time and the
    lead are known and the rows do not fill ``max_batch``: nothing is cut
    until the step is about to end, rows that arrive meanwhile do not cut
    early, and all of them ride the one cut."""
    clock, eng, cb, metrics, first = _held_queue(monkeypatch)
    try:
        subs = [cb.submit(_row(1))]
        time.sleep(0.03)
        assert len(eng.steps) == 1, "cut at once: the hold did not engage"
        for tag in (2, 3, 4):  # each arrival wakes the dispatcher
            subs.append(cb.submit(_row(tag)))
            clock.now += 10.0
        time.sleep(0.03)
        assert len(eng.steps) == 1 and len(cb) == 4, \
            "an arrival during the hold cut early"
        clock.now += 29.0  # 59 s into a step of 60
        _poke(cb)
        time.sleep(0.03)
        assert len(eng.steps) == 1, "cut before the step was about to end"
        clock.now += 1.0
        _poke(cb)
        _wait(lambda: len(eng.steps) == 2)
        assert eng.steps[1]["tags"] == [1.0, 2.0, 3.0, 4.0]
        assert len(cb) == 0
        hold, late = _hold_metrics(metrics)
        assert hold["count"] == 2, "observed once a cut"
        assert hold["sum"] == pytest.approx(60_000.0), \
            "0.0 for the idle device's cut, the step for the held one"
        assert late == 0
        assert cb.stats()["lead_ms"] == {8: 0.0}
    finally:
        _let_go(eng)
    for sub in [first] + subs:
        assert sub.future.result(timeout=10).shape == (1, 10)


@pytest.mark.timeout(60)
def test_max_batch_rows_arriving_during_a_hold_cut_at_once(monkeypatch):
    clock, eng, cb, metrics, first = _held_queue(monkeypatch, max_batch=8)
    try:
        subs = [cb.submit(_row(1)) for _ in range(7)]
        time.sleep(0.03)
        assert len(eng.steps) == 1, "seven rows of eight: still held"
        subs.append(cb.submit(_row(1)))  # the clock has not moved
        _wait(lambda: len(eng.steps) == 2)
        assert eng.steps[1]["rows"] == 8
        # a full batch may park on the ring, as before: both slots busy,
        # eight more rows still cut
        subs += [cb.submit(_row(2, n=8))]
        _wait(lambda: len(cb) == 0)
        assert cb.inflight == 3, "the full batch parks on the ring"
    finally:
        _let_go(eng)
    for sub in [first] + subs:
        sub.future.result(timeout=10)
    assert [s["rows"] for s in eng.steps] == [1, 8, 8]
    hold, late = _hold_metrics(metrics)
    assert hold["count"] == 3 and hold["sum"] == 0.0 and late == 0


@pytest.mark.timeout(60)
@pytest.mark.parametrize("case", [
    "no_step_time", "no_lead_for_the_bucket", "one_slot", "step_overdue"])
def test_without_a_reading_or_with_no_time_left_the_cut_is_at_once(
        monkeypatch, case):
    """The rule before ISSUE 33 is what the hold falls back to: an engine
    without ``step_ms``, a bucket that no cut has timed yet, a ring of one
    slot, or a running step that should have ended already."""
    clock, eng, cb, metrics, first = _held_queue(
        monkeypatch,
        step_ms={} if case == "no_step_time" else MINUTE,
        capacity=1 if case == "one_slot" else 2)
    try:
        if case == "step_overdue":
            clock.now += 61.0
        # nine rows of a flat step pad to 32, which no cut has timed
        rows = 9 if case == "no_lead_for_the_bucket" else 2
        subs = [cb.submit(_row(1, n=rows))]
        if case == "one_slot":
            time.sleep(0.03)
            assert len(eng.steps) == 1, "the one slot is taken"
            eng.finish_step()
        _wait(lambda: len(eng.steps) == 2)  # the clock never moved
        assert eng.steps[1]["rows"] == rows
    finally:
        _let_go(eng)
    for sub in [first] + subs:
        sub.future.result(timeout=10)
    hold, late = _hold_metrics(metrics)
    assert hold["count"] == 2 and hold["sum"] == 0.0 and late == 0


@pytest.mark.timeout(60)
@pytest.mark.parametrize("release", ["flush", "close"])
def test_flush_and_close_release_a_hold(monkeypatch, release):
    clock, eng, cb, metrics, first = _held_queue(monkeypatch)
    try:
        subs = [cb.submit(_row(1)), cb.submit(_row(2))]
        time.sleep(0.03)
        assert len(eng.steps) == 1 and len(cb) == 2
        getattr(cb, release)()
        _wait(lambda: len(eng.steps) == 2)
        assert eng.steps[1]["tags"] == [1.0, 2.0] and len(cb) == 0
    finally:
        _let_go(eng)
    for sub in [first] + subs:
        assert sub.future.result(timeout=10).shape == (1, 10)
    if release == "close":
        cb._thread.join(timeout=5)
        assert not cb._thread.is_alive()
        with pytest.raises(RuntimeError):
            cb.submit(_row(3))


@pytest.mark.timeout(60)
def test_a_hold_that_outlives_its_step_counts_as_late(monkeypatch):
    """The step ends while its successor is still held (a step shorter
    than the least seen, or a dispatcher that woke late): the cut follows
    at once, ``cut_hold_ms`` has the time it was held, ``cuts_late`` the
    cut; the next cut, in time, does not count."""
    clock, eng, cb, metrics, first = _held_queue(monkeypatch)
    try:
        second = cb.submit(_row(1))
        time.sleep(0.03)
        assert len(eng.steps) == 1
        clock.now += 20.0
        eng.finish_step()  # 40 s before the queue expected it
        _wait(lambda: len(eng.steps) == 2)
        hold, late = _hold_metrics(metrics)
        assert hold["count"] == 2 and hold["sum"] == pytest.approx(20_000.0)
        assert late == 1
        _wait(lambda: len(cb._flying) == 1)
        third = cb.submit(_row(2))
        time.sleep(0.03)
        assert len(eng.steps) == 2, "held again, behind the second step"
        clock.now += 60.0
        _poke(cb)
        _wait(lambda: len(eng.steps) == 3)
        hold, late = _hold_metrics(metrics)
        assert hold["count"] == 3 and hold["sum"] == pytest.approx(80_000.0)
        assert late == 1
    finally:
        _let_go(eng)
    for sub in (first, second, third):
        sub.future.result(timeout=10)


@pytest.mark.timeout(60)
def test_hold_bookkeeping_survives_many_submitters():
    """Eight threads submit while the dispatcher holds and cuts and the
    device thread lands batches, with the interpreter switching threads
    every 10 us: every row is answered, every cut observed once, and
    nothing is left on the queue's list of what the device holds."""
    eng = _RingEngine({8: 10.0, 32: 10.0})  # about twenty rows a step
    metrics = MetricsRegistry()
    cb = continuous_for(eng, BatchConfig(buckets=(8, 32), max_batch=32))
    cb.bind(metrics, "inference-bolt")
    subs, lock = [], threading.Lock()

    def feed(tag):
        for _ in range(60):
            sub = cb.submit(_row(tag))
            with lock:
                subs.append(sub)
            time.sleep(0.004)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=feed, args=(t,), daemon=True)
                   for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
        cb.flush()
        for sub in subs:
            assert sub.future.result(timeout=30).shape == (1, 10)
    finally:
        sys.setswitchinterval(interval)
        eng.run_free()
    assert len(subs) == 8 * 60 and cb.rows_dispatched == len(subs)
    assert len(cb) == 0 and cb.inflight == 0 and not cb._flying
    hold, _ = _hold_metrics(metrics)
    assert hold["count"] == cb.batches == len(eng.steps)
    assert hold["sum"] > 0.0, "no cut was ever held"
