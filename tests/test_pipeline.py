"""Split-phase pipelined inference engine (dispatch / fetch overlap).

Covers the ISSUE-3 tentpole contract: H2D of batch N+1 overlaps compute of
batch N (bounded by ``pipeline_depth``), exceptions fail only their own
batch, staging buffers recycle instead of growing per batch, the operator
drains batches still in the ring on ``flush()``, and the staging path
performs no extra full-batch host copies (allocation-count guard).

Device-overlap ordering is made deterministic with gated fake jit outputs
(``block_until_ready``/``__array__`` wait on events the test controls) —
no sleeps racing real XLA execution.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time

import numpy as np
import pytest

from storm_tpu.config import BatchConfig, Config, ModelConfig, QosConfig, \
    ShardingConfig
from storm_tpu.infer.engine import InferenceEngine, InflightBatch, \
    NullEngine, StagingPool
from storm_tpu.infer.operator import InferenceBolt
from storm_tpu.runtime.base import TopologyContext
from storm_tpu.runtime.metrics import MetricsRegistry
from storm_tpu.runtime.tracing import DEVICE_SUBSTAGES
from storm_tpu.runtime.tuples import Tuple


# ---- engine-level: overlap / isolation / staging -----------------------------


@pytest.fixture()
def pipe_engine():
    return InferenceEngine(
        ModelConfig(name="lenet5", dtype="float32", input_shape=(28, 28, 1)),
        ShardingConfig(data_parallel=0),
        BatchConfig(max_batch=8, buckets=(8,), pipeline_depth=2),
    )


class _GatedOut:
    """Stands in for a jit output: the fetch thread blocks on our gate, so
    the test decides exactly when each in-flight batch 'finishes'."""

    def __init__(self, tag: int, gate: threading.Event, n: int,
                 fail: bool = False) -> None:
        self.tag = tag
        self.gate = gate
        self.n = n
        self.fail = fail
        self.reached_fetch = threading.Event()

    def block_until_ready(self):
        self.reached_fetch.set()
        assert self.gate.wait(10), "test never opened the gate"
        if self.fail:
            raise RuntimeError(f"device fault in batch {self.tag}")
        return self

    def __array__(self, dtype=None, copy=None):
        return np.full((self.n, 10), float(self.tag), np.float32)


def _gate_fwd(eng, fail_tags=()):
    """Replace the engine's jit fwd with a launch recorder returning gated
    outputs; returns (launches, gates)."""
    launches = []
    gates = {}

    def fake_fwd(params, state, x):
        tag = len(launches)
        launches.append(time.perf_counter())
        gates[tag] = threading.Event()
        return _GatedOut(tag, gates[tag], x.shape[0], fail=tag in fail_tags)

    eng._fwd = fake_fwd
    return launches, gates


def test_dispatch_overlaps_next_batch_h2d_with_compute(pipe_engine):
    """Batch 1's staging+H2D+launch completes while batch 0 is still in
    'compute' (its gate closed) — the serialized engine could not launch
    batch 1 before batch 0's fetch returned."""
    launches, gates = _gate_fwd(pipe_engine)
    x = np.zeros((8, 28, 28, 1), np.float32)
    h0 = pipe_engine.dispatch((x,))
    h1 = pipe_engine.dispatch((x,))
    assert len(launches) == 2, "second H2D+launch must not wait for fetch"
    assert not h0.future.done() and not h1.future.done()
    # depth=2: a third dispatch parks on the ring until a fetch completes.
    h2_box = []
    t = threading.Thread(
        target=lambda: h2_box.append(pipe_engine.dispatch((x,))))
    t.start()
    time.sleep(0.2)
    assert len(launches) == 2, "ring must bound in-flight batches at depth"
    gates[0].set()  # batch 0 finishes -> slot frees -> batch 2 launches
    assert np.all(h0.future.result(10) == 0.0)
    t.join(10)
    assert not t.is_alive() and len(launches) == 3
    gates[1].set()
    gates[2].set()
    assert np.all(h1.future.result(10) == 1.0)
    assert np.all(h2_box[0].future.result(10) == 2.0)
    # Per-phase timings landed on every handle.
    for h in (h0, h1, h2_box[0]):
        assert set(h.timings) == {k for k, _ in DEVICE_SUBSTAGES}


def test_exception_fails_only_its_own_batch(pipe_engine):
    launches, gates = _gate_fwd(pipe_engine, fail_tags={0})
    x = np.zeros((8, 28, 28, 1), np.float32)
    h0 = pipe_engine.dispatch((x,))
    h1 = pipe_engine.dispatch((x,))
    gates[0].set()
    gates[1].set()
    with pytest.raises(RuntimeError, match="batch 0"):
        h0.future.result(10)
    assert np.all(h1.future.result(10) == 1.0), \
        "batch 1 must survive batch 0's failure"
    # The failed batch released its ring slot + staging buffer: the
    # pipeline still accepts and completes new batches.
    h2 = pipe_engine.dispatch((x,))
    gates[2].set()
    assert np.all(h2.future.result(10) == 2.0)


def test_staging_buffers_recycle_no_per_batch_growth(pipe_engine):
    pipe_engine.warmup()
    x = np.random.rand(5, 28, 28, 1).astype(np.float32)
    pipe_engine.predict(x)  # fault in the bucket's pool buffer
    before = pipe_engine._staging.allocated
    for _ in range(25):
        pipe_engine.predict(x)
    assert pipe_engine._staging.allocated == before, \
        "steady-state batches must reuse pooled staging buffers"


def test_dispatch_parts_match_stacked_predict(pipe_engine):
    """Multi-part dispatch (the operator's per-record arrays) computes the
    same result as the stacked single-array path."""
    rng = np.random.RandomState(7)
    parts = [rng.rand(3, 28, 28, 1).astype(np.float32),
             rng.rand(2, 28, 28, 1).astype(np.float32)]
    want = pipe_engine.predict(np.concatenate(parts))
    got = pipe_engine.dispatch(parts).future.result(30)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_pipeline_depth_zero_serializes(pipe_engine):
    eng = InferenceEngine(
        ModelConfig(name="lenet5", dtype="float32", input_shape=(28, 28, 1)),
        ShardingConfig(data_parallel=0),
        BatchConfig(max_batch=8, buckets=(8,), pipeline_depth=0),
    )
    assert eng._ring is None and eng.pipeline_depth == 0
    x = np.random.rand(4, 28, 28, 1).astype(np.float32)
    h = eng.dispatch((x,))
    assert h.future.done(), "depth 0 resolves synchronously (serialized)"
    np.testing.assert_allclose(
        h.future.result(), pipe_engine.predict(x), atol=1e-6)


def test_staging_pool_bounds_and_reuses():
    pool = StagingPool(limit=2)
    a = pool.acquire((4, 2), np.float32)
    b = pool.acquire((4, 2), np.float32)
    assert pool.allocated == 2
    got = []
    t = threading.Thread(
        target=lambda: got.append(pool.acquire((4, 2), np.float32)))
    t.start()
    time.sleep(0.1)
    assert not got, "third acquire must block at the pool limit"
    pool.release(a)
    t.join(10)
    assert got and got[0] is a, "released buffer is recycled, not realloced"
    assert pool.allocated == 2
    # Distinct shapes/dtypes get their own sub-pool.
    c = pool.acquire((8, 2), np.float32)
    assert pool.allocated == 3
    pool.release(b), pool.release(c), pool.release(got[0])


def test_batch_config_validates_pipeline_knobs():
    with pytest.raises(ValueError):
        BatchConfig(pipeline_depth=-1)
    with pytest.raises(ValueError):
        BatchConfig(staging_pool=-2)


# ---- operator-level: futures, drain, alloc guard, prewarm --------------------


class _Collector:
    def __init__(self):
        self.emitted = []
        self.acked = []
        self.failed = []
        self.errors = []

    def set_output_fields(self, fields):
        pass

    async def emit(self, values, stream="default", anchors=None, **kw):
        self.emitted.append((stream, list(values)))
        return 1

    def ack(self, t):
        self.acked.append(t)

    def fail(self, t):
        self.failed.append(t)

    def report_error(self, e):
        self.errors.append(e)


def _tuple(payload) -> Tuple:
    return Tuple(values=[payload], fields=("message",),
                 source_component="spout", root_ts=time.perf_counter())


def _prepared_bolt(engine, **batch_kw) -> "tuple[InferenceBolt, _Collector]":
    bolt = InferenceBolt(
        ModelConfig(name="lenet5", dtype="float32", input_shape=(28, 28, 1)),
        BatchConfig(**batch_kw), engine=engine, warmup=False)
    ctx = TopologyContext("inference-bolt", 0, 1, Config(),
                          metrics=MetricsRegistry())
    coll = _Collector()
    bolt.prepare(ctx, coll)
    return bolt, coll


class _ManualEngine:
    """dispatch-protocol engine whose futures the TEST resolves — the
    operator's completion path is exercised without device timing."""

    input_shape = (28, 28, 1)

    def __init__(self):
        self.handles = []

    def warmup(self, buckets=None):
        pass

    def predict(self, x):  # pragma: no cover - dispatch path is used
        raise AssertionError("operator must use dispatch, not predict")

    def dispatch(self, parts):
        n = sum(int(p.shape[0]) for p in parts)
        h = InflightBatch(n, n)
        h.timings = {"h2d_ms": 0.5, "compute_ms": 1.0, "d2h_ms": 0.25}
        self.handles.append(h)
        return h


def _payload(n=1):
    return json.dumps(
        {"instances": np.zeros((n, 28, 28, 1), np.float32).tolist()})


def test_operator_completes_tuples_from_fetch_futures(run):
    async def go():
        eng = _ManualEngine()
        bolt, coll = _prepared_bolt(eng, max_batch=2, max_wait_ms=10_000,
                                    max_inflight=4)
        tuples = [_tuple(_payload()) for _ in range(4)]
        for t in tuples:
            await bolt.execute(t)
        await asyncio.sleep(0.05)
        assert len(eng.handles) == 2 and not coll.acked, \
            "acks must defer until the fetch future resolves"
        # Batch 0 fails -> only ITS tuples fail; batch 1 acks normally.
        eng.handles[0].future.set_exception(RuntimeError("boom"))
        eng.handles[1].future.set_result(
            np.full((2, 10), 0.1, np.float32))
        await bolt.flush()
        assert {id(t) for t in coll.failed} == {id(t) for t in tuples[:2]}
        assert {id(t) for t in coll.acked} == {id(t) for t in tuples[2:]}
        assert len(coll.emitted) == 2  # predictions for batch 1 only
        assert coll.errors and "boom" in str(coll.errors[0])
        # Substage timings landed in the operator's histograms (for the
        # one batch that completed; the failed batch records nothing).
        m = bolt.context.metrics
        for key, _ in DEVICE_SUBSTAGES:
            assert m.histogram("inference-bolt", key).count == 1

    run(go(), timeout=60)


def test_operator_flush_drains_ring_and_queue(run):
    async def go():
        eng = _ManualEngine()
        eng.ring_capacity = 2
        bolt, coll = _prepared_bolt(eng, max_batch=2, max_wait_ms=10_000,
                                    max_inflight=4)
        cb = bolt._cbs[None]
        # Two full batches in the ring + one row queued. Each full batch is
        # one two-row record: rows that arrive one record at a time coalesce
        # or not by when the queue's thread wakes (a free slot behind a
        # batch in flight is refilled at once where the engine has no
        # ``step_ms``), and one run in five of this test cut three batches.
        for rows in (2, 2, 1):
            await bolt.execute(_tuple(_payload(rows)))
        await asyncio.sleep(0.05)
        assert len(eng.handles) == 2 and len(cb) == 1

        async def resolve():
            # flush() forces the queued partial batch out (handle 3
            # appears), then waits until every group is emitted.
            for _ in range(100):
                if len(eng.handles) == 3:
                    break
                await asyncio.sleep(0.01)
            for h in eng.handles:
                if not h.future.done():
                    h.future.set_result(
                        np.zeros((h.n, 10), np.float32))

        _, _ = await asyncio.gather(bolt.flush(), resolve())
        assert len(coll.acked) == 3 and not coll.failed
        assert len(cb) == 0 and not bolt._cb_rows and not bolt._inflight

    run(go(), timeout=60)


def test_operator_staging_no_extra_host_copies(run):
    """Alloc-count guard: the operator's per-record arrays go through the
    engine's queue straight to the engine's pooled staging write — zero
    new staging allocations per batch at steady state."""

    async def go():
        eng = InferenceEngine(
            ModelConfig(name="lenet5", dtype="float32",
                        input_shape=(28, 28, 1)),
            ShardingConfig(data_parallel=0),
            BatchConfig(max_batch=8, buckets=(8,), pipeline_depth=2),
        )
        eng.warmup()
        bolt, coll = _prepared_bolt(eng, max_batch=8, buckets=(8,),
                                    max_wait_ms=10_000, pipeline_depth=2)
        # Warm the pool to steady state: with depth 2 up to two batches
        # overlap, so the pool legitimately grows to two buffers — but
        # never beyond, however many batches follow.
        for _ in range(24):
            await bolt.execute(_tuple(_payload()))
        await bolt.flush()
        assert len(coll.acked) == 24
        for _ in range(40):  # five more full batches
            await bolt.execute(_tuple(_payload()))
        await bolt.flush()
        assert len(coll.acked) == 64 and not coll.failed
        # the queue's dispatcher thread may first overlap two batches only
        # in the second phase; the ring still bounds the pool at two
        assert eng._staging.allocated <= 2, \
            "full-batch host buffers must come from the pool, not fresh"

    run(go(), timeout=120)


def test_null_engine_dispatch_protocol():
    ne = NullEngine((28, 28, 1), 10)
    h = ne.dispatch((np.zeros((3, 28, 28, 1), np.float32),))
    assert h.future.done()
    out = h.future.result()
    assert out.shape == (3, 10)
    np.testing.assert_allclose(out.sum(-1), np.ones(3), atol=1e-6)
    assert set(h.timings) == {k for k, _ in DEVICE_SUBSTAGES}


# ---- QoS degrade engine prewarm ---------------------------------------------


class _RecordingEngine:
    def __init__(self, name):
        self.name = name
        self.input_shape = (28, 28, 1)
        self.warmed = 0

    def warmup(self, buckets=None):
        self.warmed += 1


def test_degrade_engine_warmed_in_prepare_and_prewarm(monkeypatch):
    built = {}

    def fake_shared(model_cfg, sharding=None, batch=None):
        return built.setdefault(model_cfg.name, _RecordingEngine(
            model_cfg.name))

    monkeypatch.setattr(
        "storm_tpu.infer.operator.shared_engine", fake_shared)
    qos = QosConfig(enabled=True, degrade_model="resnet20")
    ctx = TopologyContext("inference-bolt", 0, 1, Config(),
                          metrics=MetricsRegistry())

    # prepare() alone warms BOTH engines (no lazy compile on first shed).
    bolt = InferenceBolt(ModelConfig(name="lenet5"), qos=qos)
    bolt.prepare(ctx, _Collector())
    assert built["lenet5"].warmed == 1
    assert built["resnet20"].warmed == 1, \
        "degrade engine must compile at prepare, not on the shed path"

    # prewarm() (warm scale-up) builds+warms both off-loop; prepare()
    # then skips the redundant in-loop warmup.
    built.clear()
    bolt2 = InferenceBolt(ModelConfig(name="lenet5"), qos=qos)
    bolt2.prewarm()
    assert built["lenet5"].warmed == 1 and built["resnet20"].warmed == 1
    bolt2.prepare(ctx, _Collector())
    assert built["lenet5"].warmed == 1 and built["resnet20"].warmed == 1
