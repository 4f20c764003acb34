"""The step log (PR 41): one row a device step, built on the engine's handle
by the queue that cut the batch and the two engine threads that passed it on,
kept by the profile sink as a ring of the last ``STEP_LOG`` steps
(``obs/profile.py ProfileStore.steps()``; docs/OPERATIONS.md, "Reading the
step log")."""

import time

import numpy as np
import pytest

from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
from storm_tpu.infer.continuous import _reset_registry, continuous_for
from storm_tpu.infer.engine import InferenceEngine
from storm_tpu.models.registry import build_model
from storm_tpu.obs import profile
from storm_tpu.obs.profile import STEP_MOMENTS, new_step_row
from storm_tpu.runtime.metrics import MetricsRegistry
from storm_tpu.runtime.tracing import TraceContext, Tracer


@pytest.fixture(autouse=True)
def _fresh():
    _reset_registry()
    profile.set_enabled(True)
    profile.profile_store().reset()
    yield
    profile.set_enabled(True)
    _reset_registry()


def _engine(name):
    model = build_model(name)
    cfg = ModelConfig(name=name, num_classes=model.num_classes,
                      input_shape=tuple(model.input_shape))
    batch = BatchConfig(buckets=(8,), max_batch=8, max_wait_ms=2.0)
    engine = InferenceEngine(cfg, ShardingConfig(), batch)
    engine.warmup((8,))
    return engine, batch


def _instances(engine, n, rows=1):
    shape = (rows, *engine.input_shape)
    if len(engine.input_shape) == 1:  # token ids ride float32
        return [np.full(shape, i % 7, np.float32) for i in range(n)]
    return [np.full(shape, i / n, np.float32) for i in range(n)]


@pytest.mark.parametrize("name", ["vit_tiny", "nemotron_h_tiny"])
def test_one_row_a_step_with_its_moments_in_order(name):
    engine, batch = _engine(name)
    store = profile.profile_store()
    # the warm-up's step, a direct predict: its row is appended on the
    # engine's fetch thread *after* the result that ``warmup`` waited for is
    # handed over, so on a busy host it may not be there yet (it then read
    # as a fourth of the queue's three batches)
    deadline = time.time() + 10
    while not store.steps():
        assert time.time() < deadline
        time.sleep(0.01)
    warm = len(store.steps())
    queue = continuous_for(engine, batch)
    subs = []
    for i, x in enumerate(_instances(engine, 24)):
        subs.append(queue.submit(x, source=f"task-{i % 3}"))
        if i % 8 == 7:
            time.sleep(0.02)
    for s in subs:
        assert s.future.result(60).shape[0] == 1
    deadline = time.time() + 10
    while queue.batches < 3 or sum(r["rows"] for r in store.steps()[warm:]) \
            < 24:
        assert time.time() < deadline
        time.sleep(0.01)
    rows = store.steps()[warm:]
    # one row a dispatched batch, numbered as the engine dispatched them
    assert len(rows) == queue.batches
    assert [r["step"] for r in rows] == list(range(warm, warm + len(rows)))
    assert sum(r["rows"] for r in rows) == 24
    for r in rows:
        assert r["engine"] == name and r["padded"] == 8
        assert 1 <= r["rows"] <= 8 and 1 <= r["sources"] <= 3
        moments = [r[m] for m in STEP_MOMENTS]
        assert all(t is not None for t in moments), r
        assert moments == sorted(moments), r
        # on ``time.time()``, the broker's clock: the run lies around now
        assert abs(moments[0] - time.time()) < 120
        assert isinstance(r["seen"], bool)
    # the warm-up went through no queue: its row lacks the queue's moments
    first = store.steps()[0]
    assert first["t_cut"] is None and first["t_first_enq"] is None
    assert first["t_resolved"] is None and first["t_launched"] is not None
    # what the operator's route shows of it
    shown = store.snapshot()["steps"]
    assert shown["count"] == warm + len(rows)
    assert shown["last"][-1]["step"] == rows[-1]["step"]
    gap = shown["longest_gap"]
    assert gap["gap_ms"] > 0 and gap["interval"] in {
        n for n, _a, _b in profile.STEP_INTERVALS}


def test_the_ring_keeps_the_last_4096_steps_oldest_first():
    store = profile.ProfileStore()
    assert profile.STEP_LOG == 4096
    for n in range(profile.STEP_LOG + 10):
        store.record_batch("m", 8, 8, {"compute_ms": 1.0},
                           new_step_row(n, "m", 8, 8))
    rows = store.steps()
    assert len(rows) == profile.STEP_LOG
    assert [rows[0]["step"], rows[-1]["step"]] == [10, profile.STEP_LOG + 9]
    rows[0]["step"] = -1  # a copy: the log is not the reader's to change
    assert store.steps()[0]["step"] == 10
    store.reset()
    assert store.steps() == []


def test_switched_off_no_row_is_built_or_kept():
    engine, batch = _engine("vit_tiny")
    store = profile.profile_store()
    store.reset()
    profile.set_enabled(False)
    queue = continuous_for(engine, batch)
    handle = engine.dispatch(_instances(engine, 2))
    assert handle.step is None
    handle.future.result(60)
    for s in [queue.submit(x) for x in _instances(engine, 4)]:
        s.future.result(60)
    time.sleep(0.05)
    assert store.steps() == []
    profile.set_enabled(True)
    engine.dispatch(_instances(engine, 2)).future.result(60)
    deadline = time.time() + 5
    while not store.steps():
        assert time.time() < deadline
        time.sleep(0.01)
    assert len(store.steps()) == 1


def test_the_longest_gap_names_the_interval_the_time_went_to():
    def row(n, ready, launched):
        return dict(new_step_row(n, "m", 8, 8), t_cut=ready - 0.05,
                    t_staged=ready - 0.045, t_launched=launched,
                    t_ready=ready, t_fetched=ready + 0.001)

    rows = [row(n, 10.0 + 0.1 * n, 10.0 + 0.1 * n - 0.04) for n in range(9)]
    rows.append(row(9, 11.4, 10.86))  # 0.5 s after the eighth, on the device
    found = profile.longest_gap(rows)
    assert found["gap_ms"] == pytest.approx(600.0)
    assert (found["before"]["step"], found["after"]["step"]) == (8, 9)
    assert found["interval"] == "launched->ready"
    assert found["over_median_ms"] == pytest.approx(500.0)
    assert profile.longest_gap(rows[:1]) is None


def test_a_sampled_records_batch_span_names_its_step():
    engine, batch = _engine("vit_tiny")
    store = profile.profile_store()
    tracer = Tracer(sample_rate=1.0)
    queue = continuous_for(engine, batch)
    queue.bind(MetricsRegistry(), "inference-bolt", tracer=tracer,
               trace_of=lambda payload: payload)
    contexts = [tracer.maybe_trace() for _ in range(3)]
    assert all(isinstance(c, TraceContext) for c in contexts)
    subs = [queue.submit(x, payload=c)
            for x, c in zip(_instances(engine, 3), contexts)]
    for s in subs:
        s.future.result(60)
    deadline = time.time() + 5
    while len(store.steps()) < 2:  # the warm-up's and this batch's
        assert time.time() < deadline
        time.sleep(0.01)
    logged = {r["step"] for r in store.steps() if r["t_cut"] is not None}
    for ctx, sub in zip(contexts, subs):
        spans = tracer.store.get(ctx.trace_id)["spans"]
        batch_spans = [s for s in spans if s["name"] == "device_execute"]
        assert len(batch_spans) == 1
        assert batch_spans[0]["attrs"]["step"] in logged
        assert batch_spans[0]["span_id"] == sub.batch_span
