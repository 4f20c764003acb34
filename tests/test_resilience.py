"""Resilience layer (round 14): retry/backoff classification, circuit
breaker transitions, token-bucket replay pacing, chaos injection, the
engine fetch-ring watchdog, and the PeerSender park/reroute path.

Everything here is fast-tier: fakes for the gRPC/worker surfaces, one
real (CPU) engine for the watchdog->quarantine arc. The dist-level
chaos integration (worker kill, frame corruption over a live cluster)
lives in tests/test_dist.py (slow tier).
"""

from __future__ import annotations

import asyncio
import queue
import threading
import time
from types import SimpleNamespace

import grpc
import numpy as np
import pytest

from storm_tpu.config import BatchConfig, ModelConfig, ResilienceConfig, \
    ShardingConfig
from storm_tpu.resilience import (ChaosDrop, ChaosInjector, CircuitBreaker,
                                  RetryPolicy, TokenBucket)
from storm_tpu.resilience.retry import (FATAL_CODES, RETRYABLE_BROAD,
                                        RETRYABLE_NARROW, is_fatal_rpc,
                                        is_retryable)


class FakeRpcError(grpc.RpcError):
    def __init__(self, code):
        self._code = code

    def code(self):
        return self._code


# ---- retry classification ----------------------------------------------------


def test_retryable_codes_classification():
    assert is_retryable(FakeRpcError(grpc.StatusCode.UNAVAILABLE))
    assert is_retryable(FakeRpcError(grpc.StatusCode.DEADLINE_EXCEEDED))
    assert not is_retryable(FakeRpcError(grpc.StatusCode.UNAUTHENTICATED))
    assert not is_retryable(FakeRpcError(grpc.StatusCode.INVALID_ARGUMENT))
    # the narrow (Deliver) set refuses DEADLINE_EXCEEDED: the payload may
    # already be enqueued on the receiver — re-sending double-delivers
    assert not is_retryable(FakeRpcError(grpc.StatusCode.DEADLINE_EXCEEDED),
                            codes=RETRYABLE_NARROW)
    assert is_retryable(FakeRpcError(grpc.StatusCode.UNAVAILABLE),
                        codes=RETRYABLE_NARROW)


def test_non_rpc_connection_errors_are_retryable():
    assert is_retryable(ConnectionError("boom"))
    assert is_retryable(ChaosDrop("injected"))  # chaos drops = real outages
    assert not is_retryable(TypeError("encode bug"))
    assert not is_retryable(ValueError("protocol"))


def test_fatal_classification():
    for code in FATAL_CODES:
        assert is_fatal_rpc(FakeRpcError(code))
    assert not is_fatal_rpc(FakeRpcError(grpc.StatusCode.UNAVAILABLE))
    assert not is_fatal_rpc(ConnectionError("down"))


def test_backoff_full_jitter_bounds():
    p = RetryPolicy(base_s=0.1, cap_s=0.5)
    for attempt in range(6):
        for _ in range(20):
            d = p.backoff(attempt)
            assert 0.0 <= d <= min(0.5, 0.1 * 2 ** attempt)


def test_call_sync_retries_then_succeeds():
    p = RetryPolicy(attempts=3, base_s=0.001, cap_s=0.002, deadline_s=5.0)
    calls = []

    def flaky(timeout):
        calls.append(timeout)
        if len(calls) < 3:
            raise FakeRpcError(grpc.StatusCode.UNAVAILABLE)
        return "ok"

    assert p.call_sync(flaky) == "ok"
    assert len(calls) == 3


def test_call_sync_fails_fast_on_fatal():
    p = RetryPolicy(attempts=5, base_s=0.001)
    calls = []

    def rejected(timeout):
        calls.append(1)
        raise FakeRpcError(grpc.StatusCode.UNAUTHENTICATED)

    with pytest.raises(grpc.RpcError):
        p.call_sync(rejected)
    assert len(calls) == 1  # no retry burned on an auth reject


def test_call_sync_exhausts_attempts():
    p = RetryPolicy(attempts=3, base_s=0.001, cap_s=0.002)
    calls = []

    def down(timeout):
        calls.append(1)
        raise ConnectionError("down")

    with pytest.raises(ConnectionError):
        p.call_sync(down)
    assert len(calls) == 3


def test_call_sync_respects_deadline_budget():
    p = RetryPolicy(attempts=100, base_s=0.05, cap_s=0.05, deadline_s=0.15)
    t0 = time.monotonic()
    with pytest.raises(ConnectionError):
        p.call_sync(lambda t: (_ for _ in ()).throw(ConnectionError("x")))
    assert time.monotonic() - t0 < 1.0  # budget, not 100 attempts


def test_call_async_retries():
    p = RetryPolicy(attempts=3, base_s=0.001, cap_s=0.002)
    calls = []

    def flaky(timeout):
        calls.append(1)
        if len(calls) < 2:
            raise ConnectionError("x")
        return 7

    assert asyncio.run(p.call_async(flaky)) == 7
    assert len(calls) == 2


# ---- circuit breaker ---------------------------------------------------------


def test_circuit_opens_after_consecutive_failures():
    opened, closed = [], []
    cb = CircuitBreaker(failures=3, reset_s=60.0,
                        on_open=lambda: opened.append(1),
                        on_close=lambda: closed.append(1))
    assert cb.allow()
    cb.record_failure()
    cb.record_failure()
    assert cb.allow()  # still closed below threshold
    cb.record_failure()
    assert not cb.allow()
    assert opened == [1] and cb.opens == 1


def test_circuit_success_resets_consecutive_count():
    cb = CircuitBreaker(failures=3, reset_s=60.0)
    cb.record_failure()
    cb.record_failure()
    cb.record_success()
    cb.record_failure()
    cb.record_failure()
    assert cb.allow()  # never hit 3 CONSECUTIVE


def test_circuit_half_open_probe_and_close():
    now = [0.0]
    closed = []
    cb = CircuitBreaker(failures=1, reset_s=5.0, clock=lambda: now[0],
                        on_close=lambda: closed.append(1))
    cb.record_failure()
    assert not cb.allow()
    now[0] = 6.0
    assert cb.allow()        # the ONE half-open probe
    assert not cb.allow()    # concurrent sends stay parked during the probe
    cb.record_success()
    assert cb.allow() and closed == [1]


def test_circuit_half_open_failure_reopens():
    now = [0.0]
    cb = CircuitBreaker(failures=1, reset_s=5.0, clock=lambda: now[0])
    cb.record_failure()
    now[0] = 6.0
    assert cb.allow()
    cb.record_failure()  # probe failed
    assert not cb.allow()
    now[0] = 7.0
    assert not cb.allow()  # reset clock restarted at the probe failure
    now[0] = 12.0
    assert cb.allow()
    assert cb.opens == 2


# ---- token bucket ------------------------------------------------------------


def test_token_bucket_paces_and_records_evidence():
    now = [0.0]
    tb = TokenBucket(rate=10.0, burst=10.0, clock=lambda: now[0])
    assert tb.take(10) == 0.0          # burst goes immediately
    w1 = tb.take(10)                   # next 10 must wait a full second
    assert w1 == pytest.approx(1.0)
    w2 = tb.take(10)                   # debt model: FIFO behind the first
    assert w2 == pytest.approx(2.0)
    assert tb.waits == 2
    assert tb.waited_s == pytest.approx(3.0)
    now[0] = 3.0
    assert tb.take(1) == 0.0  # refilled


# ---- chaos injector ----------------------------------------------------------


def test_injector_rejects_unknown_knob():
    inj = ChaosInjector()
    with pytest.raises(ValueError):
        inj.configure(wire_latency_msec=5)


def test_injector_corruption_flips_a_byte_and_consumes_budget():
    inj = ChaosInjector(seed=3)
    payload = bytes(range(64))
    assert inj.corrupt(payload) is None  # unarmed
    inj.configure(corrupt_next=1)
    bad = inj.corrupt(payload)
    assert bad is not None and bad != payload and len(bad) == len(payload)
    assert sum(a != b for a, b in zip(bad, payload)) == 1
    assert inj.corrupt(payload) is None  # budget consumed
    assert inj.counts.get("frame_corruption") == 1


def test_injector_corruption_breaks_the_binary_wire_crc():
    from storm_tpu.dist import transport, wire

    t = __import__("storm_tpu.runtime.tuples", fromlist=["Tuple"]).Tuple(
        values=["payload"], fields=("f",), source_component="s", edge_id=7)
    frame = wire.encode_deliveries([("b", 0, t)])
    # flip a byte INSIDE the frame (not the magic, which would just route
    # the payload to the JSON decoder and fail differently)
    bad = bytearray(frame)
    bad[len(bad) // 2] ^= 0x40
    with pytest.raises(wire.WireError):
        transport.decode_deliveries(bytes(bad))


def test_injector_engine_hang_budget():
    inj = ChaosInjector()
    assert inj.engine_hang_s() == 0.0
    inj.configure(engine_hang_ms=250.0, engine_hang_next=2)
    assert inj.engine_hang_s() == pytest.approx(0.25)
    assert inj.engine_hang_s() == pytest.approx(0.25)
    assert inj.engine_hang_s() == 0.0  # budget exhausted
    assert inj.counts["engine_hang"] == 2


def test_injector_drop_and_latency():
    inj = ChaosInjector(seed=1)
    assert not inj.should_drop()
    assert inj.wire_delay_s() == 0.0
    inj.configure(wire_drop_pct=1.0, wire_latency_ms=5.0)
    assert inj.should_drop()
    assert inj.wire_delay_s() == pytest.approx(0.005)


# ---- engine watchdog ---------------------------------------------------------


def test_fetch_loop_watchdog_trips_and_releases_ring():
    from storm_tpu.infer.engine import (EngineWatchdogTimeout, InflightBatch,
                                        StagingPool, _fetch_loop)

    class NeverReady:
        def is_ready(self):
            return False

    fetch_q: "queue.SimpleQueue" = queue.SimpleQueue()
    ring = threading.BoundedSemaphore(1)
    ring.acquire()
    staging = StagingPool(1)
    outcomes = []

    handle = InflightBatch(1, 8)
    handle._out = NeverReady()
    handle._buf = staging.acquire((8, 2), np.float32)
    handle.watchdog_ms = 40.0
    handle.on_done = outcomes.append

    t = threading.Thread(target=_fetch_loop,
                         args=(fetch_q, ring, staging, {}), daemon=True)
    t.start()
    try:
        fetch_q.put(handle)
        with pytest.raises(EngineWatchdogTimeout):
            handle.future.result(timeout=5)
        # the stuck batch released its ring slot and staging buffer — the
        # pipeline is NOT wedged behind it
        assert ring.acquire(timeout=2)
        assert isinstance(outcomes[0], EngineWatchdogTimeout)
        assert handle._buf is None
    finally:
        fetch_q.put(None)
        t.join(timeout=5)


def test_fetch_loop_no_watchdog_blocks_normally():
    from storm_tpu.infer.engine import InflightBatch, StagingPool, _fetch_loop

    class Ready:
        def is_ready(self):
            return True

        def block_until_ready(self):
            return self

        def __array__(self, dtype=None):
            return np.zeros((4, 2), np.float32)

    fetch_q: "queue.SimpleQueue" = queue.SimpleQueue()
    ring = threading.BoundedSemaphore(1)
    ring.acquire()
    handle = InflightBatch(3, 4)
    handle._out = Ready()
    handle._t_launched = time.perf_counter()
    t = threading.Thread(target=_fetch_loop,
                         args=(fetch_q, ring, StagingPool(1), {}),
                         daemon=True)
    t.start()
    try:
        fetch_q.put(handle)
        out = handle.future.result(timeout=5)
        assert out.shape == (3, 2)  # sliced to true n
    finally:
        fetch_q.put(None)
        t.join(timeout=5)


def test_watchdog_note_quarantines_on_consecutive_trips():
    from storm_tpu.infer.engine import (EngineWatchdogTimeout,
                                        InferenceEngine)

    fired = []
    eng = SimpleNamespace(
        batch_cfg=BatchConfig(watchdog_ms=10.0, watchdog_trips=2),
        model_cfg=SimpleNamespace(name="stub"),
        _watchdog_lock=threading.Lock(),
        _watchdog_trips=0,
        quarantined=False,
        on_quarantine=fired.append,
    )
    note = InferenceEngine._watchdog_note
    note(eng, EngineWatchdogTimeout("t1"))
    assert not eng.quarantined
    note(eng, None)  # a success resets the consecutive count
    note(eng, EngineWatchdogTimeout("t2"))
    note(eng, EngineWatchdogTimeout("t3"))
    assert eng.quarantined
    assert fired == [2]
    # already quarantined: further trips must not re-fire the hook
    note(eng, EngineWatchdogTimeout("t4"))
    assert fired == [2]


def test_engine_hang_injection_quarantines_real_engine():
    """End-to-end on a real (CPU) engine: armed engine-hang injections
    make dispatched batches miss their fetch deadline; two consecutive
    trips quarantine the engine and dispatch starts failing fast."""
    from storm_tpu.infer.engine import (EngineQuarantined,
                                        EngineWatchdogTimeout,
                                        InferenceEngine)
    from storm_tpu.resilience import get_injector

    eng = InferenceEngine(
        ModelConfig(name="lenet5", dtype="float32", input_shape=(28, 28, 1)),
        ShardingConfig(data_parallel=1),
        # a deadline a loaded CPU's honest batch keeps (100 ms was missed by
        # the warm-up once in two whole runs under six workers: PR 41)
        BatchConfig(max_batch=8, buckets=(8,), watchdog_ms=400.0,
                    watchdog_trips=2),
    )
    eng.warmup()
    x = np.zeros((4, 28, 28, 1), np.float32)
    assert eng.dispatch((x,)).future.result(timeout=30).shape == (4, 10)
    inj = get_injector()
    inj.configure(engine_hang_ms=1500.0, engine_hang_next=2)
    try:
        for _ in range(2):
            with pytest.raises(EngineWatchdogTimeout):
                eng.dispatch((x,)).future.result(timeout=10)
        assert eng.quarantined
        with pytest.raises(EngineQuarantined):
            eng.dispatch((x,))
    finally:
        inj.configure(engine_hang_ms=0.0, engine_hang_next=0)


# ---- PeerSender park / reroute ----------------------------------------------


def _tuple(v="x"):
    from storm_tpu.runtime.tuples import Tuple

    return Tuple(values=[v], fields=("f",), source_component="s", edge_id=9)


def test_sender_reroutes_while_circuit_open():
    from storm_tpu.dist.worker import PeerSender

    async def run():
        s = PeerSender("127.0.0.1:1",
                       resilience=ResilienceConfig(circuit_failures=1,
                                                   circuit_reset_s=60.0))
        s.circuit.record_failure()  # open
        rerouted = []

        async def reroute(c, i, t):
            rerouted.append((c, i, t))
            return True

        s.set_reroute(reroute)
        await asyncio.wait_for(s._flush([("b", 0, _tuple())], []), timeout=5)
        return rerouted

    rerouted = asyncio.run(run())
    assert len(rerouted) == 1 and rerouted[0][0] == "b"


def test_sender_parks_then_sends_after_probe():
    from storm_tpu.dist.worker import PeerSender

    async def run():
        s = PeerSender("127.0.0.1:1",
                       resilience=ResilienceConfig(circuit_failures=1,
                                                   circuit_reset_s=0.05))
        s.circuit.record_failure()  # open; no reroute hook -> park
        sent = []

        async def fake_negotiate():
            return True

        async def fake_send(fn, payload, *, codes):
            sent.append((payload, codes))

        s._negotiate = fake_negotiate
        s._send = fake_send
        await asyncio.wait_for(s._flush([("b", 0, _tuple())], []), timeout=5)
        return sent, s.circuit.allow()

    sent, closed = asyncio.run(run())
    # parked through the open window, then delivered on the probe — never
    # silently dropped — and the successful send closed the circuit
    assert len(sent) == 1 and closed


def test_sender_drops_only_non_retryable_failures():
    from storm_tpu.dist.worker import PeerSender

    async def run():
        s = PeerSender("127.0.0.1:1")
        calls = []

        async def fake_negotiate():
            return False

        async def fake_send(fn, payload, *, codes):
            calls.append(1)
            raise TypeError("raw bytes on the JSON wire")

        s._negotiate = fake_negotiate
        s._send = fake_send
        # returns (leaves the batch to ledger replay) instead of looping
        await asyncio.wait_for(s._flush([("b", 0, _tuple())], []), timeout=5)
        return calls

    assert asyncio.run(run()) == [1]


def test_sender_pacing_records_against_real_registry():
    """Regression: ``_pace`` must work against the REAL metrics objects —
    the first cut called ``Histogram.record`` (which doesn't exist), so
    every throttled flush raised AttributeError after the counter inc and
    ``_flush`` dropped the batch to replay as 'non-retryable'."""
    from storm_tpu.dist.worker import PeerSender
    from storm_tpu.runtime.metrics import MetricsRegistry
    from storm_tpu.runtime.tracing import FlightRecorder

    async def run():
        s = PeerSender("127.0.0.1:1")
        m = MetricsRegistry()
        s.bind_obs(m, FlightRecorder(), 3)
        # bind_obs resets the per-peer circuit gauge (a replacement sender
        # re-binds the same name; the dead one's open=1 must not latch).
        assert m.snapshot()["_transport"]["dist_circuit_open_w3"] == 0.0
        s.begin_recovery_pacing(rate=100.0, window_s=30.0)
        s._pacer.take(100)  # drain the burst allowance: next take waits
        await s._pace(5)    # ~50ms of debt at 100 tuples/s
        return m.snapshot()["_transport"]

    snap = asyncio.run(run())
    assert snap["dist_replay_throttled"] >= 1
    hist = snap["dist_replay_throttle_ms"]
    assert hist["count"] >= 1 and hist["max"] > 0


def test_reroute_tuple_respects_groupings():
    from storm_tpu.dist.worker import DistRuntime
    from storm_tpu.runtime.groupings import FieldsGrouping, ShuffleGrouping

    class Inbox:
        def __init__(self, sender):
            self._sender = sender
            self.got = []

        async def put(self, t):
            self.got.append(t)

    dead = object()
    live = object()
    inboxes = [Inbox(dead), Inbox(live), Inbox(live)]
    rt = SimpleNamespace(
        topology=SimpleNamespace(specs={"b": SimpleNamespace(
            inputs=[SimpleNamespace(grouping=ShuffleGrouping())])}),
        groups={"b": SimpleNamespace(inboxes=inboxes)},
        _reroute_rr=0,
    )
    t = _tuple()
    ok = asyncio.run(DistRuntime.reroute_tuple(rt, "b", 0, t, dead))
    assert ok
    assert sum(len(i.got) for i in inboxes[1:]) == 1
    assert not inboxes[0].got  # never back to the dead peer

    # fields grouping pins tuples to their task: reroute must refuse
    rt.topology.specs["b"].inputs = [
        SimpleNamespace(grouping=FieldsGrouping(["f"]))]
    assert not asyncio.run(DistRuntime.reroute_tuple(rt, "b", 0, t, dead))

    # no survivors (component wholly on the dead worker): park instead
    rt.topology.specs["b"].inputs = [
        SimpleNamespace(grouping=ShuffleGrouping())]
    rt.groups["b"].inboxes = [Inbox(dead)]
    assert not asyncio.run(DistRuntime.reroute_tuple(rt, "b", 0, t, dead))


# ---- wait_ready classification ----------------------------------------------


def test_wait_ready_fails_fast_on_auth_reject():
    from storm_tpu.dist.transport import WorkerClient

    c = WorkerClient("127.0.0.1:1")
    c._control = lambda *a, **kw: (_ for _ in ()).throw(
        FakeRpcError(grpc.StatusCode.UNAUTHENTICATED))
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="rejected the handshake"):
        c.wait_ready(timeout=30.0)
    assert time.monotonic() - t0 < 5.0  # no 30s of polling a hard reject
    c.close()


def test_wait_ready_times_out_on_connectivity():
    from storm_tpu.dist.transport import WorkerClient

    c = WorkerClient("127.0.0.1:1")
    c._control = lambda *a, **kw: (_ for _ in ()).throw(
        FakeRpcError(grpc.StatusCode.UNAVAILABLE))
    with pytest.raises(TimeoutError):
        c.wait_ready(timeout=0.3)
    c.close()
