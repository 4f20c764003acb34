"""Continuous profiling & SLO-burn observatory (round-11 tentpole).

Unit coverage for the three obs pillars — the ProfileStore's
per-(engine, bucket) cost curves, the multi-window burn-rate tracker,
and the Observatory's regression sentinel — plus the metrics-layer
satellites they lean on (thread-safe Histogram mutation, the windowed-
rate helper). The end-to-end behaviour (burn trips before the shed
level moves under real overload; the /profile route serves live curves)
was seen in a CPU-host run of round 11 and is not re-measured here.
"""

from __future__ import annotations

import json
import threading
from types import SimpleNamespace

import pytest

from storm_tpu.obs.profile import ProfileStore
from storm_tpu.obs.slo import SloBurnTracker
from storm_tpu.runtime.metrics import Histogram, MetricsRegistry


class FakeFlight:
    def __init__(self) -> None:
        self.events = []

    def event(self, kind, **fields):
        fields.pop("throttle_s", None)
        self.events.append({"kind": kind, **fields})


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t


# ---- ProfileStore: curves ----------------------------------------------------


def _feed_linear(store: ProfileStore, key: str, buckets=(16, 64, 256),
                 batches: int = 30, scale: float = 1.0) -> None:
    """Synthetic stage costs that grow linearly with the bucket — the
    shape a real device produces once per-batch overhead amortizes."""
    for padded in buckets:
        for i in range(batches):
            jitter = 1.0 + 0.01 * (i % 5)
            store.record_batch(key, padded, padded, {
                "h2d_ms": scale * 0.02 * padded * jitter,
                "compute_ms": scale * 0.05 * padded * jitter,
                "d2h_ms": scale * 0.01 * padded * jitter,
            })


def test_profile_store_builds_monotone_curves():
    store = ProfileStore()
    _feed_linear(store, "lenet5")
    store.record_compile("lenet5", 16, 120.0)
    store.record_compile("lenet5", 64, 150.0)

    snap = store.snapshot()
    eng = snap["engines"]["lenet5"]
    assert set(eng["buckets"]) == {"16", "64", "256"}
    p50s = [eng["buckets"][b]["stages"]["device_ms"]["p50"]
            for b in ("16", "64", "256")]
    assert p50s == sorted(p50s)  # whole-batch cost grows with the bucket
    row64 = eng["buckets"]["64"]
    assert row64["batches"] == 30 and row64["rows"] == 30 * 64
    # device_ms is the synthetic sum of the three phases
    st = row64["stages"]
    assert st["device_ms"]["mean"] == pytest.approx(
        st["h2d_ms"]["mean"] + st["compute_ms"]["mean"]
        + st["d2h_ms"]["mean"], rel=1e-6)
    assert row64["ms_per_row"] == pytest.approx(
        st["device_ms"]["mean"] / 64, rel=1e-3)
    assert row64["throughput_rows_s"] > 0
    assert eng["compiles"]["16"]["last_ms"] == 120.0
    assert eng["compiles"]["64"]["count"] == 1


def test_profile_cost_of_reads_largest_bucket():
    store = ProfileStore()
    _feed_linear(store, "resnet20")
    cost = store.cost_of("resnet20")
    assert cost["bucket"] == 256
    assert cost["ms_per_row"] == pytest.approx(
        cost["device_ms_mean"] / 256, rel=1e-3)
    assert store.cost_of("never-profiled") is None


def test_profile_partial_timings_skip_missing_stages():
    store = ProfileStore()
    store.record_batch("m", 8, 8, {"compute_ms": 3.0})  # no h2d/d2h
    row = store.snapshot()["engines"]["m"]["buckets"]["8"]
    assert "h2d_ms" not in row["stages"]
    assert row["stages"]["device_ms"]["mean"] == pytest.approx(3.0)
    store.record_batch("m", 8, 8, {})  # empty timings: ignored
    assert store.snapshot()["engines"]["m"]["buckets"]["8"]["batches"] == 1


# ---- ProfileStore: baseline round-trip + sentinel ----------------------------


def test_profile_snapshot_round_trips_as_baseline():
    store = ProfileStore()
    _feed_linear(store, "lenet5")
    snap = json.loads(json.dumps(store.snapshot()))  # the artifact path
    store.load_baseline(snap)
    assert store.baseline is snap
    # Self-comparison is clean at any sample floor: the committed
    # artifact is directly usable as the sentinel's baseline.
    assert store.regressions(factor=1.5, min_samples=1) == []
    with pytest.raises(ValueError):
        store.load_baseline({"not": "a snapshot"})


def test_profile_baseline_accepts_bench_artifact_form():
    # obs.baseline_path points at a saved `storm-tpu profile --json`, whose
    # snapshot lives under the document's "profile" key (the top-level
    # "engines" there is a list of names, not the curves mapping).
    store = ProfileStore()
    _feed_linear(store, "lenet5")
    snap = json.loads(json.dumps(store.snapshot()))
    artifact = {"metric": "profile_curves", "engines": ["lenet5"],
                "profile": snap}
    store.load_baseline(artifact)
    assert store.baseline == snap
    assert store.regressions(factor=1.5, min_samples=1) == []
    with pytest.raises(ValueError):
        store.load_baseline({"engines": ["lenet5"]})  # list, no profile


def test_profile_regressions_detect_drift():
    base_store = ProfileStore()
    _feed_linear(base_store, "lenet5")
    live = ProfileStore()
    _feed_linear(live, "lenet5", scale=2.0)  # every stage 2x slower
    live.load_baseline(base_store.snapshot())
    regs = live.regressions(factor=1.5, min_samples=10)
    assert regs  # all (bucket, stage) cells drifted
    assert {r["engine"] for r in regs} == {"lenet5"}
    assert all(1.8 < r["ratio"] < 2.2 for r in regs)
    # Below the sample floor the same drift is NOT reported (cold
    # curves flap; the sentinel waits for evidence).
    assert live.regressions(factor=1.5, min_samples=10_000) == []
    # Without a baseline there is nothing to compare against.
    assert ProfileStore().regressions() == []


def test_observatory_sentinel_records_flight_events():
    from storm_tpu.obs import Observatory
    from storm_tpu.config import ObsConfig
    from storm_tpu.obs.profile import profile_store

    store = profile_store()
    store.reset()
    rt = SimpleNamespace(metrics=MetricsRegistry(), flight=FakeFlight())
    clock = FakeClock()
    obs = Observatory(rt, ObsConfig(enabled=True, min_samples=10),
                      clock=clock)
    assert rt.obs is obs  # exposed for the UI /profile route
    try:
        # Baseline at 1x, live traffic at 3x: drift the sentinel must see.
        base = ProfileStore()
        _feed_linear(base, "drift-model")
        store.load_baseline(base.snapshot())
        _feed_linear(store, "drift-model", scale=3.0)
        regs = obs.sentinel_check()
        assert regs and obs.last_regressions == regs
        kinds = {e["kind"] for e in rt.flight.events}
        assert "profile_regression" in kinds
        ev = next(e for e in rt.flight.events
                  if e["kind"] == "profile_regression")
        assert ev["engine"] == "drift-model" and ev["ratio"] > 1.5
        assert rt.metrics.counter(
            "obs", "profile_regressions").value == len(regs)
        snap = obs.snapshot()
        assert snap["baseline_loaded"] is True
        assert snap["regressions"] == regs
        assert "slo" in snap and "occupancy" in snap
    finally:
        store.reset()


# ---- SloBurnTracker ----------------------------------------------------------


def _mk_burn(**kw):
    reg = MetricsRegistry()
    flight = FakeFlight()
    clock = FakeClock()
    kw.setdefault("objective", 0.99)
    kw.setdefault("fast_window_s", 60.0)
    kw.setdefault("slow_window_s", 600.0)
    tracker = SloBurnTracker(reg, components=("kafka-bolt",), flight=flight,
                             clock=clock, **kw)
    return tracker, reg, flight, clock


def test_burn_trips_on_dual_window_and_untrips():
    tracker, reg, flight, clock = _mk_burn()
    delivered = reg.counter("kafka-bolt", "delivered")
    breaches = reg.counter("kafka-bolt", "slo_breaches")

    out = tracker.step()  # baseline sample, nothing flowing
    assert out == {"fast_burn": 0.0, "slow_burn": 0.0, "tripped": False}

    # 5% breach ratio against a 1% budget => burn 5 in BOTH windows.
    delivered.inc(1000)
    breaches.inc(50)
    clock.t = 1.0
    out = tracker.step()
    assert out["fast_burn"] == pytest.approx(5.0)
    assert out["slow_burn"] == pytest.approx(5.0)
    assert out["tripped"] is True
    assert tracker.trips == 1
    assert reg.gauge("slo", "burn_rate").value == pytest.approx(5.0)
    assert reg.gauge("slo", "tripped").value == 1.0
    (ev,) = flight.events
    assert ev["kind"] == "slo_burn" and ev["fast_burn"] == 5.0

    # Clean traffic beyond both windows: burn decays to 0, gauge untrips,
    # and the flight event is RE-ARMED (a second trip fires again).
    clock.t = 700.0
    delivered.inc(10_000)
    tracker.step()
    assert tracker.tripped is False
    assert reg.gauge("slo", "tripped").value == 0.0
    clock.t = 701.0
    delivered.inc(1000)
    breaches.inc(100)
    tracker.step()
    assert tracker.tripped is True and tracker.trips == 2
    assert len(flight.events) == 2


def test_burn_fast_window_alone_does_not_trip():
    # Old breaches inside the slow window but outside the fast one:
    # slow burn stays hot, fast burn reads clean recent traffic -> no
    # trip (the classic multi-window de-flap, in the recovering
    # direction).
    tracker, reg, flight, clock = _mk_burn(
        fast_window_s=10.0, slow_window_s=600.0)
    delivered = reg.counter("kafka-bolt", "delivered")
    breaches = reg.counter("kafka-bolt", "slo_breaches")
    tracker.step()
    delivered.inc(100)
    breaches.inc(50)  # the incident
    clock.t = 5.0
    assert tracker.step()["tripped"] is True
    clock.t = 100.0  # incident now outside the fast window
    delivered.inc(2000)  # recovery traffic, no new breaches
    out = tracker.step()
    assert out["fast_burn"] == 0.0
    assert out["slow_burn"] > 1.0  # slow window still remembers
    assert out["tripped"] is False
    assert tracker.trips == 1


def test_burn_zero_delivery_counts_as_full_burn():
    tracker, reg, _, clock = _mk_burn()
    breaches = reg.counter("kafka-bolt", "slo_breaches")
    tracker.step()
    breaches.inc(7)  # breaches with NO deliveries: everything failing
    clock.t = 1.0
    out = tracker.step()
    assert out["fast_burn"] == pytest.approx(1.0 / tracker.budget)
    assert out["tripped"] is True


def test_burn_validates_config():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        SloBurnTracker(reg, objective=1.0)
    with pytest.raises(ValueError):
        SloBurnTracker(reg, fast_window_s=60.0, slow_window_s=10.0)


def test_burn_snapshot_shape():
    tracker, reg, _, clock = _mk_burn()
    snap = tracker.snapshot()
    assert snap["components"] == ["kafka-bolt"]
    assert snap["budget"] == pytest.approx(0.01)
    assert snap["tripped"] is False and snap["trips"] == 0


# ---- metrics satellites: thread-safe Histogram + window helper ---------------


def test_histogram_concurrent_observe_reset_hammer():
    """Regression: an unguarded reset racing observe could tear the ring
    indices (negative counts / percentile reading stale rows). Hammer
    observe from 4 threads while the main thread resets and reads."""
    h = Histogram(256)
    stop = threading.Event()
    errors = []

    def writer():
        try:
            while not stop.is_set():
                h.observe(1.0)
        except Exception as e:  # pragma: no cover - the failure under test
            errors.append(e)

    threads = [threading.Thread(target=writer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(300):
            h.reset()
            p = h.percentile(95)
            assert p != p or p == 1.0  # NaN (empty) or the only value
            snap = h.snapshot()
            assert snap["count"] >= 0
            assert h.count * 1.0 == h.sum  # all observations are 1.0
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors
    assert 0 <= h._n <= 256 and 0 <= h._i < 256


def test_histogram_snapshot_has_p90_and_max():
    h = Histogram()
    for v in range(1, 101):
        h.observe(float(v))
    snap = h.snapshot()
    assert snap["max"] == 100.0
    assert 89.0 <= snap["p90"] <= 91.0
    assert snap["p50"] == pytest.approx(50.5)
    empty = Histogram().snapshot()
    assert empty["p90"] is None and empty["max"] is None


def test_histogram_window_named_cursors():
    h = Histogram()
    # First read of a cursor is a zero-length window, not a huge delta.
    assert h.window("a")["count"] == 0
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    wa = h.window("a")
    assert wa["count"] == 3 and wa["sum"] == 6.0
    assert wa["mean"] == pytest.approx(2.0)
    # Independent cursor "b" starts fresh and doesn't steal a's delta.
    assert h.window("b")["count"] == 0
    h.observe(10.0)
    assert h.window("a")["count"] == 1
    assert h.window("b")["count"] == 1
    # reset clears the cursors too: next read is zero-length again.
    h.reset()
    assert h.window("a")["count"] == 0
