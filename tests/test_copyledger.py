"""Data-plane copy ledger (round-18 tentpole).

Unit coverage for :mod:`storm_tpu.obs.copyledger`: exact byte accounting
over a synthetic 3-hop record path, the cross-worker window merge (raw
quantities ADD, ratios re-derive), the detached zero-overhead path, the
``copy_amplification_high`` flight trip/de-flap in the Observatory step,
and the cursor/hop hygiene CapacityTracker pioneered — two rebalances
must not leak a cursor or pin a retired engine's histograms. The ledger's
own cost on a live path is not measured here, nor by the benchmark.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import pytest

from storm_tpu.obs import copyledger
from storm_tpu.obs.copyledger import (
    INGEST_STAGE,
    CopyLedger,
    derive_tree,
    live_keys,
    merge_windows,
)
from storm_tpu.runtime.metrics import MetricsRegistry


class FakeFlight:
    def __init__(self) -> None:
        self.events = []

    def event(self, kind, **fields):
        fields.pop("throttle_s", None)
        self.events.append({"kind": kind, **fields})


# ---- exact accounting --------------------------------------------------------


def test_three_hop_exact_byte_accounting():
    """A synthetic record path — ingest, decode, wire — folds into the
    copy tree with exact bytes/copies per record and the amplification
    ratio derived as (bytes moved excluding ingest) / ingest bytes."""
    led = CopyLedger()
    # 10 records arrive as 1000 payload bytes (arrival is not a copy).
    led.record(INGEST_STAGE, 1000, copies=0, allocs=0, records=10,
               engine="kafka-spout")
    # Decode doubles them into float arrays: one copy, one alloc each.
    led.record("json_decode", 2000, copies=10, allocs=10, records=10,
               engine="inference-bolt")
    # The wire frames all 10 in one call: one copy pass, one buffer.
    led.record("wire_encode", 1500, copies=1, allocs=1, records=10)

    tree = led.snapshot()
    st = tree["stages"]
    assert list(st) == [INGEST_STAGE, "json_decode", "wire_encode"]
    assert st[INGEST_STAGE]["bytes_per_record"] == 100.0
    assert st[INGEST_STAGE]["copies_per_record"] == 0.0
    assert st["json_decode"]["bytes_per_record"] == 200.0
    assert st["json_decode"]["copies_per_record"] == 1.0
    assert st["wire_encode"]["bytes_per_record"] == 150.0
    assert st["wire_encode"]["engines"]["-"]["bytes"] == 1500
    # Numerator excludes the ingest denominator: (2000 + 1500) / 1000.
    assert tree["copy_amplification"] == 3.5
    assert tree["totals"] == {
        "bytes": 3500.0, "copies": 11, "allocs": 11,
        "ingest_bytes": 1000.0, "ingest_records": 10}


def test_windowed_reports_only_the_delta():
    led = CopyLedger()
    led.record(INGEST_STAGE, 100, copies=0, records=1, engine="s")
    assert led.windowed("k")["stages"] == {}  # first call primes
    led.record(INGEST_STAGE, 300, copies=0, records=3, engine="s")
    led.record("staging", 900, copies=1, records=3, engine="lenet5")
    w = led.windowed("k")
    assert w["stages"][INGEST_STAGE]["bytes"] == 300.0
    assert w["stages"][INGEST_STAGE]["records"] == 3
    # The staging hop was born mid-window: its first cursor read primes
    # (the Histogram.window zero-length contract), so it reports next
    # window — bench-exact accounting uses reset + cumulative instead.
    assert "staging" not in w["stages"]
    assert led.windowed("k")["stages"].get("staging", {}).get("bytes") == 0


def test_derive_tree_sorts_by_record_path_order():
    rows = [{"stage": "sink_encode", "engine": "k", "bytes": 1,
             "copies": 1, "allocs": 1, "records": 1, "calls": 1},
            {"stage": "h2d", "engine": "e", "bytes": 1, "copies": 1,
             "allocs": 1, "records": 1, "calls": 1},
            {"stage": "unknown_stage", "engine": "-", "bytes": 1,
             "copies": 1, "allocs": 0, "records": 1, "calls": 1}]
    tree = derive_tree(rows)
    # Path order, unknown stages last.
    assert list(tree["stages"]) == ["h2d", "sink_encode", "unknown_stage"]


# ---- dist merge math ---------------------------------------------------------


def test_merge_windows_adds_quantities_and_rederives_ratio():
    """Raw bytes/copies/records ADD across workers; per-record figures
    and amplification are re-derived from the sums — merging the ratios
    themselves would be wrong whenever workers saw different traffic."""
    a, b = CopyLedger(), CopyLedger()
    a.record(INGEST_STAGE, 1000, copies=0, records=10, engine="spout")
    a.record("wire_encode", 3000, copies=1, records=10)
    b.record(INGEST_STAGE, 3000, copies=0, records=30, engine="spout")
    b.record("wire_encode", 4000, copies=1, records=30)
    b.record("d2h", 1000, copies=1, records=30, engine="lenet5")

    merged = merge_windows({0: a.snapshot(), 1: b.snapshot()})
    st = merged["stages"]
    assert st[INGEST_STAGE]["bytes"] == 4000.0
    assert st[INGEST_STAGE]["records"] == 40
    assert st["wire_encode"]["bytes"] == 7000.0
    assert st["wire_encode"]["copies"] == 2
    assert st["d2h"]["records"] == 30
    # Re-derived from totals: (7000 + 1000) / 4000 — NOT the mean of
    # the per-worker amplifications (3.0 and 5000/3000).
    assert merged["copy_amplification"] == 2.0
    per_worker_mean = (3.0 + 5000 / 3000) / 2
    assert merged["copy_amplification"] != pytest.approx(per_worker_mean)
    assert st["wire_encode"]["bytes_per_record"] == 175.0


def test_merge_windows_takes_max_window_span():
    a, b = CopyLedger(), CopyLedger()
    for led in (a, b):
        led.record(INGEST_STAGE, 10, copies=0, records=1, engine="s")
        led.windowed("w")
        led.record(INGEST_STAGE, 10, copies=0, records=1, engine="s")
    ta, tb = a.windowed("w"), b.windowed("w")
    tb["dt_s"] = ta["dt_s"] + 5.0  # one worker's window is longer
    merged = merge_windows({0: ta, 1: tb})
    assert merged["dt_s"] == tb["dt_s"]


# ---- disabled path -----------------------------------------------------------


def test_detached_record_is_a_noop_and_never_raises():
    """With the sink detached (the overhead A/B's off arm) the module
    entry point must not touch the ledger; attached, it must swallow
    anything — an observability hook never fails a batch."""
    before = copyledger.active()
    try:
        copyledger.set_enabled(False)
        assert not copyledger.active()
        base = copyledger.copy_ledger().snapshot()["totals"]["bytes"]
        copyledger.record("json_decode", 4096, copies=1, records=4)
        assert (copyledger.copy_ledger().snapshot()["totals"]["bytes"]
                == base)
        copyledger.set_enabled(True)
        assert copyledger.active()
        # Bad arguments reach the sink but must not escape the hook.
        copyledger.record("json_decode", "not-a-size")  # type: ignore
    finally:
        copyledger.set_enabled(True)
        if not before:
            # restore a detached initial state for test isolation
            copyledger._SINK = None


def test_set_enabled_false_survives_ensure_installed():
    try:
        copyledger.set_enabled(False)
        copyledger.ensure_installed()  # an operator prepare mid-bench
        assert not copyledger.active()
    finally:
        copyledger.set_enabled(True)


# ---- flight trip / de-flap ---------------------------------------------------


def _mk_obs(ceiling: float):
    from storm_tpu.config import ObsConfig
    from storm_tpu.obs import Observatory

    rt = SimpleNamespace(metrics=MetricsRegistry(), flight=FakeFlight())
    obs = Observatory(rt, ObsConfig(enabled=True,
                                    copy_amp_ceiling=ceiling))
    return obs, rt


def test_amplification_flight_trips_once_and_dearms_below_80pct():
    obs, rt = _mk_obs(ceiling=10.0)
    led = obs.ledger
    led.reset()
    try:
        obs._step_copies()  # prime the "obs" cursors (empty tree)

        def traffic(ingest, moved):
            # engine "-" so live_keys() pruning on a bare runtime
            # cannot drop the hops under the test's feet
            led.record(INGEST_STAGE, ingest, copies=0, records=1,
                       engine="-")
            led.record("wire_encode", moved, copies=1, records=1)

        led.record(INGEST_STAGE, 1, copies=0, records=1, engine="-")
        led.record("wire_encode", 1, copies=1, records=1)
        obs._step_copies()  # hop cursors now primed too
        traffic(100, 5000)  # amplification 50 > ceiling
        obs._step_copies()
        trips = [e for e in rt.flight.events
                 if e["kind"] == "copy_amplification_high"]
        assert len(trips) == 1
        assert trips[0]["amplification"] == 50.0
        assert trips[0]["ceiling"] == 10.0
        assert trips[0]["top_stage"] == "wire_encode"
        assert obs.last_copies["copy_amplification"] == 50.0

        traffic(100, 5000)  # still high: latched, no re-fire
        obs._step_copies()
        assert len([e for e in rt.flight.events
                    if e["kind"] == "copy_amplification_high"]) == 1

        traffic(100, 900)  # amp 9.0: above 80% of ceiling -> still armed? no:
        obs._step_copies()  # 9.0 > 8.0, latch holds
        traffic(100, 5000)
        obs._step_copies()
        assert len([e for e in rt.flight.events
                    if e["kind"] == "copy_amplification_high"]) == 1

        traffic(100, 500)  # amp 5.0 < 8.0: de-arm
        obs._step_copies()
        traffic(100, 5000)  # high again -> second trip
        obs._step_copies()
        assert len([e for e in rt.flight.events
                    if e["kind"] == "copy_amplification_high"]) == 2
    finally:
        led.reset()
        led.drop_window("obs")


def test_ceiling_zero_disables_the_flight_check():
    obs, rt = _mk_obs(ceiling=0.0)
    led = obs.ledger
    led.reset()
    try:
        obs._step_copies()
        led.record(INGEST_STAGE, 1, copies=0, records=1, engine="-")
        led.record("wire_encode", 1, copies=1, records=1)
        obs._step_copies()
        led.record(INGEST_STAGE, 10, copies=0, records=1, engine="-")
        led.record("wire_encode", 99999, copies=1, records=1)
        obs._step_copies()
        assert not [e for e in rt.flight.events
                    if e["kind"] == "copy_amplification_high"]
    finally:
        led.reset()
        led.drop_window("obs")


def test_observatory_snapshot_carries_the_copy_tree():
    obs, _rt = _mk_obs(ceiling=32.0)
    obs.ledger.reset()
    try:
        obs.ledger.record(INGEST_STAGE, 640, copies=0, records=4,
                          engine="-")
        snap = obs.copies_snapshot()
        assert snap["cumulative"]["totals"]["ingest_bytes"] == 640.0
        assert snap["amp_ceiling"] == 32.0
        assert "window" in snap
    finally:
        obs.ledger.reset()
        obs.ledger.drop_window("obs")


# ---- cursor / hop hygiene (satellite: rebalance pruning) --------------------


def test_prune_drops_dead_engines_keeps_shared_hops():
    led = CopyLedger()
    led.record("staging", 100, engine="lenet5")
    led.record("staging", 100, engine="resnet20")
    led.record("wire_encode", 100)  # engine "-" always survives
    assert led.prune({"lenet5"}) == 1
    assert led.hop_keys() == [("staging", "lenet5"), ("wire_encode", "-")]
    # Idempotent: nothing more to drop.
    assert led.prune({"lenet5"}) == 0


def test_no_cursor_leak_across_two_rebalances():
    """The regression the satellite demands: two rebalances that retire
    and replace an engine must leave hop count and live cursor names
    flat — a retired engine's histograms (and every named cursor on
    them) must not pin for the process lifetime."""
    led = CopyLedger()
    rt = SimpleNamespace(spout_execs={"kafka-spout": []},
                         bolt_execs={"inference-bolt": [],
                                     "kafka-bolt": []})

    def traffic(engine):
        led.record(INGEST_STAGE, 1000, copies=0, records=10,
                   engine="kafka-spout")
        led.record("json_decode", 2000, copies=10, records=10,
                   engine="inference-bolt")
        led.record("staging", 4000, copies=1, records=10, engine=engine)
        led.record("wire_encode", 1500, copies=1, records=10)

    def poll():
        # Two windowed consumers, like the real system (obs + dist ui).
        led.prune(live_keys(rt) | {CURRENT_ENGINE})
        led.windowed("obs")
        led.windowed("ui")

    CURRENT_ENGINE = "lenet5-v1"
    traffic(CURRENT_ENGINE)
    poll()
    baseline_hops = len(led.hop_keys())
    baseline_cursors = set(led.cursor_keys())
    assert baseline_cursors == {"obs", "ui"}

    for gen in (2, 3):  # two rebalances, each swapping the engine
        CURRENT_ENGINE = f"lenet5-v{gen}"
        traffic(CURRENT_ENGINE)
        poll()
        # The retired engine's hop is gone, the new one took its slot.
        engines = {e for _s, e in led.hop_keys()}
        assert f"lenet5-v{gen - 1}" not in engines
        assert CURRENT_ENGINE in engines
        assert len(led.hop_keys()) == baseline_hops
        assert set(led.cursor_keys()) == baseline_cursors

    # cursor_keys is the CapacityTracker-compatible alias.
    assert led.cursor_keys() == led.window_keys()


def test_drop_window_forgets_one_consumer_everywhere():
    led = CopyLedger()
    led.record("staging", 100, engine="a")
    led.record("d2h", 100, engine="a")
    led.windowed("bench")
    led.windowed("obs")
    assert set(led.window_keys()) == {"bench", "obs"}
    assert led.drop_window("bench") is True
    assert set(led.window_keys()) == {"obs"}
    assert led.drop_window("bench") is False


# ---- marshal measurement must not copy (satellite #6) ------------------------


def test_marshal_decode_reports_view_bytes_without_copying():
    """The Arrow decode path is a zero-copy view, so it ledgers ZERO
    bytes moved (the amplification numerator counts copies, and the
    other view hops — batch_route, shm wire_decode — already report 0);
    the ``records`` count alone proves the hop ran. The measurement must
    not copy either: no ``len(bytes(buf))`` round trip (which would BE
    a copy, made by the measurement)."""
    pytest.importorskip("pyarrow")
    from storm_tpu.serve.marshal import decode_tensor, encode_tensor

    led = copyledger.copy_ledger()
    prev_sink = copyledger._SINK
    copyledger.set_enabled(True)
    led.reset()
    try:
        x = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
        buf = encode_tensor(x)
        arr = decode_tensor(buf)
        np.testing.assert_array_equal(arr, x)
        tree = led.snapshot()
        enc = tree["stages"]["marshal_encode"]
        dec = tree["stages"]["marshal_decode"]
        assert enc["bytes"] == len(buf)
        assert enc["copies"] >= 1 and enc["records"] == 2
        # Zero-copy read side: no bytes moved, no copy passes.
        assert dec["bytes"] == 0
        assert dec["copies"] == 0 and dec["allocs"] == 0
        assert dec["records"] == 2
    finally:
        led.reset()
        copyledger._SINK = prev_sink


def test_live_keys_collects_components_and_engines():
    rt = SimpleNamespace(spout_execs={"s": []}, bolt_execs={"b": []})
    keys = live_keys(rt)
    assert {"s", "b"} <= keys
