"""Trace-driven fleet loadgen (round-16 tentpole).

The determinism contract — same spec + seed produces a *byte-identical*
trace file and an identical arrival schedule on any host — is what lets
a scorecard record only ``{spec, seed, sha256}`` per cell instead
of carrying megabyte trace files: anyone can regenerate the exact
workload and check the hash. Replay is tested entirely in virtual time
(injectable clock/sleep), so round-trip equality costs no wall-clock.
Also covers the cell scoring gates, the window-cursor hygiene added for
the fleet driver (Histogram.drop_window / MetricsRegistry.drop_windows /
CapacityTracker cross-key pruning), and the ``scenario_phase`` flight
event shape.
"""

from __future__ import annotations

from types import SimpleNamespace

import pytest

from storm_tpu.loadgen import (
    CellTargets,
    Trace,
    TraceSpec,
    generate,
    load_trace,
    render_table,
    replay,
    score_cell,
)
from storm_tpu.obs.capacity import CapacityTracker
from storm_tpu.runtime.metrics import Histogram, MetricsRegistry
from storm_tpu.runtime.tracing import FlightRecorder


def _spec(**kw) -> TraceSpec:
    base = dict(seed=7, pattern="heavy_tail", duration_s=5.0,
                base_rate=300.0, tenants=200)
    base.update(kw)
    return TraceSpec(**base)


# ---- determinism -------------------------------------------------------------


def test_same_seed_trace_file_is_byte_identical(tmp_path):
    spec = _spec()
    a, b = generate(spec), generate(spec)
    assert a.rows == b.rows
    assert a.to_bytes() == b.to_bytes()
    assert a.sha256() == b.sha256()
    pa, pb = tmp_path / "a.trace", tmp_path / "b.trace"
    a.save(str(pa))
    b.save(str(pb))
    assert pa.read_bytes() == pb.read_bytes()


@pytest.mark.parametrize("pattern", ["heavy_tail", "diurnal", "flash_crowd"])
def test_same_seed_identical_schedule_every_pattern(pattern):
    spec = _spec(pattern=pattern, seed=16)
    a, b = generate(spec), generate(spec)
    assert len(a) > 100
    assert a.rows == b.rows
    assert [e for e in a.events()] == [e for e in b.events()]


def test_different_seeds_differ():
    assert generate(_spec(seed=1)).rows != generate(_spec(seed=2)).rows


def test_round_trip_load_replay_equality(tmp_path):
    spec = _spec(pattern="flash_crowd", seed=4, duration_s=4.0)
    tr = generate(spec)
    path = str(tmp_path / "t.trace")
    tr.save(path)
    loaded = load_trace(path)
    assert loaded.spec == spec
    assert loaded.rows == tr.rows
    assert loaded.sha256() == tr.sha256()

    def run(trace: Trace):
        clock = SimpleNamespace(t=0.0)
        out = []
        n = replay(trace, out.append,
                   clock=lambda: clock.t,
                   sleep=lambda dt: setattr(clock, "t", clock.t + dt))
        return n, out

    na, ea = run(tr)
    nb, eb = run(loaded)
    assert (na, ea) == (nb, eb)
    assert na == len(tr)


# ---- replay pacing -----------------------------------------------------------


def test_replay_paces_on_virtual_clock_and_honors_stop():
    tr = generate(_spec(seed=9, duration_s=2.0, base_rate=100.0))
    clock = SimpleNamespace(t=0.0)
    seen = []
    replay(tr, seen.append, clock=lambda: clock.t,
           sleep=lambda dt: setattr(clock, "t", clock.t + dt))
    # The virtual clock advanced to (at least) the last event's offset,
    # and every emit happened at/after its scheduled time.
    assert clock.t >= tr.rows[-1][0] / 1e6
    assert seen == list(tr.events())

    clock.t = 0.0
    few = []
    n = replay(tr, few.append, clock=lambda: clock.t,
               sleep=lambda dt: setattr(clock, "t", clock.t + dt),
               stop=lambda: len(few) >= 5)
    assert n == 5 and few == list(tr.events())[:5]


def test_replay_speed_compresses_virtual_time():
    tr = generate(_spec(seed=9, duration_s=2.0, base_rate=100.0))
    clock = SimpleNamespace(t=0.0)
    replay(tr, lambda e: None, speed=4.0, clock=lambda: clock.t,
           sleep=lambda dt: setattr(clock, "t", clock.t + dt))
    end = tr.rows[-1][0] / 1e6
    assert end / 4.0 <= clock.t < end


# ---- pattern shaping ---------------------------------------------------------


def test_heavy_tail_concentrates_on_top_tenants():
    st = generate(_spec(seed=11)).stats()
    # Zipf(1.1) over 200 tenants: the top-10 share dwarfs the uniform 5%.
    assert st["top10_tenant_share"] > 0.30
    assert st["distinct_tenants"] > 20
    assert set(st["lanes"]) == {"high", "normal", "best_effort"}


def test_diurnal_wave_moves_the_rate():
    spec = _spec(pattern="diurnal", seed=12, duration_s=8.0,
                 diurnal_period_s=8.0, diurnal_amp=0.6)
    assert spec.profile(0.0) == pytest.approx(0.4)   # trough at t=0
    assert spec.profile(4.0) == pytest.approx(1.6)   # peak mid-trace
    tr = generate(spec)
    mid = [r for r in tr.rows if 3.0e6 <= r[0] < 5.0e6]
    edge = [r for r in tr.rows if r[0] < 1.0e6 or r[0] >= 7.0e6]
    assert len(mid) > 1.5 * len(edge)


def test_flash_crowd_spikes_into_hot_tenants_on_one_lane():
    spec = _spec(pattern="flash_crowd", seed=13, duration_s=10.0,
                 flash_at_frac=0.3, flash_ramp_s=1.0, flash_hold_s=3.0,
                 flash_mult=4.0)
    assert spec.profile(0.0) == 1.0
    assert spec.profile(4.5) == pytest.approx(4.0)   # inside the hold
    tr = generate(spec)
    spike = [r for r in tr.rows if 4.0e6 <= r[0] < 7.0e6]
    calm = [r for r in tr.rows if r[0] < 3.0e6]
    # ~4x the rate during the spike vs the same-length calm window.
    assert len(spike) > 2.5 * len(calm)
    lane_be = spec.lanes.index("best_effort")
    crowd = [r for r in spike if r[1] < spec.flash_tenants
             and r[2] == lane_be]
    assert len(crowd) > 0.4 * len(spike)


def test_event_key_matches_admission_format():
    tr = generate(_spec(seed=3))
    ev = next(tr.events())
    tenant, lane = ev.key().decode().split(":")
    assert tenant == ev.tenant and lane == ev.lane
    assert tenant.startswith("t") and len(tenant) == 6


def test_spec_validation_rejects_nonsense():
    with pytest.raises(ValueError):
        generate(_spec(pattern="square_wave"))
    with pytest.raises(ValueError):
        generate(_spec(lane_mix=(0.5, 0.5, 0.5)))
    with pytest.raises(ValueError):
        generate(_spec(flash_lane="vip"))


# ---- cell scoring ------------------------------------------------------------


def _scores(**kw):
    base = dict(lane_p99_ms={"high": 40.0, "normal": 60.0},
                goodput_frac=0.95, shed_frac=0.0, burn_peak=0.2,
                burn_tripped=False)
    base.update(kw)
    return base


def test_score_cell_steady_gates():
    t = CellTargets(p99_ms=50.0, min_goodput_frac=0.8, max_shed_frac=0.05,
                    forbid_burn_trip=True)
    res = score_cell(_scores(), t)
    assert res["ok"] and all(g["ok"] for g in res["gates"].values())
    assert set(res["gates"]) == {"p99_high_ms", "goodput_frac",
                                 "shed_frac", "burn_not_tripped"}

    bad = score_cell(_scores(lane_p99_ms={"high": 80.0}, burn_tripped=True), t)
    assert not bad["ok"]
    assert not bad["gates"]["p99_high_ms"]["ok"]
    assert not bad["gates"]["burn_not_tripped"]["ok"]


def test_score_cell_overload_gates_require_protection():
    t = CellTargets(p99_ms=150.0, min_goodput_frac=0.3,
                    expect_shed=True, expect_burn_trip=True)
    quiet = score_cell(_scores(lane_p99_ms={"high": 100.0}), t)
    # Protection never engaged: an overload cell FAILS even though the
    # latency/goodput numbers look healthy.
    assert not quiet["ok"]
    assert not quiet["gates"]["shed_engaged"]["ok"]
    assert not quiet["gates"]["burn_tripped"]["ok"]

    hot = score_cell(_scores(lane_p99_ms={"high": 120.0}, goodput_frac=0.4,
                             shed_frac=0.3, burn_tripped=True), t)
    assert hot["ok"]


def test_score_cell_missing_measurement_fails_closed():
    t = CellTargets(p99_ms=50.0)
    res = score_cell(_scores(lane_p99_ms={}), t)
    assert not res["ok"]


def test_render_table_shows_verdict_and_tally():
    card = {"seed": 16, "cells": [{
        "scenario": "classify", "pattern": "flash_crowd", "ok": True,
        "scores": _scores(offered_rate_per_s=500.0, goodput_per_s=400.0,
                          shed_frac=0.31, burn_tripped=True),
        "bottleneck": {"leader": "inference-bolt"},
    }]}
    txt = render_table(card)
    assert "inference-bolt" in txt
    assert "PASS" in txt and "1/1 cells pass" in txt and "seed 16" in txt


# ---- window-cursor hygiene (satellite: prune on rebalance) -------------------


def test_histogram_drop_window_forgets_named_cursor():
    h = Histogram()
    h.observe(1.0)
    assert h.window("cell-a")["count"] == 0  # primes the cursor
    h.observe(2.0)
    assert "cell-a" in h.window_keys()
    assert h.drop_window("cell-a") is True
    assert "cell-a" not in h.window_keys()
    assert h.drop_window("cell-a") is False
    # Re-reading after drop re-primes instead of replaying the old delta.
    assert h.window("cell-a")["count"] == 0


def test_registry_drop_windows_sweeps_every_histogram():
    reg = MetricsRegistry()
    for comp in ("sink", "bolt"):
        hist = reg.histogram(comp, "e2e_ms")
        hist.observe(1.0)
        hist.window("cell-a")
        hist.window("keep")
    assert reg.drop_windows("cell-a") == 2
    assert reg.drop_windows("cell-a") == 0
    for comp in ("sink", "bolt"):
        assert reg.histogram(comp, "e2e_ms").window_keys() == ("keep",)


def _fake_exec(task_index=0):
    return SimpleNamespace(task_index=task_index, busy_s=0.0, wait_s=0.0,
                           flush_s=0.0)


def test_capacity_tracker_prunes_stale_tasks_across_all_keys():
    clock = SimpleNamespace(t=0.0)
    e0, e1 = _fake_exec(0), _fake_exec(1)
    rt = SimpleNamespace(metrics=MetricsRegistry(),
                         bolt_execs={"b": [e0, e1]}, spout_execs={})
    tr = CapacityTracker(rt, clock=lambda: clock.t)
    tr.sample(key="obs")
    tr.sample(key="cell")
    assert set(tr.cursor_keys()) == {"obs", "cell"}
    # Rebalance removes task 1. Only "obs" keeps sampling — but the
    # retired task's cursor must vanish from "cell" too, not linger until
    # that key happens to sample again (it may never).
    rt.bolt_execs["b"] = [e0]
    clock.t += 1.0
    tr.sample(key="obs")
    assert set(tr._cursors["cell"]) == {("b", 0)}
    assert set(tr._cursors["obs"]) == {("b", 0)}


def test_capacity_tracker_drop_forgets_whole_key():
    clock = SimpleNamespace(t=0.0)
    rt = SimpleNamespace(metrics=MetricsRegistry(),
                         bolt_execs={"b": [_fake_exec(0)]}, spout_execs={})
    tr = CapacityTracker(rt, clock=lambda: clock.t)
    tr.sample(key="cell")
    assert tr.drop("cell") is True
    assert tr.drop("cell") is False
    assert tr.cursor_keys() == ()


# ---- scenario_phase flight event (satellite) ---------------------------------


def test_fleet_classify_scenario_runs_the_default_batching():
    """One way to form a batch: the ``continuous`` scenario, which
    differed from ``classify`` by a flag, is gone, and ``classify``
    pins nothing about where batches form."""
    from storm_tpu.config import BatchConfig
    from storm_tpu.loadgen import fleet

    assert fleet.SCENARIOS == ("classify", "cascade", "serve_path", "decode")
    with pytest.raises(KeyError):
        fleet._make_scenarios(["continuous"])
    (sc,) = fleet._make_scenarios(["classify"])
    batch = sc._cfg(400.0).batch
    assert (batch.max_inflight, batch.eager) == (
        BatchConfig().max_inflight, BatchConfig().eager)


def test_scenario_phase_flight_event_shape():
    fr = FlightRecorder()
    assert fr.event("scenario_phase", scenario="classify",
                    pattern="flash_crowd", cell="cell-classify-flash_crowd",
                    phase="hold", offered=0)
    (ev,) = [e for e in fr.tail() if e["kind"] == "scenario_phase"]
    assert ev["scenario"] == "classify"
    assert ev["pattern"] == "flash_crowd"
    assert ev["phase"] == "hold"
    assert ev["cell"] == "cell-classify-flash_crowd"
    fr.close()
