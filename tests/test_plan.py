"""storm_tpu.plan: cost model read-through, solver determinism +
infeasibility attribution, Plan -> config-knob round-trip, and the
online corrector's named-limiter-only / hysteresis contract."""

import asyncio
import json
import os
from types import SimpleNamespace

import pytest

from storm_tpu.config import PlanConfig
from storm_tpu.obs.profile import ProfileStore
from storm_tpu.plan import (
    Candidate,
    CostModel,
    PlanCorrector,
    Target,
    solve,
    unwrap_snapshot,
)
from storm_tpu.runtime.autoscale import (
    ACCEL_MAX_PARALLELISM,
    CPU_MAX_PARALLELISM,
)
from storm_tpu.runtime.metrics import MetricsRegistry

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "profile_snapshot.json")


@pytest.fixture(scope="module")
def snap():
    with open(FIXTURE) as fh:
        return unwrap_snapshot(json.load(fh))


# ---- cost model ---------------------------------------------------------------


def test_stage_ms_reads_curve_exactly_and_interpolates(snap):
    """At a profiled bucket the model returns the curve value verbatim
    (zero prediction error against its own input); between buckets it
    interpolates linearly, bounded by the two neighbors."""
    m = CostModel(snap)
    direct = snap["engines"]["lenet5"]["buckets"]["64"]["stages"][
        "compute_ms"]["mean"]
    assert m.stage_ms("lenet5", 64, "compute_ms") == pytest.approx(direct)
    v16 = m.stage_ms("lenet5", 16, "compute_ms")
    v64 = m.stage_ms("lenet5", 64, "compute_ms")
    mid = m.stage_ms("lenet5", 40, "compute_ms")
    assert min(v16, v64) <= mid <= max(v16, v64)


def test_evaluate_prediction_is_bounded_by_its_terms(snap):
    """The p99 prediction decomposes into window + queue + device p95 +
    overhead: it must never undercut the device p95 floor, and the
    per-stage predictions must be the curve's own numbers."""
    m = CostModel(snap)
    t = Target(rate_rows_s=600.0, slo_p99_ms=1000.0)
    pred = m.evaluate(Candidate(engine="lenet5", bucket=64,
                                deadline_ms=50.0), t)
    assert pred["feasible"]
    p95 = m.stage_ms("lenet5", 64, "device_ms", q="p95")
    assert pred["p99_ms"] >= p95
    for stage in ("h2d_ms", "compute_ms", "d2h_ms", "device_ms"):
        assert pred["stages"][stage] == pytest.approx(
            round(m.stage_ms("lenet5", 64, stage), 3))  # 3-decimal rounding
    # fill-limited batching: the wait prediction is half the window
    assert pred["stages"]["batch_wait_ms"] <= 50.0 / 2 + 1e-9


def test_batches_fill_at_the_offered_rate_whatever_the_parallelism(snap):
    """All replicas co-batch in the engine's one queue, so more bolt tasks
    neither shrink the predicted batch nor the capacity."""
    m = CostModel(snap)
    t = Target(rate_rows_s=600.0, slo_p99_ms=1000.0)
    one, three = (
        m.evaluate(Candidate(engine="lenet5", bucket=64, deadline_ms=25.0,
                             parallelism=par), t) for par in (1, 3))
    assert three["rows_per_batch"] == one["rows_per_batch"] == 15.0
    assert three["capacity_rows_s"] == one["capacity_rows_s"]
    assert "continuous" not in one["candidate"]


# ---- solver -------------------------------------------------------------------


def test_solve_is_deterministic_on_the_fixture(snap):
    a = solve(snap, Target(600.0, 250.0), engine="lenet5")
    b = solve(snap, Target(600.0, 250.0), engine="lenet5")
    assert a.feasible and b.feasible
    assert a.to_dict() == b.to_dict()
    assert a.plan.parallelism == 1  # cheapest-first: fewest replicas
    assert a.considered > 100  # the grid was actually searched


def test_solve_validates_onto_real_config_knobs(snap):
    from storm_tpu.config import Config

    res = solve(snap, Target(600.0, 250.0), engine="lenet5")
    plan = res.plan
    assert plan.validate()
    cfg = Config()
    cfg.apply_dict(plan.to_overrides())
    assert cfg.topology.inference_parallelism == plan.parallelism
    assert cfg.batch.bucket_for(1) == plan.bucket
    assert cfg.batch.max_wait_ms == pytest.approx(plan.deadline_ms)
    # the CLI form round-trips through --set parsing (section.key=json)
    assert any(arg.startswith("batch.max_batch=")
               for arg in plan.override_args())


def test_infeasible_target_names_the_binding_stage(snap):
    """'No plan' must say WHY: the stage that caps capacity, with the
    coverage table so cold/unknown is distinguishable from can't."""
    res = solve(snap, Target(rate_rows_s=50000.0, slo_p99_ms=50.0),
                engine="resnet20")
    assert not res.feasible
    assert res.plan is None
    assert res.binding_stage in ("h2d_ms", "compute_ms", "d2h_ms",
                                 "device_ms", "batch_wait_ms", "queue_ms")
    assert res.binding_stage in res.why
    assert "resnet20" in res.coverage
    assert res.best_infeasible is not None


def test_solve_auto_engine_picks_cheapest_tier(snap):
    res = solve(snap, Target(600.0, 250.0))
    assert res.feasible
    assert res.engines_ranked[0]["engine"] == res.plan.engine
    # ranked by ms/row ascending: the cascade tier order
    costs = [r["ms_per_row"] for r in res.engines_ranked]
    assert costs == sorted(costs)


def test_solve_refuses_untrusted_curves():
    """A snapshot whose cells are all below min_samples is 'cold', not
    silently planned over."""
    snap = {"engines": {"m": {"buckets": {"64": {"stages": {"device_ms": {
        "count": 2, "mean": 5.0, "p95": 6.0}}}}, "compiles": {}}}}
    res = solve(snap, Target(100.0, 100.0), engine="m", min_samples=8)
    assert not res.feasible
    assert "cold" in res.why or "trusted" in res.why
    assert res.coverage["m"]["buckets"]["64"]["status"] == "cold"


# ---- ProfileStore coverage (cold vs unknown) ----------------------------------


def test_profile_store_coverage_disambiguates_cold_from_unknown():
    store = ProfileStore()
    for _ in range(3):
        store.record_batch("m", 64, rows=60,
                           timings={"h2d_ms": 1.0, "compute_ms": 2.0,
                                    "d2h_ms": 0.1})
    store.record_compile("m", 64, 100.0)
    cov = store.coverage(min_samples=8)
    assert cov["m"]["buckets"]["64"] == {"samples": 3, "status": "cold"}
    assert "128" not in cov["m"]["buckets"]  # unknown = absent, a 3rd state
    assert cov["m"]["compile_known"] == ["64"]
    # cost_of honors the same threshold; default stays back-compatible
    assert store.cost_of("m", min_samples=8) is None
    assert store.cost_of("m") is not None
    assert store.cost_of("never-profiled") is None


# ---- corrector ----------------------------------------------------------------


class FlightLog:
    def __init__(self):
        self.events = []

    def event(self, name, **kw):
        self.events.append((name, kw))


class Rig:
    """Duck-typed runtime for the corrector: parallelism ledger +
    rebalance recorder + real metrics registry + flight capture."""

    def __init__(self, par=None):
        self.par = dict(par or {"inference-bolt": 1, "resize-bolt": 1})
        self.calls = []
        self.metrics = MetricsRegistry()
        self.flight = FlightLog()

    def parallelism_of(self, c):
        return self.par.get(c, 1)

    async def rebalance(self, c, n):
        self.calls.append((c, n))
        self.par[c] = n


def _step(c):
    return asyncio.run(c.step())


def _mk(rig, leader="resize-bolt", tripped=True, **cfg):
    attributor = SimpleNamespace(last_verdict={
        "leader": leader,
        "ranked": [{"component": leader, "score": 0.93}],
    })
    burn = SimpleNamespace(tripped=tripped)
    return PlanCorrector(rig, PlanConfig(enabled=True, **cfg),
                         attributor=attributor, burn=burn), attributor, burn


def test_corrector_moves_only_the_named_limiter():
    """Burn tripped + leader named -> ONE bounded step on that component
    and nothing else; the flight tail carries the decision."""
    rig = Rig()
    c, _, _ = _mk(rig, hot_steps=2, hold_steps=0)
    assert _step(c) is None  # hot #1: hysteresis
    assert _step(c) == ("resize-bolt", 2)  # hot #2: one step
    assert rig.calls == [("resize-bolt", 2)]
    assert rig.par["inference-bolt"] == 1  # untouched non-limiter
    assert [e for e, _ in rig.flight.events] == ["plan_correction"]
    assert rig.flight.events[0][1]["action"] == "up"
    assert rig.metrics.counter("plan", "plan_corrections").value == 1


def test_corrector_does_not_flap_during_hold():
    """After a move, hold_steps of cooldown ignore even sustained heat —
    one knob step per observation window, never a runaway ramp."""
    rig = Rig()
    c, _, _ = _mk(rig, hot_steps=2, hold_steps=3)
    _step(c)
    assert _step(c) == ("resize-bolt", 2)
    for _ in range(3):  # cooldown: hot but silent
        assert _step(c) is None
    assert rig.calls == [("resize-bolt", 2)]
    _step(c)  # hot #1 of the next window
    assert _step(c) == ("resize-bolt", 3)
    assert rig.calls == [("resize-bolt", 2), ("resize-bolt", 3)]


def test_corrector_pins_at_cap_instead_of_pushing_past_it():
    rig = Rig(par={"inference-bolt": ACCEL_MAX_PARALLELISM})
    c, _, _ = _mk(rig, leader="inference-bolt", hot_steps=1, hold_steps=0)
    assert _step(c) is None
    assert rig.calls == []  # never rebalances past the measured cliff
    acts = [kw["action"] for _, kw in rig.flight.events]
    assert acts == ["pinned"]
    # caps resolve by component kind; explicit override wins
    assert c.cap_for("inference-bolt") == ACCEL_MAX_PARALLELISM
    assert c.cap_for("resize-bolt") == CPU_MAX_PARALLELISM
    c2, _, _ = _mk(Rig(), max_parallelism=2)
    assert c2.cap_for("resize-bolt") == 2


def test_corrector_reverts_its_own_move_after_sustained_calm():
    rig = Rig()
    c, _, burn = _mk(rig, hot_steps=1, hold_steps=0, calm_steps=2)
    assert _step(c) == ("resize-bolt", 2)
    burn.tripped = False  # budget stops burning
    assert _step(c) is None  # calm #1
    assert _step(c) == ("resize-bolt", 1)  # calm #2: walk it back
    assert rig.par["resize-bolt"] == 1
    assert c.snapshot()["outstanding"] == {}
    # nothing left to revert: sustained calm is now a no-op
    assert _step(c) is None
    assert _step(c) is None


def test_corrector_disabled_is_inert():
    rig = Rig()
    c, _, _ = _mk(rig, correct=False, hot_steps=1)
    assert not c.enabled
    assert _step(c) is None
    assert rig.calls == []
    assert rig.metrics.gauge("plan", "plan_active").value == 0


def test_autoscaler_defers_scale_up_to_enabled_corrector(run):
    """With an enabled corrector attached, the Autoscaler records
    defer_plan instead of scaling its fixed policy component."""
    from tests.test_autoscale import _mk_runtime
    from storm_tpu.runtime.autoscale import AutoscalePolicy, Autoscaler

    async def go():
        cluster, rt = await _mk_runtime()
        scaler = Autoscaler(
            rt, AutoscalePolicy(high_ms=100, max_parallelism=4))
        scaler.corrector = SimpleNamespace(enabled=True)
        hist = rt.metrics.histogram("kafka-bolt", "e2e_latency_ms")
        for _ in range(50):
            hist.observe(500.0)  # hot
        r1 = await scaler.step()
        r2 = await scaler.step()  # would scale up without the corrector
        par = rt.parallelism_of("inference-bolt")
        await cluster.shutdown()
        return r1, r2, par

    r1, r2, par = run(go())
    assert r1 is None and r2 is None
    assert par == 2  # untouched


def test_observatory_snapshot_carries_corrector_state(run):
    """obs.corrector is stepped by the Observatory loop and surfaces in
    its snapshot (what the /plan route serves)."""
    from tests.test_autoscale import _mk_runtime
    from storm_tpu.obs import Observatory

    async def go():
        cluster, rt = await _mk_runtime()
        obs = Observatory(rt)
        corr = PlanCorrector(rt, PlanConfig(enabled=True),
                             attributor=obs.bottleneck, burn=obs.burn)
        obs.corrector = corr
        snap = obs.snapshot()
        await cluster.shutdown()
        return snap

    snap = run(go())
    assert snap["corrector"]["enabled"] is True
    assert snap["corrector"]["corrections"] == []
