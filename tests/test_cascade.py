"""Confidence-gated model cascade (ISSUE 5 tentpole + satellites).

Covers the policy math (uncertainty metrics, threshold identities,
temperature fitting, config validation), the router's ROW-level
accept/escalate split with deterministic fake engines (confidence
encoded in the input pixels — no sleeps, no real models; a
multi-instance record's uncertain rows escalate alone and the output
merges across tiers), the operator integration (escalated
residue re-batches into the next tier under the shared max_inflight
semaphore; acks stay deferred and exactly-once), the QoS coupling (shed
pins eligible lanes to tier 0; qos.degrade_model synthesizes a shed-only
cascade replacing the old 1-slot degrade semaphore), and the UI
``/cascade`` route's per-tier engine attribution.
"""

from __future__ import annotations

import asyncio
import json

import numpy as np
import pytest

from storm_tpu.cascade.policy import (
    CascadeConfig, fit_temperature, uncertainty)
from storm_tpu.config import BatchConfig, Config, ModelConfig, QosConfig
from storm_tpu.infer.operator import InferenceBolt
from storm_tpu.runtime.base import TopologyContext
from storm_tpu.runtime.metrics import MetricsRegistry
from storm_tpu.runtime.tuples import Tuple

from tests.test_pipeline import _Collector, _tuple  # noqa: F401

SHAPE = (8, 8, 1)


# ---- policy: uncertainty math ------------------------------------------------


def _row(pmax, k=10):
    rest = (1.0 - pmax) / (k - 1)
    row = np.full(k, rest)
    row[0] = pmax
    return row


@pytest.mark.parametrize("metric", ["max_softmax", "margin", "entropy"])
def test_uncertainty_bounds_and_ordering(metric):
    certain = _row(0.999)
    clueless = np.full(10, 0.1)
    u = uncertainty(np.stack([certain, clueless]), metric)
    assert u.shape == (2,)
    assert np.all((u >= 0.0) & (u <= 1.0))
    assert u[0] < u[1], f"{metric}: confident row must score lower"
    # Uniform is maximally uncertain for entropy/margin exactly.
    if metric == "entropy":
        assert u[1] == pytest.approx(1.0, abs=1e-9)
    if metric == "margin":
        assert u[1] == pytest.approx(1.0, abs=1e-9)


def test_uncertainty_temperature_flattens():
    row = _row(0.99)
    cold = uncertainty(row, "max_softmax", temperature=1.0)[0]
    hot = uncertainty(row, "max_softmax", temperature=4.0)[0]
    assert hot > cold, "T > 1 must spread an over-confident row"


def test_fit_temperature_prefers_calibrated():
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 10, 256)
    # Over-confident but often WRONG probabilities: p_max=0.99 on a random
    # class. The NLL fit must pick a T > 1 to soften them.
    probs = np.stack([_row(0.99)[np.roll(np.arange(10), lab)]
                      for lab in rng.integers(0, 10, 256)])
    fit = fit_temperature(probs, labels)
    assert fit["temperature"] > 1.0
    assert fit["curve"], "artifact wants the full NLL curve"
    assert min(r["nll"] for r in fit["curve"]) == fit["nll"]


def test_cascade_config_validation():
    ok = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                       thresholds=(0.4,))
    assert ok.last_tier == 1
    with pytest.raises(ValueError):  # single tier is not a cascade
        CascadeConfig(enabled=True, tiers=("lenet5",), thresholds=())
    with pytest.raises(ValueError):  # one threshold per non-final tier
        CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                      thresholds=(0.4, 0.5))
    with pytest.raises(ValueError):  # thresholds live in [0, 1]
        CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                      thresholds=(1.5,))
    with pytest.raises(ValueError):
        CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                      thresholds=(0.4,), metric="vibes")
    with pytest.raises(ValueError):
        CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                      thresholds=(0.4,), escalation_budget=2.0)
    with pytest.raises(ValueError):  # lane override length must match
        CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                      thresholds=(0.4,),
                      lane_thresholds={"high": (0.4, 0.5)})
    # disabled configs skip validation so Config() defaults stay inert
    CascadeConfig(enabled=False, tiers=("lenet5",))


def test_threshold_for_lane_override_and_shed_widening():
    cfg = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                        thresholds=(0.2,),
                        lane_thresholds={"high": (0.6,)},
                        shed_tighten=0.5)
    assert cfg.threshold_for(0, None, 0) == pytest.approx(0.2)
    assert cfg.threshold_for(0, "high", 0) == pytest.approx(0.6)
    # Each shed level halves the remaining strictness: 1-(1-0.2)*0.5 = 0.6
    assert cfg.threshold_for(0, None, 1) == pytest.approx(0.6)
    assert cfg.threshold_for(0, None, 2) == pytest.approx(0.8)


def test_config_embeds_cascade_section():
    cfg = Config()
    assert cfg.cascade.enabled is False
    cas = CascadeConfig(enabled=True, tiers=["lenet5", "resnet20"],
                        thresholds=[0.4])
    assert cas.tiers == ("lenet5", "resnet20")  # list -> tuple coercion


# ---- operator integration: deterministic fake tiers --------------------------


class _ConfEngine:
    """predict() echoes each record's confidence: a record whose pixels
    are the constant c yields a softmax row with max prob c at class
    ``tag`` — so the test picks, per record, exactly which tier accepts
    it, and the argmax proves WHICH tier answered."""

    input_shape = SHAPE

    def __init__(self, tag: int, fail: bool = False) -> None:
        self.tag = tag
        self.fail = fail
        self.calls = []  # records served per predict()
        self.warmed = 0

    def warmup(self, buckets=None):
        self.warmed += 1

    def predict(self, x):
        if self.fail:
            raise RuntimeError(f"tier {self.tag} device fault")
        self.calls.append(int(x.shape[0]))
        out = np.zeros((x.shape[0], 10), np.float32)
        for i in range(x.shape[0]):
            c = float(np.clip(x[i, 0, 0, 0], 1e-3, 0.999))
            out[i] = (1.0 - c) / 9.0
            out[i, self.tag] = c
        return out


def _conf_payload(c, n=1):
    return json.dumps(
        {"instances": np.full((n, *SHAPE), c, np.float32).tolist()})


def _cascade_bolt(monkeypatch, cascade, qos=None, engines=None, **batch_kw):
    """An InferenceBolt over fake tier engines: shared_engine is patched in
    the operator module (the prewarm-test seam), so the router builds one
    _ConfEngine per registry name — tier i answers with argmax == i."""
    engines = {} if engines is None else engines
    tags = {"lenet5": 0, "resnet20": 1, "vit_tiny": 2}

    def fake_shared(mc, sharding=None, batch=None):
        return engines.setdefault(mc.name, _ConfEngine(tag=tags[mc.name]))

    monkeypatch.setattr("storm_tpu.infer.operator.shared_engine", fake_shared)
    names = cascade.tiers if cascade is not None else \
        (qos.degrade_model, "resnet20")
    bolt = InferenceBolt(
        ModelConfig(name=names[-1], dtype="float32", input_shape=SHAPE),
        BatchConfig(**batch_kw), warmup=False, qos=qos, cascade=cascade)
    ctx = TopologyContext("inference-bolt", 0, 1, Config(),
                          metrics=MetricsRegistry())
    coll = _Collector()
    bolt.prepare(ctx, coll)
    return bolt, coll, engines


def _argmaxes(coll):
    return [int(np.argmax(json.loads(msg)["predictions"][0]))
            for stream, (msg, *_) in coll.emitted if stream == "default"]


def test_deterministic_accept_escalate_split(run, monkeypatch):
    async def go():
        cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                            thresholds=(0.5,))
        bolt, coll, engines = _cascade_bolt(
            monkeypatch, cas, max_batch=4, max_wait_ms=10_000,
            max_inflight=1)
        # Two confident records (u = 1-0.9 = 0.1 < 0.5: accept at tier 0)
        # and two unconfident (u = 0.8: escalate to the flagship).
        for c in (0.9, 0.2, 0.9, 0.2):
            await bolt.execute(_tuple(_conf_payload(c)))
        await bolt.flush()
        # rows per tier, however each tier's queue cut them into batches
        # (residues join the flagship's queue one record at a time)
        assert sum(engines["lenet5"].calls) == 4
        assert sum(engines["resnet20"].calls) == 2, \
            "only the low-confidence residue reaches the flagship"
        assert len(coll.acked) == 4 and not coll.failed
        assert sorted(_argmaxes(coll)) == [0, 0, 1, 1], \
            "accepted records answer from tier 0, escalated from tier 1"
        m = bolt.context.metrics.snapshot()["inference-bolt"]
        assert m["cascade_accepted_tier0"] == 2
        assert m["cascade_accepted_tier1"] == 2
        assert m["cascade_escalations"] == 2
        assert m["device_ms"]["count"] == len(
            engines["lenet5"].calls + engines["resnet20"].calls)
        rate = bolt.context.metrics.snapshot()["cascade"]["escalation_rate"]
        assert rate == pytest.approx(0.5)

    run(go(), timeout=60)


def test_threshold_one_is_tier0_only(run, monkeypatch):
    async def go():
        cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                            thresholds=(1.0,))
        bolt, coll, engines = _cascade_bolt(
            monkeypatch, cas, max_batch=4, max_wait_ms=10_000,
            max_inflight=1)
        for c in (0.9, 0.11, 0.5, 0.2):  # even near-clueless accepts
            await bolt.execute(_tuple(_conf_payload(c)))
        await bolt.flush()
        assert engines["lenet5"].calls == [4]
        assert engines["resnet20"].calls == [], \
            "threshold=1 must be identical to tier-0-only"
        assert len(coll.acked) == 4 and _argmaxes(coll) == [0, 0, 0, 0]

    run(go(), timeout=60)


def test_threshold_zero_is_flagship_only(run, monkeypatch):
    async def go():
        cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                            thresholds=(0.0,))
        bolt, coll, engines = _cascade_bolt(
            monkeypatch, cas, max_batch=4, max_wait_ms=10_000,
            max_inflight=1)
        for c in (0.999, 0.999, 0.999, 0.999):  # max confidence, still out
            await bolt.execute(_tuple(_conf_payload(c)))
        await bolt.flush()
        assert sum(engines["resnet20"].calls) == 4
        assert len(coll.acked) == 4 and _argmaxes(coll) == [1, 1, 1, 1], \
            "threshold=0 must be identical to flagship-only"

    run(go(), timeout=60)


def test_escalation_budget_caps_flagship_load(run, monkeypatch):
    async def go():
        cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                            thresholds=(0.5,), escalation_budget=0.0)
        bolt, coll, engines = _cascade_bolt(
            monkeypatch, cas, max_batch=4, max_wait_ms=10_000,
            max_inflight=1)
        for c in (0.2, 0.2, 0.2, 0.2):  # all WANT to escalate
            await bolt.execute(_tuple(_conf_payload(c)))
        await bolt.flush()
        assert engines["resnet20"].calls == [], \
            "budget 0 must never escalate"
        assert len(coll.acked) == 4 and _argmaxes(coll) == [0, 0, 0, 0]
        m = bolt.context.metrics.snapshot()["inference-bolt"]
        assert m["cascade_budget_capped"] == 4
        assert "cascade_escalations" not in m or m["cascade_escalations"] == 0

    run(go(), timeout=60)


def test_tier_failure_fails_original_tuples_for_replay(run, monkeypatch):
    async def go():
        cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                            thresholds=(0.5,))
        engines = {"resnet20": _ConfEngine(tag=1, fail=True)}
        bolt, coll, engines = _cascade_bolt(
            monkeypatch, cas, engines=engines, max_batch=2,
            max_wait_ms=10_000, max_inflight=1)
        tuples = [_tuple(_conf_payload(c)) for c in (0.9, 0.2)]
        for t in tuples:
            await bolt.execute(t)
        await bolt.flush()
        # The confident record acked at tier 0; the escalated one hit the
        # failing flagship — its ORIGINAL tuple fails (Escalated unwraps)
        # so the spout replays it from tier 0. Never both, never neither.
        assert {id(t) for t in coll.acked} == {id(tuples[0])}
        assert {id(t) for t in coll.failed} == {id(tuples[1])}
        assert coll.errors and "device fault" in str(coll.errors[0])

    run(go(), timeout=60)


def test_shed_pins_eligible_lane_to_tier0(run, monkeypatch):
    async def go():
        qos = QosConfig(enabled=True)
        cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                            thresholds=(0.5,))
        bolt, coll, engines = _cascade_bolt(
            monkeypatch, cas, qos=qos, max_batch=1, max_wait_ms=10_000,
            max_inflight=1)
        bolt.context.metrics.gauge("qos", "shed_level").set(1.0)
        # Low-confidence records in BOTH lanes: best_effort is shed-eligible
        # at level 1 -> pinned at tier 0; high still escalates.
        t_be = Tuple(values=[_conf_payload(0.2), "best_effort"],
                     fields=("message", "qos_lane"),
                     source_component="spout")
        t_hi = Tuple(values=[_conf_payload(0.2), "high"],
                     fields=("message", "qos_lane"),
                     source_component="spout")
        await bolt.execute(t_be)
        await bolt.execute(t_hi)
        await bolt.flush()
        assert len(coll.acked) == 2 and not coll.failed
        assert sorted(_argmaxes(coll)) == [0, 1], \
            "pinned best_effort answers from tier 0, high from flagship"
        m = bolt.context.metrics.snapshot()["inference-bolt"]
        assert m["cascade_shed_pinned"] == 1
        assert m["shed_degraded"] == 1  # only the shed-eligible record
        assert m["cascade_escalated_lane_high"] == 1
        assert "shed_rejected" not in m or m["shed_rejected"] == 0

    run(go(), timeout=60)


def test_degrade_model_synthesizes_shed_only_cascade(run, monkeypatch):
    async def go():
        qos = QosConfig(enabled=True, degrade_model="lenet5")
        bolt, coll, engines = _cascade_bolt(
            monkeypatch, None, qos=qos, max_batch=1, max_wait_ms=10_000,
            max_inflight=2)
        assert bolt._router is not None and bolt._router.cfg.shed_only
        # Level 0: normal traffic goes STRAIGHT to the flagship tier.
        t0 = Tuple(values=[_conf_payload(0.2), "best_effort"],
                   fields=("message", "qos_lane"), source_component="spout")
        await bolt.execute(t0)
        await bolt.flush()
        assert engines["lenet5"].calls == []
        assert _argmaxes(coll) == [1]
        # Level 1: shed-eligible traffic enters pinned at tier 0 and is
        # SERVED there (batched, normal concurrency — the old 1-slot
        # degrade semaphore is gone), not answered Overloaded.
        bolt.context.metrics.gauge("qos", "shed_level").set(1.0)
        t1 = Tuple(values=[_conf_payload(0.2), "best_effort"],
                   fields=("message", "qos_lane"), source_component="spout")
        await bolt.execute(t1)
        await bolt.flush()
        assert engines["lenet5"].calls == [1]
        assert _argmaxes(coll) == [1, 0]
        assert len(coll.acked) == 2 and not coll.failed
        m = bolt.context.metrics.snapshot()["inference-bolt"]
        assert m["shed_degraded"] == 1
        assert "shed_rejected" not in m or m["shed_rejected"] == 0
        assert not hasattr(bolt, "_degrade_sem"), \
            "the 1-slot degrade semaphore must be gone (ISSUE 5 satellite)"

    run(go(), timeout=60)


def test_escalation_survives_max_inflight_one(run, monkeypatch):
    """An escalation never parks behind the row bound its own completion
    frees: with ``max_inflight=1`` the task is AT its bound (one full
    batch outstanding) when the residue of that batch goes on to tier 1 —
    ``_enqueue`` must not wait, or tier 1 deadlocks."""

    async def go():
        cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                            thresholds=(0.5,))
        bolt, coll, engines = _cascade_bolt(
            monkeypatch, cas, max_batch=8, max_wait_ms=10_000,
            max_inflight=1)
        for _ in range(2):
            for c in (0.2,) * 8:  # full batch, all escalate
                await bolt.execute(_tuple(_conf_payload(c)))
        await bolt.flush()
        assert len(coll.acked) == 16 and not coll.failed
        assert sum(engines["resnet20"].calls) == 16

    run(go(), timeout=60)


def test_partial_rows_split_across_tiers(run, monkeypatch):
    """Row-level residue: a multi-instance record's confident rows answer
    at tier 0 and ONLY its uncertain rows escalate; the single output
    message merges rows from both tiers in original row order, and the
    record acks exactly once."""

    async def go():
        cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                            thresholds=(0.5,))
        bolt, coll, engines = _cascade_bolt(
            monkeypatch, cas, max_batch=4, max_wait_ms=10_000,
            max_inflight=1)
        imgs = [np.full(SHAPE, c, np.float32).tolist()
                for c in (0.9, 0.2, 0.9)]
        t = _tuple(json.dumps({"instances": imgs}))
        await bolt.execute(t)
        await bolt.flush()
        assert coll.acked == [t] and not coll.failed
        (msg, *_), = [v for s, v in coll.emitted if s == "default"]
        preds = json.loads(msg)["predictions"]
        assert [int(np.argmax(p)) for p in preds] == [0, 1, 0], \
            "rows 0/2 answer from tier 0, row 1 from the flagship, " \
            "merged in original order"
        assert engines["lenet5"].calls == [3]
        assert engines["resnet20"].calls == [1], \
            "only the one uncertain ROW reaches the flagship"
        m = bolt.context.metrics.snapshot()["inference-bolt"]
        assert m["cascade_accepted_tier0"] == 2  # rows, not records
        assert m["cascade_accepted_tier1"] == 1
        assert m["cascade_escalations"] == 1

    run(go(), timeout=60)


def test_chunked_tuples_ride_the_cascade(run, monkeypatch):
    async def go():
        cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                            thresholds=(0.5,))
        bolt, coll, engines = _cascade_bolt(
            monkeypatch, cas, max_batch=4, max_wait_ms=10_000,
            max_inflight=1)
        # One chunked tuple, 4 records: 2 accept, 2 escalate. The chunk
        # handle acks once, after EVERY record completed — across tiers.
        t = _tuple([_conf_payload(c) for c in (0.9, 0.2, 0.9, 0.2)])
        await bolt.execute(t)
        await bolt.flush()
        assert coll.acked == [t] and not coll.failed
        assert sorted(_argmaxes(coll)) == [0, 0, 1, 1]

    run(go(), timeout=60)


def test_router_inventory_attributes_tiers():
    from storm_tpu.cascade.router import CascadeRouter

    cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                        thresholds=(0.3,))
    router = CascadeRouter(cas)
    router.build(ModelConfig(name="resnet20", input_shape=SHAPE),
                 build_engine=lambda mc: _ConfEngine(0))
    inv = router.inventory()
    assert [r["model"] for r in inv] == ["lenet5", "resnet20"]
    assert inv[0]["threshold"] == pytest.approx(0.3)
    assert inv[1]["threshold"] is None  # the flagship always accepts
    assert all(r["pending_records"] == 0 for r in inv)


def test_router_inventory_counts_rows_waiting_in_a_tiers_queue():
    """``pending_records`` is what waits in the tier engine's own queue
    (the router keeps no batcher of its own)."""
    from storm_tpu.cascade.router import CascadeRouter
    from storm_tpu.infer.continuous import continuous_for

    cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                        thresholds=(0.3,))
    router = CascadeRouter(cas)
    router.build(ModelConfig(name="resnet20", input_shape=SHAPE),
                 build_engine=lambda mc: _ConfEngine(0))
    cb = continuous_for(router.tiers[1].engine,
                        BatchConfig(max_batch=8, max_wait_ms=60_000))
    cb._ensure_thread_locked = lambda: None  # nothing dispatches here
    cb.submit(np.zeros((3, *SHAPE), np.float32))
    assert [r["pending_records"] for r in router.inventory()] == [0, 3]


def _conf_rows(cs, tag=0):
    out = np.zeros((len(cs), 10), np.float32)
    for i, c in enumerate(cs):
        out[i] = (1.0 - c) / 9.0
        out[i, tag] = c
    return out


@pytest.mark.parametrize("case,cfg_kw,lane,level,confs,want", [
    ("accept", {}, None, 0, (0.9, 0.9),
     {"accepted": 2, "escalated": 0, "pinned": 0, "budget_capped": 0}),
    ("escalate", {}, None, 0, (0.9, 0.2, 0.2),
     {"accepted": 1, "escalated": 2, "pinned": 0, "budget_capped": 0}),
    ("shed_pinned", {}, "best_effort", 1, (0.2, 0.2),
     {"accepted": 2, "escalated": 0, "pinned": 2, "budget_capped": 0}),
    ("budget_capped", {"escalation_budget": 0.0}, None, 0, (0.2, 0.9),
     {"accepted": 2, "escalated": 0, "pinned": 0, "budget_capped": 1}),
])
def test_decide_item_info_counts_rows(case, cfg_kw, lane, level, confs, want):
    """``decide_item`` is the one cascade decision: its ``info`` counts
    the record's ROWS by outcome, and a residue holds exactly the rows
    that escalate."""
    from storm_tpu.cascade.router import CascadeRouter

    cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                        thresholds=(0.5,), **cfg_kw)
    qos = QosConfig(enabled=True)
    router = CascadeRouter(cas, qos=qos)
    router.build(ModelConfig(name="resnet20", input_shape=SHAPE),
                 build_engine=lambda mc: _ConfEngine(0))
    data = np.stack([np.full(SHAPE, c, np.float32) for c in confs])
    merged, residue, info = router.decide_item(
        "record", data, _conf_rows(confs), lane, 0, level, ts=1.0)
    assert info == want
    if want["escalated"]:
        assert merged is None
        assert residue.data.shape[0] == want["escalated"]
        assert (residue.lane, residue.ts) == (lane, 1.0)
        assert residue.payload.payload == "record"
    else:
        assert residue is None and merged.shape == (len(confs), 10)


def _make_conf_spout():
    from storm_tpu.runtime.base import Spout
    from storm_tpu.runtime.tuples import Values

    class ConfSpout(Spout):
        async def next_tuple(self):
            await asyncio.sleep(0.01)
            await self.collector.emit(
                Values([_conf_payload(0.9)]), msg_id=object())
            return True

        def ack(self, msg_id):
            pass

        def fail(self, msg_id):
            pass

    return ConfSpout()


def test_ui_cascade_route_serves_tier_inventory(run, monkeypatch):
    from storm_tpu.runtime.cluster import AsyncLocalCluster
    from storm_tpu.runtime import TopologyBuilder
    from storm_tpu.runtime.ui import UIServer
    from tests.test_qos import _http_get

    def fake_shared(mc, sharding=None, batch=None):
        return _ConfEngine(0 if mc.name == "lenet5" else 1)

    monkeypatch.setattr("storm_tpu.infer.operator.shared_engine", fake_shared)

    async def go():
        cfg = Config()
        cas = CascadeConfig(enabled=True, tiers=("lenet5", "resnet20"),
                            thresholds=(0.5,))
        tb = TopologyBuilder()
        tb.set_spout("spout", _make_conf_spout(), parallelism=1)
        tb.set_bolt(
            "inference-bolt",
            InferenceBolt(ModelConfig(name="resnet20", input_shape=SHAPE),
                          BatchConfig(max_batch=4), warmup=False,
                          cascade=cas),
            parallelism=1).shuffle_grouping("spout")
        cluster = AsyncLocalCluster()
        await cluster.submit("demo", cfg, tb.build())
        ui = await UIServer(cluster, port=0).start()
        try:
            st, body = await _http_get(
                ui.port, "/api/v1/topology/demo/cascade")
            assert st == 200
            assert body["topology"] == "demo"
            (b,) = body["bolts"]
            assert b["component"] == "inference-bolt"
            assert [r["model"] for r in b["tiers"]] == \
                ["lenet5", "resnet20"]
            assert b["tiers"][0]["threshold"] == pytest.approx(0.5)
            assert "escalation_rate" in b
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)
