"""Unit tests for the bench harness's measurement protocol — the code the
round artifacts (BENCH_*_r0N.json) depend on. The protocol logic (backlog
guard, calibration bail-out, stage bookkeeping) must hold whatever the
host's load, so it is tested synthetically here, without a device.
"""

import pathlib
import sys

import numpy as np
import pytest

# bench.py lives at the repo root, one level above tests/
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import bench  # noqa: E402


def test_offer_load_paces_and_completes():
    sent_ids = []
    sent, aborted = bench.offer_load(sent_ids.append, rate=2000.0,
                                     seconds=0.25)
    assert not aborted
    assert sent == len(sent_ids)
    # Upper bound only: the pacer must never overshoot the rate. A lower
    # bound would flake on this 1-core host when a scheduler stall spans
    # the end of the window (the catch-up loop can't recover past `end`).
    assert 0 < sent <= 600, sent


def test_offer_load_backlog_guard_trips_on_monotonic_growth():
    """An offered load the 'topology' never drains must abort (round 1
    integrated queueing delay without bound and recorded p50 = 52s)."""
    sent, aborted = bench.offer_load(
        lambda i: None, rate=2000.0, seconds=5.0,
        backlog_fn=lambda sent: sent,  # nothing ever delivered
        guard_checks=4, check_interval=0.05)
    assert aborted
    assert sent < 2000 * 5  # aborted well before the full window


def test_offer_load_guard_tolerates_bounded_backlog():
    """A backlog that stops growing (deadline batch in flight) must NOT
    trip the guard."""
    sent, aborted = bench.offer_load(
        lambda i: None, rate=500.0, seconds=0.4,
        backlog_fn=lambda sent: 10,  # constant small backlog
        guard_checks=3, check_interval=0.05)
    assert not aborted


def test_run_latency_phase_invalid_when_probe_never_drains(monkeypatch):
    """No clean calibration -> the phase reports valid=False rather than
    percentiles from a saturated window."""
    # No real 180s grace window in a unit test: an undrained system stays
    # undrained, so the wait can resolve instantly.
    monkeypatch.setattr(
        bench, "await_outputs",
        lambda size_fn, sent, grace_s=60.0: size_fn() >= sent)
    p50, p99, rate, valid = bench.run_latency_phase(
        produce_nth=lambda i: None,
        out_size_fn=lambda: 0,  # nothing is ever delivered
        reset_hists=lambda: None,
        read_lat=lambda: (123.0, 456.0),
        seconds=0.1)
    assert not valid
    assert rate == 0.0
    assert (p50, p99) == (123.0, 456.0)  # reported but flagged


def test_null_engine_contract():
    from storm_tpu.infer import NullEngine

    eng = NullEngine((28, 28, 1), 10)
    assert eng.input_shape == (28, 28, 1)
    out = eng.predict(np.zeros((7, 28, 28, 1), np.float32))
    assert out.shape == (7, 10)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-5)
    eng.warmup()  # no-op, must not raise


def test_merge_offsets_max_wins():
    from storm_tpu.runtime.tuples import merge_offsets

    dst = {("t", 0): 5}
    merge_offsets(dst, [(("t", 0), 3), (("t", 1), 7), (("t", 0), 9)])
    assert dst == {("t", 0): 9, ("t", 1): 7}


def test_stage_list_matches_operator_histograms():
    """bench.STAGES must reference histograms the operator, the engine's
    queue or the sink actually record — a renamed metric would silently
    drop a stage from the decomposition artifact."""
    import inspect

    from storm_tpu.connectors import sink as sink_mod
    from storm_tpu.infer import continuous as queue_mod
    from storm_tpu.infer import operator as op_mod

    from storm_tpu.runtime.tracing import DEVICE_SUBSTAGES

    source = (inspect.getsource(op_mod) + inspect.getsource(queue_mod)
              + inspect.getsource(sink_mod))
    substage_keys = {key for key, _ in DEVICE_SUBSTAGES}
    for comp, hist, _label in bench.STAGES:
        if hist in substage_keys:
            # Device substages are recorded by iterating the shared
            # DEVICE_SUBSTAGES constant (the same one bench derives its
            # rows from), not by quoted literals.
            assert "DEVICE_SUBSTAGES" in source, \
                f"substage {hist} not recorded via DEVICE_SUBSTAGES"
            continue
        # Histograms are recorded either by their full name or via
        # span(..., "<base>") which appends "_ms" — both as QUOTED string
        # literals; a bare-word match would be satisfied by comments and
        # identifiers, making the check vacuous.
        base = hist[: -len("_ms")]
        quoted = (f'"{hist}"', f"'{hist}'", f'"{base}"', f"'{base}'")
        assert any(q in source for q in quoted), f"stage {hist} not recorded"


def test_offer_load_depth_guard_catches_bursty_saturation():
    """The absolute queue-depth guard: a backlog that OSCILLATES (bursty
    deliveries reset the monotonic-growth streak) but holds above 2.5s of
    offered work must abort — the saturation shape that produced 'valid'
    multi-second percentiles for heavy-payload configs before the fix."""
    calls = {"n": 0}

    def sawtooth_backlog(sent):
        calls["n"] += 1
        # oscillate between 3s and 4s of offered work: growth streak
        # resets every other check, depth stays above the 2.5s bound
        return int(100 * 2.5 * (1.2 + 0.3 * (calls["n"] % 2)))

    sent, aborted = bench.offer_load(
        lambda i: None, rate=100.0, seconds=3.0,
        backlog_fn=sawtooth_backlog,
        guard_checks=12, check_interval=0.05)
    assert aborted
    assert calls["n"] <= 3  # first depth check trips it


def test_offer_load_depth_guard_time_based_at_low_rates():
    """At low rates the bound must stay TIME-based (2.5s of work), not a
    fixed count — 50 queued messages at 2 msg/s is 25s of queueing."""
    sent, aborted = bench.offer_load(
        lambda i: None, rate=4.0, seconds=3.0,
        backlog_fn=lambda sent: 12,  # 3s of work at 4 msg/s
        guard_checks=12, check_interval=0.05)
    assert aborted


def test_repeatable_rows_selection():
    """Interleaved-repeat eligibility (--all --repeats): single-model
    configs only — 'multi' is a run_multi aggregate (run_single would
    KeyError, the bug the first r04 capture hit), demo rows aren't
    configs, and failed first passes don't repeat."""
    matrix = [("lenet5", {}), ("resnet20", {"weights": "int8"}),
              ("multi", {}), ("autoscale", {}), ("resnet50", {})]
    results = [{"value": 1}, {"value": 2}, {"value": 3}, {"value": 4},
               {"config": "resnet50", "error": "boom"}]
    rows = bench._repeatable_rows(matrix, results)
    assert [(i, n) for i, n, _ in rows] == [(0, "lenet5"), (1, "resnet20")]
