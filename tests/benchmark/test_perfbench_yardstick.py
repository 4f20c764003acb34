"""The yardstick's own arithmetic: arrivals, pairing, the trace reduction,
operation counts, and the plain references against the program's models at
toy size. All in this process, on the CPU."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.core import harness, pairing, spec, xplane  # noqa: E402


def test_poisson_schedule_is_the_seeds_and_the_same_work_for_every_seed():
    poisson = spec.plugin("arrivals", "poisson")
    traffic = {"rate": 500, "gaps_seed": 3}
    a = poisson.schedule(traffic, 3000000019, 10.0)
    b = poisson.schedule(traffic, 3000000019, 10.0)
    c = poisson.schedule(traffic, 11, 10.0)
    assert np.array_equal(a, b)
    assert len(a) != len(c) or not np.array_equal(a, c)
    assert np.all(np.diff(a) > 0) and a[-1] < 10.0
    assert abs(len(a) - 5000) < 300 and abs(len(c) - 5000) < 300
    # the same gaps in another order: what a window holds barely moves
    one, other = (np.sort(poisson.gaps(traffic, s, 10.0))
                  for s in (3000000019, 11))
    assert np.array_equal(one, other)


def test_closed_loop_keeps_the_stated_number_outstanding():
    closed = spec.plugin("arrivals", "closed_loop")

    class Gen:
        traffic = {"outstanding": 5}
        appended, answered = 0, 0

        polls = 0

        def done(self):
            self.polls += 1
            return self.polls > 3

        def landed(self):
            return self.answered

        def append(self):
            self.appended += 1

    gen = Gen()
    assert closed.schedule(gen.traffic, 1, 10.0) is None
    closed.run(gen)
    assert gen.appended == 5
    gen.polls, gen.answered = 0, 2
    closed.run(gen)
    assert gen.appended == 7


def test_pairing_out_of_order_and_an_unmatched_output():
    ref = np.array([[0.7, 0.2, 0.1], [0.1, 0.2, 0.7], [0.3, 0.4, 0.3]])
    # requests: A row 0 due 0.0, B row 1 due 1.0, C row 0 due 2.0
    req_due, req_row = [0.0, 1.0, 2.0], [0, 1, 0]
    outputs = np.array([[0.1, 0.21, 0.69],    # row 1, at 1.5
                        [0.71, 0.19, 0.1],    # row 0, at 2.6 (answers C)
                        [0.69, 0.2, 0.11],    # row 0, at 2.5 (answers A)
                        [0.5, 0.0, 0.5],      # nobody's
                        [0.3, 0.4, 0.3]])     # row 2: no request left
    out_ts = [1.5, 2.6, 2.5, 2.7, 2.8]
    rows, err = pairing.match_rows(outputs, ref, tol=0.03)
    assert rows.tolist() == [1, 0, 0, -1, 2]
    # sqrt(2e-4) from a row of length sqrt(0.54)
    assert err[0] == pytest.approx(0.019245) and err[3] > 0.03
    delivered, unclaimed = pairing.pair_latencies(req_due, req_row, out_ts,
                                                  rows)
    assert (delivered - np.asarray(req_due)).tolist() == \
        pytest.approx([2.5, 0.5, 0.6])
    assert unclaimed == 1
    # an answer that never came stays NaN
    delivered, _ = pairing.pair_latencies(req_due, req_row, out_ts[:1],
                                          rows[:1])
    assert np.isnan(delivered).tolist() == [True, False, True]
    # rows 0 and 2: sqrt(0.24) apart, the longer sqrt(0.54) long
    assert pairing.row_separation(ref) == pytest.approx(2 / 3)
    assert pairing.quantile([1.0, 2.0, 3.0, 4.0], 0.5) == 2.5


def test_farthest_rows_spreads_the_pool():
    rows = np.array([[0.0, 1.0], [0.01, 0.99], [1.0, 0.0], [0.5, 0.5],
                     [0.02, 0.98]])
    assert sorted(harness.farthest_rows(rows, 3).tolist()) == [0, 2, 3]


MS = 1e6  # a trace counts nanoseconds


def _synthetic_trace():
    """Four whole executions: the first and the last hold as many operations
    as the executions of their programs between them."""
    device = ("/device:TPU:0", [
        ("XLA Modules", [("jit_fwd(1)", 10 * MS, 20 * MS),
                         ("jit_other(2)", 40 * MS, 10 * MS),
                         ("jit_fwd(1)", 55 * MS, 20 * MS),
                         ("jit_other(2)", 80 * MS, 10 * MS)]),
        ("XLA Ops", [("fusion.1", 10 * MS, 8 * MS),
                     ("fusion.2", 16 * MS, 14 * MS),   # overlaps fusion.1
                     ("copy.3", 40 * MS, 10 * MS),
                     ("fusion.1", 55 * MS, 8 * MS),
                     ("fusion.2", 63 * MS, 12 * MS),
                     ("copy.3", 80 * MS, 10 * MS)]),
    ])
    host = ("/host:CPU", [("main", [("window", 0.0, 100 * MS),
                                    ("stage_batch", 31 * MS, 8 * MS)])])
    return [host, device]


def _cut_trace(whole: int):
    """What the profiler writes of a device that is never idle: the
    execution running when the trace starts and the one running when it
    stops are events of what is left of them, one operation where a whole
    execution holds two; ``whole`` executions lie between."""
    mods = [("jit_fwd(1)", 0.0, 8 * MS)]
    ops = [("fusion.2", 0.0, 8 * MS)]
    at = 10.0
    for _ in range(whole):
        mods.append(("jit_fwd(1)", at * MS, 20 * MS))
        ops += [("fusion.1", at * MS, 8 * MS),
                ("fusion.2", (at + 8) * MS, 12 * MS)]
        at += 22.0
    mods.append(("jit_fwd(1)", at * MS, 6 * MS))
    ops.append(("fusion.1", at * MS, 6 * MS))
    return [("/device:TPU:0", [("XLA Modules", mods), ("XLA Ops", ops)])]


def test_trace_reduction_on_a_hand_made_trace():
    got = xplane.reduce(_synthetic_trace())
    assert got["window_s"] == pytest.approx(0.100)
    # busy: [10, 30] + [40, 50] + [55, 75] + [80, 90] ms
    assert got["busy_s"] == pytest.approx(0.060)
    assert got["devices"] == 1
    assert xplane.module_times(got, "jit_fwd") == pytest.approx([0.02, 0.02])
    assert xplane.module_times(got, "jit_") == \
        pytest.approx([0.02, 0.02, 0.01, 0.01])
    assert got["cut_modules"] == {}
    assert got["device_ops"][0] == ["fusion.2", pytest.approx(0.026)]
    assert got["module_ops"] == {"jit_fwd(1)": ["fusion.1", "fusion.2"],
                                 "jit_other(2)": ["copy.3"]}
    gaps = got["idle_gaps"]
    assert [round(s, 6) for _, s in gaps] == [0.01, 0.01, 0.01, 0.005, 0.005]
    assert sorted(name for name, _ in gaps) == [
        "after jit_fwd(1)", "after jit_fwd(1)", "after jit_other(2)",
        "after jit_other(2)", "after window start"]

    class Run:
        trace = got
    assert spec.plugin("readers", "trace_idle_share").read(Run) == \
        pytest.approx(40.0)
    assert spec.plugin("readers", "trace_module_time").read(
        Run, prefix="jit_fwd") == pytest.approx(20.0)
    Run.trace = {}
    assert spec.plugin("readers", "trace_idle_share").read(Run) is None
    assert xplane.reduce([("/host:CPU", [("main", [("x", 0.0, 5.0)])])]) == {}


@pytest.mark.parametrize("whole", [3, 2, 1, 0])
def test_program_times_count_whole_executions_only(whole):
    """The same program, traced with 5, 4, 3 or 2 events of which two are
    cut: the step reads 20 ms whatever the number (the parent read the
    span over the events: 14.8, 14.5, 14.0, 7.0), busy time and the idle
    gaps still see every event, and a trace with nothing between its two
    cut executions, or one execution that nothing shows to be whole, gives
    nothing to read."""
    got = xplane.reduce(_cut_trace(whole))
    span = 10 + 22 * whole + 6
    assert got["window_s"] == pytest.approx(span / 1e3)
    assert got["busy_s"] == pytest.approx((8 + 20 * whole + 6) / 1e3)
    assert len(got["idle_gaps"]) == whole + 1
    assert sum(s for _, s in got["idle_gaps"]) == \
        pytest.approx(2 * (whole + 1) / 1e3)
    assert got["device_ops"][0][0] == "fusion.2"
    # with one execution between them the two cut ones are told by it; with
    # none, nothing shows either whole
    assert got["cut_modules"] == {"jit_fwd(1)": 2}
    run = harness.Run({"name": "c"}, spec.config("vit_g14"), {}, 0, 1.0)
    run.device = {"kind": "TPU v5 lite"}
    run.trace = got
    step = spec.plugin("readers", "trace_module_time").read(
        run, prefix="jit_fwd")
    share = spec.plugin("readers", "roofline_share").read(
        run, prefix="jit_fwd")
    if whole:
        assert xplane.module_times(got, "jit_fwd") == \
            pytest.approx([0.02] * whole)
        assert step == pytest.approx(20.0)
        assert got["module_ops"] == {"jit_fwd(1)": ["fusion.1", "fusion.2"]}
    else:
        assert got["modules"] == {} and step is None and share is None


def test_an_edge_execution_with_all_its_operations_is_whole():
    """A device that idles at the trace's ends: the first and last events
    are whole executions and count, where another of their program shows
    how many operations that is; one seen at an edge alone does not."""
    planes = _cut_trace(2)
    mods, ops = planes[0][1][0][1], planes[0][1][1][1]
    del mods[0], ops[0], mods[-1], ops[-1]   # the two cut ones go
    mods.append(("jit_fwd(1)", 54 * MS, 20 * MS))
    ops += [("fusion.1", 54 * MS, 8 * MS), ("fusion.2", 62 * MS, 12 * MS)]
    got = xplane.reduce(planes)
    assert xplane.module_times(got, "jit_fwd") == pytest.approx([0.02] * 3)
    assert got["cut_modules"] == {}
    mods.append(("jit_late(3)", 76 * MS, 4 * MS))
    ops.append(("copy.9", 76 * MS, 4 * MS))
    got = xplane.reduce(planes)
    assert xplane.module_times(got, "jit_") == pytest.approx([0.02] * 3)
    assert got["cut_modules"] == {"jit_late(3)": 1}


def _staircase(phase: float, cadence=0.909, rows=256, width=0.05,
               seconds=20.0, stall_at=None, stall=0.0):
    """Delivery times inside ``[0, seconds)`` of landings of ``rows`` spread
    evenly over ``width`` seconds, one every ``cadence`` seconds from
    ``phase - cadence`` on; a stall delays every landing after it."""
    times, start = [], phase - 2 * cadence
    while start < seconds:
        if stall_at is not None and start >= stall_at:
            start, stall_at = start + stall, None
        times.append(start + np.arange(rows) * (width / rows))
        start += cadence
    times = np.concatenate(times)
    return np.sort(times[(times >= 0) & (times < seconds)])


def _rate(times, seconds=20.0):
    run = harness.Run({"name": "c"}, spec.config("vit_tiny"), {}, 0, seconds)
    run.delivery_times, run.delivered_in_window = times, len(times)
    return spec.plugin("readers", "window_rate").read(run), run


@pytest.mark.parametrize("phase", [0.0, 0.02, 0.3, 0.6, 0.9])
def test_rate_between_landings_is_rows_over_cadence_at_any_phase(phase):
    """Count over seconds reads 268.8 or 281.6 (21 or 22 landings over 20 s)
    by where the window's edges fall, and 275.2 where an edge cuts a landing
    in two; between whole landings the same staircase reads rows over
    cadence at every phase."""
    times = _staircase(phase)
    rate, run = _rate(times)
    assert rate == pytest.approx(256 / 0.909, rel=1e-3)
    note = run.notes["window_rate"]
    assert note["read"] == "between landings"
    assert note["seconds_between"] > 18.0
    assert note["seconds_between"] / 0.909 == pytest.approx(
        round(note["seconds_between"] / 0.909), abs=1e-6)
    assert note["landings"] in (21, 22)


def test_count_over_seconds_moves_in_steps_where_the_new_reading_does_not():
    # a landing every 930 ms: 21 or 22 of them in 20 s
    stairs = [_staircase(p, cadence=0.93) for p in (0.0, 0.02, 0.3, 0.6, 0.9)]
    old = [len(times) / 20.0 for times in stairs]
    new = [_rate(times)[0] for times in stairs]
    assert max(old) / min(old) - 1 > 0.04   # a landing more or less: 4.5 %
    assert max(new) / min(new) - 1 < 1e-3


def test_rate_between_landings_pays_for_a_stall():
    """1.5 s in which nothing lands, inside the window: the time is in the
    denominator, so the rate is lower by the stall's share of the time
    between the first and the last landing."""
    rate, run = _rate(_staircase(0.3, stall_at=8.0, stall=1.5))
    between = run.notes["window_rate"]["seconds_between"]
    assert run.notes["window_rate"]["read"] == "between landings"
    assert rate == pytest.approx(256 / 0.909 * (1 - 1.5 / between), rel=1e-3)
    assert 0.07 < 1.5 / between < 0.085


@pytest.mark.parametrize("case", ["two_landings", "evenly_spread", "empty"])
def test_rate_falls_back_to_count_over_seconds(case):
    times = {"two_landings": _staircase(0.5, cadence=9.0),
             "evenly_spread": np.arange(0.0, 20.0, 0.01),
             "empty": np.zeros(0)}[case]
    rate, run = _rate(times)
    assert rate == len(times) / 20.0
    assert run.notes["window_rate"]["read"] == "count over seconds"


sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tools"))

FAR = [84.1, 84.3, 83.9, 84.6, 84.2, 88.0]       # one far run
EVEN = [84.0, 84.4, 83.8, 84.8, 84.2, 85.2]
BOUNDS = {
    # the far run is left out: 5 x the mean of 0.45 / 84.2 and 0.7 / 84.2
    # = 0.0341, where five times the quartile spread with it in is 0.084
    "one_far_run_widens_nothing": ([FAR, EVEN], 0.035),
    # 5 x 0.7 / 84.2 = 0.0416
    "a_set_alone": ([EVEN], 0.042),
    # two far runs on one side: one is left out, the other stretches the
    # quartiles to (101.65 - 100.05) / 100.2; 5 x 0.01597 = 0.0798 is more
    # than twice the trimmed range, 2 x 3.0 / 100.25
    "two_far_runs_do": ([[100.0, 100.1, 100.2, 100.3, 103.0, 103.2]], 0.08),
    "equal_readings_stand_on_the_floor": ([[5.0] * 6, [5.0] * 6], 0.01),
    "under_a_fifth_of_a_percent_buys_nothing": (
        [[10000, 10001, 10002, 10003, 10004, 10005]], 0.01),
    "no_bound_over_a_tenth": ([[100, 104, 108, 112, 116, 120]], 0.1),
    # one set that spreads and one that does not: five times their mean
    # (1 % and 0) is 0.025, and twice the wide one's trimmed range is more
    "twice_the_trimmed_range": ([[99.0, 100.0, 100.0, 100.0, 101.0, 105.0],
                                 [100.0] * 6], 0.04),
}


def test_the_three_readings_of_a_set():
    import spread

    # statistics.quantiles: q1 = 84.05, q3 = 85.45 over the median 84.25
    assert spread.quartile_spread(FAR) == pytest.approx(1.4 / 84.25)
    assert sorted(spread.without_farthest(FAR)) == \
        [83.9, 84.1, 84.2, 84.3, 84.6]
    # of the five kept: q1 = 84.0, q3 = 84.45 over their median 84.2
    assert spread.quartile_spread(spread.without_farthest(FAR)) == \
        pytest.approx(0.45 / 84.2)
    assert spread.trimmed_range(FAR) == pytest.approx(0.7 / 84.25)
    assert spread.without_farthest([1.0, 2.0, 4.0]) == [2.0, 1.0, 4.0]
    assert spread.round_up(0.0437) == 0.044 and spread.round_up(0.05) == 0.05


@pytest.mark.parametrize("case", BOUNDS)
def test_the_bound_rule_on_hand_numbers(case):
    import spread

    sets, expected = BOUNDS[case]
    assert spread.bound(sets) == expected


def test_vit_g14_operations_match_a_hand_count():
    ops = spec.plugin("ops", "vit")
    sizes = spec.config("vit_g14")["published"]
    # per block: 2*257*(4*1408^2 + 2*1408*6144) + 4*257^2*1408
    #   = 12,968,919,040 + 371,987,968 = 13,340,907,008; forty of them
    # patches 256*588*1408*2 = 423,886,848; head 1408*1000*2 = 2,816,000
    assert ops.flops_per_row(sizes) == 534_062_983_168
    # the count jax.eval_shape gives for the program's own parameter tree
    assert ops.parameters(sizes) == 1_012_611_432
    got = ops.counts(sizes, rows=512, steps=2, bytes_per_value=2)
    assert got["flops"] == 512 * 534_062_983_168
    assert got["bytes"] == 2 * 1_012_611_432 * 2 + 512 * (150_528 * 2 + 4000)


def test_roofline_share_on_hand_numbers():
    reader = spec.plugin("readers", "roofline_share")
    run = harness.Run({"name": "c"}, spec.config("vit_g14"), {}, 0, 1.0)
    run.device = {"kind": "TPU v5 lite"}
    run.trace = {
        "modules": {"jit_fwd(7)": [1.0, 1.0], "jit_fwd(8)": [0.3],
                    "jit_other(9)": [5.0]},
        "module_ops": {
            "jit_fwd(7)": ["%fusion.1 = bf16[256,257,1408]{2,0,1} fusion(...)",
                           "%fusion.2 = bf16[256,257,6144]{2,0,1} fusion()"],
            "jit_fwd(8)": ["%f = (f32[32,257], bf16[32,257,1408]{2,1,0}) x"]}}
    least = (2 * 256 + 32) * 534_062_983_168 / 197e12
    assert reader.read(run, prefix="jit_fwd") == \
        pytest.approx(100 * least / 2.3)
    assert run.roofline_bound == "compute"
    run.trace["module_ops"]["jit_fwd(8)"] = ["%copy = f32[8]{0} copy()"]
    assert reader.read(run, prefix="jit_fwd") is None
    run.trace = {}
    assert reader.read(run, prefix="jit_fwd") is None


@pytest.mark.parametrize("config_name", ["vit_tiny"])
def test_plain_reference_agrees_with_the_programs_model(config_name):
    """The benchmark's own float32 forward against the program's model code
    in float32 on the same parameters: they are two writings of one
    mathematics, so they agree to float32 rounding (1e-5 of a probability);
    and bfloat16 serving stays inside the configuration's tolerance."""
    import jax
    import jax.numpy as jnp

    from storm_tpu.models.registry import build_model

    config = spec.config(config_name)
    runner = spec.plugin("runners", config["runner"])
    reference = spec.plugin("references", config["reference"])
    params, state = runner.parameters(config, 3000000019)
    model = build_model(config["model"]["name"],
                        num_classes=config["model"]["num_classes"],
                        input_shape=tuple(config["model"]["input_shape"]))
    raw = spec.plugin("inputs", config["inputs"]["kind"]).make(
        8, tuple(config["model"]["input_shape"]), 5)
    x = np.round(raw * 0.05, 5).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        mine = np.asarray(reference.forward(config["published"], params,
                                            state, x))
        theirs = np.asarray(jax.nn.softmax(
            model.apply(params, state, x, train=False)[0], axis=-1))
    assert mine.shape == (8, config["model"]["num_classes"])
    assert np.abs(mine - theirs).max() < 1e-5
    served = np.asarray(jax.nn.softmax(model.apply(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), params), state,
        x.astype(jnp.bfloat16), train=False)[0].astype(jnp.float32), axis=-1))
    apart = np.sqrt(((served - mine) ** 2).sum(1) / (mine ** 2).sum(1))
    assert apart.max() < config["tolerance"]["relative_distance"]


def test_json_payload_parses_back_to_the_same_values():
    import json

    x = np.round(np.random.RandomState(1).randn(4, 4, 3) * 0.01,
                 5).astype(np.float32)
    text = spec.plugin("payloads", "json").encode(x, 5)
    back = np.asarray(json.loads(text)["instances"], np.float32)
    assert back.shape == (1, 4, 4, 3) and np.array_equal(back[0], x)
    assert len(text) < 12 * x.size
    frame = spec.plugin("payloads", "arrow_tensor").encode(x, 5)
    assert frame[0] == 0xFF and len(frame) >= x.nbytes
