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


def _synthetic_trace():
    ms = 1e6
    device = ("/device:TPU:0", [
        ("XLA Modules", [("jit_fwd(1)", 10 * ms, 20 * ms),
                         ("jit_fwd(1)", 40 * ms, 20 * ms),
                         ("jit_other(2)", 70 * ms, 10 * ms)]),
        ("XLA Ops", [("fusion.1", 10 * ms, 8 * ms),
                     ("fusion.2", 16 * ms, 14 * ms),   # overlaps fusion.1
                     ("fusion.1", 40 * ms, 20 * ms),
                     ("copy.3", 70 * ms, 10 * ms)]),
    ])
    host = ("/host:CPU", [("main", [("window", 0.0, 100 * ms),
                                    ("stage_batch", 31 * ms, 8 * ms)])])
    return [host, device]


def test_trace_reduction_on_a_hand_made_trace():
    got = xplane.reduce(_synthetic_trace())
    assert got["window_s"] == pytest.approx(0.100)
    # busy: [10, 30] + [40, 60] + [70, 80] ms
    assert got["busy_s"] == pytest.approx(0.050)
    assert got["devices"] == 1
    assert xplane.module_times(got, "jit_fwd") == pytest.approx([0.02, 0.02])
    assert xplane.module_times(got, "jit_") == \
        pytest.approx([0.02, 0.02, 0.01])
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(0.028)]
    assert got["module_ops"] == {"jit_fwd(1)": ["fusion.1", "fusion.2"],
                                 "jit_other(2)": ["copy.3"]}
    gaps = got["idle_gaps"]
    assert [round(s, 6) for _, s in gaps] == [0.02, 0.01, 0.01, 0.01]
    assert gaps[0][0] == "after jit_other(2)"
    assert sorted(name for name, _ in gaps[1:]) == [
        "after jit_fwd(1)", "after jit_fwd(1)", "after window start"]

    class Run:
        trace = got
    assert spec.plugin("readers", "trace_idle_share").read(Run) == \
        pytest.approx(50.0)
    assert spec.plugin("readers", "trace_module_time").read(
        Run, prefix="jit_fwd") == pytest.approx(20.0)
    Run.trace = {}
    assert spec.plugin("readers", "trace_idle_share").read(Run) is None
    assert xplane.reduce([("/host:CPU", [("main", [("x", 0.0, 5.0)])])]) == {}


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
