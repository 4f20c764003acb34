"""The benchmark's Solar Open 2 files (PR 52): the configuration against the
catalog row it is cut from and the program's own parameter tree,
``ops/solar_open2.py`` against the issue's table counted by hand, every
per-layer metric that lists the new cell over a trace of its shapes made by
hand (and silent on Kimi-Linear's and Nemotron's shapes), the new entries in
``BENCHMARK.json`` (found by name: neither how many cells there are nor which
is last is this file's business), the windows' kind, and rehearsals of
``solar_open2_tiny.tokens_backlog`` and of the two tools on the CPU."""

import json
import math
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.core import harness, spec, xplane  # noqa: E402
from benchmarks.tools import check_line  # noqa: E402

CELL = "solar_open2_250b.tokens_backlog"
BENCH = spec.benchmark()
CONFIG = spec.config("solar_open2_250b")
SIZES = CONFIG["published"]
OPS = spec.plugin("ops", "solar_open2")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMETERS = 3_308_353_344
ROWS = 8  # windows a step
TOKENS = ROWS * 4096
# every width of the row: none may differ from the published value
WIDTHS = {"hidden_size": 4096, "intermediate_size": 10240,
          "moe_intermediate_size": 1280, "num_attention_heads": 64,
          "num_key_value_heads": 8, "head_dim": 128,
          "num_experts_per_tok": 8, "n_shared_experts": 1,
          "routed_scaling_factor": 1, "rms_norm_eps": 1e-05,
          "first_k_dense_replace": 0}
SHARED = {"parse_ms_per_record", "batch_size_mean", "model_step_ms",
          "model_roofline_share", "egress_ms_per_record", "device_idle_share",
          "cut_hold_mean_ms", "step_named_share", "step_gap_max_ms",
          "mixer_elementwise_ms", "projections_ms", "moe_routing_ms",
          "expert_tokens_max_over_mean", "expert_assignments_held_share"}
NEW = {"solar_kda_scan_ms", "solar_kda_scan_roofline_share",
       "solar_gqa_attention_ms", "solar_gqa_attention_roofline_share",
       "solar_expert_matmul_ms", "solar_expert_matmul_roofline_share",
       "solar_expert_combine_ms"}


def _entry(group, name):
    (found,) = [e for e in BENCH[group] if e["name"] == name]
    return found


def test_configuration_states_the_cut_and_keeps_every_width():
    held = SIZES["held"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    # the top level is the configuration as run; ``published`` as published
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (4, 40, 24576)
    assert (SIZES["num_hidden_layers"], SIZES["n_routed_experts"],
            SIZES["vocab_size"]) == (48, 320, 196608)
    assert (held["num_hidden_layers"], held["n_routed_experts"],
            held["vocab_size"], held["chips_per_layer"], held["first_expert"],
            held["sequence_length"], held["rows_per_step"],
            held["kda_chunk"]) == (4, 40, 24576, 8, 0, 4096, ROWS, 64)
    for key, value in WIDTHS.items():
        assert CONFIG[key] == SIZES[key] == value, key
    for key, value in SIZES.items():
        if key not in CONFIG["reduced"] and key != "held":
            assert CONFIG[key] == value, key
    assert SIZES["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert SIZES["gqa_layers"] == list(range(0, 48, 4))
    assert (SIZES["use_rope"], SIZES["use_gqa_gate"],
            SIZES["kda_allow_neg_eigval"], SIZES["kda_use_full_proj"]) == (
        False, True, True, False)
    assert "Eight chips share each layer" in CONFIG["deployment"]
    assert "experts 0-39 of 320" in CONFIG["deployment"]
    assert CONFIG["model"] == {"name": "solar_open2_250b",
                               "input_shape": [4096], "num_classes": 24576,
                               "dtype": "bfloat16"}
    # the floors of the model-configs guide: a whole period (no layer is
    # dense, so four layers), eight experts or more, an eighth of the
    # vocabulary
    assert OPS._layers(SIZES) == (1, 3)
    assert held["n_routed_experts"] >= 8
    assert held["vocab_size"] * 8 == SIZES["vocab_size"]
    assert held["n_routed_experts"] * held["chips_per_layer"] \
        == SIZES["n_routed_experts"]
    for key in ("gqa_gate", "gqa", "kda_projections", "decay", "step",
                "short_convolution", "output_gate", "router",
                "intermediate_size", "weights", "inputs", "ids", "tiles",
                "stream"):
        assert CONFIG["assumed"][key], key
    assert CONFIG["on_device"]["parameters"] == PARAMETERS
    assert CONFIG["on_device"]["parameters_bytes"] == 2 * PARAMETERS
    assert CONFIG["on_device"]["parameters_float32_at_load_bytes"] == 0
    assert CONFIG["inputs"] == {"kind": "solar_open2_tokens", "decimals": 0,
                                "candidates": 32}
    assert 0 < CONFIG["tolerance"]["relative_distance"] < 0.2
    assert "float8" in CONFIG["tolerance"]["why"]
    for key in ("delivery", "malformed_records", "offsets", "experts"):
        assert CONFIG["guarantees"][key], key
    entry = _entry("configs", "solar_open2_250b")
    assert entry["file"] == "benchmarks/configs/solar_open2_250b.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] and len(entry["why"]) <= 200


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_in_the_file():
    rows = [json.loads(line) for line in open(CATALOG)]
    (row,) = [r for r in rows if r["name"] == "Solar-Open2-250B"]
    assert CONFIG["source"] == row["source_url"]
    assert row["config"]["model_type"] == "solar_open2"
    for key, value in row["config"].items():
        assert SIZES[key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


def test_ops_count_the_issues_table_by_hand():
    """One token through each kind of layer, a parameter at a time."""
    d, w = 4096, 64 * 128
    kda_mixer = (4 * d * w + 2 * (d * 128 + 128 * w) + d * 64  # products
                 + 3 * 4 * w + 64 + w + 128)  # convs, A_log, dt_bias, norm
    assert 4 * d * w == 4 * 33_554_432 and d * 128 + 128 * w == 1_572_864
    assert 3 * 4 * w + 64 + w + 128 == 106_688
    assert kda_mixer == 137_732_288
    gqa_mixer = 3 * d * w + 2 * d * 8 * 128
    assert gqa_mixer == 109_051_904 == OPS.gqa_projection_parameters(SIZES)
    assert OPS.kda_projection_parameters(SIZES) == kda_mixer - 106_688
    expert = 3 * d * 1280
    assert expert == 15_728_640
    expert_layer = 40 * expert + expert + d * 320 + 320
    assert expert_layer == 646_185_280
    layers = gqa_mixer + 3 * kda_mixer + 4 * (expert_layer + 2 * d)
    assert layers == 3_107_022_656
    ends = 2 * 24576 * d + d
    assert ends == 201_330_688
    assert layers + ends == PARAMETERS == OPS.parameters(SIZES)
    # the published size: the shared expert's width is read right
    whole = 12 * gqa_mixer + 36 * kda_mixer + 48 * (
        321 * expert + d * 320 + 320 + 2 * d) + 2 * 196608 * d + d
    assert 249e9 < whole < 251e9
    parts = OPS.kernels(SIZES, rows=ROWS, bytes_per_value=2)
    tokens = ROWS * 4096
    # per token and head: two tables of 32 x 128, the triangle applied to
    # 128 + 128 columns, three products with the 128 x 128 state, 32 x 128
    macs = 64 * (32 * 128 * 2 + 32 * 256 + 3 * 128 * 128 + 32 * 128)
    assert parts["kda_scan"]["flops"] == 2 * 3 * tokens * macs
    assert parts["kda_scan"]["bytes"] == 3 * tokens * (
        4 * w * 2 + 4 * w + 4 * 64)
    # a query meets 2048.5 keys, 128 + 128 multiply-adds a pair and head
    assert parts["gqa_attention"]["flops"] == \
        2 * tokens * 64 * 256 * 2048.5
    assert parts["gqa_attention"]["bytes"] == tokens * 2 * 72 * 128 * 2
    # an assignment a token and layer is held: 8 * 40 / 320
    assert parts["expert_matmul"]["flops"] == 2 * 4 * tokens * expert
    assert parts["expert_matmul"]["bytes"] == \
        4 * 40 * expert * 2 + 4 * tokens * d * 6
    counted = OPS.kernels(SIZES, ROWS, 2, assignments=1000)["expert_matmul"]
    assert counted["flops"] == 2 * 1000 * expert
    per_token = 2 * (3 * (kda_mixer - 106_688) + gqa_mixer
                     + 4 * (d * 320 + expert))
    row = 4096 * per_token + sum(
        k["flops"] for k in OPS.kernels(SIZES, 1, 2).values()) + 2 * d * 24576
    assert OPS.flops_per_row(SIZES) == row
    assert 5.7e12 < row < 5.8e12
    got = OPS.counts(SIZES, rows=ROWS, steps=1, bytes_per_value=2)
    assert got["flops"] == ROWS * row
    assert got["bytes"] == 2 * PARAMETERS + ROWS * 4 * (4096 + 24576)


def test_ops_parameters_are_the_programs():
    import jax

    from storm_tpu.models.registry import build_model

    for name, count in (("solar_open2_250b", PARAMETERS),
                        ("solar_open2_tiny", None)):
        model = build_model(name)
        params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        held = sum(x.size for x in jax.tree.leaves(params))
        sizes = spec.config(name)["published"]
        assert OPS.parameters(sizes) == held, name
        assert count in (None, held)
        assert model.max_rows == sizes["held"]["rows_per_step"]


def test_rows_per_step_reads_the_window_shape():
    """The window and the stream are both 4,096 wide here: only a shape of
    three numbers counts, the first of them the rows."""
    names = ["%fusion.1 = bf16[8,4096,4096]{2,1,0} fusion(bf16[8,4096,4096])",
             "%fusion.2 = bf16[8,64,4096,4096]{3,2,1,0} fusion()",
             "%fusion.3 = f32[32768,320]{1,0} fusion()"]
    assert OPS.rows_per_step(names, SIZES) == 8
    assert OPS.rows_per_step(names[1:], SIZES) is None


# ---- every listed metric over a trace of this cell's shapes ------------------

MS = 1e6  # nanoseconds
DEV = "/device:TPU:0"


def _loop(number, carried):
    """A ``while`` as a trace names it: its tuple type, then its operand's."""
    return (f"%while.{number} = ({carried}) while(({carried}) %tuple.3), "
            "condition=%c, body=%b")


# one step's top-level operations, the loops as the v5e compiler names them
# (a compile for the described chip at 8 windows, layouts dropped)
B = ROWS
HELD = TOKENS * 8  # assignments a layer, and the tiles' worst case beside them
STREAM = f"%fusion.9 = bf16[{B},4096,4096]{{2,1,0}} fusion(%p), kind=kOutput"
GATE = f"%fusion.7 = bf16[{B},4096,8192]{{2,1,0}} fusion(%a, %g), kind=kLoop"
ATTN = _loop(59, f"s32[], bf16[{B},64,4096,128], s32[4], "
             f"bf16[{B},64,4096,128], bf16[{B},8,4096,128], "
             f"bf16[{B},8,4096,128], s32[], s32[1]")
BODY = "%custom-call.2 = bf16[1,64,4096,128] custom-call(%q, %k, %v)"
TABLES = _loop(60, f"s32[], bf16[64,{B},64,64,128], f32[64,{B},64,64,128], "
               f"bf16[64,{B},64,64,128], bf16[64,{B},64,64,128], "
               f"bf16[64,{B},64,64,64], f32[{B},64,64,128], "
               f"bf16[{B},4096,8192], bf16[{B},4096,8192], "
               f"bf16[{B},4096,8192], f32[{B},4096,8192], f32[{B},64,64,64], "
               "s32[]")
CHAIN = _loop(71, f"s32[], f32[{B},64,128,128], bf16[64,{B},64,64,128], "
              f"bf16[64,{B},64,64,128], f32[64,{B},64,64,128], "
              f"bf16[64,{B},64,64,128], bf16[64,{B},64,64,128], "
              f"bf16[64,{B},64,64,64], f32[64,{B},64,128], s32[], s32[]")
SORT = f"%sort.8 = (f32[{TOKENS},320], s32[{TOKENS},320]) sort(%a, %i)"
INNER = _loop(74, f"s32[], s32[41], s32[41], s32[{HELD}], s32[], s32[]")
EXP = _loop(63, f"s32[], bf16[{HELD + 40 * 512 + 1},4096], s32[], s32[40], "
            f"s32[40], s32[40], s32[40], s32[{HELD + 512}], "
            f"bf16[{TOKENS},4096], bf16[40,1280,4096], bf16[40,4096,1280], "
            f"bf16[40,4096,1280], f32[{HELD + 512}], s32[]")
COMB = _loop(64, f"s32[], f32[{TOKENS},4096], s32[], s32[64], s32[64], "
             f"s32[64], s32[64], s32[{HELD + 512}], "
             f"bf16[{HELD + 40 * 512 + 1},4096], s32[{HELD + 512}], s32[]")
ZERO = (f"%broadcast.70 = f32[{TOKENS},4096]{{1,0}} "
        "broadcast(f32[] %constant.3)")
STEP_OPS = [
    (STREAM, "jit(fwd)/mix.elementwise/proj/dot_general", 0, 220),
    (ATTN, None, 220, 22),
    (BODY, "jit(fwd)/mix.elementwise/mix.attention/while/body/pallas_call",
     222, 2),
    (GATE, "jit(fwd)/mix.elementwise/mul", 242, 56),
    (TABLES, "jit(fwd)/mix.elementwise/mix.kda_tables/while", 298, 98),
    (CHAIN, "jit(fwd)/mix.elementwise/mix.kda_scan/while", 396, 24),
    (SORT, "jit(fwd)/moe.route/jit(sort)/sort", 420, 24),
    (INNER, "jit(fwd)/moe.route/jit(searchsorted)/vmap()/while", 444, 2),
    (EXP, "jit(fwd)/moe.experts/while", 446, 44),
    (COMB, "jit(fwd)/moe.combine/while", 490, 24),
    (ZERO, None, 514, 6),
]
STEP_MS = 520.0
HELD_A_STEP = 4 * ROWS * 4096 * 8 * 40 // 320  # the expected assignments held
WANT = {"model_step_ms": STEP_MS, "solar_kda_scan_ms": 122.0,
        "solar_gqa_attention_ms": 22.0, "solar_expert_matmul_ms": 44.0,
        "solar_expert_combine_ms": 24.0, "moe_routing_ms": 26.0,
        "mixer_elementwise_ms": 56.0, "projections_ms": 220.0,
        "step_named_share": 100.0 * 514 / 520,
        # the two cut executions lack their first 242 ms of operations
        "device_idle_share": 100.0 * 2 * 242 / (8 * 520),
        "batch_size_mean": 8.0, "cut_hold_mean_ms": 0.0,
        "expert_assignments_held_share": 12.5,
        "expert_tokens_max_over_mean": 1.5,
        "parse_ms_per_record": 0.05,
        "egress_ms_per_record": 2.5, "step_gap_max_ms": STEP_MS}


def _traced_run(steps=8):
    mods, ops, log = [], [], []
    for i in range(steps):  # the first and the last are cut: fewer operations
        at = i * STEP_MS
        cut = i in (0, steps - 1)
        mods.append(("jit_fwd(5)", at * MS, STEP_MS * MS))
        ops += [(n, (at + s) * MS, d * MS) for n, _o, s, d in
                STEP_OPS[3 * cut:]]
    planes = [(DEV, [("XLA Modules", mods), ("XLA Ops", ops)])]
    cell = spec.cell(BENCH, CELL)
    run = harness.Run(cell, CONFIG, {}, 0, 14.0)
    run.device = {"kind": "TPU v5 lite"}
    run.trace = xplane.reduce(planes)
    run._device_planes = planes
    run._trace_meta = {"op_names": {DEV: {n: o for n, o, _s, _d in STEP_OPS
                                          if o}}, "start_s": None}
    off = 7000.0  # the device's zero on the host's clock
    for n in range(34):  # steps 26.. are the traced executions
        ready = off + STEP_MS / 1e3 * (n - 26 + 1) + 2e-4
        log.append({"step": n, "engine": "solar_open2_250b", "padded": ROWS,
                    "rows": ROWS, "sources": 2, "seen": True,
                    "t_first_enq": ready - 1.6, "t_cut": ready - 1.06,
                    "t_staged": ready - 1.05, "t_launched": ready - 1.04,
                    "t_ready": ready, "t_fetched": ready + 0.001,
                    "t_resolved": ready + 0.002})
    run._step_rows = log
    run.delivery_times = [off - 26 * STEP_MS / 1e3, off]
    run.delivered_in_window = ROWS * 26
    hist = lambda count, total: {"count": count, "sum": total}  # noqa: E731
    run.registry_before = {"inference-bolt": {}, "kafka-bolt": {}}
    run.registry_after = {
        "inference-bolt": {
            "decode_ms": hist(208, 208 * 0.05), "batch_size": hist(26, 208.0),
            "encode_ms": hist(208, 208 * 2.0), "cut_hold_ms": hist(26, 0.0),
            "expert_tokens_max_over_mean": hist(104, 156.0),
            "expert_assignments_held": 26 * HELD_A_STEP,
            "expert_assignments_absent": 26 * HELD_A_STEP * 7},
        "kafka-bolt": {"produce_ms": hist(208, 208 * 0.5)}}
    return run


def test_the_new_entries_list_what_reads_here():
    """Found by name. How many cells the benchmark has and which comes last
    is no business of this file's: the next cell must not fail it."""
    cell = spec.cell(BENCH, CELL)
    assert cell in BENCH["workloads"]
    assert cell["chips"] == 1 and cell["traffic"] == "tokens_backlog"
    assert cell["config"] == "solar_open2_250b" and len(cell["why"]) <= 200
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == "solar_open2_250b"] == [CELL]  # no second cell
    assert BENCH["run_seconds"] == 20
    e2e = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end", cell)}
    assert e2e == {"records_per_s", "setup_s"}
    assert _entry("end_to_end", "records_per_s")["bound"] == 0.01
    assert _entry("end_to_end", "setup_s")["bound"] == 0.1
    layer = {m["name"]: m for m in spec.metrics_for(BENCH, "per_layer", cell)}
    assert set(layer) == SHARED | NEW | {"compile_s", "cache_misses"}
    # loops told by other models' shapes are not this cell's to report
    assert not {"kda_scan_ms", "kda_scan_roofline_share", "gqa_attention_ms",
                "gqa_attention_roofline_share", "expert_matmul_ms",
                "expert_matmul_roofline_share", "expert_combine_ms",
                "k2_expert_matmul_ms", "k2_expert_combine_ms",
                "mla_attention_ms"} & set(layer)
    for name in NEW:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["layer"] == "engine and model"
        assert layer[name]["moves"] == "records_per_s"
        assert layer[name]["unit"] == ("%" if name.endswith("_share")
                                       else "ms")
        assert layer[name]["source"] == "device_trace"
        assert spec.metric(name)["reader"] == "trace_ops_time"
    for name in SHARED:
        assert CELL in layer[name]["workloads"]
    # the eleven shared lists minicpm_sala's cell is on, and the experts' three
    sala = spec.cell(BENCH, "minicpm_sala.tokens16k_backlog")
    assert SHARED == {m["name"] for m in spec.metrics_for(
        BENCH, "per_layer", sala) if len(m.get("workloads", [])) > 1} | {
        "moe_routing_ms", "expert_tokens_max_over_mean",
        "expert_assignments_held_share"}
    # the new metrics stand after every metric an earlier PR brought
    names = [m["name"] for m in BENCH["per_layer"]]
    first = min(names.index(n) for n in NEW)
    assert set(names[first:first + len(NEW)]) == NEW
    assert first > names.index("eva_summarised_pairs_share")
    # the mix is the one three cells share, unchanged
    traffic = spec.traffic("tokens_backlog")
    assert (traffic["outstanding"], traffic["pool"], traffic["payload"],
            traffic["arrivals"], traffic["warmup_seconds"],
            traffic["trace_seconds"]) == (
        128, 32, "arrow_tensor", "closed_loop", 4, 6)
    assert traffic["program"] == {"topology.spout_scheme": "raw"}


def test_every_listed_metric_reads_a_number_from_a_trace_of_its_shapes():
    run = _traced_run()
    cell = spec.cell(BENCH, CELL)
    listed = spec.metrics_for(BENCH, "per_layer", cell)
    got = harness.read_metrics(run, [m for m in listed if m["name"]
                                     not in ("compile_s", "cache_misses")])
    assert set(got) == SHARED | NEW
    for name, want in WANT.items():
        assert got[name]["value"] == pytest.approx(want, abs=1e-6), name
    shares = ("model_roofline_share", "solar_kda_scan_roofline_share",
              "solar_gqa_attention_roofline_share",
              "solar_expert_matmul_roofline_share")
    for name in shares:
        assert 0 < got[name]["value"] < 100 and math.isfinite(
            got[name]["value"])
    work = OPS.kernels(SIZES, ROWS, 2, assignments=HELD_A_STEP)
    # KDA's least time is its bytes', the two others' their operations'
    assert got["solar_kda_scan_roofline_share"]["value"] == pytest.approx(
        100 * work["kda_scan"]["bytes"] / 819e9 / 0.122)
    assert work["kda_scan"]["flops"] / 197e12 \
        < work["kda_scan"]["bytes"] / 819e9
    assert got["solar_gqa_attention_roofline_share"]["value"] == \
        pytest.approx(100 * work["gqa_attention"]["flops"] / 197e12 / 0.022)
    assert got["solar_expert_matmul_roofline_share"]["value"] == \
        pytest.approx(100 * work["expert_matmul"]["flops"] / 197e12 / 0.044)
    assert run.notes["kernels"]["expert_matmul"]["rows"] == ROWS
    assert got["model_roofline_share"]["value"] == pytest.approx(
        100 * ROWS * OPS.flops_per_row(SIZES) / 197e12 / 0.520)
    assert run.roofline_bound == "compute"
    # the loops' metrics read the very events the parts' times read
    assert run.notes["part_loops"] == pytest.approx(
        {"mix.attention": 22.0, "mix.kda_tables": 98.0, "mix.kda_scan": 24.0,
         "moe.route": 2.0, "moe.experts": 44.0, "moe.combine": 24.0})
    # the line such a run prints is complete by the driver's own check
    got.update(compile_s={"value": 1.0, "unit": "s"},
               cache_misses={"value": 0.0, "unit": "count"})
    row = {"correct": True, "attempted": 1, "failed": 0, "metrics": got,
           "device": {}}
    assert check_line.problems(row, CELL, traced=True) == []


# loops of the two models that share this one's code, as their own tests
# name them (tests/benchmark/test_perfbench_kimi.py, _nemotron.py), and what
# the compiler makes of Kimi-Linear's at its 32 heads and Nemotron's 2 key heads
OTHERS = [
    "%while.3 = (s32[], bf16[8,32,64,64,64]{4,3,2,1,0}) while(%t), body=%b",
    _loop(3, "s32[], bf16[64,8,32,64,128], f32[64,8,32,64,128], "
          "bf16[64,8,32,64,64], f32[8,32,64,128], bf16[8,4096,4096]"),
    "%while.4 = (s32[], bf16[8,32,4096,192]{3,2,1,0}) while(%t), body=%b",
    "%while.5 = (s32[], bf16[32,2304,1024]{2,1,0}) while(%t), body=%b",
    _loop(41, "s32[], bf16[8,32,4096,128], s32[4], bf16[8,32,4096,128], "
          "bf16[8,2,4096,128], bf16[8,2,4096,128]"),
    _loop(44, "s32[], bf16[229377,2688], bf16[32,2688,1856], "
          "bf16[32,1856,2688]"),
    "%while.6 = (s32[], f32[32768,2304]{1,0}) while(%t), body=%b",
    "%while.7 = (s32[], f32[32768,2688]{1,0}) while(%t), body=%b",
    "%while.8 = (s32[], f32[16384,7168]{1,0}) while(%t), body=%b",
    "%while.9 = (s32[], bf16[4,64,4096,192]{3,2,1,0}) while(%t), body=%b",
]
OWN = {"solar_kda_scan_ms": (TABLES, CHAIN), "solar_gqa_attention_ms": (ATTN,),
       "solar_expert_matmul_ms": (EXP,), "solar_expert_combine_ms": (COMB,)}


@pytest.mark.parametrize("name", sorted(OWN))
def test_a_loops_pattern_finds_its_own_and_no_other_models(name):
    pattern = spec.metric(name)["args"]["pattern"]
    for loop in OWN[name]:
        assert re.search(pattern, loop), loop
    for loop in OTHERS + [INNER, STREAM, GATE, BODY, ZERO] + [
            x for other, loops in OWN.items() if other != name
            for x in loops]:
        assert not re.search(pattern, loop), loop
    share = name.replace("_ms", "_roofline_share")
    if share in NEW:
        assert spec.metric(share)["args"]["pattern"] == pattern
    # and the accepted metrics of the same loops elsewhere stay off this
    # cell's lists, whatever their patterns would match
    for accepted in ("kda_scan_ms", "gqa_attention_ms", "expert_matmul_ms",
                     "expert_combine_ms"):
        assert CELL not in _entry("per_layer", accepted)["workloads"]


@pytest.mark.parametrize("rows", [4, 8, 16])
def test_the_loops_patterns_hold_at_other_rows_a_step(rows):
    """No pattern pins the step's token count: another ``max_rows`` silences
    none of the four (KDA's and attention's carry the rows as a number of
    their own, the experts' weights have none, the combine's sums are told
    by the stream's width)."""
    at = {"solar_kda_scan_ms": f"bf16[64,{rows},64,64,64]",
          "solar_gqa_attention_ms": f"bf16[{rows},8,4096,128]",
          "solar_expert_matmul_ms": "bf16[40,4096,1280]",
          "solar_expert_combine_ms": f"f32[{rows * 4096},4096]"}
    for name, carried in at.items():
        pattern = spec.metric(name)["args"]["pattern"]
        assert re.search(pattern, _loop(7, f"s32[], {carried}, s32[]")), name
        # a fusion of the same shape is no loop
        assert not re.search(pattern, f"%fusion.7 = {carried} fusion(%p)")


def test_a_program_without_the_new_loops_reads_nothing_and_raises_nothing():
    """The parent's programs, or another model's: each of the seven is left
    out of the line."""
    planes = [(DEV, [
        ("XLA Modules", [("jit_fwd(1)", t * MS, 90 * MS)
                         for t in (0, 100, 200)]),
        ("XLA Ops", [(op, t * MS + 1 + i, 8 * MS) for t in (0, 100, 200)
                     for i, op in enumerate(OTHERS)])])]
    run = harness.Run(spec.cell(BENCH, CELL), CONFIG, {}, 0, 1.0)
    run.device = {"kind": "TPU v5 lite"}
    run.trace = xplane.reduce(planes)
    run._device_planes = planes
    run._trace_meta = {"op_names": {}, "start_s": None}
    run.registry_before = run.registry_after = {"inference-bolt": {
        "expert_assignments_held": 5, "expert_assignments_absent": 35}}
    for name in sorted(NEW):
        doc = spec.metric(name)
        assert spec.plugin("readers", doc["reader"]).read(
            run, **doc["args"]) is None, name
    untraced = harness.Run(spec.cell(BENCH, CELL), CONFIG, {}, 0, 1.0)
    for name in sorted(NEW):
        doc = spec.metric(name)
        assert spec.plugin("readers", doc["reader"]).read(
            untraced, **doc["args"]) is None


def test_the_windows_come_from_the_held_slice_and_a_kind_of_their_own():
    """A kind of input a family (PERF.md section 7 item 4 (d)): no two
    configurations are coupled through one kind's look-up by shape. The same
    seed draws what the other kinds draw over a slice of the same size."""
    make = spec.plugin("inputs", "solar_open2_tokens").make
    a, b = make(5, (4096,), 3_000_000_019), make(5, (4096,), 3_000_000_019)
    assert (a == b).all() and a.shape == (5, 4096)
    assert a.min() >= 0 and 24000 < a.max() < 24576
    assert (a == a.round()).all()
    assert not (a == make(5, (4096,), 3_000_000_020)).all()
    assert make(3, (40,), 1).max() < 96
    assert (make(3, (40,), 7) == spec.plugin("inputs", "kimi_k2_tokens").make(
        3, (40,), 7)).all()
    with pytest.raises(ValueError):
        make(1, (44,), 1)  # Nemotron's toy window: another kind's
    kinds = {}
    for name in sorted(os.listdir(os.path.join(spec.BENCH_DIR, "configs"))):
        doc = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", name))
        kinds.setdefault(doc["inputs"]["kind"], []).append(
            tuple(doc["model"]["input_shape"]))
    assert sorted(kinds["solar_open2_tokens"]) == [(40,), (4096,)]
    for shapes in kinds.values():  # no kind has one shape twice
        assert len(shapes) == len(set(shapes))


@pytest.mark.timeout(115)
def test_rehearsal_of_the_tiny_cell_on_the_cpu(tmp_path):
    # a compile cache of its own: tests/test_infer.py watches the checkout's
    # while other workers run
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    command = [sys.executable if w == "python3" else w
               for w in BENCH["command"]]
    proc = subprocess.run(
        command + ["--workload", "solar_open2_tiny.tokens_backlog", "--seed",
                   "3000000029", "--seconds", "2", "--trace", "0",
                   "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=105)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    row = lines[-1]
    assert row["correct"] is True and row["failed"] == 0
    assert row["attempted"] > 0
    assert set(row["metrics"]) == {"records_per_s", "setup_s"}
    assert 0 < row["checks"]["farthest_output"][0] <= 0.02
    every = [line for line in lines if line.get("phase") == "all_metrics"][0]
    layer = every["per_layer"]
    assert layer["batch_size_mean"] <= 4.0  # the toy's one bucket: (4,)
    assert 15 < layer["expert_assignments_held_share"] < 35  # 5 of 20 held
    assert layer["expert_tokens_max_over_mean"] >= 1.0


@pytest.mark.timeout(115)
def test_the_tolerances_two_readings_and_the_mixers_check_at_toy_sizes(
        tmp_path):
    """``tools/tolerance.py`` at the toy sizes: the program answers every
    row, the float8 control does not, each by ``pairing.match_rows`` under
    the configuration's limit. ``tools/solar_mixer_check.py``: both mixers in
    both forms (on the CPU each rule gives XLA's) against the reference's, in
    float32 here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/tolerance.py", "--config",
         "solar_open2_tiny", "--rehearse", "5:f8"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    (row,) = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert row["program"]["correct"] is True
    assert row["program"]["rows_failed"] == 0 and row["program"]["rows"] == 32
    assert row["program"]["max"] < 1e-5  # float32 here: summation order
    assert row["tolerance"] == 0.02
    assert row["float8"]["correct"] is False
    assert row["float8"]["rows_failed"] == 32
    assert row["float8"]["min"] > 100 * row["program"]["max"]
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/solar_mixer_check.py", "--config",
         "solar_open2_tiny", "--rehearse", "--seed", "5", "--limit", "1e-4"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=50)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert [(r["mixer"], r["case"]) for r in rows] == [
        ("kda", "random")] * 2 + [("kda", "near_parallel")] * 2 + [
        ("gqa", "random")] * 2
    assert all(r["pass"] and r["length"] == 40 for r in rows)
    assert rows[2]["steps_past_1.9"] > 0.1 > rows[0]["steps_past_1.9"]
    assert rows[0]["step_max"] < 2 and rows[0]["step_min"] > 0
    assert "kda_tables=xla" in rows[1]["forms"]
    assert rows[5]["forms"] == ["causal_attention=blocked-grouped"]
