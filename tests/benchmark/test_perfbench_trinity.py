"""The benchmark's Trinity files (PR 57): the configuration against the
catalog row it is cut from and the program's own parameter tree,
``ops/trinity.py`` against the issue's table counted by hand, every per-layer
metric that lists the new cell over a trace of its shapes made by hand (two
attention loops of identical shapes that only their parts' names tell apart),
the new entries in ``BENCHMARK.json`` (found by name: neither how many cells
there are nor which is last is this file's business), the metric files
against their readers, the windows' kind, and rehearsals of
``trinity_tiny.tokens16k_backlog`` and of the two tools on the CPU."""

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

CELL = "trinity_mini.tokens16k_backlog"
BENCH = spec.benchmark()
CONFIG = spec.config("trinity_mini")
SIZES = CONFIG["published"]
OPS = spec.plugin("ops", "trinity")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMETERS = 4_241_534_720
ROWS, SEQ, WINDOW = 4, 16384, 2048
TOKENS = ROWS * SEQ
# every width of the row: none may differ from the published value
WIDTHS = {"hidden_size": 2048, "intermediate_size": 6144,
          "moe_intermediate_size": 1024, "num_attention_heads": 32,
          "num_key_value_heads": 4, "head_dim": 128, "num_experts": 128,
          "num_experts_per_tok": 8, "num_shared_experts": 1,
          "route_scale": 2.826, "rms_norm_eps": 1e-05,
          "sliding_window": 2048, "rope_theta": 10000, "vocab_size": 200192,
          "global_attn_every_n_layers": 4}
SHARED = {"parse_ms_per_record", "batch_size_mean", "model_step_ms",
          "model_roofline_share", "egress_ms_per_record", "device_idle_share",
          "cut_hold_mean_ms", "step_named_share", "step_gap_max_ms",
          "mixer_elementwise_ms", "projections_ms", "moe_routing_ms",
          "expert_tokens_max_over_mean", "expert_assignments_held_share"}
NEW = {"window_attention_ms": "trace_part_time",
       "window_attention_roofline_share": "trace_part_share",
       "trinity_full_attention_ms": "trace_part_time",
       "trinity_full_attention_roofline_share": "trace_part_share",
       "trinity_expert_matmul_ms": "trace_ops_time",
       "trinity_expert_matmul_roofline_share": "trace_ops_time",
       "trinity_expert_combine_ms": "trace_ops_time",
       "trinity_rope_ms": "trace_part_time"}


def _entry(group, name):
    (found,) = [e for e in BENCH[group] if e["name"] == name]
    return found


def test_configuration_states_the_cut_and_keeps_every_width():
    held = SIZES["held"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_dense_layers"]
    # the top level is the configuration as run; ``published`` as published
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"]) == (5, 1)
    assert (SIZES["num_hidden_layers"], SIZES["num_dense_layers"]) == (32, 2)
    assert (held["layers"], held["num_hidden_layers"],
            held["num_dense_layers"], held["pipeline_stages"],
            held["chips_per_layer"], held["first_expert"],
            held["sequence_length"], held["rows_per_step"]) == (
        [1, 4, 5, 6, 7], 5, 1, 8, 1, 0, SEQ, ROWS)
    for key, value in WIDTHS.items():
        assert CONFIG[key] == SIZES[key] == value, key
    for key, value in SIZES.items():
        if key not in CONFIG["reduced"] and key != "held":
            assert CONFIG[key] == value, key
    assert SIZES["layer_types"] == (["sliding_attention"] * 3
                                    + ["full_attention"]) * 8
    assert (SIZES["score_func"], SIZES["route_norm"], SIZES["mup_enabled"],
            SIZES["tie_word_embeddings"]) == ("sigmoid", True, True, False)
    # the held layers: a dense window layer, then one whole period of expert
    # layers in its published order
    kinds = [SIZES["layer_types"][i] for i in held["layers"]]
    assert kinds == ["sliding_attention"] * 4 + ["full_attention"]
    assert [i < SIZES["num_dense_layers"] for i in held["layers"]] == [
        True, False, False, False, False]
    assert OPS._layers(SIZES) == (4, 1, 1, 4)
    assert "Eight pipeline stages" in CONFIG["deployment"]
    assert "all 128 routed experts" in CONFIG["deployment"]
    assert "four window layers to one full" in CONFIG["deployment"]
    assert CONFIG["model"] == {"name": "trinity_mini", "input_shape": [SEQ],
                               "num_classes": 200192, "dtype": "bfloat16"}
    for key in ("why", "qk_norm", "output_gate", "rotary", "window",
                "sandwich", "embedding", "attention", "router", "weights",
                "inputs", "output", "ids", "tiles", "stream"):
        assert CONFIG["assumed"][key], key
    on_device = CONFIG["on_device"]
    assert on_device["parameters"] == PARAMETERS
    assert on_device["parameters_bytes"] == 2 * PARAMETERS
    assert on_device["parameters_float32_at_load_bytes"] == 0
    # the issue's rule: the whole vocabulary where parameters and the
    # compiler's temporaries stay at or under 15.0 GB
    assert on_device["parameters_bytes"] \
        + on_device["program_temporaries_bucket_4_bytes"] <= 15.0e9
    assert CONFIG["inputs"] == {"kind": "trinity_tokens", "decimals": 0,
                                "candidates": 16}
    assert 0 < CONFIG["tolerance"]["relative_distance"] < 0.2
    assert "float8" in CONFIG["tolerance"]["why"]
    for key in ("delivery", "malformed_records", "offsets", "experts"):
        assert CONFIG["guarantees"][key], key
    entry = _entry("configs", "trinity_mini")
    assert entry["file"] == "benchmarks/configs/trinity_mini.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] and len(entry["why"]) <= 200


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_in_the_file():
    rows = [json.loads(line) for line in open(CATALOG)]
    (row,) = [r for r in rows if r["name"] == "Trinity-Mini"]
    assert CONFIG["source"] == row["source_url"]
    assert row["config"]["model_type"] == "afmoe"
    for key, value in row["config"].items():
        assert SIZES[key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


def test_ops_count_the_issues_table_by_hand():
    """The table of ISSUE 57, a row at a time."""
    d = 2048
    attention = 3 * 8_388_608 + 2 * 1_048_576 + 256
    assert attention == 27_263_232 == OPS.attention_parameters(SIZES)
    dense_ffn = 3 * d * 6144
    assert dense_ffn == 37_748_736
    expert = 3 * d * 1024
    expert_layer = 128 * expert + expert + d * 128 + 128
    assert (expert, expert_layer) == (6_291_456, 811_860_096)
    dense_block = attention + 4 * d + dense_ffn
    expert_block = attention + 4 * d + expert_layer
    assert (dense_block, expert_block) == (65_020_160, 839_131_520)
    ends = 2 * 200_192 * d + d
    assert ends == 819_988_480
    assert dense_block + 4 * expert_block + ends == PARAMETERS \
        == OPS.parameters(SIZES)
    # the published model: two dense blocks, thirty expert blocks, the ends
    assert round((2 * dense_block + 30 * expert_block + ends) / 1e8) == 261
    # pairs inside the windows and under the diagonal, a head and window
    in_window, causal = OPS.pairs(SIZES)
    assert in_window == WINDOW * (WINDOW + 1) // 2 + (SEQ - WINDOW) * WINDOW \
        == sum(min(t + 1, WINDOW) for t in range(SEQ))
    assert causal == SEQ * (SEQ + 1) // 2
    assert round(in_window / 1e5) == 315 and round(causal / 1e5) == 1342
    work = OPS.kernels(SIZES, ROWS, 2)
    assert work["window_attention"]["flops"] == 4 * ROWS * 32 * 4 * 128 \
        * in_window
    assert work["full_attention"]["flops"] == ROWS * 32 * 4 * 128 * causal
    assert work["window_attention"]["bytes"] == 4 * TOKENS * 2 * 36 * 128 * 2
    # every expert is held: 8 assignments a token a layer, none expected away
    held = 4 * TOKENS * 8
    assert work["expert_matmul"] == OPS.kernels(
        SIZES, ROWS, 2, assignments=held)["expert_matmul"]
    assert work["expert_matmul"]["flops"] == 2 * held * expert
    assert work["expert_matmul"]["bytes"] == 4 * 128 * expert * 2 \
        + held * d * 6
    # a row: the projections of every token, the three kernels, the head
    per_token = 2 * (5 * (attention - 256) + dense_ffn
                     + 4 * (d * 128 + expert))
    assert OPS.flops_per_row(SIZES) == SEQ * per_token + sum(
        k["flops"] for k in OPS.kernels(SIZES, 1, 2).values()) \
        + 2 * d * 200_192
    counts = OPS.counts(SIZES, rows=8, steps=2, bytes_per_value=2)
    assert counts["flops"] == 8 * OPS.flops_per_row(SIZES)
    assert counts["bytes"] == 2 * 2 * PARAMETERS + 8 * 4 * (SEQ + 200_192)


def test_ops_parameters_are_the_programs():
    import jax

    from storm_tpu.models.registry import build_model

    for name, count in (("trinity_mini", PARAMETERS), ("trinity_tiny", None)):
        model = build_model(name)
        params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        held = sum(x.size for x in jax.tree.leaves(params))
        sizes = spec.config(name)["published"]
        assert OPS.parameters(sizes) == held, name
        assert count in (None, held)
        assert model.max_rows == sizes["held"]["rows_per_step"]
        assert model.hyper["window"] == sizes["sliding_window"]
        assert len(params["layers"]) == len(sizes["held"]["layers"])


def test_rows_per_step_reads_the_window_shape():
    names = ["%fusion.1 = f32[4,16384,2048]{2,1,0} fusion(f32[4,16384,2048])",
             "%fusion.2 = bf16[4,32,16384,128]{3,2,1,0} fusion()",
             "%fusion.3 = f32[65536,128]{1,0} fusion()"]
    assert OPS.rows_per_step(names, SIZES) == 4
    assert OPS.rows_per_step(names[1:], SIZES) is None


# ---- every listed metric over a trace of this cell's shapes ------------------

MS = 1e6  # nanoseconds
DEV = "/device:TPU:0"


def _loop(number, carried):
    """A ``while`` as a trace names it: its tuple type, then its operand's."""
    return (f"%while.{number} = ({carried}) while(({carried}) %tuple.3), "
            "condition=%c, body=%b")


# one step's top-level operations, the loops as the v5e compiler names them
# (a compile for the described chip at 4 windows, layouts dropped)
B = ROWS
HELD = TOKENS * 8  # assignments a layer
BUFFER = HELD + 128 * 512 + 1  # the tiles' buffer and its zero row
STREAM = f"%fusion.9 = f32[{B},16384,2048]{{2,1,0}} fusion(%p), kind=kOutput"
GATE = f"%fusion.7 = bf16[{B},16384,4096]{{2,1,0}} fusion(%a, %g), kind=kLoop"
TURN = f"%custom-call.5 = bf16[{B},16384,4096]{{2,1,0}} custom-call(%c, %s, %q)"
CARRIED = (f"s32[], bf16[{B},32,16384,128], s32[4], bf16[{B},32,16384,128], "
           f"bf16[{B},4,16384,128], bf16[{B},4,16384,128], s32[], s32[1]")
WINDOWED, FULL = _loop(54, CARRIED), _loop(61, CARRIED)
SORT = f"%sort.8 = (s32[{HELD}], s32[{HELD}], f32[{HELD}]) sort(%a, %i, %w)"
INNER = _loop(75, f"s32[], s32[129], s32[129], s32[{HELD}], s32[], s32[]")
EXP = _loop(63, f"s32[], bf16[{BUFFER},2048], s32[], s32[1152], s32[1152], "
            f"s32[1152], s32[{HELD + 512}], bf16[{TOKENS},2048], "
            "bf16[128,1024,2048], bf16[128,2048,1024], bf16[128,2048,1024], "
            f"f32[{HELD + 512}], s32[]")
COMB = _loop(64, f"s32[], f32[{TOKENS},2048], s32[], s32[1280], s32[1280], "
             f"s32[1280], s32[{HELD + 512}], bf16[{BUFFER},2048], "
             f"s32[{HELD + 512}], s32[]")
ZERO = (f"%broadcast.70 = f32[{TOKENS},2048]{{1,0}} "
        "broadcast(f32[] %constant.3)")
STEP_OPS = [
    (STREAM, "jit(fwd)/mix.elementwise/proj/dot_general", 0, 200),
    (TURN, "jit(fwd)/mix.elementwise/mix.rope/pallas_call", 200, 12),
    (WINDOWED, "jit(fwd)/mix.elementwise/mix.window_attention/while", 212,
     100),
    (FULL, "jit(fwd)/mix.elementwise/mix.attention/while", 312, 80),
    (GATE, "jit(fwd)/mix.elementwise/mul", 392, 60),
    (SORT, "jit(fwd)/moe.route/jit(sort)/sort", 452, 58),
    (INNER, "jit(fwd)/moe.route/jit(searchsorted)/vmap()/while", 510, 2),
    (EXP, "jit(fwd)/moe.experts/while", 512, 250),
    (COMB, "jit(fwd)/moe.combine/while", 762, 130),
    (ZERO, None, 892, 8),
]
STEP_MS = 900.0
HELD_A_STEP = 4 * HELD  # four expert layers, every assignment held
WANT = {"model_step_ms": STEP_MS, "window_attention_ms": 100.0,
        "trinity_full_attention_ms": 80.0, "trinity_rope_ms": 12.0,
        "trinity_expert_matmul_ms": 250.0, "trinity_expert_combine_ms": 130.0,
        "moe_routing_ms": 60.0, "mixer_elementwise_ms": 60.0,
        "projections_ms": 200.0, "step_named_share": 100.0 * 892 / 900,
        # the two cut executions lack their first 212 ms of operations
        "device_idle_share": 100.0 * 2 * 212 / (8 * 900),
        "batch_size_mean": 4.0, "cut_hold_mean_ms": 0.0,
        "expert_assignments_held_share": 100.0,
        "expert_tokens_max_over_mean": 1.5, "parse_ms_per_record": 0.05,
        "egress_ms_per_record": 2.5, "step_gap_max_ms": STEP_MS}


def _traced_run(steps=8):
    mods, ops, log = [], [], []
    for i in range(steps):  # the first and the last are cut: fewer operations
        at = i * STEP_MS
        cut = i in (0, steps - 1)
        mods.append(("jit_fwd(5)", at * MS, STEP_MS * MS))
        ops += [(n, (at + s) * MS, d * MS) for n, _o, s, d in
                STEP_OPS[2 * cut:]]
    planes = [(DEV, [("XLA Modules", mods), ("XLA Ops", ops)])]
    cell = spec.cell(BENCH, CELL)
    run = harness.Run(cell, CONFIG, {}, 0, 12.0)
    run.device = {"kind": "TPU v5 lite"}
    run.trace = xplane.reduce(planes)
    run._device_planes = planes
    run._trace_meta = {"op_names": {DEV: {n: o for n, o, _s, _d in STEP_OPS
                                          if o}}, "start_s": None}
    off = 7000.0  # the device's zero on the host's clock
    for n in range(22):  # steps 14.. are the traced executions
        ready = off + STEP_MS / 1e3 * (n - 14 + 1) + 2e-4
        log.append({"step": n, "engine": "trinity_mini", "padded": ROWS,
                    "rows": ROWS, "sources": 2, "seen": True,
                    "t_first_enq": ready - 2.7, "t_cut": ready - 1.82,
                    "t_staged": ready - 1.81, "t_launched": ready - 1.80,
                    "t_ready": ready, "t_fetched": ready + 0.001,
                    "t_resolved": ready + 0.002})
    run._step_rows = log
    run.delivery_times = [off - 14 * STEP_MS / 1e3, off]
    run.delivered_in_window = ROWS * 14
    hist = lambda count, total: {"count": count, "sum": total}  # noqa: E731
    run.registry_before = {"inference-bolt": {}, "kafka-bolt": {}}
    run.registry_after = {
        "inference-bolt": {
            "decode_ms": hist(56, 56 * 0.05), "batch_size": hist(14, 56.0),
            "encode_ms": hist(56, 56 * 2.0), "cut_hold_ms": hist(14, 0.0),
            "expert_tokens_max_over_mean": hist(56, 84.0),
            "expert_assignments_held": 14 * HELD_A_STEP,
            "expert_assignments_absent": 0},
        "kafka-bolt": {"produce_ms": hist(56, 56 * 0.5)}}
    return run


def test_the_new_entries_list_what_reads_here():
    """Found by name. How many cells the benchmark has and which comes last
    is no business of this file's: the next cell must not fail it."""
    cell = spec.cell(BENCH, CELL)
    assert cell in BENCH["workloads"]
    assert cell["chips"] == 1 and cell["traffic"] == "tokens16k_backlog"
    assert cell["config"] == "trinity_mini" and len(cell["why"]) <= 200
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == "trinity_mini"] == [CELL]  # no second cell
    assert BENCH["run_seconds"] == 20
    e2e = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end", cell)}
    assert e2e == {"records_per_s", "setup_s"}
    assert _entry("end_to_end", "records_per_s")["bound"] == 0.01
    assert _entry("end_to_end", "setup_s")["bound"] == 0.1
    layer = {m["name"]: m for m in spec.metrics_for(BENCH, "per_layer", cell)}
    assert set(layer) == SHARED | set(NEW) | {"compile_s", "cache_misses"}
    # loops told by other models' shapes are not this cell's to report
    assert not {"gqa_attention_ms", "solar_gqa_attention_ms",
                "expert_matmul_ms", "expert_combine_ms", "rope_ms",
                "lightning_rope_ms", "sparse_attention_ms",
                "solar_expert_matmul_ms", "k2_expert_combine_ms"} & set(layer)
    for name, reader in NEW.items():
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["layer"] == "engine and model"
        assert layer[name]["moves"] == "records_per_s"
        assert layer[name]["unit"] == ("%" if name.endswith("_share")
                                       else "ms")
        assert layer[name]["better"] == ("higher" if name.endswith("_share")
                                         else "lower")
        assert layer[name]["source"] == "device_trace"
        assert spec.metric(name)["reader"] == reader
    for name in SHARED:
        assert CELL in layer[name]["workloads"]
    # a share of a roofline the accepted benchmark has moves records_per_s:
    # the new cell reports it
    assert CELL in _entry("per_layer", "model_roofline_share")["workloads"]
    # the new metrics stand after every metric an earlier PR brought
    names = [m["name"] for m in BENCH["per_layer"]]
    first = min(names.index(n) for n in NEW)
    assert set(names[first:first + len(NEW)]) == set(NEW)
    assert first > names.index("solar_expert_combine_ms")
    # the mix is the one minicpm_sala's cell runs, unchanged
    traffic = spec.traffic("tokens16k_backlog")
    assert (traffic["outstanding"], traffic["pool"], traffic["payload"],
            traffic["arrivals"], traffic["warmup_seconds"],
            traffic["trace_seconds"]) == (
        32, 16, "arrow_tensor", "closed_loop", 6, 8)
    assert traffic["program"] == {"topology.spout_scheme": "raw"}


def test_the_metric_files_name_their_readers_parts_and_patterns():
    from storm_tpu.ops import parts

    args = {name: spec.metric(name)["args"] for name in NEW}
    assert all(a["prefix"] == "jit_fwd" for a in args.values())
    assert args["window_attention_ms"]["part"] == \
        args["window_attention_roofline_share"]["part"] == \
        parts.MIX_WINDOW_ATTENTION
    assert args["trinity_full_attention_ms"]["part"] == \
        args["trinity_full_attention_roofline_share"]["part"] == \
        parts.MIX_ATTENTION
    assert args["trinity_rope_ms"]["part"] == parts.MIX_ROPE
    assert parts.MIX_WINDOW_ATTENTION in parts.VOCABULARY
    kernels = OPS.kernels(SIZES, ROWS, 2)
    for name in NEW:
        if name.endswith("_roofline_share"):
            assert args[name]["kernel"] in kernels, name
            twin = name.replace("_roofline_share", "_ms")
            for key in ("part", "pattern"):  # a share reads its time's events
                assert args[name].get(key) == args[twin].get(key), name
    # the two attention loops carry the same shapes: no pattern could part
    # them, which is why their metrics go by the part's name
    assert WINDOWED.split(" = ")[1] == FULL.split(" = ")[1]
    for name in ("trinity_expert_matmul_ms", "trinity_expert_combine_ms"):
        pattern = args[name]["pattern"]
        own = EXP if "matmul" in name else COMB
        assert re.search(pattern, own), name
        for other in (WINDOWED, FULL, INNER, STREAM, ZERO,
                      COMB if own is EXP else EXP):
            assert not re.search(pattern, other), (name, other)


@pytest.mark.parametrize("rows", [2, 4, 8])
def test_the_loops_patterns_hold_at_other_rows_a_step(rows):
    at = {"trinity_expert_matmul_ms": "bf16[128,2048,1024]",
          "trinity_expert_combine_ms": f"f32[{rows * SEQ},2048]"}
    for name, carried in at.items():
        pattern = spec.metric(name)["args"]["pattern"]
        assert re.search(pattern, _loop(7, f"s32[], {carried}, s32[]")), name
        # a fusion of the same shape is no loop
        assert not re.search(pattern, f"%fusion.7 = {carried} fusion(%p)")


def test_every_listed_metric_reads_a_number_from_a_trace_of_its_shapes():
    run = _traced_run()
    cell = spec.cell(BENCH, CELL)
    listed = spec.metrics_for(BENCH, "per_layer", cell)
    got = harness.read_metrics(run, [m for m in listed if m["name"]
                                     not in ("compile_s", "cache_misses")])
    assert set(got) == SHARED | set(NEW)
    for name, want in WANT.items():
        assert got[name]["value"] == pytest.approx(want, abs=1e-6), name
    shares = ("model_roofline_share", "window_attention_roofline_share",
              "trinity_full_attention_roofline_share",
              "trinity_expert_matmul_roofline_share")
    for name in shares:
        assert 0 < got[name]["value"] < 100 and math.isfinite(
            got[name]["value"])
    work = OPS.kernels(SIZES, ROWS, 2, assignments=HELD_A_STEP)
    # all three kernels' least times are their operations'
    for kernel in work.values():
        assert kernel["flops"] / 197e12 > kernel["bytes"] / 819e9
    assert got["window_attention_roofline_share"]["value"] == pytest.approx(
        100 * work["window_attention"]["flops"] / 197e12 / 0.100)
    assert got["trinity_full_attention_roofline_share"]["value"] == \
        pytest.approx(100 * work["full_attention"]["flops"] / 197e12 / 0.080)
    assert got["trinity_expert_matmul_roofline_share"]["value"] == \
        pytest.approx(100 * work["expert_matmul"]["flops"] / 197e12 / 0.250)
    assert {k: v["rows"] for k, v in run.notes["kernels"].items()} == {
        "window_attention": ROWS, "full_attention": ROWS,
        "expert_matmul": ROWS}
    assert got["model_roofline_share"]["value"] == pytest.approx(
        100 * ROWS * OPS.flops_per_row(SIZES) / 197e12 / 0.900)
    assert run.roofline_bound == "compute"
    # the loops' metrics read the very events the parts' times read
    assert run.notes["part_loops"] == pytest.approx(
        {"mix.window_attention": 100.0, "mix.attention": 80.0,
         "moe.route": 2.0, "moe.experts": 250.0, "moe.combine": 130.0})
    # the line such a run prints is complete by the driver's own check
    got.update(compile_s={"value": 1.0, "unit": "s"},
               cache_misses={"value": 0.0, "unit": "count"})
    row = {"correct": True, "attempted": 1, "failed": 0, "metrics": got,
           "device": {}}
    assert check_line.problems(row, CELL, traced=True) == []


def test_a_program_without_the_new_names_reads_nothing_and_raises_nothing():
    """Another model's program (Solar Open 2's loops, no window part, no
    such weights): each of the shape-told and window metrics is left out of
    the line; so is every one where there is no trace."""
    others = [
        _loop(59, "s32[], bf16[8,64,4096,128], s32[4], bf16[8,64,4096,128], "
              "bf16[8,8,4096,128], bf16[8,8,4096,128], s32[], s32[1]"),
        _loop(63, "s32[], bf16[282625,4096], s32[], bf16[32768,4096], "
              "bf16[40,1280,4096], bf16[40,4096,1280], bf16[40,4096,1280]"),
        _loop(64, "s32[], f32[32768,4096], s32[], s32[64]")]
    planes = [(DEV, [
        ("XLA Modules", [("jit_fwd(1)", t * MS, 90 * MS)
                         for t in (0, 100, 200)]),
        ("XLA Ops", [(op, t * MS + 1 + i, 8 * MS) for t in (0, 100, 200)
                     for i, op in enumerate(others)])])]
    run = harness.Run(spec.cell(BENCH, CELL), CONFIG, {}, 0, 1.0)
    run.device = {"kind": "TPU v5 lite"}
    run.trace = xplane.reduce(planes)
    run._device_planes = planes
    run._trace_meta = {"op_names": {DEV: {
        others[0]: "jit(fwd)/mix.elementwise/mix.attention/while"}},
        "start_s": None}
    run.registry_before = run.registry_after = {"inference-bolt": {}}
    silent = set(NEW) - {"trinity_full_attention_ms",
                         "trinity_full_attention_roofline_share"}
    for name in sorted(silent):
        doc = spec.metric(name)
        value = spec.plugin("readers", doc["reader"]).read(run, **doc["args"])
        # a part's time reads 0.0 where the program has the names and nothing
        # under this one: the share beside it is left out
        assert value in (None, 0.0), name
        if doc["reader"] != "trace_part_time":
            assert value is None, name
    untraced = harness.Run(spec.cell(BENCH, CELL), CONFIG, {}, 0, 1.0)
    for name in sorted(NEW):
        doc = spec.metric(name)
        assert spec.plugin("readers", doc["reader"]).read(
            untraced, **doc["args"]) is None


def test_the_windows_come_from_the_whole_vocabulary_and_a_kind_of_their_own():
    """A kind of input a family (PERF.md section 7 item 4 (d)): no two
    configurations are coupled through one kind's look-up by shape;
    ``minicpm_sala``'s windows are as long and its vocabulary another."""
    make = spec.plugin("inputs", "trinity_tokens").make
    a, b = make(5, (SEQ,), 3_000_000_019), make(5, (SEQ,), 3_000_000_019)
    assert (a == b).all() and a.shape == (5, SEQ)
    assert a.min() >= 0 and 200_000 < a.max() < 200_192
    assert (a == a.round()).all()
    assert not (a == make(5, (SEQ,), 3_000_000_020)).all()
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
    assert sorted(kinds["trinity_tokens"]) == [(40,), (SEQ,)]
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
        command + ["--workload", "trinity_tiny.tokens16k_backlog", "--seed",
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
    assert layer["expert_assignments_held_share"] == 100.0  # all 20 held
    assert layer["expert_tokens_max_over_mean"] >= 1.0


@pytest.mark.timeout(115)
def test_the_tolerances_two_readings_and_the_mixers_check_at_toy_sizes(
        tmp_path):
    """``tools/tolerance.py`` at the toy sizes: the program answers every
    row, the float8 control does not. ``tools/trinity_mixer_check.py``: the
    window alone and the mixer of both kinds, each in both forms (on the CPU
    the rule gives XLA's either way), against the reference's, in float32
    here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/tolerance.py", "--config",
         "trinity_tiny", "--rehearse", "5:f8"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    (row,) = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert row["program"]["correct"] is True
    assert row["program"]["rows_failed"] == 0 and row["program"]["rows"] == 32
    assert row["program"]["max"] < 1e-5  # float32 here: summation order
    assert row["tolerance"] == 0.02
    assert row["float8"]["correct"] is False
    assert row["float8"]["min"] > 100 * row["program"]["max"]
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/trinity_mixer_check.py",
         "--config", "trinity_tiny", "--rehearse", "--seed", "5", "--limit",
         "1e-4"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=50)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert [(r["check"], r["kind"]) for r in rows] == [
        ("attention", "sliding_attention")] * 2 + [
        ("mixer", "sliding_attention")] * 2 + [("mixer", "full_attention")] * 2
    assert all(r["pass"] and r["length"] == 40 and r["window"] == 12
               for r in rows)
    assert rows[0]["forms"] == ["window_attention=blocked-grouped"]
    assert rows[2]["forms"] == ["rotary_turn=halves",
                                "window_attention=blocked-grouped"]
    assert rows[5]["forms"] == ["causal_attention=blocked-grouped"]
