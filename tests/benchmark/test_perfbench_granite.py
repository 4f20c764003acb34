"""The benchmark's Granite files (PR 63): the configuration against the
catalog row it is cut from and the program's own parameter tree,
``ops/granite.py`` against the issue's table and its sums by hand, every
per-layer metric that lists the new cell over a trace of its shapes made by
hand, the new entries in ``BENCHMARK.json`` (found by name: neither how many
cells there are nor which is last is this file's business), the metric files
against their readers and parts, the windows' kind, and rehearsals of
``granite_h_tiny.tokens_backlog`` and of the two tools on the CPU."""

import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.core import harness, spec, xplane  # noqa: E402
from benchmarks.tools import check_line  # noqa: E402

CELL = "granite_4_h_small.tokens_backlog"
BENCH = spec.benchmark()
CONFIG = spec.config("granite_4_h_small")
SIZES = CONFIG["published"]
OPS = spec.plugin("ops", "granite")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMETERS = 4_757_211_776
ROWS, SEQ, LAYERS, D = 8, 4096, 10, 4096
TOKENS = ROWS * SEQ
PATTERN = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# every width of the row: none may differ from the published value
WIDTHS = {"hidden_size": 4096, "intermediate_size": 768,
          "shared_intermediate_size": 1536, "num_attention_heads": 32,
          "num_key_value_heads": 8, "num_experts_per_tok": 10,
          "mamba_n_heads": 128, "mamba_d_head": 64, "mamba_d_state": 128,
          "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 256,
          "mamba_expand": 2, "attention_multiplier": 0.0078125,
          "embedding_multiplier": 12, "residual_multiplier": 0.22,
          "logits_scaling": 16, "rms_norm_eps": 1e-05,
          "max_position_embeddings": 131072}
SHARED = {"parse_ms_per_record", "batch_size_mean", "model_step_ms",
          "model_roofline_share", "egress_ms_per_record", "device_idle_share",
          "cut_hold_mean_ms", "step_named_share", "step_gap_max_ms",
          "mixer_elementwise_ms", "projections_ms", "moe_routing_ms",
          "expert_tokens_max_over_mean", "expert_assignments_held_share"}
# metric -> (reader, part, kernel)
NEW = {"granite_ssd_scan_ms": ("trace_part_time", "mix.ssd_scan", None),
       "granite_ssd_scan_roofline_share": (
           "trace_part_share", "mix.ssd_scan", "ssd_scan"),
       "granite_gqa_attention_ms": (
           "trace_part_time", "mix.attention", None),
       "granite_gqa_attention_roofline_share": (
           "trace_part_share", "mix.attention", "gqa_attention"),
       "granite_expert_matmul_ms": ("trace_part_time", "moe.experts", None),
       "granite_expert_matmul_roofline_share": (
           "trace_part_share", "moe.experts", "expert_matmul"),
       "granite_expert_combine_ms": ("trace_part_time", "moe.combine", None),
       "granite_expert_tile_fill_share": (
           "registry_counter_share", None, None)}


def _entry(group, name):
    (found,) = [e for e in BENCH[group] if e["name"] == name]
    return found


def test_configuration_states_the_cut_and_keeps_every_width():
    held = SIZES["held"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_local_experts",
                                 "vocab_size"]
    # the top level is the configuration as run; ``published`` as published
    assert (CONFIG["num_hidden_layers"], CONFIG["num_local_experts"],
            CONFIG["vocab_size"]) == (LAYERS, 36, 50176)
    assert (SIZES["num_hidden_layers"], SIZES["num_local_experts"],
            SIZES["vocab_size"]) == (40, 72, 100352)
    assert (held["layers"], held["num_hidden_layers"],
            held["pipeline_stages"], held["chips_per_layer"],
            held["num_local_experts"], held["first_expert"],
            held["vocab_size"], held["sequence_length"],
            held["expert_tile"]) == (
        list(range(10)), LAYERS, 4, 2, 36, 0, 50176, SEQ, 512)
    assert held["rows_per_step"] in (8, 4) and held["ssd_chunk"] in (128, 256)
    for key, value in WIDTHS.items():
        assert CONFIG[key] == SIZES[key] == value, key
    for key, value in SIZES.items():
        if key not in CONFIG["reduced"] and key != "held":
            assert CONFIG[key] == value, key
    # a period of ten, four times; the held ten are one whole period
    assert SIZES["layer_types"] == PATTERN * 4
    assert [SIZES["layer_types"][i] for i in held["layers"]] == PATTERN
    assert (SIZES["tie_word_embeddings"], SIZES["attention_bias"],
            SIZES["mamba_conv_bias"], SIZES["mamba_proj_bias"],
            SIZES["position_embedding_type"], SIZES["model_type"]) == (
        True, False, True, False, "nope", "granitemoehybrid")
    deployment = CONFIG["deployment"]
    assert "Two chips share each layer" in deployment
    assert "one v5e-8 host" in deployment
    assert "four pipeline stages" in deployment
    assert "no code stands in" in deployment
    assert CONFIG["model"] == {"name": "granite_4_h_small",
                               "input_shape": [SEQ], "num_classes": 50176,
                               "dtype": "bfloat16"}
    for key in ("why", "head_dim", "intermediate_size", "mamba2",
                "attention", "router", "experts", "multipliers", "weights",
                "inputs", "output", "ids", "chunk", "stream"):
        assert CONFIG["assumed"][key], key
    assert "no part of the mathematics" in CONFIG["assumed"]["mamba2"]
    on_device = CONFIG["on_device"]
    assert on_device["parameters"] == PARAMETERS
    assert on_device["parameters_bytes"] == 2 * PARAMETERS
    assert on_device["parameters_float32_at_load_bytes"] == 0
    # the issue's rule: the rows that ship are those at which parameters and
    # the compiler's temporaries stay at or under 15.0 GB
    shipped = held["rows_per_step"]
    assert on_device["parameters_bytes"] + on_device[
        f"program_temporaries_bucket_{shipped}_bytes"] <= 15.0e9
    assert CONFIG["inputs"] == {"kind": "granite_tokens", "decimals": 0,
                                "candidates": 32}
    assert 0 < CONFIG["tolerance"]["relative_distance"] < 0.2
    assert "float8" in CONFIG["tolerance"]["why"]
    for key in ("delivery", "malformed_records", "offsets", "experts"):
        assert CONFIG["guarantees"][key], key
    entry = _entry("configs", "granite_4_h_small")
    assert entry["file"] == "benchmarks/configs/granite_4_h_small.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] and len(entry["why"]) <= 200


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_in_the_file():
    rows = [json.loads(line) for line in open(CATALOG)]
    (row,) = [r for r in rows if r["name"] == "granite-4.0-h-small"]
    assert CONFIG["source"] == row["source_url"]
    assert row["config"]["model_type"] == "granitemoehybrid"
    for key, value in row["config"].items():
        assert SIZES[key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


def test_ops_count_the_issues_table_by_hand():
    """The table of ISSUE 63, a row at a time, and its sums."""
    mamba = D * 16_768 + 5 * 8_448 + 3 * 128 + 8_192 + 8_192 * D
    assert mamba == 102_286_976 == OPS.mamba_parameters(SIZES)
    attention = 2 * 16_777_216 + 2 * 4_194_304
    assert attention == 41_943_040 == OPS.attention_parameters(SIZES)
    experts = 36 * 3 * D * 768 + D * 72 + 3 * D * 1_536
    assert experts == 358_907_904 == OPS.expert_layer_parameters(SIZES)
    assert mamba + experts + 2 * D == 461_203_072
    assert attention + experts + 2 * D == 400_859_136
    blocks = 9 * 461_203_072 + 400_859_136
    ends = 50_176 * D + D
    assert (blocks, ends) == (4_551_686_784, 205_524_992)
    assert blocks + ends == PARAMETERS == OPS.parameters(SIZES)
    whole = dict(SIZES, held={"sequence_length": SEQ})  # nothing cut
    assert OPS.parameters(whole) == 36 * 800_941_696 + 4 * 740_597_760 \
        + 411_045_888
    assert round(OPS.parameters(whole) / 1e8) == 322  # the published 32.2 B
    # the kernels of a step of 8
    work = OPS.kernels(SIZES, ROWS, 2)
    macs = 128 * (128 * 64 + 2 * 64 * 128) + 128 * 128
    assert macs == 3_162_112
    assert work["ssd_scan"]["flops"] == 2 * 9 * TOKENS * macs
    assert work["ssd_scan"]["bytes"] == 9 * TOKENS * 33_792
    assert round(work["ssd_scan"]["flops"] / 1e10) == 187
    assert round(work["ssd_scan"]["bytes"] / 1e7) == 997
    # bound by its bytes: 12.2 ms a step at the least
    assert work["ssd_scan"]["bytes"] / 819e9 \
        > work["ssd_scan"]["flops"] / 197e12
    assert round(1e4 * work["ssd_scan"]["bytes"] / 819e9) == 122
    pairs = SEQ * (SEQ + 1) // 2
    assert pairs == 8_390_656
    assert work["gqa_attention"]["flops"] == ROWS * 32 * 4 * 128 * pairs
    assert round(work["gqa_attention"]["flops"] / 1e10) == 110
    assert work["gqa_attention"]["bytes"] == TOKENS * 2 * 40 * 128 * 2
    held = LAYERS * TOKENS * 5  # 10 a token, half the router held
    expert = 3 * D * 768
    assert work["expert_matmul"] == OPS.kernels(
        SIZES, ROWS, 2, assignments=held)["expert_matmul"]
    assert work["expert_matmul"]["flops"] == 2 * held * expert
    assert round(work["expert_matmul"]["flops"] / 1e11) == 309
    assert work["expert_matmul"]["bytes"] == LAYERS * 36 * expert * 2 \
        + held * D * 6
    counted = OPS.kernels(SIZES, ROWS, 2, assignments=held + 1000)
    assert counted["expert_matmul"]["flops"] == 2 * (held + 1000) * expert
    # the issue's matrix work a step: 107.4 TFLOP without scan, router, head
    per_token = 2 * (9 * (mamba - 5 * 8_448 - 3 * 128 - 8_192) + attention
                     + LAYERS * (D * 72 + 3 * D * 1_536))
    step = TOKENS * per_token - TOKENS * 2 * LAYERS * D * 72 \
        + work["gqa_attention"]["flops"] + work["expert_matmul"]["flops"]
    assert round(step / 1e11) == 1074
    assert OPS.flops_per_row(SIZES) == SEQ * per_token + sum(
        k["flops"] for k in OPS.kernels(SIZES, 1, 2).values()) \
        + 2 * D * 50_176
    counts = OPS.counts(SIZES, rows=16, steps=2, bytes_per_value=2)
    assert counts["flops"] == 16 * OPS.flops_per_row(SIZES)
    assert counts["bytes"] == 2 * 2 * PARAMETERS + 16 * 4 * (SEQ + 50_176)


def test_ops_parameters_are_the_programs():
    import jax

    from storm_tpu.models.registry import build_model

    for name, count in (("granite_4_h_small", PARAMETERS),
                        ("granite_h_tiny", None)):
        model = build_model(name)
        params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        held = sum(x.size for x in jax.tree.leaves(params))
        sizes = spec.config(name)["published"]
        assert OPS.parameters(sizes) == held, name
        assert count in (None, held)
        assert model.max_rows == sizes["held"]["rows_per_step"]
        assert model.hyper["chunk"] == sizes["held"]["ssd_chunk"]
        assert list(model.hyper["layer_types"]) == [
            sizes["layer_types"][i] for i in sizes["held"]["layers"]]
        assert (model.hyper["groups"], model.hyper["top_k"],
                model.hyper["n_experts"], model.hyper["experts_held"]) == (
            sizes["mamba_n_groups"], sizes["num_experts_per_tok"],
            sizes["num_local_experts"], sizes["held"]["num_local_experts"])
        assert model.hyper["attention_multiplier"] \
            == sizes["attention_multiplier"]
        assert len(params["layers"]) == len(sizes["held"]["layers"])


def test_rows_per_step_reads_the_window_shape():
    names = ["%fusion.1 = f32[8,4096,4096]{2,1,0} fusion(f32[8,4096,4096])",
             "%fusion.2 = bf16[8,4096,8448]{2,1,0} fusion()",
             "%fusion.3 = f32[32768,72]{1,0} fusion()"]
    assert OPS.rows_per_step(names, SIZES) == 8
    assert OPS.rows_per_step(names[1:], SIZES) is None


# ---- every listed metric over a trace of this cell's shapes ------------------

MS = 1e6  # nanoseconds
DEV = "/device:TPU:0"


def _loop(number, carried):
    return (f"%while.{number} = ({carried}) while(({carried}) %tuple.3), "
            "condition=%c, body=%b")


# one step's top-level operations as the v5e compiler names them (a compile
# for the described chip at 8 windows, layouts dropped)
HELD = TOKENS * 5  # held assignments a layer, as a uniform router gives them
BUFFER = (640 + 36) * 512 + 1
STREAM = "%fusion.9 = f32[8,4096,4096]{2,1,0} fusion(%p), kind=kLoop"
PROJ = "%fusion.12 = bf16[8,4096,8576]{2,1,0} fusion(%n, %w), kind=kOutput"
CONV = ("%mix.elementwise.9 = bf16[8,4096,8448]{2,1,0} "
        "custom-call(%f, %w, %b)")
SCAN = _loop(188, "s32[], f32[8,1,128,64,128], bf16[8,4096,8192], "
             "bf16[8,4096,8448], f32[8,4096,128], f32[8,4096,128], "
             "f32[1,128,1], s32[]")
ATTN = _loop(120, "s32[], bf16[8,32,4096,128], s32[8], bf16[8,8,4096,128], "
             "bf16[8,8,4096,128], bf16[8,32,4096,128]")
SORT = (f"%sort.8 = (s32[{TOKENS * 10}], s32[{TOKENS * 10}], "
        f"f32[{TOKENS * 10}]) sort(%a, %i, %w)")
EXP = _loop(148, f"s32[], bf16[{BUFFER},4096], s32[], s32[676], s32[676], "
            f"s32[676], s32[328192], bf16[{TOKENS},4096], "
            "bf16[36,768,4096], bf16[36,4096,768], bf16[36,4096,768], "
            "f32[328192], s32[]")
COMB = _loop(150, f"s32[], f32[32832,4096], s32[], s32[982], s32[982], "
             f"s32[982], s32[328192], s32[328192], bf16[{BUFFER},4096]")
ZERO = ("%broadcast.70 = f32[32832,4096]{1,0} "
        "broadcast(f32[] %constant.3)")
STEP_OPS = [
    (STREAM, "jit(fwd)/norm/mul", 0, 40),
    (PROJ, "jit(fwd)/mix.elementwise/proj/dot_general", 40, 460),
    (CONV, "jit(fwd)/mix.elementwise/pallas_call", 500, 70),
    (SCAN, "jit(fwd)/mix.elementwise/mix.ssd_scan/while", 570, 60),
    (ATTN, "jit(fwd)/mix.elementwise/mix.attention/while", 630, 10),
    (SORT, "jit(fwd)/moe.route/jit(sort)/sort", 640, 50),
    (EXP, "jit(fwd)/moe.experts/while", 690, 300),
    (COMB, "jit(fwd)/moe.combine/while", 990, 150),
    (ZERO, None, 1140, 10),
]
STEP_MS = 1150.0
HELD_A_STEP = LAYERS * HELD
COMPUTED_A_STEP = HELD_A_STEP + LAYERS * 36 * 128  # half a small tile a run
WANT = {"model_step_ms": STEP_MS, "granite_ssd_scan_ms": 60.0,
        "granite_gqa_attention_ms": 10.0, "granite_expert_matmul_ms": 300.0,
        "granite_expert_combine_ms": 150.0, "moe_routing_ms": 50.0,
        "mixer_elementwise_ms": 70.0, "projections_ms": 460.0,
        "step_named_share": 100.0 * 1140 / 1150,
        # the two cut executions lack their first 500 ms of operations
        "device_idle_share": 100.0 * 2 * 500 / (8 * 1150),
        "batch_size_mean": 8.0, "cut_hold_mean_ms": 0.0,
        "expert_assignments_held_share": 50.0,
        "expert_tokens_max_over_mean": 1.25, "parse_ms_per_record": 0.05,
        "egress_ms_per_record": 2.5, "step_gap_max_ms": STEP_MS,
        "granite_expert_tile_fill_share":
            100.0 * HELD_A_STEP / COMPUTED_A_STEP}


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
        log.append({"step": n, "engine": "granite_4_h_small", "padded": ROWS,
                    "rows": ROWS, "sources": 2, "seen": True,
                    "t_first_enq": ready - 3.4, "t_cut": ready - 2.32,
                    "t_staged": ready - 2.31, "t_launched": ready - 2.30,
                    "t_ready": ready, "t_fetched": ready + 0.001,
                    "t_resolved": ready + 0.002})
    run._step_rows = log
    run.delivery_times = [off - 14 * STEP_MS / 1e3, off]
    run.delivered_in_window = ROWS * 14
    hist = lambda count, total: {"count": count, "sum": total}  # noqa: E731
    run.registry_before = {"inference-bolt": {}, "kafka-bolt": {}}
    run.registry_after = {
        "inference-bolt": {
            "decode_ms": hist(112, 112 * 0.05), "batch_size": hist(14, 112.0),
            "encode_ms": hist(112, 112 * 2.0), "cut_hold_ms": hist(14, 0.0),
            "expert_tokens_max_over_mean": hist(140, 175.0),
            "expert_assignments_held": 14 * HELD_A_STEP,
            "expert_assignments_absent": 14 * HELD_A_STEP,
            "expert_rows_computed": 14 * COMPUTED_A_STEP},
        "kafka-bolt": {"produce_ms": hist(112, 112 * 0.5)}}
    return run


def test_the_new_entries_list_what_reads_here():
    """Found by name. How many cells the benchmark has and which comes last
    is no business of this file's: the next cell must not fail it."""
    cell = spec.cell(BENCH, CELL)
    assert cell in BENCH["workloads"]
    assert cell["chips"] == 1 and cell["traffic"] == "tokens_backlog"
    assert cell["config"] == "granite_4_h_small" and len(cell["why"]) <= 200
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == "granite_4_h_small"] == [CELL]  # no second cell
    assert not [w for w in BENCH["workloads"] if w["chips"] != 1]
    assert BENCH["run_seconds"] == 20
    e2e = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end", cell)}
    assert e2e == {"records_per_s", "setup_s"}
    assert _entry("end_to_end", "records_per_s")["bound"] == 0.01
    assert _entry("end_to_end", "setup_s")["bound"] == 0.1
    layer = {m["name"]: m for m in spec.metrics_for(BENCH, "per_layer", cell)}
    assert set(layer) == SHARED | set(NEW) | {"compile_s", "cache_misses"}
    # loops told by other models' shapes or parts are not this cell's
    assert not {"ssd_scan_ms", "gqa_attention_ms", "expert_matmul_ms",
                "relu2_expert_matmul_ms", "expert_combine_ms", "rope_ms",
                "keye_expert_matmul_ms", "solar_gqa_attention_ms"} \
        & set(layer)
    for name, (reader, _part, _kernel) in NEW.items():
        counted = name == "granite_expert_tile_fill_share"
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["layer"] == "engine and model"
        assert layer[name]["moves"] == "records_per_s"
        assert layer[name]["unit"] == ("%" if name.endswith("_share")
                                       else "ms")
        assert layer[name]["better"] == (
            "higher" if name.endswith("_share") else "lower")
        assert layer[name]["source"] == (
            "program_counter" if counted else "device_trace")
        assert spec.metric(name)["reader"] == reader
    for name in SHARED:
        assert CELL in layer[name]["workloads"]
        # beside the other Mamba-2/expert hybrid under the same mix
        assert "nemotron_3_nano_30b.tokens_backlog" in layer[name]["workloads"]
    # the new metrics stand after every metric an earlier PR brought
    names = [m["name"] for m in BENCH["per_layer"]]
    first = min(names.index(n) for n in NEW)
    assert set(names[first:first + len(NEW)]) == set(NEW)
    assert first > names.index("index_blocks_picked_share")
    # the mix is the one Nemotron's, Kimi-Linear's, Kimi K2's and Solar's run
    traffic = spec.traffic("tokens_backlog")
    assert (traffic["outstanding"], traffic["pool"], traffic["payload"],
            traffic["arrivals"], traffic["warmup_seconds"],
            traffic["trace_seconds"]) == (
        128, 32, "arrow_tensor", "closed_loop", 4, 6)
    assert traffic["program"] == {"topology.spout_scheme": "raw"}


def test_the_metric_files_name_their_readers_and_parts():
    from storm_tpu.ops import parts

    kernels = OPS.kernels(SIZES, ROWS, 2)
    for name, (reader, part, kernel) in NEW.items():
        doc = spec.metric(name)
        assert doc["reader"] == reader and doc["doc"]
        if part is None:
            continue
        assert doc["args"]["prefix"] == "jit_fwd"
        assert doc["args"]["part"] == part and part in parts.VOCABULARY
        assert "pattern" not in doc["args"]  # by the part, not by a shape
        assert doc["args"].get("kernel") == kernel
        assert kernel is None or kernel in kernels
    counted = spec.metric("granite_expert_tile_fill_share")["args"]
    assert counted == {"component": "inference-bolt",
                       "of": "expert_assignments_held",
                       "among": ["expert_rows_computed"]}
    # the counters' names are the program's
    import inspect

    from storm_tpu.parallel import moe
    source = inspect.getsource(moe.observe_expert_counts)
    assert '"expert_assignments_held"' in source
    assert '"expert_rows_computed"' in source


def test_every_listed_metric_reads_a_number_from_a_trace_of_its_shapes():
    run = _traced_run()
    cell = spec.cell(BENCH, CELL)
    listed = spec.metrics_for(BENCH, "per_layer", cell)
    got = harness.read_metrics(run, [m for m in listed if m["name"]
                                     not in ("compile_s", "cache_misses")])
    assert set(got) == SHARED | set(NEW)
    for name, want in WANT.items():
        assert got[name]["value"] == pytest.approx(want, abs=1e-6), name
    shares = ("model_roofline_share", "granite_ssd_scan_roofline_share",
              "granite_gqa_attention_roofline_share",
              "granite_expert_matmul_roofline_share",
              "granite_expert_tile_fill_share")
    for name in shares:
        assert 0 < got[name]["value"] < 100 and math.isfinite(
            got[name]["value"])
    work = OPS.kernels(SIZES, ROWS, 2)
    assert got["granite_ssd_scan_roofline_share"]["value"] == pytest.approx(
        100 * work["ssd_scan"]["bytes"] / 819e9 / 0.060)
    assert got["granite_gqa_attention_roofline_share"]["value"] == \
        pytest.approx(100 * work["gqa_attention"]["flops"] / 197e12 / 0.010)
    assert got["granite_expert_matmul_roofline_share"]["value"] == \
        pytest.approx(100 * work["expert_matmul"]["flops"] / 197e12 / 0.300)
    assert {k: v["rows"] for k, v in run.notes["kernels"].items()} == {
        "ssd_scan": ROWS, "gqa_attention": ROWS, "expert_matmul": ROWS}
    assert got["model_roofline_share"]["value"] == pytest.approx(
        100 * ROWS * OPS.flops_per_row(SIZES) / 197e12 / 1.150)
    assert run.roofline_bound == "compute"
    assert run.notes["part_loops"] == pytest.approx(
        {"mix.ssd_scan": 60.0, "mix.attention": 10.0, "moe.experts": 300.0,
         "moe.combine": 150.0})
    # the line such a run prints is complete by the driver's own check
    got.update(compile_s={"value": 1.0, "unit": "s"},
               cache_misses={"value": 0.0, "unit": "count"})
    row = {"correct": True, "attempted": 1, "failed": 0, "metrics": got,
           "device": {}}
    assert check_line.problems(row, CELL, traced=True) == []


def test_a_program_without_the_new_names_reads_nothing_and_raises_nothing():
    """Another model's program (a rotary model's loops: no scan, no expert
    layer, no counters): each time reads 0.0, each share is left out of the
    line; every one is where there is no trace."""
    others = [
        _loop(59, "s32[], bf16[4,32,16384,128], s32[4], bf16[4,4,16384,128], "
              "bf16[4,4,16384,128], s32[], s32[1]")]
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
        others[0]: "jit(fwd)/mix.elementwise/mix.window_attention/while"}},
        "start_s": None}
    run.registry_before = run.registry_after = {"inference-bolt": {}}
    for name, (reader, _part, _kernel) in sorted(NEW.items()):
        doc = spec.metric(name)
        value = spec.plugin("readers", doc["reader"]).read(run, **doc["args"])
        assert value == (0.0 if reader == "trace_part_time" else None), name
    untraced = harness.Run(spec.cell(BENCH, CELL), CONFIG, {}, 0, 1.0)
    untraced.registry_before = untraced.registry_after = {}
    for name in sorted(NEW):
        doc = spec.metric(name)
        assert spec.plugin("readers", doc["reader"]).read(
            untraced, **doc["args"]) is None


def test_the_windows_come_from_the_held_slice_and_a_kind_of_their_own():
    """A kind of input a family (PERF.md section 7 item 4 (d)): no two
    configurations are coupled through one kind's look-up by shape; four
    other configurations' windows are as long and their slices others."""
    make = spec.plugin("inputs", "granite_tokens").make
    a, b = make(5, (SEQ,), 3_000_000_019), make(5, (SEQ,), 3_000_000_019)
    assert (a == b).all() and a.shape == (5, SEQ)
    assert a.min() >= 0 and 50_000 < a.max() < 50_176
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
    assert sorted(kinds["granite_tokens"]) == [(40,), (SEQ,)]
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
        command + ["--workload", "granite_h_tiny.tokens_backlog", "--seed",
                   "3000000029", "--seconds", "2", "--trace", "0",
                   "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=105)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    row = lines[-1]
    assert row["correct"] is True and row["failed"] == 0
    assert row["attempted"] > 0
    assert set(row["metrics"]) == {"records_per_s", "setup_s"}
    assert 0 < row["checks"]["farthest_output"][0] <= 0.005
    every = [line for line in lines if line.get("phase") == "all_metrics"][0]
    layer = every["per_layer"]
    assert layer["batch_size_mean"] <= 4.0  # the toy's one bucket: (4,)
    assert 40.0 < layer["expert_assignments_held_share"] < 70.0  # 5 of 9
    assert layer["expert_tokens_max_over_mean"] >= 1.0
    assert 50.0 < layer["granite_expert_tile_fill_share"] <= 100.0


@pytest.mark.timeout(115)
def test_the_tolerances_two_readings_and_the_mixers_check_at_toy_sizes(
        tmp_path):
    """``tools/tolerance.py`` at the toy sizes: the program answers every
    row, the float8 control none. ``tools/granite_mixer_check.py``: the
    Mamba-2 mixer at both chunks and the expert layer against the
    reference's, in float32 here, and the scan's two timings' lines."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/tolerance.py", "--config",
         "granite_h_tiny", "--rehearse", "5:f8"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    (row,) = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert row["program"]["correct"] is True
    assert row["program"]["rows_failed"] == 0 and row["program"]["rows"] == 32
    assert row["program"]["max"] < 1e-5  # float32 here: summation order
    assert row["tolerance"] == 0.005
    assert row["float8"]["correct"] is False
    assert row["float8"]["rows_failed"] == 32
    assert row["float8"]["min"] > 100 * row["program"]["max"]
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/granite_mixer_check.py",
         "--config", "granite_h_tiny", "--rehearse", "--seed", "5",
         "--limit", "1e-4", "--repeats", "2"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=50)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert [(r["check"], r.get("chunk")) for r in rows] == [
        ("mamba", 128), ("mamba", 256), ("experts", None), ("scan", 128),
        ("scan", 256)]
    assert all(r["pass"] and r["length"] == 40 for r in rows)
    assert rows[0]["forms"] == ["short_conv=xla", "ssd_scan=chunked"]
    assert (rows[2]["held"], rows[2]["width"]) == (5, 9)
    assert "expert_ffn=swiglu" in rows[2]["forms"]
    assert all(r["ms_median"] > 0 and r["rows"] == 4 for r in rows[3:])
