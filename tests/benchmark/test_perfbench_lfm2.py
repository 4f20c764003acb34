"""The benchmark's LFM2 files (PR 68): the configuration against the catalog
row it is cut from and the program's own parameter tree, ``ops/lfm2.py``
against the issue's table and its sums by hand, every per-layer metric that
lists the new cell over a trace of its shapes made by hand, the new entries
in ``BENCHMARK.json`` (found by name: neither how many cells there are nor
which is last is this file's business), the metric files against their
readers and parts, the windows' kind, and rehearsals of
``lfm2_tiny.tokens_backlog`` and of the two tools on the CPU."""

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

CELL = "lfm2_24b_a2b.tokens_backlog"
BENCH = spec.benchmark()
CONFIG = spec.config("lfm2_24b_a2b")
SIZES = CONFIG["published"]
OPS = spec.plugin("ops", "lfm2")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMETERS = 5_267_090_176
ROWS, SEQ, LAYERS, D, F, VOCAB = 8, 4096, 10, 2048, 1536, 65536
TOKENS = ROWS * SEQ
PATTERN = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2
# every width and count of the row: none may differ from the published value
WIDTHS = {"hidden_size": 2048, "intermediate_size": 11776,
          "moe_intermediate_size": 1536, "num_attention_heads": 32,
          "num_key_value_heads": 8, "num_experts": 64,
          "num_experts_per_tok": 4, "num_dense_layers": 2, "conv_L_cache": 3,
          "conv_bias": False, "norm_eps": 1e-05, "norm_topk_prob": True,
          "use_expert_bias": True, "routed_scaling_factor": 1,
          "vocab_size": 65536, "max_position_embeddings": 128000,
          "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
SHARED = {"parse_ms_per_record", "batch_size_mean", "model_step_ms",
          "model_roofline_share", "egress_ms_per_record", "device_idle_share",
          "cut_hold_mean_ms", "step_named_share", "step_gap_max_ms",
          "mixer_elementwise_ms", "projections_ms", "moe_routing_ms",
          "expert_tokens_max_over_mean", "expert_assignments_held_share"}
# metric -> (reader, part, kernel)
NEW = {"lfm2_gated_conv_ms": ("trace_part_time", "mix.gated_conv", None),
       "lfm2_gated_conv_roofline_share": (
           "trace_part_share", "mix.gated_conv", "gated_conv"),
       "lfm2_attention_ms": ("trace_part_time", "mix.attention", None),
       "lfm2_attention_roofline_share": (
           "trace_part_share", "mix.attention", "attention"),
       "lfm2_expert_matmul_ms": ("trace_part_time", "moe.experts", None),
       "lfm2_expert_matmul_roofline_share": (
           "trace_part_share", "moe.experts", "expert_matmul"),
       "lfm2_expert_combine_ms": ("trace_part_time", "moe.combine", None),
       "lfm2_dense_feed_forward_ms": ("trace_part_time", "ffn", None),
       "lfm2_rope_ms": ("trace_part_time", "mix.rope", None),
       "lfm2_expert_tile_fill_share": (
           "registry_counter_share", None, None)}


def _entry(group, name):
    (found,) = [e for e in BENCH[group] if e["name"] == name]
    return found


def test_configuration_states_the_cut_and_keeps_every_width():
    held = SIZES["held"]
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    # the top level is the configuration as run; ``published`` as published
    assert CONFIG["num_hidden_layers"] == LAYERS
    assert SIZES["num_hidden_layers"] == 40
    assert (held["layers"], held["num_hidden_layers"],
            held["pipeline_stages"], held["chips_per_layer"],
            held["num_experts"], held["first_expert"], held["vocab_size"],
            held["sequence_length"], held["head_dim"],
            held["expert_tile"]) == (
        list(range(10)), LAYERS, 4, 1, 64, 0, VOCAB, SEQ, 64, 1024)
    assert held["rows_per_step"] in (8, 4)
    for key, value in WIDTHS.items():
        assert CONFIG[key] == SIZES[key] == value, key
    for key, value in SIZES.items():
        if key not in CONFIG["reduced"] and key != "held":
            assert CONFIG[key] == value, key
    # two leading layers, then a period of four, nine and a half times; the
    # held ten are both dense layers and two whole periods
    assert SIZES["layer_types"] == (PATTERN[:2] + PATTERN[2:6] * 10)[:40]
    assert [SIZES["layer_types"][i] for i in held["layers"]] == PATTERN
    assert SIZES["model_type"] == "lfm2_moe"
    deployment = CONFIG["deployment"]
    assert "one v5e-4 host" in deployment
    assert "four pipeline stages" in deployment
    assert "One chip a layer" in deployment
    assert "no code stands in" in deployment
    assert "5,267,090,176" in deployment and "23,843,661,440" in deployment
    assert CONFIG["model"] == {"name": "lfm2_24b_a2b", "input_shape": [SEQ],
                               "num_classes": VOCAB, "dtype": "bfloat16"}
    for key in ("why", "head_dim", "gated_conv", "attention", "router",
                "experts", "tied", "weights", "inputs", "output", "ids",
                "tiles", "stream"):
        assert CONFIG["assumed"][key], key
    assert "1e-6" in CONFIG["assumed"]["router"]
    assert "two matrices" in CONFIG["assumed"]["tied"]
    on_device = CONFIG["on_device"]
    assert on_device["parameters"] == PARAMETERS
    assert on_device["parameters_bytes"] == 2 * PARAMETERS
    assert on_device["parameters_float32_at_load_bytes"] == 0
    # the issue's rule: the rows that ship are those at which parameters and
    # the compiler's temporaries stay at or under 15.0 GB
    shipped = held["rows_per_step"]
    assert on_device["parameters_bytes"] + on_device[
        f"program_temporaries_bucket_{shipped}_bytes"] <= 15.0e9
    assert on_device["reference_temporaries_32_rows_bytes"] > 0
    assert CONFIG["inputs"] == {"kind": "lfm2_tokens", "decimals": 0,
                                "candidates": 32}
    assert 0 < CONFIG["tolerance"]["relative_distance"] < 0.2
    assert "float8" in CONFIG["tolerance"]["why"]
    for key in ("delivery", "malformed_records", "offsets", "experts"):
        assert CONFIG["guarantees"][key], key
    entry = _entry("configs", "lfm2_24b_a2b")
    assert entry["file"] == "benchmarks/configs/lfm2_24b_a2b.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] and len(entry["why"]) <= 200


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_in_the_file():
    rows = [json.loads(line) for line in open(CATALOG)]
    (row,) = [r for r in rows if r["name"] == "LFM2-24B-A2B"]
    assert CONFIG["source"] == row["source_url"]
    assert row["config"]["model_type"] == "lfm2_moe"
    assert row["head_dim"] is None  # the file's ``assumed.head_dim``
    for key, value in row["config"].items():
        assert SIZES[key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


def test_ops_count_the_issues_table_by_hand():
    """The table of ISSUE 68, a row at a time, and its sums."""
    conv = D * 3 * D + 3 * D + D * D
    assert conv == 16_783_360 == OPS.conv_parameters(SIZES)
    attention = 2 * 4_194_304 + 2 * 1_048_576 + 2 * 64
    assert attention == 10_485_888 == OPS.attention_parameters(SIZES)
    dense = 3 * D * 11_776
    assert dense == 72_351_744 == OPS.dense_parameters(SIZES)
    experts = 64 * 3 * D * F + D * 64 + 64
    assert experts == 604_110_912 == OPS.expert_layer_parameters(SIZES)
    assert conv + dense + 2 * D == 89_139_200
    assert conv + experts + 2 * D == 620_898_368
    assert attention + experts + 2 * D == 614_600_896
    ends = VOCAB * D + D
    assert ends == 134_219_776
    assert 2 * 89_139_200 + 2 * 614_600_896 + 6 * 620_898_368 + ends \
        == PARAMETERS == OPS.parameters(SIZES)
    whole = dict(SIZES, held={"sequence_length": SEQ})  # nothing cut
    assert OPS.parameters(whole) == 23_843_661_440  # the published "24B"
    # the kernels of a step of 8
    work = OPS.kernels(SIZES, ROWS, 2)
    assert work["gated_conv"]["flops"] == 8 * TOKENS * D * 7
    assert work["gated_conv"]["bytes"] == 8 * TOKENS * 4 * D * 2
    # bound by its bytes: 0.66 ms a layer at the least
    assert work["gated_conv"]["bytes"] / 819e9 \
        > 100 * work["gated_conv"]["flops"] / 197e12 / 100
    assert round(1e5 * work["gated_conv"]["bytes"] / 819e9 / 8) == 66
    pairs = SEQ * (SEQ + 1) // 2
    assert pairs == 8_390_656
    assert work["attention"]["flops"] == 2 * ROWS * 32 * 4 * 64 * pairs
    assert round(work["attention"]["flops"] / 1e10) == 110  # 1.1 TFLOP
    assert work["attention"]["bytes"] == 2 * TOKENS * 2 * 40 * 64 * 2
    held = 8 * TOKENS * 4  # four a token, every one held, eight layers
    expert = 3 * D * F
    assert work["expert_matmul"] == OPS.kernels(
        SIZES, ROWS, 2, assignments=held)["expert_matmul"]
    assert work["expert_matmul"]["flops"] == 2 * held * expert
    assert round(work["expert_matmul"]["flops"] / 1e11) == 198  # 19.8 TFLOP
    assert work["expert_matmul"]["bytes"] == 8 * 64 * expert * 2 \
        + held * D * 6
    counted = OPS.kernels(SIZES, ROWS, 2, assignments=held + 1000)
    assert counted["expert_matmul"]["flops"] == 2 * (held + 1000) * expert
    # the issue's matrix work a step: 40.6 TFLOP
    per_token = 2 * (8 * 4 * D * D + 2 * (attention - 128) + 2 * dense
                     + 8 * D * 64)
    step = TOKENS * per_token + work["attention"]["flops"] \
        + work["expert_matmul"]["flops"]
    assert round(step / 1e11) == 406
    assert OPS.flops_per_row(SIZES) == SEQ * per_token + (
        work["attention"]["flops"] + work["expert_matmul"]["flops"]) // ROWS \
        + 2 * D * VOCAB
    counts = OPS.counts(SIZES, rows=16, steps=2, bytes_per_value=2)
    assert counts["flops"] == 16 * OPS.flops_per_row(SIZES)
    assert counts["bytes"] == 2 * 2 * PARAMETERS + 16 * 4 * (SEQ + VOCAB)


def test_ops_parameters_are_the_programs():
    import jax

    from storm_tpu.models.registry import build_model

    for name, count in (("lfm2_24b_a2b", PARAMETERS), ("lfm2_tiny", None)):
        model = build_model(name)
        params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        held = sum(x.size for x in jax.tree.leaves(params))
        sizes = spec.config(name)["published"]
        assert OPS.parameters(sizes) == held, name
        assert count in (None, held)
        assert model.max_rows == sizes["held"]["rows_per_step"]
        assert list(model.hyper["layer_types"]) == [
            sizes["layer_types"][i] for i in sizes["held"]["layers"]]
        assert (model.hyper["dense"], model.hyper["top_k"],
                model.hyper["n_experts"], model.hyper["experts_held"],
                model.hyper["taps"], model.hyper["head_dim"],
                model.hyper["rope_theta"]) == (
            sizes["num_dense_layers"], sizes["num_experts_per_tok"],
            sizes["num_experts"], sizes["held"]["num_experts"],
            sizes["conv_L_cache"], sizes["held"]["head_dim"],
            sizes["rope_parameters"]["rope_theta"])
        assert len(params["layers"]) == len(sizes["held"]["layers"])


def test_rows_per_step_reads_the_window_shape():
    names = ["%fusion.1 = f32[8,4096,2048]{2,1,0} fusion(f32[8,4096,2048])",
             "%fusion.2 = bf16[8,4096,6144]{2,1,0} fusion()",
             "%fusion.3 = f32[32768,64]{1,0} fusion()"]
    assert OPS.rows_per_step(names, SIZES) == 8
    assert OPS.rows_per_step(names[1:], SIZES) is None


# ---- every listed metric over a trace of this cell's shapes ------------------

MS = 1e6  # nanoseconds
DEV = "/device:TPU:0"


def _loop(number, carried):
    return (f"%while.{number} = ({carried}) while(({carried}) %tuple.3), "
            "condition=%c, body=%b")


# one step's top-level operations as the v5e compiler names them (layouts
# dropped)
HELD = TOKENS * 4  # assignments a layer: four a token, every one held
BUFFER = (128 + 64) * 1024 + 1
STREAM = "%fusion.9 = f32[8,4096,2048]{2,1,0} fusion(%p), kind=kLoop"
PROJ = "%fusion.12 = bf16[8,4096,6144]{2,1,0} fusion(%n, %w), kind=kOutput"
CONV = "%mix.gated_conv.9 = bf16[8,4096,2048]{2,1,0} custom-call(%f, %w, %f, %f)"
TURN = ("%_norm_turn_lanes.3 = bf16[8,4096,2048]{2,1,0} "
        "custom-call(%s, %cos, %sin, %q)")
ATTN = _loop(120, "s32[], bf16[8,4096,2048], bf16[8,4096,2048], "
             "bf16[8,4096,512], bf16[8,4096,512]")
FFN = _loop(130, "s32[], bf16[8,4096,2048], bf16[8,4096,2048], "
            "bf16[2048,11776], bf16[2048,11776], bf16[11776,2048]")
SORT = (f"%sort.8 = (s32[{HELD}], s32[{HELD}], f32[{HELD}]) "
        "sort(%a, %i, %w)")
EXP = _loop(148, f"s32[], bf16[{BUFFER},2048], s32[], s32[192], s32[192], "
            f"s32[192], s32[{HELD + 1024}], bf16[{TOKENS},2048], "
            "bf16[64,1536,2048], bf16[64,2048,1536], bf16[64,2048,1536], "
            f"f32[{HELD + 1024}], s32[]")
COMB = _loop(150, f"s32[], f32[{TOKENS},2048], s32[], s32[256], s32[256], "
             f"s32[256], s32[{HELD + 512}], s32[{HELD + 512}], "
             f"bf16[{BUFFER},2048]")
ZERO = ("%broadcast.70 = f32[1,2048]{1,0} broadcast(f32[] %constant.3)")
STEP_OPS = [
    (STREAM, "jit(fwd)/norm/mul", 0, 20),
    (PROJ, "jit(fwd)/mix.elementwise/proj/dot_general", 20, 60),
    (CONV, "jit(fwd)/mix.elementwise/mix.gated_conv/pallas_call", 80, 8),
    (TURN, "jit(fwd)/mix.elementwise/mix.rope/jit(_norm_turn_lanes)/"
     "pallas_call", 88, 2),
    (ATTN, "jit(fwd)/mix.elementwise/mix.attention/while", 90, 20),
    (FFN, "jit(fwd)/ffn/while", 110, 55),
    (SORT, "jit(fwd)/moe.route/jit(sort)/sort", 165, 25),
    (EXP, "jit(fwd)/moe.experts/while", 190, 180),
    (COMB, "jit(fwd)/moe.combine/while", 370, 38),
    (ZERO, None, 408, 2),
]
STEP_MS = 410.0
HELD_A_STEP = 8 * HELD
COMPUTED_A_STEP = HELD_A_STEP + 8 * 64 * 256  # half a small tile a run
WANT = {"model_step_ms": STEP_MS, "lfm2_gated_conv_ms": 8.0,
        "lfm2_attention_ms": 20.0, "lfm2_expert_matmul_ms": 180.0,
        "lfm2_expert_combine_ms": 38.0, "lfm2_dense_feed_forward_ms": 55.0,
        "lfm2_rope_ms": 2.0, "moe_routing_ms": 25.0,
        "mixer_elementwise_ms": 0.0, "projections_ms": 60.0,
        "step_named_share": 100.0 * 408 / 410,
        # the two cut executions lack their first 80 ms of operations
        "device_idle_share": 100.0 * 2 * 80 / (8 * 410),
        "batch_size_mean": 8.0, "cut_hold_mean_ms": 0.0,
        "expert_assignments_held_share": 100.0,
        "expert_tokens_max_over_mean": 1.25, "parse_ms_per_record": 0.05,
        "egress_ms_per_record": 2.5, "step_gap_max_ms": STEP_MS,
        "lfm2_expert_tile_fill_share":
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
        log.append({"step": n, "engine": "lfm2_24b_a2b", "padded": ROWS,
                    "rows": ROWS, "sources": 2, "seen": True,
                    "t_first_enq": ready - 3.4, "t_cut": ready - 0.82,
                    "t_staged": ready - 0.81, "t_launched": ready - 0.80,
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
            "expert_tokens_max_over_mean": hist(112, 140.0),
            "expert_assignments_held": 14 * HELD_A_STEP,
            "expert_assignments_absent": 0,
            "expert_rows_computed": 14 * COMPUTED_A_STEP},
        "kafka-bolt": {"produce_ms": hist(112, 112 * 0.5)}}
    return run


def test_the_new_entries_list_what_reads_here():
    """Found by name. How many cells the benchmark has and which comes last
    is no business of this file's: the next cell must not fail it."""
    cell = spec.cell(BENCH, CELL)
    assert cell in BENCH["workloads"]
    assert cell["chips"] == 1 and cell["traffic"] == "tokens_backlog"
    assert cell["config"] == "lfm2_24b_a2b" and len(cell["why"]) <= 200
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == "lfm2_24b_a2b"] == [CELL]  # no second cell
    assert BENCH["run_seconds"] == 20
    e2e = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end", cell)}
    assert e2e == {"records_per_s", "setup_s"}
    assert _entry("end_to_end", "records_per_s")["bound"] == 0.01
    assert _entry("end_to_end", "setup_s")["bound"] == 0.1
    layer = {m["name"]: m for m in spec.metrics_for(BENCH, "per_layer", cell)}
    assert set(layer) == SHARED | set(NEW) | {"compile_s", "cache_misses"}
    # loops told by other models' shapes or parts are not this cell's
    assert not {"ssd_scan_ms", "gqa_attention_ms", "expert_matmul_ms",
                "expert_combine_ms", "rope_ms", "granite_expert_matmul_ms",
                "falcon_h1_feed_forward_ms", "trinity_rope_ms"} & set(layer)
    for name, (reader, _part, _kernel) in NEW.items():
        counted = name == "lfm2_expert_tile_fill_share"
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
        # beside the other hybrid with experts under the same mix
        assert "granite_4_h_small.tokens_backlog" in layer[name]["workloads"]
    # the new metrics stand together, after every metric an earlier PR brought
    names = [m["name"] for m in BENCH["per_layer"]]
    first = min(names.index(n) for n in NEW)
    assert set(names[first:first + len(NEW)]) == set(NEW)
    assert first > names.index("falcon_h1_rope_ms")
    # the mix is the one Granite's, Nemotron's, Kimi-Linear's and Solar's run
    traffic = spec.traffic("tokens_backlog")
    assert (traffic["outstanding"], traffic["pool"], traffic["payload"],
            traffic["arrivals"], traffic["warmup_seconds"],
            traffic["trace_seconds"]) == (
        128, 32, "arrow_tensor", "closed_loop", 4, 6)
    assert traffic["program"] == {"topology.spout_scheme": "raw"}


def test_the_metric_files_name_their_readers_and_parts():
    from storm_tpu.ops import parts

    assert parts.MIX_GATED_CONV == "mix.gated_conv"
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
    counted = spec.metric("lfm2_expert_tile_fill_share")["args"]
    assert counted == {"component": "inference-bolt",
                       "of": "expert_assignments_held",
                       "among": ["expert_rows_computed"]}
    # no reader came with these metrics: each was there
    for reader in {r for r, _p, _k in NEW.values()}:
        assert os.path.exists(os.path.join(
            spec.BENCH_DIR, "readers", reader + ".py"))


def test_every_listed_metric_reads_a_number_from_a_trace_of_its_shapes():
    run = _traced_run()
    cell = spec.cell(BENCH, CELL)
    listed = spec.metrics_for(BENCH, "per_layer", cell)
    got = harness.read_metrics(run, [m for m in listed if m["name"]
                                     not in ("compile_s", "cache_misses")])
    assert set(got) == SHARED | set(NEW)
    for name, want in WANT.items():
        assert got[name]["value"] == pytest.approx(want, abs=1e-6), name
    shares = ("model_roofline_share", "lfm2_gated_conv_roofline_share",
              "lfm2_attention_roofline_share",
              "lfm2_expert_matmul_roofline_share",
              "lfm2_expert_tile_fill_share")
    for name in shares:
        assert 0 < got[name]["value"] < 100 and math.isfinite(
            got[name]["value"])
    work = OPS.kernels(SIZES, ROWS, 2)
    assert got["lfm2_gated_conv_roofline_share"]["value"] == pytest.approx(
        100 * work["gated_conv"]["bytes"] / 819e9 / 0.008)
    assert got["lfm2_attention_roofline_share"]["value"] == pytest.approx(
        100 * work["attention"]["flops"] / 197e12 / 0.020)
    assert got["lfm2_expert_matmul_roofline_share"]["value"] == \
        pytest.approx(100 * work["expert_matmul"]["flops"] / 197e12 / 0.180)
    assert {k: v["rows"] for k, v in run.notes["kernels"].items()} == {
        "gated_conv": ROWS, "attention": ROWS, "expert_matmul": ROWS}
    assert got["model_roofline_share"]["value"] == pytest.approx(
        100 * ROWS * OPS.flops_per_row(SIZES) / 197e12 / 0.410)
    assert run.roofline_bound == "compute"
    assert run.notes["part_loops"] == pytest.approx(
        {"mix.attention": 20.0, "ffn": 55.0, "moe.experts": 180.0,
         "moe.combine": 38.0})
    # the line such a run prints is complete by the driver's own check
    got.update(compile_s={"value": 1.0, "unit": "s"},
               cache_misses={"value": 0.0, "unit": "count"})
    row = {"correct": True, "attempted": 1, "failed": 0, "metrics": got,
           "device": {}}
    assert check_line.problems(row, CELL, traced=True) == []


def test_a_program_without_the_new_names_reads_nothing_and_raises_nothing():
    """Another model's program (a rotary model's window loops: no gated
    convolution, no feed-forward under its own part, no expert layer, no
    counters): each time reads 0.0, each share is left out of the line; every
    one is None where there is no trace."""
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


def test_the_windows_come_from_the_whole_vocabulary_and_a_kind_of_their_own():
    """A kind of input a family (PERF.md section 7 item 4 (d)): no two
    configurations are coupled through one kind's look-up by shape; five
    other configurations' windows are as long over other vocabularies."""
    make = spec.plugin("inputs", "lfm2_tokens").make
    a, b = make(5, (SEQ,), 3_000_000_019), make(5, (SEQ,), 3_000_000_019)
    assert (a == b).all() and a.shape == (5, SEQ)
    assert a.min() >= 0 and 65_000 < a.max() < VOCAB
    assert (a == a.round()).all()
    assert not (a == make(5, (SEQ,), 3_000_000_020)).all()
    assert make(3, (40,), 1).max() < 96
    assert (make(3, (40,), 7) == spec.plugin("inputs", "granite_tokens").make(
        3, (40,), 7)).all()
    with pytest.raises(ValueError):
        make(1, (44,), 1)  # Nemotron's toy window: another kind's
    kinds = {}
    for name in sorted(os.listdir(os.path.join(spec.BENCH_DIR, "configs"))):
        doc = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", name))
        kinds.setdefault(doc["inputs"]["kind"], []).append(
            tuple(doc["model"]["input_shape"]))
    assert sorted(kinds["lfm2_tokens"]) == [(40,), (SEQ,)]
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
        command + ["--workload", "lfm2_tiny.tokens_backlog", "--seed",
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
    assert layer["expert_assignments_held_share"] == 100.0  # all 12 held
    assert layer["expert_tokens_max_over_mean"] >= 1.0
    assert 50.0 < layer["lfm2_expert_tile_fill_share"] <= 100.0


@pytest.mark.timeout(115)
def test_the_tolerances_two_readings_and_the_mixers_check_at_toy_sizes(
        tmp_path):
    """``tools/tolerance.py`` at the toy sizes: the program answers every
    row, the float8 control none. ``tools/lfm2_mixer_check.py``: both
    operators and the expert layer against the reference's, in float32 here,
    and the three parts' timings' lines (one a part: the CPU's rules give
    XLA's forms alone)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/tolerance.py", "--config",
         "lfm2_tiny", "--rehearse", "5:f8"],
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
        [sys.executable, "benchmarks/tools/lfm2_mixer_check.py",
         "--config", "lfm2_tiny", "--rehearse", "--seed", "5",
         "--limit", "1e-4", "--repeats", "2"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=50)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert [r["check"] for r in rows] == [
        "conv_mixer", "gated_conv", "attention_mixer", "attention",
        "head_norm_turn", "experts"]
    assert all(r["pass"] and r["length"] == 40 and r["rows"] == 4
               for r in rows)
    assert rows[0]["forms"] == rows[1]["forms"] == ["gated_conv=xla"]
    assert rows[3]["forms"] == ["causal_attention=blocked-grouped"]
    assert (rows[5]["held"], rows[5]["width"], rows[5]["absent"]) == (
        12, 12, 0)
    assert "expert_ffn=swiglu" in rows[5]["forms"]
    assert all(r["ms_median"] > 0 for r in rows if "ms_median" in r)
    assert all(r["shipped"] for r in rows if "shipped" in r)
