"""The benchmark's Keye files (PR 59): the configuration against the catalog
row it is cut from and the program's own parameter tree, ``ops/keye.py``
against the issue's table and pair counts by hand, every per-layer metric
that lists the new cell over a trace of its shapes made by hand (rows
unrolled: kernel calls that only their parts' names find), the new entries in
``BENCHMARK.json`` (found by name: neither how many cells there are nor which
is last is this file's business), the metric files against their readers and
parts, the windows' kind, and rehearsals of ``keye_tiny.tokens16k_backlog``
and of the two tools on the CPU."""

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

CELL = "keye_vl2_30b.tokens16k_backlog"
BENCH = spec.benchmark()
CONFIG = spec.config("keye_vl2_30b")
SIZES = CONFIG["published"]
OPS = spec.plugin("ops", "keye")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMETERS = 4_374_622_464
ROWS, SEQ, TOPK, LAYERS = 4, 16384, 2048, 6
TOKENS = ROWS * SEQ
# every width of the row: none may differ from the published value
WIDTHS = {"hidden_size": 2048, "intermediate_size": 6144,
          "moe_intermediate_size": 768, "num_attention_heads": 32,
          "num_key_value_heads": 4, "head_dim": 128, "num_experts": 128,
          "num_local_experts": 128, "num_experts_per_tok": 8,
          "rms_norm_eps": 1e-06, "rope_theta": 10000000,
          "vocab_size": 151936, "max_position_embeddings": 262144}
INDEXER = {"indexer_head_dim": 64, "indexer_num_heads": 16,
           "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
           "q_chunk_size": 512, "topk": 2048}
SHARED = {"parse_ms_per_record", "batch_size_mean", "model_step_ms",
          "model_roofline_share", "egress_ms_per_record", "device_idle_share",
          "cut_hold_mean_ms", "step_named_share", "step_gap_max_ms",
          "mixer_elementwise_ms", "projections_ms", "moe_routing_ms",
          "expert_tokens_max_over_mean", "expert_assignments_held_share"}
# metric -> (reader, part, kernel)
NEW = {"index_select_ms": ("trace_part_time", "mix.index_select", None),
       "index_select_roofline_share": (
           "trace_part_share", "mix.index_select", "index_select"),
       "keye_sparse_attention_ms": (
           "trace_part_time", "mix.sparse_attention", None),
       "keye_sparse_attention_roofline_share": (
           "trace_part_share", "mix.sparse_attention", "sparse_attention"),
       "keye_expert_matmul_ms": ("trace_part_time", "moe.experts", None),
       "keye_expert_matmul_roofline_share": (
           "trace_part_share", "moe.experts", "expert_matmul"),
       "keye_expert_combine_ms": ("trace_part_time", "moe.combine", None),
       "keye_rope_ms": ("trace_part_time", "mix.rope", None),
       "index_blocks_picked_share": ("registry_counter_share", None, None)}


def _entry(group, name):
    (found,) = [e for e in BENCH[group] if e["name"] == name]
    return found


def test_configuration_states_the_cut_and_keeps_every_width():
    held = SIZES["held"]
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    # the top level is the configuration as run; ``published`` as published
    assert CONFIG["num_hidden_layers"] == LAYERS
    assert SIZES["num_hidden_layers"] == 48
    assert (held["layers"], held["num_hidden_layers"],
            held["pipeline_stages"], held["chips_per_layer"],
            held["first_expert"], held["sequence_length"],
            held["rows_per_step"]) == (
        [24, 25, 26, 27, 28, 29], LAYERS, 8, 1, 0, SEQ, ROWS)
    for key, value in WIDTHS.items():
        assert CONFIG[key] == SIZES[key] == value, key
    assert CONFIG["sa_config"] == SIZES["sa_config"] == INDEXER
    assert CONFIG["rope_scaling"] == SIZES["rope_scaling"] == {
        "mrope_section": [16, 24, 24], "rope_type": "default",
        "type": "default"}
    for key, value in SIZES.items():
        if key not in CONFIG["reduced"] and key != "held":
            assert CONFIG[key] == value, key
    # every published block is an expert block
    assert (SIZES["decoder_sparse_step"], SIZES["mlp_only_layers"]) == (1, [])
    assert (SIZES["norm_topk_prob"], SIZES["attention_bias"],
            SIZES["tie_word_embeddings"], SIZES["model_type"]) == (
        True, False, False, "KeyeVL2")
    deployment = CONFIG["deployment"]
    assert "Eight pipeline stages of six layers" in deployment
    assert "all 128 experts" in deployment
    assert "image tower" in deployment and "vision_config" in deployment
    assert "is absent" in deployment
    assert CONFIG["model"] == {"name": "keye_vl2_30b", "input_shape": [SEQ],
                               "num_classes": 151936, "dtype": "bfloat16"}
    for key in ("why", "qk_norm", "mrope", "indexer", "chunk_sizes",
                "attention", "router", "weights", "inputs", "output", "ids",
                "tiles", "stream"):
        assert CONFIG["assumed"][key], key
    assert "no part of the mathematics" in CONFIG["assumed"]["chunk_sizes"]
    on_device = CONFIG["on_device"]
    assert on_device["parameters"] == PARAMETERS
    assert on_device["parameters_bytes"] == 2 * PARAMETERS
    assert on_device["parameters_float32_at_load_bytes"] == 0
    # the issue's rule: the whole vocabulary where parameters and the
    # compiler's temporaries stay at or under 15.0 GB
    assert on_device["parameters_bytes"] \
        + on_device["program_temporaries_bucket_4_bytes"] <= 15.0e9
    assert CONFIG["inputs"] == {"kind": "keye_tokens", "decimals": 0,
                                "candidates": 16}
    assert 0 < CONFIG["tolerance"]["relative_distance"] < 0.2
    assert "float8" in CONFIG["tolerance"]["why"]
    for key in ("delivery", "malformed_records", "offsets", "selection",
                "experts"):
        assert CONFIG["guarantees"][key], key
    entry = _entry("configs", "keye_vl2_30b")
    assert entry["file"] == "benchmarks/configs/keye_vl2_30b.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] and len(entry["why"]) <= 200


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_in_the_file():
    rows = [json.loads(line) for line in open(CATALOG)]
    (row,) = [r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B"]
    assert CONFIG["source"] == row["source_url"]
    assert row["config"]["model_type"] == "KeyeVL2"
    for key, value in row["config"].items():
        assert SIZES[key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


def test_ops_count_the_issues_table_by_hand():
    """The table of ISSUE 59, a row at a time, and its pair counts."""
    d = 2048
    attention = 2 * 8_388_608 + 2 * 1_048_576 + 256
    assert attention == 18_874_624 == OPS.attention_parameters(SIZES)
    indexer = d * 1024 + d * 64 + d * 16 + 128
    assert indexer == 2_261_120 == OPS.indexer_parameters(SIZES)
    experts = 128 * 3 * d * 768 + d * 128
    assert experts == 604_241_920 == OPS.expert_parameters(SIZES)
    block = attention + indexer + 2 * d + experts
    assert block == 625_381_760 == OPS.block_parameters(SIZES)
    assert 6 * block == 3_752_290_560
    ends = 2 * 151_936 * d + d
    assert ends == 622_331_904
    assert 6 * block + ends == PARAMETERS == OPS.parameters(SIZES)
    assert round((48 * block + ends) / 1e8) == 306  # the published 30.6 B
    # the pairs the indexer must score and those a query head reads
    scored, picked = OPS.pairs(SIZES)
    assert scored == SEQ * (SEQ + 1) // 2 - TOPK * (TOPK + 1) // 2 \
        == 132_127_744 == sum(t + 1 for t in range(TOPK, SEQ))
    assert picked == TOPK * (TOPK + 1) // 2 + (SEQ - TOPK) * TOPK \
        == 31_458_304 == sum(min(t + 1, TOPK) for t in range(SEQ))
    assert round(1000 * picked / (SEQ * (SEQ + 1) // 2)) == 234  # 23.4 %
    work = OPS.kernels(SIZES, ROWS, 2)
    assert work["index_select"]["flops"] == LAYERS * ROWS * 2 * 16 * 64 \
        * scored
    assert round(work["index_select"]["flops"] / 1e10) == 649
    assert work["index_select"]["bytes"] == LAYERS * TOKENS * (
        1024 + 64 + 16) * 2
    assert work["sparse_attention"]["flops"] == LAYERS * ROWS * 32 * 4 * 128 \
        * picked
    assert round(work["sparse_attention"]["flops"] / 1e11) == 124
    assert work["sparse_attention"]["bytes"] == LAYERS * TOKENS * 2 * 36 \
        * 128 * 2
    # the least times the issue gives: 33 ms and 63 ms a step, the operations'
    assert round(1e3 * work["index_select"]["flops"] / 197e12) == 33
    assert round(1e3 * work["sparse_attention"]["flops"] / 197e12) == 63
    # every expert is held: 8 assignments a token a layer, none expected away
    held = LAYERS * TOKENS * 8
    expert = 3 * d * 768
    assert work["expert_matmul"] == OPS.kernels(
        SIZES, ROWS, 2, assignments=held)["expert_matmul"]
    assert work["expert_matmul"]["flops"] == 2 * held * expert
    assert work["expert_matmul"]["bytes"] == LAYERS * 128 * expert * 2 \
        + held * d * 6
    for kernel in work.values():  # all three are bound by their operations
        assert kernel["flops"] / 197e12 > kernel["bytes"] / 819e9
    # a row: the projections of every token, the three kernels, the head
    per_token = 2 * LAYERS * (attention - 256 + indexer - 128 + d * 128)
    assert OPS.flops_per_row(SIZES) == SEQ * per_token + sum(
        k["flops"] for k in OPS.kernels(SIZES, 1, 2).values()) \
        + 2 * d * 151_936
    counts = OPS.counts(SIZES, rows=8, steps=2, bytes_per_value=2)
    assert counts["flops"] == 8 * OPS.flops_per_row(SIZES)
    assert counts["bytes"] == 2 * 2 * PARAMETERS + 8 * 4 * (SEQ + 151_936)


def test_ops_parameters_are_the_programs():
    import jax

    from storm_tpu.models.registry import build_model

    for name, count in (("keye_vl2_30b", PARAMETERS), ("keye_tiny", None)):
        model = build_model(name)
        params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        held = sum(x.size for x in jax.tree.leaves(params))
        sizes = spec.config(name)["published"]
        assert OPS.parameters(sizes) == held, name
        assert count in (None, held)
        assert model.max_rows == sizes["held"]["rows_per_step"]
        assert model.hyper["topk"] == sizes["sa_config"]["topk"]
        assert model.hyper["chunk"] == sizes["sa_config"]["q_chunk_size"] \
            == sizes["sa_config"]["kv_chunk_size"]
        assert list(model.hyper["mrope_section"]) \
            == sizes["rope_scaling"]["mrope_section"]
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
    return (f"%while.{number} = ({carried}) while(({carried}) %tuple.3), "
            "condition=%c, body=%b")


# one step's top-level operations as the v5e compiler names them (a compile
# for the described chip at 4 windows, layouts dropped): the rows of the
# selection and of the second pass are kernel calls of their own, no loop
HELD = TOKENS * 8  # assignments a layer
BUFFER = HELD + 128 * 512 + 1
STREAM = "%fusion.9 = f32[4,16384,2048]{2,1,0} fusion(%p), kind=kOutput"
TURN = "%custom-call.5 = bf16[4,16384,4096]{2,1,0} custom-call(%c, %s, %q)"
SELECT = [f"%_select_kernel_row.{n} = s8[1,16384,16384]{{2,1,0}} "
          "custom-call(%qi, %ki, %w)" for n in range(2)]
COUNT = "%fusion.21 = s32[] fusion(s8[1,16384,16384] %m), kind=kInput"
SECOND = [f"%_kernel_row.{n} = bf16[4,8,16384,128]{{3,2,1,0}} "
          "custom-call(%at, %q, %k, %v, %m)" for n in range(2)]
MOVE = "%fusion.30 = bf16[4,16384,4096]{2,1,0} fusion(%o), kind=kLoop"
SORT = f"%sort.8 = (s32[{HELD}], s32[{HELD}], f32[{HELD}]) sort(%a, %i, %w)"
EXP = _loop(63, f"s32[], bf16[{BUFFER},2048], s32[], s32[1152], "
            f"bf16[{TOKENS},2048], bf16[128,768,2048], bf16[128,2048,768], "
            "bf16[128,2048,768], s32[]")
COMB = _loop(64, f"s32[], f32[{TOKENS},2048], s32[], s32[1280], "
             f"bf16[{BUFFER},2048], s32[]")
ZERO = (f"%broadcast.70 = f32[{TOKENS},2048]{{1,0}} "
        "broadcast(f32[] %constant.3)")
SELECT_OP = ("jit(fwd)/mix.elementwise/mix.index_select/"
             "jit(_select_kernel_row)/pallas_call")
SECOND_OP = ("jit(fwd)/mix.elementwise/mix.sparse_attention/"
             "jit(_kernel_row)/pallas_call")
STEP_OPS = [
    (STREAM, "jit(fwd)/mix.elementwise/proj/dot_general", 0, 150),
    (TURN, "jit(fwd)/mix.elementwise/mix.rope/pallas_call", 150, 20),
    (SELECT[0], SELECT_OP, 170, 120),
    (COUNT, "jit(fwd)/mix.elementwise/mix.index_select/reduce_max", 290, 10),
    (SECOND[0], SECOND_OP, 300, 160),
    (SELECT[1], SELECT_OP, 460, 120),
    (SECOND[1], SECOND_OP, 580, 160),
    (MOVE, "jit(fwd)/mix.elementwise/transpose", 740, 30),
    (SORT, "jit(fwd)/moe.route/jit(sort)/sort", 770, 40),
    (EXP, "jit(fwd)/moe.experts/while", 810, 260),
    (COMB, "jit(fwd)/moe.combine/while", 1070, 170),
    (ZERO, None, 1240, 10),
]
STEP_MS = 1250.0
HELD_A_STEP = LAYERS * HELD
WANT = {"model_step_ms": STEP_MS, "index_select_ms": 250.0,
        "keye_sparse_attention_ms": 320.0, "keye_rope_ms": 20.0,
        "keye_expert_matmul_ms": 260.0, "keye_expert_combine_ms": 170.0,
        "moe_routing_ms": 40.0, "mixer_elementwise_ms": 30.0,
        "projections_ms": 150.0, "step_named_share": 100.0 * 1240 / 1250,
        # the two cut executions lack their first 170 ms of operations
        "device_idle_share": 100.0 * 2 * 170 / (8 * 1250),
        "batch_size_mean": 4.0, "cut_hold_mean_ms": 0.0,
        "expert_assignments_held_share": 100.0,
        "expert_tokens_max_over_mean": 1.5, "parse_ms_per_record": 0.05,
        "egress_ms_per_record": 2.5, "step_gap_max_ms": STEP_MS,
        "index_blocks_picked_share": 100.0 * 500 / 528}


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
        log.append({"step": n, "engine": "keye_vl2_30b", "padded": ROWS,
                    "rows": ROWS, "sources": 2, "seen": True,
                    "t_first_enq": ready - 3.7, "t_cut": ready - 2.52,
                    "t_staged": ready - 2.51, "t_launched": ready - 2.50,
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
            "expert_tokens_max_over_mean": hist(84, 126.0),
            "expert_assignments_held": 14 * HELD_A_STEP,
            "expert_assignments_absent": 0,
            "index_blocks_picked": 14 * LAYERS * ROWS * 500,
            "index_blocks_causal": 14 * LAYERS * ROWS * 528},
        "kafka-bolt": {"produce_ms": hist(56, 56 * 0.5)}}
    return run


def test_the_new_entries_list_what_reads_here():
    """Found by name. How many cells the benchmark has and which comes last
    is no business of this file's: the next cell must not fail it."""
    cell = spec.cell(BENCH, CELL)
    assert cell in BENCH["workloads"]
    assert cell["chips"] == 1 and cell["traffic"] == "tokens16k_backlog"
    assert cell["config"] == "keye_vl2_30b" and len(cell["why"]) <= 200
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == "keye_vl2_30b"] == [CELL]  # no second cell
    assert not [w for w in BENCH["workloads"] if w["chips"] != 1]
    assert BENCH["run_seconds"] == 20
    e2e = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end", cell)}
    assert e2e == {"records_per_s", "setup_s"}
    assert _entry("end_to_end", "records_per_s")["bound"] == 0.01
    assert _entry("end_to_end", "setup_s")["bound"] == 0.1
    layer = {m["name"]: m for m in spec.metrics_for(BENCH, "per_layer", cell)}
    assert set(layer) == SHARED | set(NEW) | {"compile_s", "cache_misses"}
    # loops told by other models' shapes or parts are not this cell's
    assert not {"gqa_attention_ms", "expert_matmul_ms", "expert_combine_ms",
                "rope_ms", "trinity_rope_ms", "sparse_attention_ms",
                "sparse_select_ms", "sparse_keys_read_share",
                "trinity_expert_matmul_ms", "window_attention_ms"} \
        & set(layer)
    for name, (reader, _part, _kernel) in NEW.items():
        counted = name == "index_blocks_picked_share"
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["layer"] == "engine and model"
        assert layer[name]["moves"] == "records_per_s"
        assert layer[name]["unit"] == ("%" if name.endswith("_share")
                                       else "ms")
        assert layer[name]["better"] == (
            "higher" if name.endswith("_roofline_share") else "lower")
        assert layer[name]["source"] == (
            "program_counter" if counted else "device_trace")
        assert spec.metric(name)["reader"] == reader
    for name in SHARED:
        assert CELL in layer[name]["workloads"]
    assert CELL in _entry("per_layer", "model_roofline_share")["workloads"]
    # the new metrics stand after every metric an earlier PR brought
    names = [m["name"] for m in BENCH["per_layer"]]
    first = min(names.index(n) for n in NEW)
    assert set(names[first:first + len(NEW)]) == set(NEW)
    assert first > names.index("trinity_rope_ms")
    # the mix is the one minicpm_sala's and trinity_mini's cells run
    traffic = spec.traffic("tokens16k_backlog")
    assert (traffic["outstanding"], traffic["pool"], traffic["payload"],
            traffic["arrivals"], traffic["warmup_seconds"],
            traffic["trace_seconds"]) == (
        32, 16, "arrow_tensor", "closed_loop", 6, 8)
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
    assert parts.MIX_INDEX_SELECT == "mix.index_select"
    counted = spec.metric("index_blocks_picked_share")["args"]
    assert counted == {"component": "inference-bolt",
                       "of": "index_blocks_picked",
                       "among": ["index_blocks_causal"]}
    # the counters' names are the program's
    import inspect

    from storm_tpu.ops import sparse_attention
    source = inspect.getsource(sparse_attention.observe_block_counts)
    assert '"index_blocks_picked"' in source
    assert '"index_blocks_causal"' in source


def test_every_listed_metric_reads_a_number_from_a_trace_of_its_shapes():
    run = _traced_run()
    cell = spec.cell(BENCH, CELL)
    listed = spec.metrics_for(BENCH, "per_layer", cell)
    got = harness.read_metrics(run, [m for m in listed if m["name"]
                                     not in ("compile_s", "cache_misses")])
    assert set(got) == SHARED | set(NEW)
    for name, want in WANT.items():
        assert got[name]["value"] == pytest.approx(want, abs=1e-6), name
    shares = ("model_roofline_share", "index_select_roofline_share",
              "keye_sparse_attention_roofline_share",
              "keye_expert_matmul_roofline_share")
    for name in shares:
        assert 0 < got[name]["value"] < 100 and math.isfinite(
            got[name]["value"])
    work = OPS.kernels(SIZES, ROWS, 2)
    assert got["index_select_roofline_share"]["value"] == pytest.approx(
        100 * work["index_select"]["flops"] / 197e12 / 0.250)
    assert got["keye_sparse_attention_roofline_share"]["value"] == \
        pytest.approx(100 * work["sparse_attention"]["flops"] / 197e12
                      / 0.320)
    assert got["keye_expert_matmul_roofline_share"]["value"] == \
        pytest.approx(100 * work["expert_matmul"]["flops"] / 197e12 / 0.260)
    assert {k: v["rows"] for k, v in run.notes["kernels"].items()} == {
        "index_select": ROWS, "sparse_attention": ROWS,
        "expert_matmul": ROWS}
    assert got["model_roofline_share"]["value"] == pytest.approx(
        100 * ROWS * OPS.flops_per_row(SIZES) / 197e12 / 1.250)
    assert run.roofline_bound == "compute"
    # the selection and the second pass are no loops: only the parts find them
    assert run.notes["part_loops"] == pytest.approx(
        {"moe.experts": 260.0, "moe.combine": 170.0})
    # the line such a run prints is complete by the driver's own check
    got.update(compile_s={"value": 1.0, "unit": "s"},
               cache_misses={"value": 0.0, "unit": "count"})
    row = {"correct": True, "attempted": 1, "failed": 0, "metrics": got,
           "device": {}}
    assert check_line.problems(row, CELL, traced=True) == []


def test_a_program_without_the_new_names_reads_nothing_and_raises_nothing():
    """Another model's program (Trinity's loops: no selection, no masked
    second pass, no counters of squares): each time reads 0.0, each share is
    left out of the line; every one is where there is no trace."""
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
        others[0]: "jit(fwd)/mix.elementwise/mix.attention/while"}},
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
    configurations are coupled through one kind's look-up by shape;
    ``minicpm_sala``'s and ``trinity_mini``'s windows are as long and their
    vocabularies others."""
    make = spec.plugin("inputs", "keye_tokens").make
    a, b = make(5, (SEQ,), 3_000_000_019), make(5, (SEQ,), 3_000_000_019)
    assert (a == b).all() and a.shape == (5, SEQ)
    assert a.min() >= 0 and 151_000 < a.max() < 151_936
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
    assert sorted(kinds["keye_tokens"]) == [(40,), (SEQ,)]
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
        command + ["--workload", "keye_tiny.tokens16k_backlog", "--seed",
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
    assert 0 < layer["index_blocks_picked_share"] <= 100.0


@pytest.mark.timeout(115)
def test_the_tolerances_two_readings_and_the_mixers_check_at_toy_sizes(
        tmp_path):
    """``tools/tolerance.py`` at the toy sizes: the program answers every
    row, the float8 control does not. ``tools/keye_mixer_check.py``: the
    selection against the sort, the second pass under its mask, the mixer
    whole, in float32 here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/tolerance.py", "--config",
         "keye_tiny", "--rehearse", "5:f8"],
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
        [sys.executable, "benchmarks/tools/keye_mixer_check.py",
         "--config", "keye_tiny", "--rehearse", "--seed", "5", "--limit",
         "1e-4"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=50)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert [r["check"] for r in rows] == ["select", "second_pass", "mixer"]
    assert all(r["pass"] and r["length"] == 40 and r["topk"] == 12
               for r in rows)
    assert rows[0]["agree"] == 1.0 and rows[0]["agree_float32"] > 0.99
    assert rows[0]["forms"] == ["index_select=top_k"]
    assert rows[1]["form"] == "blocked"
    assert rows[2]["forms"] == ["rotary_turn=halves",
                                "sparse_attention=blocked",
                                "index_select=top_k"]
