"""The benchmark's Falcon-H1 files (PR 66): the configuration against the
catalog row it is cut from and the program's own parameter tree,
``ops/falcon_h1.py`` against the issue's table and its sums by hand, every
per-layer metric that lists the new cell over a trace of its shapes made by
hand, the new entries in ``BENCHMARK.json`` (found by name: neither how many
cells there are nor which is last is this file's business), the metric files
against their readers and parts, the windows' kind, and rehearsals of
``falcon_h1_tiny.tokens16k_backlog`` and of the two tools on the CPU."""

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

CELL = "falcon_h1_34b.tokens16k_backlog"
BENCH = spec.benchmark()
CONFIG = spec.config("falcon_h1_34b")
SIZES = CONFIG["published"]
OPS = spec.plugin("ops", "falcon_h1")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMETERS = 4_394_354_048
ROWS, SEQ, LAYERS, D, F, VOCAB = 4, 16384, 4, 5120, 21504, 261120
TOKENS = ROWS * SEQ
# every width of the row and all fourteen scalars: none may differ
WIDTHS = {"hidden_size": 5120, "intermediate_size": 21504, "head_dim": 128,
          "num_attention_heads": 20, "num_key_value_heads": 4,
          "mamba_n_heads": 32, "mamba_d_head": 128, "mamba_d_ssm": 4096,
          "mamba_d_state": 256, "mamba_n_groups": 2, "mamba_d_conv": 4,
          "mamba_chunk_size": 128, "mamba_expand": 2,
          "mlp_expansion_factor": 8, "vocab_size": 261120,
          "embedding_multiplier": 5.656854249492381,
          "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1,
          "attention_out_multiplier": 0.0375,
          "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
          "ssm_out_multiplier": 0.08838834764831845,
          "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738],
          "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
          "rope_theta": 100000000000, "rms_norm_eps": 1e-05,
          "max_position_embeddings": 262144}
SHARED = {"parse_ms_per_record", "batch_size_mean", "model_step_ms",
          "model_roofline_share", "egress_ms_per_record", "device_idle_share",
          "cut_hold_mean_ms", "step_named_share", "step_gap_max_ms",
          "mixer_elementwise_ms", "projections_ms"}
# metric -> (reader, part, kernel)
NEW = {"falcon_h1_ssd_scan_ms": ("trace_part_time", "mix.ssd_scan", None),
       "falcon_h1_ssd_scan_roofline_share": (
           "trace_part_share", "mix.ssd_scan", "ssd_scan"),
       "falcon_h1_attention_ms": ("trace_part_time", "mix.attention", None),
       "falcon_h1_attention_roofline_share": (
           "trace_part_share", "mix.attention", "attention"),
       "falcon_h1_feed_forward_ms": ("trace_part_time", "ffn", None),
       "falcon_h1_feed_forward_roofline_share": (
           "trace_part_share", "ffn", "feed_forward"),
       "falcon_h1_rope_ms": ("trace_part_time", "mix.rope", None)}


def _entry(group, name):
    (found,) = [e for e in BENCH[group] if e["name"] == name]
    return found


def test_configuration_states_the_cut_and_keeps_every_width():
    held = SIZES["held"]
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    # the top level is the configuration as run; ``published`` as published
    assert CONFIG["num_hidden_layers"] == LAYERS
    assert SIZES["num_hidden_layers"] == 72
    assert (held["layers"], held["num_hidden_layers"],
            held["pipeline_stages"], held["chips_per_layer"],
            held["sequence_length"], held["rows_per_step"],
            held["ssd_chunk"]) == ([0, 1, 2, 3], LAYERS, 16, 1, SEQ, ROWS,
                                   128)
    assert held["attention_query_tile"] in (64, 128)
    for key, value in WIDTHS.items():
        assert CONFIG[key] == SIZES[key] == value, key
    for key, value in SIZES.items():
        if key not in CONFIG["reduced"] and key != "held":
            assert CONFIG[key] == value, key
    assert (SIZES["tie_word_embeddings"], SIZES["attention_bias"],
            SIZES["mamba_conv_bias"], SIZES["mamba_proj_bias"],
            SIZES["mamba_norm_before_gate"], SIZES["mamba_rms_norm"],
            SIZES["rope_scaling"], SIZES["attn_layer_indices"],
            SIZES["model_type"]) == (
        False, False, True, False, False, True, None, None, "falcon_h1")
    deployment = CONFIG["deployment"]
    assert "One chip a layer" in deployment
    assert "one v5e-16" in deployment
    assert "sixteen stages" in deployment
    assert "no code stands in" in deployment
    assert CONFIG["model"] == {"name": "falcon_h1_34b",
                               "input_shape": [SEQ], "num_classes": VOCAB,
                               "dtype": "bfloat16"}
    for key in ("why", "block", "mamba2", "attention", "rotary",
                "feed_forward", "multipliers", "unread", "weights", "inputs",
                "output", "ids", "chunk", "stream"):
        assert CONFIG["assumed"][key], key
    assert "no part of the mathematics" in CONFIG["assumed"]["chunk"]
    assert "scores' scale" in CONFIG["assumed"]["multipliers"]
    on_device = CONFIG["on_device"]
    assert on_device["parameters"] == PARAMETERS
    assert on_device["parameters_bytes"] == 2 * PARAMETERS
    assert on_device["parameters_float32_at_load_bytes"] == 0
    # the issue's rule: parameters and the compiler's temporaries of the
    # 4-row program at or under 15.0 GB
    assert on_device["parameters_bytes"] + on_device[
        "program_temporaries_bucket_4_bytes"] <= 15.0e9
    assert CONFIG["inputs"] == {"kind": "falcon_h1_tokens", "decimals": 0,
                                "candidates": 16}
    assert 0 < CONFIG["tolerance"]["relative_distance"] < 0.2
    assert "float8" in CONFIG["tolerance"]["why"]
    for key in ("delivery", "malformed_records", "offsets"):
        assert CONFIG["guarantees"][key], key
    entry = _entry("configs", "falcon_h1_34b")
    assert entry["file"] == "benchmarks/configs/falcon_h1_34b.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] and len(entry["why"]) <= 200


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_in_the_file():
    rows = [json.loads(line) for line in open(CATALOG)]
    (row,) = [r for r in rows if r["name"] == "Falcon-H1-34B-Instruct"]
    assert CONFIG["source"] == row["source_url"]
    assert row["config"]["model_type"] == "falcon_h1"
    for key, value in row["config"].items():
        assert SIZES[key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert set(SIZES) == set(row["config"]) | {"held"}


def test_ops_count_the_issues_table_by_hand():
    """The table of ISSUE 66, a row at a time, and its sums."""
    w_in = D * 9_248
    assert w_in == 47_349_760 and 9_248 == 2 * 4_096 + 2 * 512 + 32
    mamba = w_in + 25_600 + 96 + 4_096 + 4_096 * D
    assert 25_600 == 5 * 5_120 and 4_096 * D == 20_971_520
    assert mamba == OPS.mamba_parameters(SIZES)
    attention = 2 * 13_107_200 + 2 * 2_621_440
    assert attention == OPS.attention_parameters(SIZES)
    ffn = 3 * D * F
    assert ffn == 330_301_440 == OPS.feed_forward_parameters(SIZES)
    layer = mamba + attention + ffn + 10_240
    assert layer == 430_120_032 == OPS.layer_parameters(SIZES)
    ends = 2 * VOCAB * D + D
    assert ends == 2_673_873_920
    assert 4 * layer == 1_720_480_128
    assert 4 * layer + ends == PARAMETERS == OPS.parameters(SIZES)
    # the feed-forward holds 77 % of a layer
    assert round(100 * ffn / layer) == 77
    whole = dict(SIZES, held={"sequence_length": SEQ})  # nothing cut
    assert OPS.parameters(whole) == 72 * layer + ends
    assert round(OPS.parameters(whole) / 1e8) == 336  # 33.6 B
    # the kernels of a step of 4
    work = OPS.kernels(SIZES, ROWS, 2)
    macs = 32 * (64 * 128 + 2 * 128 * 256) + 2 * (64 * 256)
    assert macs == 2_392_064
    assert work["ssd_scan"]["flops"] == 2 * LAYERS * TOKENS * macs
    assert work["ssd_scan"]["bytes"] == LAYERS * TOKENS * 18_560
    # a layer's scan: 0.31 TFLOP (the issue's 0.35 counted whole triangles)
    assert round(work["ssd_scan"]["flops"] / LAYERS / 1e10) == 31
    # bound by its operations, narrowly: 1.59 ms a layer at the least
    assert work["ssd_scan"]["flops"] / 197e12 \
        > work["ssd_scan"]["bytes"] / 819e9
    assert round(1e5 * work["ssd_scan"]["flops"] / LAYERS / 197e12) == 159
    pairs = SEQ * (SEQ + 1) // 2
    assert pairs == 134_225_920
    assert work["attention"]["flops"] == LAYERS * ROWS * 20 * 4 * 128 * pairs
    # the issue's 5.50 TFLOP a layer of causal pairs
    assert round(work["attention"]["flops"] / LAYERS / 1e10) == 550
    assert work["attention"]["bytes"] == LAYERS * TOKENS * 2 * 24 * 128 * 2
    assert work["feed_forward"]["flops"] == 2 * LAYERS * TOKENS * ffn
    # the issue's 43.3 TFLOP a layer
    assert round(work["feed_forward"]["flops"] / LAYERS / 1e11) == 433
    assert work["feed_forward"]["bytes"] == LAYERS * (ffn + TOKENS * 2 * D) \
        * 2
    # the issue's sums a layer and step: Mamba-2's projections 8.96 TFLOP,
    # attention's 4.12, 62.2 with the kernels; 249 TFLOP the four layers
    proj_m = 2 * TOKENS * OPS.mamba_projection_parameters(SIZES)
    proj_a = 2 * TOKENS * attention
    assert round(proj_m / 1e10) == 896 and round(proj_a / 1e10) == 412
    a_layer = proj_m + proj_a + sum(
        k["flops"] for k in work.values()) // LAYERS
    assert round(a_layer / 1e11) == 622
    assert OPS.flops_per_row(SIZES) == SEQ * 2 * LAYERS * (
        OPS.mamba_projection_parameters(SIZES) + attention) + sum(
        k["flops"] for k in OPS.kernels(SIZES, 1, 2).values()) \
        + 2 * D * VOCAB
    assert round(ROWS * OPS.flops_per_row(SIZES) / 1e12) == 249
    # the parallel mixer is 30 % of a layer's operations
    assert round(100 * (a_layer - work["feed_forward"]["flops"] // LAYERS)
                 / a_layer) == 30
    counts = OPS.counts(SIZES, rows=8, steps=2, bytes_per_value=2)
    assert counts["flops"] == 8 * OPS.flops_per_row(SIZES)
    assert counts["bytes"] == 2 * 2 * PARAMETERS + 8 * 4 * (SEQ + VOCAB)


def test_ops_parameters_are_the_programs():
    import jax

    from storm_tpu.models.registry import build_model

    for name, count in (("falcon_h1_34b", PARAMETERS),
                        ("falcon_h1_tiny", None)):
        model = build_model(name)
        params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        held = sum(x.size for x in jax.tree.leaves(params))
        sizes = spec.config(name)["published"]
        assert OPS.parameters(sizes) == held, name
        assert count in (None, held)
        assert model.max_rows == sizes["held"]["rows_per_step"]
        assert model.hyper["chunk"] == sizes["held"]["ssd_chunk"]
        assert model.hyper["layers"] == len(sizes["held"]["layers"]) \
            == len(params["layers"])
        assert (model.hyper["groups"], model.hyper["state"],
                model.hyper["heads"], model.hyper["kv_heads"],
                model.hyper["head_dim"], model.hyper["mamba_heads"],
                model.hyper["mamba_head_dim"], model.hyper["ffn_width"],
                model.hyper["rope_theta"]) == (
            sizes["mamba_n_groups"], sizes["mamba_d_state"],
            sizes["num_attention_heads"], sizes["num_key_value_heads"],
            sizes["head_dim"], sizes["mamba_n_heads"],
            sizes["mamba_d_head"], sizes["intermediate_size"],
            sizes["rope_theta"])
        for key in ("embedding_multiplier", "lm_head_multiplier",
                    "attention_in_multiplier", "attention_out_multiplier",
                    "key_multiplier", "ssm_in_multiplier",
                    "ssm_out_multiplier"):
            assert model.hyper[key] == sizes[key], key
        assert list(model.hyper["ssm_multipliers"]) \
            == sizes["ssm_multipliers"]
        assert list(model.hyper["mlp_multipliers"]) \
            == sizes["mlp_multipliers"]
    # the tile the configuration states is the rule's
    from storm_tpu.ops.flash_attention import causal_tiles
    assert causal_tiles(20 // 4)[0] == SIZES["held"]["attention_query_tile"]


def test_rows_per_step_reads_the_window_shape():
    names = ["%fusion.1 = f32[4,16384,5120]{2,1,0} fusion(f32[4,16384,5120])",
             "%fusion.2 = bf16[4,16384,9248]{2,1,0} fusion()",
             "%fusion.3 = f32[65536,32]{1,0} fusion()"]
    assert OPS.rows_per_step(names, SIZES) == 4
    assert OPS.rows_per_step(names[1:], SIZES) is None


# ---- every listed metric over a trace of this cell's shapes ------------------

MS = 1e6  # nanoseconds
DEV = "/device:TPU:0"


def _loop(number, carried):
    return (f"%while.{number} = ({carried}) while(({carried}) %tuple.3), "
            "condition=%c, body=%b")


# one step's top-level operations as the v5e compiler names them (a compile
# for the described chip at 4 windows, layouts dropped)
STREAM = "%fusion.9 = f32[4,16384,5120]{2,1,0} fusion(%p), kind=kLoop"
PROJ = "%fusion.12 = bf16[4,16384,5152]{2,1,0} fusion(%n, %w), kind=kOutput"
CONV = ("%mix.elementwise.9 = bf16[4,16384,5120]{2,1,0} "
        "custom-call(%f, %w, %b)")
SCAN = _loop(28, "s32[], f32[4,2,16,128,256], bf16[4,16384,4096], "
             "bf16[4,16384,5120], f32[4,16384,32], f32[4,16384,32], "
             "f32[2,16,1], s32[]")
TURN = "%mix.rope.3 = bf16[4,16384,2560]{2,1,0} custom-call(%c, %s, %q)"
ATTN = _loop(40, "s32[], bf16[4,16384,2560], bf16[4,16384,2560], "
             "bf16[4,16384,512], bf16[4,16384,512]")
FFN = _loop(12, "s32[], bf16[4,16384,5120], bf16[4,16384,5120], "
            "bf16[5120,21504], bf16[5120,21504], bf16[21504,5120]")
HEAD = "%fusion.70 = bf16[4,261120]{1,0} fusion(%l, %w), kind=kOutput"
STEP_OPS = [
    (STREAM, "jit(fwd)/norm/mul", 0, 40),
    (PROJ, "jit(fwd)/mix.elementwise/proj/dot_general", 40, 330),
    (CONV, "jit(fwd)/mix.elementwise/pallas_call", 370, 70),
    (SCAN, "jit(fwd)/mix.elementwise/mix.ssd_scan/while", 440, 60),
    (TURN, "jit(fwd)/mix.elementwise/mix.rope/jit(_turn_lanes)/pallas_call",
     500, 10),
    (ATTN, "jit(fwd)/mix.elementwise/mix.attention/while", 510, 200),
    (FFN, "jit(fwd)/ffn/while", 710, 1000),
    (HEAD, None, 1710, 10),
]
STEP_MS = 1720.0
WANT = {"model_step_ms": STEP_MS, "falcon_h1_ssd_scan_ms": 60.0,
        "falcon_h1_attention_ms": 200.0, "falcon_h1_feed_forward_ms": 1000.0,
        "falcon_h1_rope_ms": 10.0, "mixer_elementwise_ms": 70.0,
        "projections_ms": 330.0,
        "step_named_share": 100.0 * 1710 / 1720,
        # the two cut executions lack their first 370 ms of operations
        "device_idle_share": 100.0 * 2 * 370 / (6 * 1720),
        "batch_size_mean": 4.0, "cut_hold_mean_ms": 0.0,
        "parse_ms_per_record": 0.05, "egress_ms_per_record": 2.5,
        "step_gap_max_ms": STEP_MS}


def _traced_run(steps=6):
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
    for n in range(14):  # steps 8.. are the traced executions
        ready = off + STEP_MS / 1e3 * (n - 8 + 1) + 2e-4
        log.append({"step": n, "engine": "falcon_h1_34b", "padded": ROWS,
                    "rows": ROWS, "sources": 2, "seen": True,
                    "t_first_enq": ready - 5.2, "t_cut": ready - 3.46,
                    "t_staged": ready - 3.45, "t_launched": ready - 3.44,
                    "t_ready": ready, "t_fetched": ready + 0.001,
                    "t_resolved": ready + 0.002})
    run._step_rows = log
    run.delivery_times = [off - 8 * STEP_MS / 1e3, off]
    run.delivered_in_window = ROWS * 8
    hist = lambda count, total: {"count": count, "sum": total}  # noqa: E731
    run.registry_before = {"inference-bolt": {}, "kafka-bolt": {}}
    run.registry_after = {
        "inference-bolt": {
            "decode_ms": hist(32, 32 * 0.05), "batch_size": hist(8, 32.0),
            "encode_ms": hist(32, 32 * 2.0), "cut_hold_ms": hist(8, 0.0)},
        "kafka-bolt": {"produce_ms": hist(32, 32 * 0.5)}}
    return run


def test_the_new_entries_list_what_reads_here():
    """Found by name. How many cells the benchmark has and which comes last
    is no business of this file's: the next cell must not fail it."""
    cell = spec.cell(BENCH, CELL)
    assert cell in BENCH["workloads"]
    assert cell["chips"] == 1 and cell["traffic"] == "tokens16k_backlog"
    assert cell["config"] == "falcon_h1_34b" and len(cell["why"]) <= 200
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == "falcon_h1_34b"] == [CELL]  # no second cell
    assert not [w for w in BENCH["workloads"] if w["chips"] != 1]
    assert BENCH["run_seconds"] == 20
    e2e = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end", cell)}
    assert e2e == {"records_per_s", "setup_s"}
    assert _entry("end_to_end", "records_per_s")["bound"] == 0.01
    assert _entry("end_to_end", "setup_s")["bound"] == 0.1
    layer = {m["name"]: m for m in spec.metrics_for(BENCH, "per_layer", cell)}
    assert set(layer) == SHARED | set(NEW) | {"compile_s", "cache_misses"}
    # loops told by other models' shapes or parts, experts and counters are
    # not this cell's: the model counts nothing that depends on the data
    assert not {"ssd_scan_ms", "gqa_attention_ms", "granite_ssd_scan_ms",
                "trinity_full_attention_ms", "trinity_rope_ms", "rope_ms",
                "moe_routing_ms", "expert_tokens_max_over_mean",
                "expert_assignments_held_share"} & set(layer)
    for name, (reader, _part, _kernel) in NEW.items():
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["layer"] == "engine and model"
        assert layer[name]["moves"] == "records_per_s"
        assert layer[name]["unit"] == ("%" if name.endswith("_share")
                                       else "ms")
        assert layer[name]["better"] == (
            "higher" if name.endswith("_share") else "lower")
        assert layer[name]["source"] == "device_trace"
        assert spec.metric(name)["reader"] == reader
    for name in SHARED:
        assert CELL in layer[name]["workloads"]
        # beside the other three cells under the same mix
        for other in ("trinity_mini", "keye_vl2_30b", "minicpm_sala"):
            assert f"{other}.tokens16k_backlog" in layer[name]["workloads"]
    # the new metrics stand after every metric an earlier PR brought
    names = [m["name"] for m in BENCH["per_layer"]]
    first = min(names.index(n) for n in NEW)
    assert set(names[first:first + len(NEW)]) == set(NEW)
    assert first > names.index("granite_expert_tile_fill_share")
    # the mix is the one Trinity's, Keye's and MiniCPM-SALA's cells run
    traffic = spec.traffic("tokens16k_backlog")
    assert (traffic["outstanding"], traffic["pool"], traffic["payload"],
            traffic["arrivals"], traffic["warmup_seconds"],
            traffic["drain_seconds"], traffic["trace_seconds"]) == (
        32, 16, "arrow_tensor", "closed_loop", 6, 60, 8)
    assert traffic["program"] == {"topology.spout_scheme": "raw"}


def test_the_metric_files_name_their_readers_and_parts():
    from storm_tpu.ops import parts

    kernels = OPS.kernels(SIZES, ROWS, 2)
    assert parts.FFN == "ffn" and parts.FFN in parts.VOCABULARY
    for name, (reader, part, kernel) in NEW.items():
        doc = spec.metric(name)
        assert doc["reader"] == reader and doc["doc"]
        assert doc["args"]["prefix"] == "jit_fwd"
        assert doc["args"]["part"] == part and part in parts.VOCABULARY
        assert "pattern" not in doc["args"]  # by the part, not by a shape
        assert doc["args"].get("kernel") == kernel
        assert kernel is None or kernel in kernels
    assert set(kernels) == {k for _r, _p, k in NEW.values() if k}


def test_every_listed_metric_reads_a_number_from_a_trace_of_its_shapes():
    run = _traced_run()
    cell = spec.cell(BENCH, CELL)
    listed = spec.metrics_for(BENCH, "per_layer", cell)
    got = harness.read_metrics(run, [m for m in listed if m["name"]
                                     not in ("compile_s", "cache_misses")])
    assert set(got) == SHARED | set(NEW)
    for name, want in WANT.items():
        assert got[name]["value"] == pytest.approx(want, abs=1e-6), name
    shares = ("model_roofline_share", "falcon_h1_ssd_scan_roofline_share",
              "falcon_h1_attention_roofline_share",
              "falcon_h1_feed_forward_roofline_share")
    for name in shares:
        assert 0 < got[name]["value"] < 100 and math.isfinite(
            got[name]["value"])
    work = OPS.kernels(SIZES, ROWS, 2)
    assert got["falcon_h1_ssd_scan_roofline_share"]["value"] == \
        pytest.approx(100 * work["ssd_scan"]["flops"] / 197e12 / 0.060)
    assert got["falcon_h1_attention_roofline_share"]["value"] == \
        pytest.approx(100 * work["attention"]["flops"] / 197e12 / 0.200)
    assert got["falcon_h1_feed_forward_roofline_share"]["value"] == \
        pytest.approx(100 * work["feed_forward"]["flops"] / 197e12 / 1.000)
    assert {k: v["rows"] for k, v in run.notes["kernels"].items()} == {
        "ssd_scan": ROWS, "attention": ROWS, "feed_forward": ROWS}
    assert got["model_roofline_share"]["value"] == pytest.approx(
        100 * ROWS * OPS.flops_per_row(SIZES) / 197e12 / 1.720)
    assert run.roofline_bound == "compute"
    assert run.notes["part_loops"] == pytest.approx(
        {"mix.ssd_scan": 60.0, "mix.attention": 200.0, "ffn": 1000.0})
    # the parallel mixer beside the feed-forward, as the acceptance reads it
    by_part = run.notes["parts"]
    mixer = sum(by_part[p] for p in ("proj", "mix.elementwise",
                                     "mix.ssd_scan", "mix.attention",
                                     "mix.rope"))
    assert mixer == pytest.approx(670.0)
    assert by_part["ffn"] == pytest.approx(1000.0)
    # the line such a run prints is complete by the driver's own check
    got.update(compile_s={"value": 1.0, "unit": "s"},
               cache_misses={"value": 0.0, "unit": "count"})
    row = {"correct": True, "attempted": 1, "failed": 0, "metrics": got,
           "device": {}}
    assert check_line.problems(row, CELL, traced=True) == []


def test_a_program_without_the_new_names_reads_nothing_and_raises_nothing():
    """Another model's program (a window layer's loop alone: no scan, no
    feed-forward under its own part, no turn): each time reads 0.0, each
    share is left out of the line; every one is None where there is no
    trace."""
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
    configurations are coupled through one kind's look-up by shape; three
    other configurations' windows are as long and their vocabularies
    others."""
    make = spec.plugin("inputs", "falcon_h1_tokens").make
    a, b = make(5, (SEQ,), 3_000_000_019), make(5, (SEQ,), 3_000_000_019)
    assert (a == b).all() and a.shape == (5, SEQ)
    assert a.min() >= 0 and 261_000 < a.max() < VOCAB
    assert (a == a.round()).all()
    assert not (a == make(5, (SEQ,), 3_000_000_020)).all()
    assert make(3, (40,), 1).max() < 96
    assert (make(3, (40,), 7) == spec.plugin("inputs", "trinity_tokens").make(
        3, (40,), 7)).all()
    with pytest.raises(ValueError):
        make(1, (44,), 1)  # Nemotron's toy window: another kind's
    kinds = {}
    for name in sorted(os.listdir(os.path.join(spec.BENCH_DIR, "configs"))):
        doc = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", name))
        kinds.setdefault(doc["inputs"]["kind"], []).append(
            tuple(doc["model"]["input_shape"]))
    assert sorted(kinds["falcon_h1_tokens"]) == [(40,), (SEQ,)]
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
        command + ["--workload", "falcon_h1_tiny.tokens16k_backlog", "--seed",
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
    # nothing of the step depends on the data: no counter of the model's
    assert not [k for k in layer if k.startswith(("expert_", "falcon_"))]


@pytest.mark.timeout(115)
def test_the_tolerances_two_readings_and_the_mixers_check_at_toy_sizes(
        tmp_path):
    """``tools/tolerance_fused.py`` (``tools/tolerance.py`` with the control's
    rounding one fused pass a leaf: the published head does not fit in
    float32 twice over) at the toy sizes: the program answers every row, the
    float8 control none. ``tools/falcon_h1_mixer_check.py``: the
    parallel mixer against the reference's, in float32 here, the attention
    loop's lines at two tiles and the scan's at a step's rows and at one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/tolerance_fused.py", "--config",
         "falcon_h1_tiny", "--rehearse", "5:f8"],
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
        [sys.executable, "benchmarks/tools/falcon_h1_mixer_check.py",
         "--config", "falcon_h1_tiny", "--rehearse", "--seed", "5",
         "--limit", "1e-4", "--repeats", "2", "--tiles", "8", "16"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=50)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert [(r["check"], r.get("query_tile"), r["rows"]) for r in rows] == [
        ("mixer", None, 4), ("attention", 8, 4), ("attention", 16, 4),
        ("scan", None, 4), ("scan", None, 1)]
    assert all(r["pass"] and r["length"] == 40 for r in rows)
    assert rows[0]["forms"] == ["short_conv=xla", "ssd_scan=chunked",
                                "rotary_turn=halves",
                                "causal_attention=blocked-grouped"]
    assert rows[0]["rms_over_rms"] < 1e-5
    assert rows[1]["group"] == 5 and rows[1]["stacked_rows"] == 40
    assert rows[2]["max_from_first_tile"] < 1e-5
    assert all(r["ms_median"] > 0 for r in rows[1:])
    assert rows[3]["state_bytes"] == 4 * 4 * 4 * 8 * 12
    assert rows[3]["loops_found"] >= 1 and rows[3]["carry_kept"] is False
