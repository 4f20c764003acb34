"""The ``ouro_2_6b`` configuration and its cell (PR 73): the configuration
holds every key of the catalog's row and cuts nothing, the operation counts
are the issue's table by hand and the program's own parameters, the entries
are found by name, every listed metric reads a number from a trace of the
cell's shapes (a step that is one ``%while`` over its passes), the reader
that descends into loops on event lists made by hand, and the rehearsal of
the toy cell on the CPU."""

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

CELL = "ouro_2_6b.tokens_backlog32"
BENCH = spec.benchmark()
CONFIG = spec.config("ouro_2_6b")
SIZES = CONFIG["published"]
OPS = spec.plugin("ops", "ouro")
LOOPS = spec.plugin("readers", "trace_loop_part_time")
FLAT = spec.plugin("readers", "trace_part_time")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMETERS = 2_667_974_657
ROWS, SEQ, LAYERS, PASSES, D, F, VOCAB = 4, 4096, 48, 4, 2048, 5632, 49152
SHARED = {"parse_ms_per_record", "batch_size_mean", "model_step_ms",
          "model_roofline_share", "egress_ms_per_record", "device_idle_share",
          "cut_hold_mean_ms", "step_gap_max_ms", "setup_topology_ready_s",
          "setup_parameters_s", "setup_programs_load_s",
          "setup_spanned_share"}
# metric -> (reader, part, kernel)
NEW = {"ouro_attention_roofline_share": (
           "trace_loop_part_time", "mix.attention", "attention"),
       "ouro_feed_forward_roofline_share": (
           "trace_loop_part_time", "ffn", "feed_forward"),
       "ouro_passes_per_record": ("registry_counter_ratio", None, None)}


def _entry(group, name):
    (found,) = [e for e in BENCH[group] if e["name"] == name]
    return found


def test_configuration_is_the_published_model_uncut():
    assert CONFIG["reduced"] == [] and _entry("configs", "ouro_2_6b") == {
        "name": "ouro_2_6b", "source": CONFIG["source"],
        "file": "benchmarks/configs/ouro_2_6b.json", "reduced": [],
        "why": _entry("configs", "ouro_2_6b")["why"]}
    assert len(_entry("configs", "ouro_2_6b")["why"]) <= 200
    held = SIZES["held"]
    assert held["layers"] == list(range(48)) and held["num_hidden_layers"] \
        == SIZES["num_hidden_layers"] == 48
    assert held["total_ut_steps"] == SIZES["total_ut_steps"] == 4
    assert held["vocab_size"] == SIZES["vocab_size"] == 49152
    assert (held["sequence_length"], held["rows_per_step"],
            held["attention_query_tile"], held["chips_per_layer"],
            held["pipeline_stages"]) == (4096, 4, 512, 1, 1)
    assert "one chip holds the model whole" in CONFIG["deployment"].lower()
    assert "nothing is cut" in CONFIG["deployment"].lower()
    for key in ("why", "block", "between_passes", "attention", "biases",
                "rotary", "feed_forward", "exit_gate", "unread", "weights",
                "inputs", "output", "ids", "stream", "tiles"):
        assert len(CONFIG["assumed"][key]) > 40, key
    for key in ("max_position_embeddings", "max_window_layers",
                "use_sliding_window", "sliding_window"):
        assert key in CONFIG["assumed"]["unread"]
    assert CONFIG["model"] == {"name": "ouro_2_6b", "input_shape": [4096],
                               "num_classes": 49152, "dtype": "bfloat16"}
    assert CONFIG["inputs"] == {"kind": "ouro_tokens", "decimals": 0,
                                "candidates": 8}
    assert (CONFIG["runner"], CONFIG["reference"], CONFIG["ops"]) == (
        "standard", "ouro", "ouro")
    assert 0 < CONFIG["tolerance"]["relative_distance"] < 0.1
    assert len(CONFIG["tolerance"]["why"]) > 400
    assert set(CONFIG["guarantees"]) == set(
        spec.config("falcon_h1_34b")["guarantees"])
    device = CONFIG["on_device"]
    assert device["parameters"] == PARAMETERS
    assert device["parameters_bytes"] == 2 * PARAMETERS
    assert device["parameters_bytes"] > 0.25 * 16e9  # the driver's floor
    # the toy twin runs the same code and is no cell
    tiny = spec.config("ouro_tiny")
    assert tiny["reference"] == "ouro" and tiny["ops"] == "ouro"
    assert tiny["published"]["total_ut_steps"] == 4
    assert "ouro_tiny" not in {c["name"] for c in BENCH["configs"]}


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_in_the_file():
    rows = [json.loads(line) for line in open(CATALOG)]
    (row,) = [r for r in rows if r["name"] == "Ouro-2.6B"]
    assert CONFIG["source"] == row["source_url"]
    assert row["config"]["model_type"] == "ouro"
    assert row["mechanisms"] == ["layers run several times"]
    for key, value in row["config"].items():
        assert SIZES[key] == value, key
        assert CONFIG[key] == value, key  # reduced is empty: every one


def test_ops_count_the_issues_table_by_hand():
    attention = 4 * D * D
    feed_forward = 3 * D * F
    assert OPS.attention_parameters(SIZES) == attention == 16_777_216
    assert OPS.feed_forward_parameters(SIZES) == feed_forward == 34_603_008
    assert OPS.layer_parameters(SIZES) == attention + feed_forward + 4 * D \
        == 51_388_416
    assert OPS.parameters(SIZES) == 48 * 51_388_416 + 2 * VOCAB * D + D \
        + D + 1 == PARAMETERS
    assert OPS.applications(SIZES) == 192
    tokens = ROWS * SEQ
    work = OPS.kernels(SIZES, ROWS, 2)
    assert set(work) == {"projections", "attention", "feed_forward"}
    assert work["projections"]["flops"] == 2 * 192 * tokens * attention
    assert work["feed_forward"]["flops"] == 2 * 192 * tokens * feed_forward
    pairs = SEQ * (SEQ + 1) // 2  # 8,390,656 a row and head
    assert work["attention"]["flops"] == 192 * ROWS * 16 * 4 * 128 * pairs
    assert work["attention"]["bytes"] == 192 * tokens * 4 * D * 2
    assert work["feed_forward"]["bytes"] == 192 * (
        feed_forward + tokens * 2 * D) * 2
    assert work["projections"]["bytes"] == 192 * (
        attention + tokens * 6 * D) * 2
    # 94.0 TFLOP a window: the products 80.8, the causal pairs 13.2
    row = OPS.flops_per_row(SIZES)
    assert row == 2 * 192 * SEQ * (attention + feed_forward) \
        + 192 * 16 * 4 * 128 * pairs + 2 * D * VOCAB + 2 * D * 4
    assert 93.95e12 < row < 94.05e12
    counts = OPS.counts(SIZES, rows=ROWS, steps=1, bytes_per_value=2)
    assert counts["flops"] == ROWS * row
    assert 1.90 < counts["flops"] / 197e12 < 1.92  # seconds at the peak
    # a pass's weights once a pass, the ends and the gate once
    assert counts["bytes"] == (192 * 51_388_416 + 2 * VOCAB * D + 2 * D + 1) \
        * 2 + ROWS * 4 * (SEQ + VOCAB)
    assert counts["bytes"] / 819e9 < 0.03 * counts["flops"] / 197e12


def test_ops_parameters_are_the_programs():
    import jax

    from storm_tpu.models.registry import build_model

    for name, count in (("ouro_2_6b", PARAMETERS), ("ouro_tiny", None)):
        model = build_model(name)
        params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        held = sum(x.size for x in jax.tree.leaves(params))
        sizes = spec.config(name)["published"]
        assert OPS.parameters(sizes) == held, name
        assert count in (None, held)
        assert model.max_rows == sizes["held"]["rows_per_step"]
        assert (model.hyper["layers"], model.hyper["passes"],
                model.hyper["dim"], model.hyper["ffn_width"],
                model.hyper["heads"], model.hyper["head_dim"],
                model.hyper["threshold"], model.hyper["rope_theta"]) == (
            sizes["num_hidden_layers"], sizes["total_ut_steps"],
            sizes["hidden_size"], sizes["intermediate_size"],
            sizes["num_attention_heads"], sizes["head_dim"],
            sizes["early_exit_threshold"], sizes["rope_theta"])
        assert len(params["layers"]) == len(sizes["held"]["layers"])


def test_rows_per_step_reads_the_window_shape():
    names = ["%fusion.1 = f32[4,4096,2048]{2,1,0} fusion(f32[4,4096,2048])",
             "%fusion.2 = bf16[4,4096,5632]{2,1,0} fusion()",
             "%fusion.3 = f32[4,2048]{1,0} fusion()"]
    assert OPS.rows_per_step(names, SIZES) == 4
    assert OPS.rows_per_step(names[1:], SIZES) is None


# ---- the reader that descends, on event lists made by hand -------------------

def _part_of(names):
    from storm_tpu.ops import parts

    return lambda event: parts.part_of(names[event]) if names.get(event) \
        else None


def test_a_loop_over_children_of_three_parts_is_read_as_its_children():
    """A top-level ``%while`` (no name of its own) over a norm, a nested row
    loop of the attention kernel (one part: read whole), a feed-forward that
    overlaps the loop before it by 2, and an unnamed copy; then a head
    outside the loop that overlaps the loop's end by 1."""
    names = {"%fusion.n": "norm/mul", "%while.a": None,
             "%call.a": "mix.elementwise/mix.attention/while/body/pallas",
             "%add.a": "mix.elementwise/mix.attention/while/body/add",
             "%fusion.f": "ffn/dot_general", "%copy.1": None,
             "%fusion.h": "jit(fwd)/head/dot_general",
             "%fusion.e": "jit(fwd)/embed/gather"}
    ops = [("%fusion.e", 0, 5),
           ("%while.p", 10, 100),       # the passes: 10..110
           ("%fusion.n", 12, 8),        # norm 12..20
           ("%while.a", 20, 40),        # the row loop 20..60, one part
           ("%call.a", 21, 30), ("%add.a", 52, 1),
           ("%fusion.f", 58, 22),       # ffn 58..80: 2 inside the loop before
           ("%copy.1", 85, 5),          # unnamed 85..90
           ("%fusion.h", 109, 6)]       # head 109..115: 1 inside the passes
    totals, descended = {}, {}
    cursor, memo = -1.0, {}
    for root in LOOPS.forest(ops):
        cursor = LOOPS.book(root, _part_of(names), cursor, totals, descended,
                            memo)
    assert totals == {"embed": 5, "norm": 8, "mix.attention": 40, "ffn": 20,
                      "(none)": 5, "(loop)": 100 - 8 - 40 - 20 - 5,
                      "head": 5}
    assert descended == {"%while.p": 100}
    # the parts sum to the time some operation ran
    assert sum(totals.values()) == sum(
        e - s for s, e in xplane.union([[s, s + d] for _, s, d in ops]))
    # the flat reader gives the whole loop its first named child's part and
    # counts the head whole
    flat = {}
    for _name, part, dur in FLAT.top_level(ops, _part_of(names)):
        flat[part] = flat.get(part, 0) + dur
    assert flat == {"embed": 5, "norm": 100, "head": 6}


def test_without_such_a_loop_it_reads_what_the_flat_reader_reads():
    names = {"%fusion.n": "jit(fwd)/norm/mul", "%while.a": None,
             "%call.a": "jit(fwd)/mix.elementwise/mix.attention/while/body/p",
             "%while.x": None, "%add.x": "jit(fwd)/jit(main)/add",
             "%fusion.p": "jit(fwd)/mix.elementwise/proj/dot_general",
             "%zero": None}
    ops = [("%fusion.n", 0, 10), ("%while.a", 10, 30), ("%call.a", 11, 20),
           ("%fusion.p", 40, 25), ("%while.x", 65, 5), ("%add.x", 66, 1),
           ("%zero", 70, 2)]
    planes = [("/device:TPU:0", [
        ("XLA Modules", [("jit_fwd(1)", t, 80) for t in (0, 100, 200)]),
        ("XLA Ops", [(n, t + s, d) for t in (0, 100, 200)
                     for n, s, d in ops])])]
    op_names = {"/device:TPU:0": {k: v for k, v in names.items() if v}}
    from storm_tpu.ops import parts

    flat, _unnamed, steps, _loops = FLAT.by_part(
        planes, op_names, "jit_fwd", parts.part_of)
    deep, busy, descended, deep_steps = LOOPS.by_part(
        planes, op_names, "jit_fwd", parts.part_of)
    assert steps == deep_steps == 3 and descended == []
    assert deep == flat == pytest.approx(
        {"norm": 10e-6, "mix.attention": 30e-6, "proj": 25e-6,
         "(none)": 7e-6})
    assert busy == pytest.approx(72e-6)


# ---- every listed metric over a trace of this cell's shapes ------------------

MS = 1e6  # nanoseconds
DEV = "/device:TPU:0"
STEP_MS = 2700.0
# one pass's operations as the v5e compiler names them (layouts dropped), a
# block standing for 48: (event, op_name, start in the pass, ms)
STREAM = "%fusion.9 = f32[4,4096,2048]{2,1,0} fusion(%p), kind=kLoop"
QKV = "%fusion.12 = bf16[4,4096,2048]{2,1,0} fusion(%n, %w), kind=kOutput"
TURN = ("%_turn_lanes.3 = bf16[4,4096,2048]{2,1,0} "
        "custom-call(%cos, %sin, %q)")
ROWLOOP = ("%while.120 = (s32[], bf16[4,4096,2048], bf16[4,4096,2048], "
           "bf16[4,4096,2048], bf16[4,4096,2048]) while(%tuple.3), "
           "condition=%c, body=%b")
KERNEL = "%flash.1 = bf16[4,4096,2048]{2,1,0} custom-call(%r, %q, %k, %v, %o)"
FFN = "%fusion.30 = bf16[4,4096,5632]{2,1,0} fusion(%m, %g, %u), kind=kOutput"
LAST = "%fusion.40 = f32[4,4096,2048]{2,1,0} fusion(%h, %g), kind=kLoop"
PASS_OPS = [
    (STREAM, "norm/mul", 2, 50),
    (QKV, "mix.elementwise/proj/dot_general", 52, 160),
    (TURN, "mix.elementwise/mix.rope/jit(_turn_lanes)/pallas_call", 212, 36),
    (ROWLOOP, None, 248, 100),
    (KERNEL, "mix.elementwise/mix.attention/while/body/closed_call/"
     "jit(flash_attention_merged)/pallas_call", 249, 98),
    (FFN, "ffn/dot_general", 348, 300),
    (LAST, "norm/mul", 650, 10),
]
PASS_MS = 673.0  # 2 of its own before its first child, 13 after its last
PASSLOOP = ("%while.900 = (s32[], f32[4,4096,2048], f32[4,4,2048]) "
            "while(%tuple.9), condition=%c2, body=%b2")
GATHER = "%fusion.1 = f32[4,4096,2048]{2,1,0} fusion(%ids, %e), kind=kLoop"
HEADOP = "%fusion.50 = f32[4,49152]{1,0} fusion(%z, %w), kind=kOutput"
NAMES = {GATHER: "jit(fwd)/embed/gather", HEADOP: "jit(fwd)/head/dot_general",
         **{n: o for n, o, _s, _d in PASS_OPS if o}}
WANT_PARTS = {"embed": 4.0, "head": 4.0, "norm": 4 * 60.0, "proj": 4 * 160.0,
              "mix.rope": 4 * 36.0, "mix.attention": 4 * 100.0,
              "ffn": 4 * 300.0, "(loop)": 2700 - 8 - 4 * 656.0}


def _step_ops(at):
    ops = [(GATHER, at, 4.0), (PASSLOOP, at + 4, 2692.0),
           (HEADOP, at + 2696, 4.0)]
    for t in range(PASSES):
        ops += [(n, at + 4 + t * PASS_MS + s, d) for n, _o, s, d in PASS_OPS]
    return ops


def _traced_run(steps=5):
    mods, ops, log = [], [], []
    for i in range(steps):  # the first and the last are cut: fewer operations
        at = i * STEP_MS
        cut = i in (0, steps - 1)
        mods.append(("jit_fwd(5)", at * MS, STEP_MS * MS))
        ops += [(n, s * MS, d * MS) for n, s, d in _step_ops(at)[2 * cut:]]
    planes = [(DEV, [("XLA Modules", mods), ("XLA Ops", ops)])]
    cell = spec.cell(BENCH, CELL)
    run = harness.Run(cell, CONFIG, {}, 0, 10.0)
    run.device = {"kind": "TPU v5 lite"}
    run.trace = xplane.reduce(planes)
    run._device_planes = planes
    run._trace_meta = {"op_names": {DEV: NAMES}, "start_s": None}
    off = 7000.0  # the device's zero on the host's clock
    for n in range(8):  # steps 4.. are the traced executions
        ready = off + STEP_MS / 1e3 * (n - 4 + 1) + 2e-4
        log.append({"step": n, "engine": "ouro_2_6b", "padded": ROWS,
                    "rows": ROWS, "sources": 2, "seen": True,
                    "t_first_enq": ready - 10.0, "t_cut": ready - 5.02,
                    "t_staged": ready - 5.01, "t_launched": ready - 5.0,
                    "t_ready": ready, "t_fetched": ready + 0.001,
                    "t_resolved": ready + 0.002})
    run._step_rows = log
    run.setup_s = 80.0
    row = lambda span, parent, name, t0, t1, **attrs: {  # noqa: E731
        "span": span, "parent": parent, "name": name, "t_start": t0,
        "t_end": t1, "thread": "MainThread", "attrs": attrs}
    run._setup_rows = [
        row(1, None, "parameters", 100.0, 104.0, source="seed"),
        row(10, None, "topology.submit", 150.0, 176.0, topology="bench"),
        row(11, 10, "component.prepare", 150.0, 175.0,
            component="inference-bolt", task=0),
        row(12, 11, "engine.build", 150.0, 175.0, engine="ouro_2_6b"),
        row(13, 12, "parameters", 150.0, 154.0, source="seed"),
        row(14, 12, "warmup.bucket", 155.0, 175.0, bucket=4, padded=4),
        row(15, 14, "program", 155.0, 167.0, padded=4, engine="ouro_2_6b"),
        row(16, 15, "jax.backend_compile", 156.0, 166.0,
            fun_name="jit(fwd)", cache="hit")]
    run.delivery_times = [off - 4 * STEP_MS / 1e3, off]
    run.delivered_in_window = ROWS * 4
    hist = lambda count, total: {"count": count, "sum": total}  # noqa: E731
    run.registry_before = {"inference-bolt": {
        "exit_pass_rows_1": 0, "exit_pass_rows_2": 0, "exit_pass_rows_3": 0,
        "exit_pass_rows_4": 40, "passes_run": 160}, "kafka-bolt": {}}
    run.registry_after = {
        "inference-bolt": {
            "decode_ms": hist(16, 16 * 0.05), "batch_size": hist(4, 16.0),
            "encode_ms": hist(16, 16 * 15.0), "cut_hold_ms": hist(4, 0.0),
            "exit_pass_rows_1": 0, "exit_pass_rows_2": 0,
            "exit_pass_rows_3": 0, "exit_pass_rows_4": 56,
            "passes_run": 224},
        "kafka-bolt": {"produce_ms": hist(16, 16 * 0.5)}}
    return run


def test_the_new_entries_list_what_reads_here():
    """Found by name. How many cells the benchmark has and which comes last
    is no business of this file's: the next cell must not fail it."""
    cell = spec.cell(BENCH, CELL)
    assert cell in BENCH["workloads"]
    assert cell["chips"] == 1 and cell["traffic"] == "tokens_backlog32"
    assert cell["config"] == "ouro_2_6b" and len(cell["why"]) <= 200
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == "ouro_2_6b"] == [CELL]  # no second cell
    assert BENCH["run_seconds"] == 20
    e2e = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end", cell)}
    assert e2e == {"records_per_s", "setup_s"}
    assert _entry("end_to_end", "records_per_s")["bound"] == 0.01
    assert _entry("end_to_end", "setup_s")["bound"] == 0.1
    layer = {m["name"]: m for m in spec.metrics_for(BENCH, "per_layer", cell)}
    assert set(layer) == SHARED | set(NEW) | {"compile_s", "cache_misses"}
    # no metric that reads parts the flat way is this cell's: its step is one
    # top-level loop, which that reader books to its first named child
    for name in layer:
        assert spec.metric(name)["reader"] not in (
            "trace_part_time", "trace_part_share", "trace_ops_time"), name
    for name, (reader, _part, _kernel) in NEW.items():
        counted = name == "ouro_passes_per_record"
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["layer"] == "engine and model"
        assert layer[name]["moves"] == "records_per_s"
        assert layer[name]["unit"] == (
            "passes/record" if counted else "%")
        assert layer[name]["better"] == ("lower" if counted else "higher")
        assert layer[name]["source"] == (
            "program_counter" if counted else "device_trace")
        assert spec.metric(name)["reader"] == reader
    for name in SHARED:
        assert CELL in layer[name]["workloads"]
    # the new metrics stand together, after every metric an earlier PR brought
    names = [m["name"] for m in BENCH["per_layer"]]
    first = min(names.index(n) for n in NEW)
    assert set(names[first:first + len(NEW)]) == set(NEW)
    assert first > names.index("setup_spanned_share")
    assert len(names) <= 128  # the contract's most: why three and not eleven
    traffic = spec.traffic("tokens_backlog32")
    assert (traffic["outstanding"], traffic["pool"], traffic["payload"],
            traffic["arrivals"], traffic["warmup_seconds"],
            traffic["drain_seconds"], traffic["trace_seconds"]) == (
        32, 8, "arrow_tensor", "closed_loop", 10, 90, 12)
    assert traffic["program"] == {"topology.spout_scheme": "raw"}


def test_the_metric_files_name_their_readers_and_parts():
    from storm_tpu.ops import parts

    kernels = OPS.kernels(SIZES, ROWS, 2)
    for name, (reader, part, kernel) in NEW.items():
        doc = spec.metric(name)
        assert doc["reader"] == reader and doc["doc"]
        assert os.path.exists(os.path.join(
            spec.BENCH_DIR, "readers", reader + ".py"))
        if part is None:
            continue
        assert doc["args"] == {"prefix": "jit_fwd", "part": part,
                               "kernel": kernel}
        assert part in parts.VOCABULARY and kernel in kernels
    assert spec.metric("ouro_passes_per_record")["args"] == {
        "component": "inference-bolt", "of": "passes_run",
        "over": [f"exit_pass_rows_{t}" for t in (1, 2, 3, 4)]}


def test_every_listed_metric_reads_a_number_from_a_trace_of_its_shapes():
    run = _traced_run()
    cell = spec.cell(BENCH, CELL)
    listed = spec.metrics_for(BENCH, "per_layer", cell)
    got = harness.read_metrics(run, [m for m in listed if m["name"]
                                     not in ("compile_s", "cache_misses")])
    assert set(got) == SHARED | set(NEW)
    assert got["model_step_ms"]["value"] == pytest.approx(STEP_MS)
    assert got["ouro_passes_per_record"]["value"] == 4.0
    assert got["batch_size_mean"]["value"] == 4.0
    assert got["step_gap_max_ms"]["value"] == pytest.approx(STEP_MS)
    assert got["setup_topology_ready_s"]["value"] == pytest.approx(26.0)
    assert got["setup_programs_load_s"]["value"] == pytest.approx(12.0)
    work = OPS.kernels(SIZES, ROWS, 2)
    assert got["ouro_attention_roofline_share"]["value"] == pytest.approx(
        100 * work["attention"]["flops"] / 197e12 / 0.400)
    assert got["ouro_feed_forward_roofline_share"]["value"] == pytest.approx(
        100 * work["feed_forward"]["flops"] / 197e12 / 1.200)
    assert got["model_roofline_share"]["value"] == pytest.approx(
        100 * ROWS * OPS.flops_per_row(SIZES) / 197e12 / 2.7)
    for name in ("model_roofline_share", "ouro_attention_roofline_share",
                 "ouro_feed_forward_roofline_share"):
        assert 0 < got[name]["value"] < 100 and math.isfinite(
            got[name]["value"])
    assert run.roofline_bound == "compute"
    assert {k: v["rows"] for k, v in run.notes["kernels"].items()} == {
        "attention": ROWS, "feed_forward": ROWS}
    # what the reader leaves beside its numbers: every part, the loop's own
    # time and the unnamed among them, summing to the step's busy time
    found = run.notes["loop_parts"]
    assert found["steps"] == 3
    assert found["parts"] == pytest.approx(WANT_PARTS)
    assert sum(found["parts"].values()) == pytest.approx(found["busy_ms"])
    assert found["busy_ms"] == pytest.approx(STEP_MS)
    assert found["named_share"] == pytest.approx(
        100 * (1 - WANT_PARTS["(loop)"] / STEP_MS)) and \
        found["named_share"] < 100
    assert [name for name, _ms in found["descended"]] == [PASSLOOP[:160]]
    assert found["descended"][0][1] == pytest.approx(2692.0)
    # the flat reader on the same step: one part
    FLAT.read(run, "jit_fwd", share=True)
    assert run.notes["parts"] == pytest.approx(
        {"embed": 4.0, "norm": 2692.0, "head": 4.0})
    # the line such a run prints is complete by the driver's own check
    got.update(compile_s={"value": 1.0, "unit": "s"},
               cache_misses={"value": 0.0, "unit": "count"})
    row = {"correct": True, "attempted": 1, "failed": 0, "metrics": got,
           "device": {}}
    assert check_line.problems(row, CELL, traced=True) == []


def test_a_program_without_the_new_counters_or_parts_reads_nothing():
    """The parent's side of a traced run with this PR's benchmark files:
    another model's program (no ``ffn`` part, no exit counters) leaves each
    new metric out and raises nothing; every one is None with no trace."""
    loop = ("%while.59 = (s32[], bf16[4,32,16384,128]) while(%t), "
            "condition=%c, body=%b")
    planes = [(DEV, [
        ("XLA Modules", [("jit_fwd(1)", t * MS, 90 * MS)
                         for t in (0, 100, 200)]),
        ("XLA Ops", [(loop, t * MS + 1, 8 * MS) for t in (0, 100, 200)])])]
    run = harness.Run(spec.cell(BENCH, CELL), CONFIG, {}, 0, 1.0)
    run.device = {"kind": "TPU v5 lite"}
    run.trace = xplane.reduce(planes)
    run._device_planes = planes
    run._trace_meta = {"op_names": {DEV: {
        loop: "jit(fwd)/mix.elementwise/mix.window_attention/while"}},
        "start_s": None}
    run.registry_before = run.registry_after = {"inference-bolt": {}}
    for name in sorted(NEW):
        doc = spec.metric(name)
        assert spec.plugin("readers", doc["reader"]).read(
            run, **doc["args"]) is None, name
    assert LOOPS.read(run, "jit_fwd", part="mix.window_attention") == 8.0
    untraced = harness.Run(spec.cell(BENCH, CELL), CONFIG, {}, 0, 1.0)
    untraced.registry_before = untraced.registry_after = {}
    for name in sorted(NEW):
        doc = spec.metric(name)
        assert spec.plugin("readers", doc["reader"]).read(
            untraced, **doc["args"]) is None


def test_the_windows_come_from_the_whole_vocabulary_and_a_kind_of_their_own():
    make = spec.plugin("inputs", "ouro_tokens").make
    a, b = make(5, (SEQ,), 3_000_000_019), make(5, (SEQ,), 3_000_000_019)
    assert (a == b).all() and a.shape == (5, SEQ)
    assert a.min() >= 0 and 49_000 < a.max() < VOCAB
    assert (a == a.round()).all()
    assert not (a == make(5, (SEQ,), 3_000_000_020)).all()
    assert make(3, (40,), 1).max() < 96
    with pytest.raises(ValueError):
        make(1, (44,), 1)  # Nemotron's toy window: another kind's
    kinds = {}
    for name in sorted(os.listdir(os.path.join(spec.BENCH_DIR, "configs"))):
        doc = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", name))
        kinds.setdefault(doc["inputs"]["kind"], []).append(
            tuple(doc["model"]["input_shape"]))
    assert sorted(kinds["ouro_tokens"]) == [(40,), (SEQ,)]


@pytest.mark.timeout(115)
def test_rehearsal_of_the_tiny_cell_on_the_cpu(tmp_path):
    # a compile cache of its own: tests/test_infer.py watches the checkout's
    # while other workers run
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    command = [sys.executable if w == "python3" else w
               for w in BENCH["command"]]
    proc = subprocess.run(
        command + ["--workload", "ouro_tiny.tokens_backlog32", "--seed",
                   "3000000029", "--seconds", "2", "--trace", "0",
                   "--rehearse", "--traffic-set", "warmup_seconds=2"],
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
    assert layer["ouro_passes_per_record"] == 4.0


@pytest.mark.timeout(115)
def test_the_tolerances_two_readings_and_the_check_at_toy_sizes(tmp_path):
    """``tools/tolerance.py`` at the toy sizes: the program answers every
    row, the float8 control none. ``tools/ouro_check.py``: both operators
    against the reference's, in float32 here, the two forms' timings' lines
    (both XLA's on the CPU), the loop over passes and the program."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/tolerance.py", "--config",
         "ouro_tiny", "--rehearse", "5:f8"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    (row,) = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert row["program"]["correct"] is True
    assert row["program"]["rows_failed"] == 0 and row["program"]["rows"] == 32
    assert row["program"]["max"] < 1e-5  # float32 here: summation order
    assert row["tolerance"] == 0.005
    assert row["float8"]["correct"] is False
    assert row["float8"]["min"] > 100 * row["program"]["max"]
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/ouro_check.py", "--config",
         "ouro_tiny", "--rehearse", "--seed", "5", "--limit", "1e-4",
         "--repeats", "2"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=50)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert [r["check"] for r in rows] == [
        "attention_mixer", "feed_forward", "attention", "attention", "passes",
        "program"]
    assert all(r["pass"] and r["length"] == 40 and r["rows"] == 4
               for r in rows)
    assert rows[0]["forms"] == ["rotary_turn=halves",
                                "causal_attention=blocked"]
    assert [r["shipped"] for r in rows[2:4]] == [True, False]
    assert rows[3]["max_from_shipped"] == 0.0  # the same form here
    assert rows[4]["passes"] == 4 and rows[4]["loop_over_passes"] > 0
    assert rows[4]["lowered_chars"]["looped"] \
        < 1.25 * rows[4]["lowered_chars"]["once"]
    assert rows[5]["whiles"] >= 4 and rows[5]["temporaries_bytes"] > 0
