"""The benchmark's Kimi K2 files: the configuration against the catalog row it
is cut from and the program's own parameter tree, ``ops/kimi_k2.py`` against
counts by hand, every per-layer metric that lists the new cell over a trace
of its shapes made by hand, what two tests of earlier PRs pin beside the
benchmark's size, and a rehearsal of ``kimi_k2_tiny.tokens_backlog`` on the
CPU."""

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

CELL = "kimi_k2_6.tokens_backlog"
BENCH = spec.benchmark()
CONFIG = spec.config("kimi_k2_6")
SIZES = CONFIG["published"]
OPS = spec.plugin("ops", "kimi_k2")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMETERS = 3_496_763_904
# every width of the row: none may differ from the published value
WIDTHS = {"hidden_size": 7168, "intermediate_size": 18432,
          "moe_intermediate_size": 2048, "q_lora_rank": 1536,
          "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128,
          "num_attention_heads": 64, "num_key_value_heads": 64,
          "num_experts_per_tok": 8, "n_shared_experts": 1,
          "routed_scaling_factor": 2.827, "rope_theta": 50000}
SHARED = {"parse_ms_per_record", "batch_size_mean", "model_step_ms",
          "model_roofline_share", "egress_ms_per_record", "device_idle_share",
          "cut_hold_mean_ms", "expert_tokens_max_over_mean",
          "expert_assignments_held_share", "step_named_share",
          "moe_routing_ms", "mixer_elementwise_ms", "projections_ms",
          "step_gap_max_ms"}
NEW = {"rope_ms", "mla_rope_attention_ms", "mla_rope_attention_roofline_share",
       "k2_expert_matmul_ms", "k2_expert_matmul_roofline_share",
       "k2_expert_combine_ms"}


def test_configuration_states_the_cut_and_keeps_every_width():
    held = SIZES["held"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    # the top level is the configuration as run; ``published`` as published
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (5, 12, 20480)
    assert (SIZES["num_hidden_layers"], SIZES["n_routed_experts"],
            SIZES["vocab_size"]) == (61, 384, 163840)
    assert (held["num_hidden_layers"], held["n_routed_experts"],
            held["vocab_size"], held["chips_per_layer"], held["first_expert"],
            held["sequence_length"], held["rows_per_step"]) == (
        5, 12, 20480, 32, 0, 4096, 4)
    for key, value in WIDTHS.items():
        assert CONFIG[key] == SIZES[key] == value, key
    for key, value in SIZES.items():
        if key not in CONFIG["reduced"] and key != "held":
            assert CONFIG[key] == value, key
    assert SIZES["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert "32 chips share each layer" in CONFIG["deployment"]
    assert "experts 0-11 of 384" in CONFIG["deployment"]
    assert CONFIG["model"] == {"name": "kimi_k2_6", "input_shape": [4096],
                               "num_classes": 20480, "dtype": "bfloat16"}
    # the floors of the model-configs guide: the leading dense layer and four
    # of those after it (the period is one layer), eight experts or more, an
    # eighth of the vocabulary
    assert OPS._layers(SIZES) == (1, 4)
    assert held["n_routed_experts"] >= 8
    assert held["vocab_size"] * 8 >= SIZES["vocab_size"]
    assert held["n_routed_experts"] * held["chips_per_layer"] \
        == SIZES["n_routed_experts"]
    for key in ("vision_tower", "rotary_pairing", "yarn", "router", "weights",
                "ids", "tiles", "stream"):
        assert CONFIG["assumed"][key], key
    assert "interleaved" in CONFIG["assumed"]["rotary_pairing"]
    assert "once" in CONFIG["assumed"]["rotary_pairing"]
    assert CONFIG["on_device"]["parameters"] == PARAMETERS
    assert CONFIG["on_device"]["parameters_bytes"] == 2 * PARAMETERS
    assert CONFIG["inputs"] == {"kind": "kimi_k2_tokens", "decimals": 0,
                                "candidates": 32}
    assert 0 < CONFIG["tolerance"]["relative_distance"] < 0.2
    entry = next(c for c in BENCH["configs"] if c["name"] == "kimi_k2_6")
    assert entry["file"] == "benchmarks/configs/kimi_k2_6.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] and len(entry["why"]) <= 200


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalog_row_is_in_the_file():
    rows = [json.loads(line) for line in open(CATALOG)]
    (row,) = [r for r in rows if r["name"] == "Kimi-K2.6"]
    assert CONFIG["source"] == row["source_url"]
    assert row["config"]["model_type"] == "kimi_k2"
    for key, value in row["config"].items():
        assert SIZES[key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


def test_ops_count_by_hand():
    """One token through each kind of layer, multiply-adds by hand (the
    issue's count, a parameter at a time)."""
    d = 7168
    mla = d * 1536 + 1536 * 12288 + d * 576 + 512 * 16384 + 8192 * d
    assert OPS.mla_projection_parameters(SIZES) == mla == 101_122_048
    mixer = mla + 1536 + 512
    assert mixer == 101_124_096
    expert = 3 * d * 2048
    assert expert == 44_040_192
    expert_layer = mixer + 2 * d + d * 384 + 384 + 13 * expert
    assert expert_layer == 676_413_824
    dense_layer = mixer + 2 * d + 3 * d * 18432
    assert dense_layer == 497_500_160
    assert dense_layer + 4 * expert_layer + 2 * 20480 * d + d == PARAMETERS
    assert OPS.parameters(SIZES) == PARAMETERS
    parts = OPS.kernels(SIZES, rows=4, bytes_per_value=2)
    tokens = 4 * 4096
    # a query meets 2048.5 keys, 192 + 128 multiply-adds a pair and head
    assert parts["mla_rope_attention"]["flops"] == \
        2 * 5 * tokens * 64 * 320 * 2048.5
    assert parts["mla_rope_attention"]["bytes"] == \
        5 * tokens * 64 * (2 * 192 + 2 * 128) * 2
    # a quarter of an assignment a token and layer is held: 8 * 12 / 384
    assert parts["expert_matmul"]["flops"] == 2 * tokens * expert
    assert parts["expert_matmul"]["bytes"] == \
        4 * 12 * expert * 2 + tokens * d * 6
    counted = OPS.kernels(SIZES, 4, 2, assignments=1000)["expert_matmul"]
    assert counted["flops"] == 2 * 1000 * expert
    per_token = 2 * (5 * mla + 3 * d * 18432 + 4 * (d * 384 + expert))
    row = 4096 * per_token + sum(
        k["flops"] for k in OPS.kernels(SIZES, 1, 2).values()) + 2 * d * 20480
    assert OPS.flops_per_row(SIZES) == row
    # the issue reckoned 2,664 MFLOP a token without the routers' 22
    assert 2.68e9 < row / 4096 < 2.69e9
    got = OPS.counts(SIZES, rows=4, steps=1, bytes_per_value=2)
    assert got["flops"] == 4 * row and 43.9e12 < got["flops"] < 44.1e12
    assert got["bytes"] == 2 * PARAMETERS + 4 * 4 * (4096 + 20480)


def test_ops_parameters_are_the_programs():
    import jax

    from storm_tpu.models.registry import build_model

    for name, count in (("kimi_k2_6", PARAMETERS), ("kimi_k2_tiny", None)):
        model = build_model(name)
        params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        held = sum(x.size for x in jax.tree.leaves(params))
        assert OPS.parameters(spec.config(name)["published"]) == held, name
        assert count in (None, held)


def test_rows_per_step_reads_the_window_shape():
    names = ["%fusion.1 = bf16[4,4096,7168]{2,1,0} fusion(bf16[4,4096,7168])",
             "%fusion.2 = f32[16384,384]{1,0} fusion()"]
    assert OPS.rows_per_step(names, SIZES) == 4
    assert OPS.rows_per_step(names[1:], SIZES) is None


# ---- every listed metric over a trace of this cell's shapes ------------------

MS = 1e6  # nanoseconds
DEV = "/device:TPU:0"
# one step's top-level operations: (name, op_name or None, start, duration)
STREAM = "%fusion.9 = bf16[4,4096,7168]{2,1,0} fusion(%p), kind=kOutput"
TURN = "%fusion.3 = bf16[4,4096,64,192]{3,2,1,0} fusion(%q), kind=kLoop"
ATTN = "%while.4 = (s32[], bf16[4,64,4096,192]{3,2,1,0}) while(%t), body=%b"
BODY = "%custom-call.2 = bf16[1,64,4096,128] custom-call(%q, %k, %v)"
SORT = "%sort.8 = (f32[16384,384], s32[16384,384]) sort(%a, %i)"
EXP = "%while.5 = (s32[], bf16[12,7168,2048]{2,1,0}) while(%t), body=%b"
COMB = "%while.6 = (s32[], f32[16384,7168]{1,0}) while(%t), body=%b"
GATE = "%fusion.7 = bf16[4,4096,64,256]{3,2,1,0} fusion(%kv), kind=kLoop"
COPY = "%copy.3 = f32[4,4096,7168] copy(%w)"
STEP_OPS = [
    (STREAM, "jit(fwd)/mix.elementwise/proj/dot_general", 0, 200),
    (TURN, "jit(fwd)/mix.elementwise/mix.rope/concatenate", 200, 10),
    (ATTN, None, 210, 80),
    (BODY, "jit(fwd)/mix.elementwise/mix.attention/while/body/pallas_call",
     211, 19),
    (GATE, "jit(fwd)/mix.elementwise/concatenate", 290, 60),
    (SORT, "jit(fwd)/moe.route/jit(sort)/sort", 350, 30),
    (EXP, "jit(fwd)/moe.experts/while", 380, 20),
    (COMB, "jit(fwd)/moe.combine/while", 400, 8),
    (COPY, None, 408, 12),
]
STEP_MS = 420.0
WANT = {"model_step_ms": STEP_MS, "rope_ms": 10.0,
        "mla_rope_attention_ms": 80.0, "k2_expert_matmul_ms": 20.0,
        "k2_expert_combine_ms": 8.0, "moe_routing_ms": 30.0,
        "mixer_elementwise_ms": 60.0, "projections_ms": 200.0,
        "step_named_share": 100.0 * 408 / 420,
        # the two cut executions lack their first 210 ms of operations
        "device_idle_share": 100.0 * 2 * 210 / (8 * 420),
        "batch_size_mean": 4.0, "cut_hold_mean_ms": 0.0,
        "expert_assignments_held_share": 3.125,
        "expert_tokens_max_over_mean": 1.5, "parse_ms_per_record": 0.05,
        "egress_ms_per_record": 2.0, "step_gap_max_ms": STEP_MS}
HELD_A_STEP = 4 * 16384 * 8 * 12 // 384  # the expected assignments held


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
    run = harness.Run(cell, CONFIG, {}, 0, 14.0)
    run.device = {"kind": "TPU v5 lite"}
    run.trace = xplane.reduce(planes)
    run._device_planes = planes
    run._trace_meta = {"op_names": {DEV: {n: o for n, o, _s, _d in STEP_OPS
                                          if o}}, "start_s": None}
    off = 7000.0  # the device's zero on the host's clock
    for n in range(40):  # steps 32.. are the traced executions
        ready = off + STEP_MS / 1e3 * (n - 32 + 1) + 2e-4
        log.append({"step": n, "engine": "kimi_k2_6", "padded": 4, "rows": 4,
                    "sources": 2, "seen": True, "t_first_enq": ready - 1.2,
                    "t_cut": ready - 0.85, "t_staged": ready - 0.845,
                    "t_launched": ready - 0.84, "t_ready": ready,
                    "t_fetched": ready + 0.001, "t_resolved": ready + 0.002})
    run._step_rows = log
    run.delivery_times = [off - 30 * STEP_MS / 1e3, off]
    run.delivered_in_window = 4 * 30
    hist = lambda count, total: {"count": count, "sum": total}  # noqa: E731
    run.registry_before = {"inference-bolt": {}, "kafka-bolt": {}}
    run.registry_after = {
        "inference-bolt": {
            "decode_ms": hist(120, 120 * 0.05), "batch_size": hist(30, 120.0),
            "encode_ms": hist(120, 120 * 1.5), "cut_hold_ms": hist(30, 0.0),
            "expert_tokens_max_over_mean": hist(120, 180.0),
            "expert_assignments_held": 30 * HELD_A_STEP,
            "expert_assignments_absent": 30 * HELD_A_STEP * 31},
        "kafka-bolt": {"produce_ms": hist(120, 120 * 0.5)}}
    return run


def test_the_cell_lists_the_shared_metrics_that_read_here_and_its_own():
    cell = spec.cell(BENCH, CELL)
    assert cell == BENCH["workloads"][4]  # the fifth; later cells come after
    assert cell["chips"] == 1 and cell["traffic"] == "tokens_backlog"
    assert cell["config"] == "kimi_k2_6" and len(cell["why"]) <= 200
    e2e = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end", cell)}
    assert e2e == {"records_per_s", "setup_s"}
    layer = {m["name"]: m for m in spec.metrics_for(BENCH, "per_layer", cell)}
    assert set(layer) == SHARED | NEW | {"compile_s", "cache_misses"}
    # loops told by other models' shapes are not this cell's to report
    assert not {"mla_attention_ms", "expert_matmul_ms", "expert_combine_ms",
                "kda_scan_ms", "gqa_attention_ms"} & set(layer)
    for name in NEW:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["layer"] == "engine and model"
        assert layer[name]["moves"] == "records_per_s"
        assert layer[name]["source"] == "device_trace"
        assert layer[name]["unit"] == (
            "%" if name.endswith("_roofline_share") else "ms")
    for name in SHARED:
        assert CELL in layer[name]["workloads"]
    # appended after everything PR 42's benchmark had, in one run
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index("rope_ms")
    assert first > names.index("idle_with_rows_share.paced")
    assert set(names[first:first + 6]) == NEW
    assert [c["name"] for c in BENCH["configs"]][3] == "kimi_k2_6"
    assert all(w["chips"] == 1 for w in BENCH["workloads"][:5])
    assert BENCH["run_seconds"] == 20


def test_every_listed_metric_reads_a_number_from_a_trace_of_its_shapes():
    run = _traced_run()
    cell = spec.cell(BENCH, CELL)
    listed = spec.metrics_for(BENCH, "per_layer", cell)
    got = harness.read_metrics(run, [m for m in listed if m["name"]
                                     not in ("compile_s", "cache_misses")])
    assert set(got) == SHARED | NEW
    for name, want in WANT.items():
        assert got[name]["value"] == pytest.approx(want, abs=1e-6), name
    for name in ("model_roofline_share", "mla_rope_attention_roofline_share",
                 "k2_expert_matmul_roofline_share"):
        assert 0 < got[name]["value"] < 100 and math.isfinite(
            got[name]["value"])
    work = OPS.kernels(SIZES, 4, 2, assignments=HELD_A_STEP)
    assert got["mla_rope_attention_roofline_share"]["value"] == pytest.approx(
        100 * work["mla_rope_attention"]["flops"] / 197e12 / 0.080)
    assert got["k2_expert_matmul_roofline_share"]["value"] == pytest.approx(
        100 * work["expert_matmul"]["flops"] / 197e12 / 0.020)
    assert work["expert_matmul"]["bytes"] / 819e9 \
        < work["expert_matmul"]["flops"] / 197e12  # 6.0 against 7.3 ms
    assert run.notes["kernels"]["expert_matmul"]["rows"] == 4
    assert got["model_roofline_share"]["value"] == pytest.approx(
        100 * 4 * OPS.flops_per_row(SIZES) / 197e12 / 0.420)
    assert run.roofline_bound == "compute"
    assert run.notes["parts"]["mix.rope"] == pytest.approx(10.0)
    assert run.notes["part_loops"] == pytest.approx(
        {"mix.attention": 80.0, "moe.experts": 20.0, "moe.combine": 8.0})
    # the line such a run prints is complete by the driver's own check
    got.update(compile_s={"value": 1.0, "unit": "s"},
               cache_misses={"value": 0.0, "unit": "count"})
    row = {"correct": True, "attempted": 1, "failed": 0, "metrics": got,
           "device": {}}
    assert check_line.problems(row, CELL, traced=True) == []


def test_a_program_without_the_new_parts_reads_nothing_and_raises_nothing():
    """The parent's programs, or another model's: each of the six is left out
    of the line but ``rope_ms``, which is 0.0 where the program has names
    and nothing under this one."""
    planes = [(DEV, [
        ("XLA Modules", [("jit_fwd(1)", t * MS, 90 * MS)
                         for t in (0, 100, 200)]),
        ("XLA Ops", [("%fusion.2 = bf16[8,4096,2304] fusion()",
                      t * MS + 1, 80 * MS) for t in (0, 100, 200)])])]
    run = harness.Run(spec.cell(BENCH, CELL), CONFIG, {}, 0, 1.0)
    run.device = {"kind": "TPU v5 lite"}
    run.trace = xplane.reduce(planes)
    run._device_planes = planes
    run._trace_meta = {"op_names": {}, "start_s": None}
    for name in sorted(NEW):
        doc = spec.metric(name)
        value = spec.plugin("readers", doc["reader"]).read(run, **doc["args"])
        assert value is None or (name == "rope_ms" and value == 0.0), name
    untraced = harness.Run(spec.cell(BENCH, CELL), CONFIG, {}, 0, 1.0)
    for name in sorted(NEW):
        doc = spec.metric(name)
        assert spec.plugin("readers", doc["reader"]).read(
            untraced, **doc["args"]) is None


# ---- what earlier PRs' tests pinned, with the fifth cell ---------------------

def test_the_listings_earlier_prs_pinned_with_the_fifth_cell():
    """Two tests under ``tests/benchmark/`` pin the benchmark's size as it
    stood (``test_perfbench_nemotron.py
    test_cell_reports_the_shared_and_the_new_metrics``: four cells and three
    configurations; ``test_perfbench_tracing.py
    test_the_listing_is_the_issues``: 17 pairs of PR 41's metrics and cells)
    and so fail with any fifth cell; only a ``benchmark`` PR may edit them
    (CHANGES.md, PR 43). Everything else they hold is held here too, with
    the new cell."""
    five = [w["name"] for w in BENCH["workloads"][:5]]  # later PRs append
    cells = {m["name"]: [c for c in m.get("workloads", five) if c in five]
             for m in BENCH["per_layer"]}
    backlog = [w["name"] for w in BENCH["workloads"][:5]
               if w["traffic"].endswith("backlog")]
    assert backlog[-1] == CELL and len(backlog) == 4
    assert [c["name"] for c in BENCH["configs"][:4]] == [
        "vit_g14", "kimi_linear_48b", "nemotron_3_nano_30b", "kimi_k2_6"]
    assert BENCH["run_seconds"] == 20
    # test_the_listing_is_the_issues, with 5 more pairs: the new cell's
    pr41 = ("step_named_share", "step_named_share.paced", "moe_routing_ms",
            "mixer_elementwise_ms", "projections_ms", "step_gap_max_ms",
            "step_gap_max_ms.paced", "cut_to_device_start_p50_ms.paced",
            "device_end_to_host_p50_ms.paced", "idle_with_rows_share.paced")
    assert sum(len(cells[name]) for name in pr41) == 17 + 5
    moves = {m["name"]: m["moves"] for m in BENCH["per_layer"]}
    for name in pr41:
        if name.endswith(".paced"):
            assert cells[name] == ["vit_g14.json_paced"]
            assert moves[name] != "records_per_s"
    assert cells["step_named_share"] == cells["step_gap_max_ms"] == backlog
    assert cells["moe_routing_ms"] == cells["projections_ms"] == \
        cells["mixer_elementwise_ms"] == backlog[1:]
    # test_cell_reports_the_shared_and_the_new_metrics: each family's own
    # loops are its cell's alone to report
    own = {"kimi_linear_48b.tokens_backlog": {
               "kda_scan_ms", "kda_scan_roofline_share", "mla_attention_ms",
               "mla_attention_roofline_share", "expert_matmul_ms",
               "expert_matmul_roofline_share"},
           "nemotron_3_nano_30b.tokens_backlog": {
               "ssd_scan_ms", "ssd_scan_roofline_share", "gqa_attention_ms",
               "gqa_attention_roofline_share", "relu2_expert_matmul_ms",
               "relu2_expert_matmul_roofline_share"},
           CELL: NEW}
    for cell_name, names in own.items():
        cell = spec.cell(BENCH, cell_name)
        assert cell["chips"] == 1 and cell["traffic"] == "tokens_backlog"
        assert {m["name"] for m in spec.metrics_for(
            BENCH, "end_to_end", cell)} == {"records_per_s", "setup_s"}
        for name in names:
            assert cells[name] == [cell_name], name
    nemotron = spec.config("nemotron_3_nano_30b")
    assert nemotron["inputs"] == {"kind": "token_windows", "decimals": 0,
                                  "candidates": 96}


def test_the_windows_come_from_the_held_slice_and_a_kind_of_their_own():
    """As ``token_windows`` for Nemotron: a kind of input a family, so that
    no two configurations are coupled through one kind's look-up by shape
    (``test_perfbench_nemotron.py
    test_token_ids_still_answers_for_both_of_its_windows`` holds that no
    kind has one shape twice). The same seed draws what ``token_ids`` draws
    over a slice of the same size: the chip's readings carry over."""
    make = spec.plugin("inputs", "kimi_k2_tokens").make
    a, b = make(5, (4096,), 3_000_000_019), make(5, (4096,), 3_000_000_019)
    assert (a == b).all() and a.shape == (5, 4096)
    assert a.min() >= 0 and 20000 < a.max() < 20480
    assert (a == a.round()).all()
    assert not (a == make(5, (4096,), 3_000_000_020)).all()
    assert (a == spec.plugin("inputs", "token_ids").make(
        5, (4096,), 3_000_000_019)).all()
    assert make(3, (40,), 1).max() < 96
    with pytest.raises(ValueError):
        make(1, (44,), 1)  # Nemotron's toy window: another kind's
    kinds = {}
    for name in sorted(os.listdir(os.path.join(spec.BENCH_DIR, "configs"))):
        doc = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", name))
        kinds.setdefault(doc["inputs"]["kind"], []).append(
            tuple(doc["model"]["input_shape"]))
    assert sorted(kinds["kimi_k2_tokens"]) == [(40,), (4096,)]
    assert sorted(kinds["token_ids"]) == [(40,), (4096,)]


@pytest.mark.timeout(115)
def test_rehearsal_of_the_tiny_cell_on_the_cpu(tmp_path):
    # a compile cache of its own: tests/test_infer.py watches the checkout's
    # while other workers run
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    command = [sys.executable if w == "python3" else w
               for w in BENCH["command"]]
    proc = subprocess.run(
        command + ["--workload", "kimi_k2_tiny.tokens_backlog", "--seed",
                   "3000000029", "--seconds", "2", "--trace", "0",
                   "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=105)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    row = lines[-1]
    assert row["correct"] is True and row["failed"] == 0
    assert row["attempted"] > 0
    assert set(row["metrics"]) == {"records_per_s", "setup_s"}
    assert 0 < row["checks"]["farthest_output"][0] <= 0.15
    every = [line for line in lines if line.get("phase") == "all_metrics"][0]
    layer = every["per_layer"]
    assert layer["batch_size_mean"] <= 4.0  # the one bucket: (4,)
    assert 15 < layer["expert_assignments_held_share"] < 35  # 4 of 16 held
    assert layer["expert_tokens_max_over_mean"] >= 1.0


@pytest.mark.timeout(115)
def test_the_tolerances_two_readings_are_judged_as_the_harness_judges(tmp_path):
    """``tools/tolerance.py`` at the toy sizes: the program answers every
    row, the float8 control does not, each by ``pairing.match_rows`` under
    the configuration's limit; the exit code says whether both read so."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/tolerance.py", "--config",
         "kimi_k2_tiny", "--rehearse", "5:f8"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=105)
    assert proc.returncode == 0, proc.stderr[-2000:]
    (row,) = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    tiny = spec.config("kimi_k2_tiny")
    assert row["tolerance"] == min(tiny["tolerance"]["relative_distance"],
                                   row["row_separation"] / 2)
    assert row["program"]["correct"] is True
    assert row["program"]["rows_failed"] == 0 and row["program"]["rows"] == 32
    assert row["program"]["max"] < 1e-5  # float32 here: summation order
    assert row["float8"]["correct"] is False
    assert row["float8"]["rows_failed"] > 0
    assert row["float8"]["min"] > 100 * row["program"]["max"]
