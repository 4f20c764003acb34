"""The benchmark's Kimi-Linear files: the configuration against the catalog
row it is cut from, ``ops/kimi_linear.py`` against a count by hand and the
program's own parameter tree, the two new readers on hand-made inputs, and a
rehearsal of ``kimi_linear_tiny.tokens_backlog`` on the CPU."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.core import harness, spec  # noqa: E402

CELL = "kimi_linear_48b.tokens_backlog"
CONFIG = spec.config("kimi_linear_48b")
SIZES = CONFIG["published"]
OPS = spec.plugin("ops", "kimi_linear")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "head_dim", "num_experts_per_token",
          "num_attention_heads", "linear_attn_config")


def test_configuration_states_the_cut_and_keeps_every_width():
    held = SIZES["held"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    # the top level is the configuration as run; ``published`` as published
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (5, 32, 20480)
    assert (SIZES["num_hidden_layers"], SIZES["num_experts"],
            SIZES["vocab_size"]) == (27, 256, 163840)
    assert (held["num_hidden_layers"], held["num_experts"],
            held["vocab_size"], held["chips_per_layer"]) == (5, 32, 20480, 8)
    for key in WIDTHS:
        assert CONFIG[key] == SIZES[key], key
    for key, value in SIZES.items():
        if key not in CONFIG["reduced"] and key != "held":
            assert CONFIG[key] == value, key
    assert "Eight chips share each layer" in CONFIG["deployment"]
    assert "experts 0-31" in CONFIG["deployment"]
    assert CONFIG["model"] == {"name": "kimi_linear_48b",
                               "input_shape": [4096], "num_classes": 20480,
                               "dtype": "bfloat16"}
    # the floors of the model-configs guide: a whole period and four layers
    # after the dense one, eight experts or more, an eighth of the vocabulary
    la = SIZES["linear_attn_config"]
    kinds = ["mla" if i in la["full_attn_layers"] else "kda"
             for i in range(1, held["num_hidden_layers"] + 1)]
    assert kinds == ["kda", "kda", "kda", "mla", "kda"]
    assert held["num_experts"] >= 8
    assert held["vocab_size"] * 8 >= SIZES["vocab_size"]
    for key in ("decay", "output_gate", "router", "weights", "ids", "chunk"):
        assert CONFIG["assumed"][key]
    assert CONFIG["on_device"]["parameters_bytes"] == 2 * 1_281_911_680


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalog_row_is_in_the_file():
    rows = [json.loads(line) for line in open(CATALOG)]
    (row,) = [r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert SIZES[key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


def test_ops_count_by_hand():
    """One token through each kind of layer, multiply-adds by hand."""
    d = 2304
    kda_proj = 4 * d * 4096 + 2 * (d * 128 + 128 * 4096) + d * 32
    assert OPS.kda_projection_parameters(SIZES) == kda_proj == 39_460_864
    mla_proj = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d
    assert OPS.mla_projection_parameters(SIZES) == mla_proj == 29_114_368
    assert OPS._layers(SIZES) == (4, 1, 4)
    parts = OPS.kernels(SIZES, rows=8, bytes_per_value=2)
    tokens = 8 * 4096
    # the chunked state at 64: 2*32*128 + 32*256 + 3*128*128 + 32*128 a head
    assert parts["kda_scan"]["flops"] == 2 * 4 * tokens * 32 * (
        8192 + 8192 + 49152 + 4096)
    # a query meets 2048.5 keys, 320 multiply-adds a pair and head
    assert parts["mla_attention"]["flops"] == 2 * tokens * 32 * 320 * 2048.5
    # one assignment a token and layer is held on average: 8 * 32 / 256
    assert parts["expert_matmul"]["flops"] == 2 * 4 * tokens * 3 * d * 1024
    counted = OPS.kernels(SIZES, 8, 2, assignments=1000)["expert_matmul"]
    assert counted["flops"] == 2 * 1000 * 3 * d * 1024
    per_token = 2 * (4 * kda_proj + mla_proj + 3 * d * 9216
                     + 4 * (d * 256 + 3 * d * 1024))
    row = 4096 * per_token + sum(
        k["flops"] for k in OPS.kernels(SIZES, 1, 2).values()) + 2 * d * 20480
    assert OPS.flops_per_row(SIZES) == row
    assert 2.7e12 < row < 2.9e12  # the issue reckoned 2.75 TFLOP a record
    got = OPS.counts(SIZES, rows=8, steps=1, bytes_per_value=2)
    assert got["flops"] == 8 * row
    assert got["bytes"] == 2 * 1_281_911_680 + 8 * 4 * (4096 + 20480)


def test_ops_parameters_are_the_programs():
    import jax

    from storm_tpu.models.registry import build_model

    for name in ("kimi_linear_48b", "kimi_linear_tiny"):
        model = build_model(name)
        params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        held = sum(x.size for x in jax.tree.leaves(params))
        assert OPS.parameters(spec.config(name)["published"]) == held, name


def test_rows_per_step_reads_the_window_shape():
    names = ["%fusion.1 = bf16[8,4096,2304]{2,1,0} fusion(bf16[8,4096,2304])",
             "%fusion.2 = f32[32768,256]{1,0} fusion()"]
    assert OPS.rows_per_step(names, SIZES) == 8
    assert OPS.rows_per_step(["%fusion.2 = f32[32768,256] fusion()"],
                             SIZES) is None


MS = 1e6  # nanoseconds
KDA = "%while.3 = (s32[], bf16[8,32,64,64,64]{4,3,2,1,0}) while(%t), body=%b"
MLA = "%while.4 = (s32[], bf16[8,32,4096,192]{3,2,1,0}) while(%t), body=%b"
EXP = "%while.5 = (s32[], bf16[32,2304,1024]{2,1,0}) while(%t), body=%b"
STREAM = "%fusion.9 = bf16[8,4096,2304]{2,1,0} fusion(%p), kind=kLoop"


def _planes():
    """Three executions of one program, 100 ms each; the first is cut (it
    holds fewer operations than the others). A whole one: a 30 ms KDA loop
    with an operation of its own inside it, 10 ms of attention, two expert
    loops of 8 ms, and the stream."""
    mods, ops = [], []
    for i, start in enumerate((0, 100, 200)):
        mods.append(("jit_fwd(7)", start * MS, 100 * MS))
        at = start * MS
        if i:
            ops += [(KDA, at + 1 * MS, 30 * MS),
                    ("%fusion.1 = f32[8] fusion()", at + 2 * MS, 5 * MS)]
        ops += [(MLA, at + 40 * MS, 10 * MS), (EXP, at + 52 * MS, 8 * MS),
                (EXP, at + 60 * MS, 8 * MS), (STREAM, at + 70 * MS, 20 * MS)]
    return [("/device:TPU:0", [("XLA Modules", mods), ("XLA Ops", ops)])]


def _run_over(planes):
    run = harness.Run({"name": CELL}, CONFIG, {}, 0, 1.0)
    run.trace = {"busy_s": 0.2}
    run._device_planes = planes
    run.device = {"kind": "TPU v5 lite"}
    return run


@pytest.mark.parametrize("metric,ms", [("kda_scan_ms", 30.0),
                                       ("mla_attention_ms", 10.0),
                                       ("expert_matmul_ms", 16.0)])
def test_trace_ops_time_sums_the_named_loops_of_whole_executions(metric, ms):
    doc = spec.metric(metric)
    reader = spec.plugin("readers", doc["reader"])
    assert reader.read(_run_over(_planes()), **doc["args"]) == \
        pytest.approx(ms)


def test_kernel_share_is_least_time_over_time_read():
    run = _run_over(_planes())
    run.registry_before = {"inference-bolt": {}}
    run.registry_after = {"inference-bolt": {
        "expert_assignments_held": 50 * 131072,
        "batch_size": {"count": 50, "sum": 400.0}}}
    for kernel, ms in (("kda_scan", 30.0), ("mla_attention", 10.0),
                       ("expert_matmul", 16.0)):
        doc = spec.metric(kernel + "_roofline_share")
        got = spec.plugin("readers", doc["reader"]).read(run, **doc["args"])
        work = OPS.kernels(SIZES, 8, 2, assignments=131072)[kernel]
        least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
        assert got == pytest.approx(100 * least / (ms / 1e3))
        assert 0 < got < 100
        assert run.notes["kernels"][kernel]["rows"] == 8


def test_a_program_without_the_loops_reads_nothing():
    """The parent's programs, or any other model's: the metric is left out
    of the line and nothing is raised."""
    planes = [("/device:TPU:0", [
        ("XLA Modules", [("jit_fwd(1)", t * MS, 90 * MS)
                         for t in (0, 100, 200)]),
        ("XLA Ops", [("%fusion.2 = bf16[256,257,1408] fusion()",
                      t * MS + 1, 80 * MS) for t in (0, 100, 200)])])]
    for name in ("kda_scan_ms", "expert_matmul_roofline_share"):
        doc = spec.metric(name)
        reader = spec.plugin("readers", doc["reader"])
        assert reader.read(_run_over(planes), **doc["args"]) is None
        untraced = _run_over(planes)
        untraced.trace = None
        assert reader.read(untraced, **doc["args"]) is None


def test_registry_counter_share():
    reader = spec.plugin("readers", "registry_counter_share")
    run = harness.Run({"name": CELL}, CONFIG, {}, 0, 1.0)
    run.registry_before = {"inference-bolt": {"held": 100, "absent": 700}}
    run.registry_after = {"inference-bolt": {"held": 228, "absent": 1596}}
    args = {"component": "inference-bolt", "of": "held",
            "among": ["held", "absent"]}
    assert reader.read(run, **args) == pytest.approx(12.5)
    run.registry_after = {"inference-bolt": {"held": 228}}
    assert reader.read(run, **args) is None
    doc = spec.metric("expert_assignments_held_share")
    assert doc["reader"] == "registry_counter_share"


def test_cell_reports_the_shared_and_the_new_metrics():
    bench = spec.benchmark()
    cell = spec.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "tokens_backlog"
    e2e = {m["name"] for m in spec.metrics_for(bench, "end_to_end", cell)}
    assert e2e == {"records_per_s", "setup_s"}
    layer = {m["name"] for m in spec.metrics_for(bench, "per_layer", cell)}
    assert layer >= {
        "parse_ms_per_record", "batch_size_mean", "model_step_ms",
        "model_roofline_share", "egress_ms_per_record", "device_idle_share",
        "cut_hold_mean_ms", "kda_scan_ms", "mla_attention_ms",
        "expert_matmul_ms", "kda_scan_roofline_share",
        "mla_attention_roofline_share", "expert_matmul_roofline_share",
        "expert_tokens_max_over_mean", "expert_assignments_held_share"}
    traffic = spec.traffic("tokens_backlog")
    assert (traffic["outstanding"], traffic["pool"], traffic["payload"],
            traffic["arrivals"]) == (128, 32, "arrow_tensor", "closed_loop")
    assert traffic["program"] == {"topology.spout_scheme": "raw"}
    assert CONFIG["inputs"] == {"kind": "token_ids", "decimals": 0,
                                "candidates": 96}


def test_token_ids_come_from_the_held_slice_and_the_seed():
    make = spec.plugin("inputs", "token_ids").make
    a, b = make(5, (4096,), 3_000_000_019), make(5, (4096,), 3_000_000_019)
    assert (a == b).all() and a.shape == (5, 4096)
    assert a.min() >= 0 and a.max() < 20480 and a.max() > 20000
    assert (a == a.round()).all()
    assert make(3, (40,), 1).max() < 96
    with pytest.raises(ValueError):
        make(1, (41,), 1)


@pytest.mark.timeout(115)
def test_rehearsal_of_the_tiny_cell_on_the_cpu():
    bench = spec.benchmark()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    command = [sys.executable if w == "python3" else w
               for w in bench["command"]]
    proc = subprocess.run(
        command + ["--workload", "kimi_linear_tiny.tokens_backlog", "--seed",
                   "3000000023", "--seconds", "2", "--trace", "0",
                   "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=105)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    row = lines[-1]
    assert row["correct"] is True and row["failed"] == 0
    assert row["attempted"] > 0
    assert set(row["metrics"]) == {"records_per_s", "setup_s"}
    # float32 against float32: about 1e-6, and a few hundredths on a seed
    # where a token's two best experts tie to the last bit
    assert 0 < row["checks"]["farthest_output"][0] <= 0.15
    every = [line for line in lines if line.get("phase") == "all_metrics"][0]
    layer = every["per_layer"]
    assert layer["batch_size_mean"] <= 8.0
    assert 40 < layer["expert_assignments_held_share"] < 60  # 4 of 8 held
    assert layer["expert_tokens_max_over_mean"] >= 1.0
