"""The benchmark's Nemotron-H files: the configuration against the catalog
row it is cut from, ``ops/nemotron_h.py`` against a count by hand and the
program's own parameter tree, the six new metric files on a hand-made plane,
the new input kind beside the one that was there, and a rehearsal of
``nemotron_h_tiny.tokens_backlog`` on the CPU."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.core import harness, spec  # noqa: E402

CELL = "nemotron_3_nano_30b.tokens_backlog"
CONFIG = spec.config("nemotron_3_nano_30b")
SIZES = CONFIG["published"]
OPS = spec.plugin("ops", "nemotron_h")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# every width of the row: none may differ from the published value
WIDTHS = {"hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
          "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
          "chunk_size": 128, "expand": 2, "num_attention_heads": 32,
          "num_key_value_heads": 2, "head_dim": 128,
          "intermediate_size": 1856, "moe_intermediate_size": 1856,
          "moe_shared_expert_intermediate_size": 3712,
          "num_experts_per_tok": 6, "n_shared_experts": 1,
          "routed_scaling_factor": 2.5}
NEW_METRICS = {"ssd_scan_ms", "ssd_scan_roofline_share", "gqa_attention_ms",
               "gqa_attention_roofline_share", "relu2_expert_matmul_ms",
               "relu2_expert_matmul_roofline_share"}


def test_configuration_states_the_cut_and_keeps_every_width():
    held = SIZES["held"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    # the top level is the configuration as run; ``published`` as published
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (9, 32, 32768)
    assert (SIZES["num_hidden_layers"], SIZES["n_routed_experts"],
            SIZES["vocab_size"]) == (52, 128, 131072)
    assert (held["num_hidden_layers"], held["n_routed_experts"],
            held["vocab_size"], held["chips_per_layer"],
            held["first_expert"]) == (9, 32, 32768, 4, 0)
    for key, value in WIDTHS.items():
        assert CONFIG[key] == SIZES[key] == value, key
    for key, value in SIZES.items():
        if key not in CONFIG["reduced"] and key != "held":
            assert CONFIG[key] == value, key
    # the pattern stands as published; what runs is its first nine letters
    assert SIZES["hybrid_override_pattern"] == PATTERN and len(PATTERN) == 52
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == (
        23, 23, 6)
    assert CONFIG["held_pattern"] == held["pattern"] == PATTERN[:9] \
        == "MEMEM*EME"
    assert "Four chips share each layer" in CONFIG["deployment"]
    assert "experts 0-31" in CONFIG["deployment"]
    assert CONFIG["model"] == {"name": "nemotron_3_nano_30b",
                               "input_shape": [4096], "num_classes": 32768,
                               "dtype": "bfloat16"}
    # the floors of the model-configs guide: every kind of layer in its
    # published ratio to the nearest whole layer, eight experts or more, an
    # eighth of the vocabulary or more
    assert OPS._layers(SIZES) == (4, 4, 1)
    assert held["n_routed_experts"] >= 8
    assert held["vocab_size"] * 8 >= SIZES["vocab_size"]
    for key in ("positions", "mamba2", "router", "experts", "weights", "ids",
                "chunk", "stream"):
        assert CONFIG["assumed"][key], key
    assert "no rotary" in CONFIG["assumed"]["positions"]
    assert CONFIG["on_device"]["parameters"] == 1_712_918_016
    assert CONFIG["on_device"]["parameters_bytes"] == 2 * 1_712_918_016
    assert CONFIG["guarantees"] == spec.config("kimi_linear_48b")[
        "guarantees"]
    assert 0 < CONFIG["tolerance"]["relative_distance"] < 0.2
    assert "float8" in CONFIG["tolerance"]["why"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalog_row_is_in_the_file():
    rows = [json.loads(line) for line in open(CATALOG)]
    (row,) = [r for r in rows
              if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]
    assert CONFIG["source"] == row["source_url"]
    (entry,) = [c for c in spec.benchmark()["configs"]
                if c["name"] == "nemotron_3_nano_30b"]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"]
    for key, value in row["config"].items():
        assert SIZES[key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


def test_ops_count_by_hand():
    """One token through each kind of layer, multiply-adds by hand."""
    d = 2688
    mamba = d * (4096 + 6144 + 64) + 4096 * d
    assert OPS.mamba_projection_parameters(SIZES) == mamba == 38_707_200
    attn = d * 4096 + 2 * d * 256 + 4096 * d
    assert OPS.attention_projection_parameters(SIZES) == attn == 23_396_352
    expert = 2 * d * 1856
    assert expert == 9_977_856
    # the issue's arithmetic, layer by layer
    e_layer = 32 * expert + 2 * expert + d * 128 + 128 + d
    m_layer = mamba + 5 * 6144 + 3 * 64 + 4096 + d
    assert (e_layer, m_layer) == (339_593_984, 38_744_896)
    assert OPS.parameters(SIZES) == (
        4 * m_layer + 4 * e_layer + attn + d + d + 2 * 32768 * d
    ) == 1_712_918_016
    parts = OPS.kernels(SIZES, rows=8, bytes_per_value=2)
    tokens = 8 * 4096
    # the chunked state at 128, triangles half: 64 * 64 within the chunk and
    # two 64 * 128 products with the state a head, 64 * 128 of C B^T a group
    assert parts["ssd_scan"]["flops"] == 2 * 4 * tokens * (
        64 * (4096 + 2 * 8192) + 8 * 8192)
    # x and y at 4,096, B and C at 1,024 each in bfloat16, the step float32
    assert parts["ssd_scan"]["bytes"] == 4 * tokens * (
        2 * (4096 + 4096 + 2048) + 4 * 64)
    # a query meets 2048.5 keys, 256 multiply-adds a pair and query head
    assert parts["gqa_attention"]["flops"] == 2 * tokens * 32 * 256 * 2048.5
    # the keys and values of 2 heads, not of 32
    assert parts["gqa_attention"]["bytes"] == tokens * 2 * 34 * 128 * 2
    # 1.5 assignments a token and layer are held on average: 6 * 32 / 128
    assert parts["expert_matmul"]["flops"] == 2 * 4 * tokens * 1.5 * expert
    counted = OPS.kernels(SIZES, 8, 2, assignments=1000)["expert_matmul"]
    assert counted["flops"] == 2 * 1000 * expert
    assert counted["bytes"] == 4 * 32 * expert * 2 + 1000 * d * 6
    per_token = 2 * (4 * mamba + attn + 4 * (d * 128 + 2 * d * 3712))
    row = 4096 * per_token + sum(
        k["flops"] for k in OPS.kernels(SIZES, 1, 2).values()) + 2 * d * 32768
    assert OPS.flops_per_row(SIZES) == row
    assert 2.7e12 < row < 2.9e12  # the issue reckoned 22.5 TFLOP a step of 8
    got = OPS.counts(SIZES, rows=8, steps=1, bytes_per_value=2)
    assert got["flops"] == 8 * row
    assert got["bytes"] == 2 * 1_712_918_016 + 8 * 4 * (4096 + 32768)


def test_ops_parameters_are_the_programs():
    import jax

    from storm_tpu.models.registry import build_model

    for name in ("nemotron_3_nano_30b", "nemotron_h_tiny"):
        model = build_model(name)
        params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        held = sum(x.size for x in jax.tree.leaves(params))
        sizes = spec.config(name)["published"]
        assert OPS.parameters(sizes) == held, name
        assert model.hyper["pattern"] == sizes["held"]["pattern"] == sizes[
            "hybrid_override_pattern"][:sizes["held"]["num_hidden_layers"]]


def test_rows_per_step_reads_the_window_shape():
    names = ["%fusion.1 = bf16[8,4096,2688]{2,1,0} fusion(bf16[8,4096,2688])",
             "%fusion.2 = f32[32768,128]{1,0} fusion()"]
    assert OPS.rows_per_step(names, SIZES) == 8
    assert OPS.rows_per_step(["%fusion.2 = f32[32768,128] fusion()"],
                             SIZES) is None


MS = 1e6  # nanoseconds
# the loops as the v5e compiler writes them for the 8-row program
SSD = ("%while.94 = (s32[], f32[8,8,8,64,128]{4,3,2,1,0}, "
       "bf16[32,8,128,8,8,64]{2,5,4,3,1,0}) while(%t), body=%b")
GQA = ("%while.41 = (s32[], bf16[8,32,4096,128]{2,3,1,0}, "
       "bf16[8,2,4096,128]{3,2,1,0}) while(%t), body=%b")
EXP = ("%while.44 = (s32[], bf16[229377,2688]{1,0}, "
       "bf16[32,1856,2688]{2,1,0}, bf16[32,2688,1856]{1,2,0}) while(%t)")
INNER = "%while.98 = (s32[], s32[32]{0}, u32[]) while(%t), body=%searchsorted"
STREAM = "%fusion.9 = bf16[8,4096,2688]{2,1,0} fusion(%p), kind=kLoop"


def _planes():
    """Three executions of one program, 100 ms each; the first is cut (it
    holds fewer operations than the others). A whole one: two scans of 7 ms
    with an operation of their own inside, 10 ms of attention, two expert
    loops of 20 ms with the routing's small loop inside, and the stream."""
    mods, ops = [], []
    for i, start in enumerate((0, 100, 200)):
        mods.append(("jit_fwd(7)", start * MS, 100 * MS))
        at = start * MS
        if i:
            ops += [(SSD, at + 1 * MS, 7 * MS),
                    ("%fusion.1 = f32[8] fusion()", at + 2 * MS, 5 * MS),
                    (SSD, at + 10 * MS, 7 * MS)]
        ops += [(GQA, at + 20 * MS, 10 * MS), (EXP, at + 30 * MS, 20 * MS),
                (INNER, at + 31 * MS, 1 * MS), (EXP, at + 50 * MS, 20 * MS),
                (STREAM, at + 70 * MS, 20 * MS)]
    return [("/device:TPU:0", [("XLA Modules", mods), ("XLA Ops", ops)])]


def _run_over(planes, config=CONFIG, cell=CELL):
    run = harness.Run({"name": cell}, config, {}, 0, 1.0)
    run.trace = {"busy_s": 0.2}
    run._device_planes = planes
    run.device = {"kind": "TPU v5 lite"}
    return run


@pytest.mark.parametrize("metric,ms", [("ssd_scan_ms", 14.0),
                                       ("gqa_attention_ms", 10.0),
                                       ("relu2_expert_matmul_ms", 40.0)])
def test_new_metric_files_sum_the_named_loops_of_whole_executions(metric, ms):
    doc = spec.metric(metric)
    assert doc["reader"] == "trace_ops_time"
    reader = spec.plugin("readers", doc["reader"])
    assert reader.read(_run_over(_planes()), **doc["args"]) == \
        pytest.approx(ms)


@pytest.mark.parametrize("metric,kernel,ms", [
    ("ssd_scan_roofline_share", "ssd_scan", 14.0),
    ("gqa_attention_roofline_share", "gqa_attention", 10.0),
    ("relu2_expert_matmul_roofline_share", "expert_matmul", 40.0)])
def test_kernel_share_is_least_time_over_time_read(metric, kernel, ms):
    run = _run_over(_planes())
    run.registry_before = {"inference-bolt": {}}
    run.registry_after = {"inference-bolt": {
        "expert_assignments_held": 50 * 196608,
        "batch_size": {"count": 50, "sum": 400.0}}}
    doc = spec.metric(metric)
    assert doc["args"]["kernel"] == kernel
    got = spec.plugin("readers", doc["reader"]).read(run, **doc["args"])
    # the experts' share is of the assignments the program counted
    work = OPS.kernels(SIZES, 8, 2, assignments=196608)[kernel]
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert got == pytest.approx(100 * least / (ms / 1e3))
    assert 0 < got < 100
    assert run.notes["kernels"][kernel]["rows"] == 8


def test_new_metrics_read_nothing_from_a_program_without_the_loops():
    """The parent's programs (no such loop), Kimi-Linear's (other shapes) and
    an untraced run: the metric is left out of the line, nothing is raised.
    Nor do Kimi-Linear's metric files find anything in this program."""
    kimi = [("/device:TPU:0", [
        ("XLA Modules", [("jit_fwd(1)", t * MS, 90 * MS)
                         for t in (0, 100, 200)]),
        ("XLA Ops", [(op, t * MS + 1 + i, 20 * MS) for t in (0, 100, 200)
                     for i, op in enumerate((
                         "%while.3 = (s32[], bf16[8,32,64,64,64]) while(%t)",
                         "%while.4 = (s32[], bf16[8,32,4096,192]) while(%t)",
                         "%while.5 = (s32[], bf16[32,2304,1024]) while(%t)",
                         "%fusion.2 = bf16[8,4096,2304] fusion()"))])])]
    for name in sorted(NEW_METRICS):
        doc = spec.metric(name)
        reader = spec.plugin("readers", doc["reader"])
        assert reader.read(_run_over(kimi), **doc["args"]) is None, name
        untraced = _run_over(_planes())
        untraced.trace = None
        assert reader.read(untraced, **doc["args"]) is None
    for name in ("kda_scan_ms", "mla_attention_ms", "expert_matmul_ms"):
        doc = spec.metric(name)
        reader = spec.plugin("readers", doc["reader"])
        assert reader.read(_run_over(_planes()), **doc["args"]) is None, name
        assert reader.read(_run_over(kimi), **doc["args"]) == \
            pytest.approx(20.0), name


def test_cell_reports_the_shared_and_the_new_metrics():
    bench = spec.benchmark()
    cell = spec.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "tokens_backlog"
    assert cell["config"] == "nemotron_3_nano_30b" and len(cell["why"]) <= 200
    e2e = {m["name"] for m in spec.metrics_for(bench, "end_to_end", cell)}
    assert e2e == {"records_per_s", "setup_s"}
    layer = {m["name"]: m for m in spec.metrics_for(bench, "per_layer", cell)}
    assert set(layer) >= NEW_METRICS | {
        "parse_ms_per_record", "batch_size_mean", "model_step_ms",
        "model_roofline_share", "egress_ms_per_record", "device_idle_share",
        "cut_hold_mean_ms", "expert_tokens_max_over_mean",
        "expert_assignments_held_share"}
    # Kimi-Linear's own loops are not this cell's to report, nor the reverse
    assert not {"kda_scan_ms", "mla_attention_ms", "expert_matmul_ms"} & set(
        layer)
    kimi = spec.cell(bench, "kimi_linear_48b.tokens_backlog")
    assert not NEW_METRICS & {
        m["name"] for m in spec.metrics_for(bench, "per_layer", kimi)}
    for name in NEW_METRICS:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["layer"] == "engine and model"
        assert layer[name]["moves"] == "records_per_s"
        assert layer[name]["source"] == "device_trace"
        assert layer[name]["unit"] == (
            "%" if name.endswith("_roofline_share") else "ms")
    assert len(bench["workloads"]) == 4 and len(bench["configs"]) == 3
    assert bench["run_seconds"] == 20
    assert CONFIG["inputs"] == {"kind": "token_windows", "decimals": 0,
                                "candidates": 96}


def test_token_windows_come_from_the_held_slice_and_the_seed():
    make = spec.plugin("inputs", "token_windows").make
    a, b = make(5, (4096,), 3_000_000_019), make(5, (4096,), 3_000_000_019)
    assert (a == b).all() and a.shape == (5, 4096)
    assert a.min() >= 0 and a.max() < 32768 and a.max() > 32000
    assert (a == a.round()).all()
    assert not (a == make(5, (4096,), 3_000_000_020)).all()
    assert make(3, (44,), 1).max() < 96
    with pytest.raises(ValueError):
        make(1, (40,), 1)  # Kimi-Linear's toy window: another kind's


def test_token_ids_still_answers_for_both_of_its_windows():
    """A second configuration with windows of 4,096 ids and another slice
    would make ``token_ids`` raise for both cells: the new ones name a kind
    of their own."""
    make = spec.plugin("inputs", "token_ids").make
    a = make(5, (4096,), 3_000_000_019)
    assert a.max() < 20480 and a.max() > 20000
    assert make(3, (40,), 1).max() < 96
    kinds = {}
    for name in sorted(os.listdir(os.path.join(spec.BENCH_DIR, "configs"))):
        doc = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", name))
        kinds.setdefault(doc["inputs"]["kind"], []).append(
            tuple(doc["model"]["input_shape"]))
    for kind, shapes in kinds.items():
        assert len(shapes) == len(set(shapes)), kind
    assert sorted(kinds["token_windows"]) == [(44,), (4096,)]


@pytest.mark.timeout(115)
def test_rehearsal_of_the_tiny_cell_on_the_cpu(tmp_path):
    bench = spec.benchmark()
    # a compile cache of its own: tests/test_infer.py watches the checkout's
    # while other workers run
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    command = [sys.executable if w == "python3" else w
               for w in bench["command"]]
    proc = subprocess.run(
        command + ["--workload", "nemotron_h_tiny.tokens_backlog", "--seed",
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
    assert layer["batch_size_mean"] <= 8.0
    assert 40 < layer["expert_assignments_held_share"] < 60  # 4 of 8 held
    assert layer["expert_tokens_max_over_mean"] >= 1.0
    assert not NEW_METRICS & set(layer)  # no trace, no TPU: left out
