"""The benchmark's MiniCPM-SALA files (PR 45): the configuration against the
catalog row it is cut from and the program's own parameter tree,
``ops/minicpm_sala.py`` against counts by hand, every per-layer metric that
lists the new cell over a trace of its shapes made by hand, the new entries'
places in ``BENCHMARK.json``, and a rehearsal of
``minicpm_sala_tiny.tokens16k_backlog`` on the CPU."""

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

CELL = "minicpm_sala.tokens16k_backlog"
BENCH = spec.benchmark()
CONFIG = spec.config("minicpm_sala")
SIZES = CONFIG["published"]
OPS = spec.plugin("ops", "minicpm_sala")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMETERS = 1_711_129_600
SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
          "topk": 64, "init_blocks": 1, "window_size": 2048,
          "dense_len": 8192}
# every width of the row: none may differ from the published value
WIDTHS = {"hidden_size": 4096, "intermediate_size": 16384, "head_dim": 128,
          "num_attention_heads": 32, "num_key_value_heads": 2,
          "lightning_nh": 32, "lightning_nkv": 32, "lightning_head_dim": 128,
          "vocab_size": 73448, "scale_emb": 12, "scale_depth": 1.4,
          "dim_model_base": 256, "rope_theta": 10000}
SHARED = {"parse_ms_per_record", "batch_size_mean", "model_step_ms",
          "model_roofline_share", "egress_ms_per_record", "device_idle_share",
          "cut_hold_mean_ms", "step_named_share", "mixer_elementwise_ms",
          "projections_ms", "step_gap_max_ms"}
NEW = {"sparse_select_ms", "sparse_attention_ms",
       "sparse_attention_roofline_share", "lightning_scan_ms",
       "lightning_scan_roofline_share", "sparse_keys_read_share",
       "lightning_rope_ms"}
# the keys one group's queries read in a window: every causal key up to 64
# blocks, then 63 whole blocks and the query's own up to its position
KEYS_READ = 4096 * 4097 // 2 + 12288 * 4032 + 192 * (64 * 65 // 2)


def test_configuration_states_the_cut_and_keeps_every_width():
    held = SIZES["held"]
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert (CONFIG["num_hidden_layers"], SIZES["num_hidden_layers"]) == (4, 32)
    assert (held["num_hidden_layers"], held["chips_per_layer"],
            held["sequence_length"], held["rows_per_step"],
            held["ssd_chunk"], held["vocab_size"]) == (
        4, 1, 16384, 4, 128, 73448)
    assert held["mixer_types"] == CONFIG["held_mixer_types"] == \
        SIZES["mixer_types"][:4] == ["minicpm4"] + ["lightning-attn"] * 3
    # one period at the published ratio: 8 minicpm4 to 24 lightning-attn
    assert (SIZES["mixer_types"].count("minicpm4"),
            SIZES["mixer_types"].count("lightning-attn")) == (8, 24)
    assert OPS._layers(SIZES) == (1, 3)
    assert held["sparse"] == SPARSE
    for key, value in WIDTHS.items():
        assert CONFIG[key] == SIZES[key] == value, key
    for key, value in SIZES.items():
        if key not in CONFIG["reduced"] and key != "held":
            assert CONFIG[key] == value, key
    assert "One chip holds each layer whole" in CONFIG["deployment"]
    assert "layers 0-3 of 32" in CONFIG["deployment"]
    assert CONFIG["model"] == {"name": "minicpm_sala", "input_shape": [16384],
                               "num_classes": 73448, "dtype": "bfloat16"}
    for key in ("sparse_attention_sizes", "one_pooling_stage",
                "forced_blocks_inside_topk", "pool_alignment", "ties",
                "lightning_decay", "output_norm", "rotary", "mup", "weights",
                "inputs", "ids", "tiles", "stream"):
        assert CONFIG["assumed"][key], key
    assert "mup_denominator" in CONFIG["assumed"]["mup"]
    assert CONFIG["on_device"]["parameters"] == PARAMETERS
    assert CONFIG["on_device"]["parameters_bytes"] == 2 * PARAMETERS
    assert CONFIG["on_device"]["parameters_bytes"] \
        + CONFIG["on_device"]["program_temporaries_bucket_4_bytes"] < 15e9
    assert CONFIG["inputs"] == {"kind": "minicpm_sala_tokens", "decimals": 0,
                                "candidates": 16}
    assert 0 < CONFIG["tolerance"]["relative_distance"] < 0.2
    assert "float8" in CONFIG["tolerance"]["why"]
    entry = BENCH["configs"][-1]
    assert entry["name"] == "minicpm_sala"
    assert entry["file"] == "benchmarks/configs/minicpm_sala.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] and len(entry["why"]) <= 200


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_in_the_file():
    rows = [json.loads(line) for line in open(CATALOG)]
    (row,) = [r for r in rows if r["name"] == "MiniCPM-SALA"]
    assert CONFIG["source"] == row["source_url"]
    assert row["config"]["model_type"] == "minicpm_sala"
    for key, value in row["config"].items():
        assert SIZES[key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert row["hidden_size"] == 4096 and row["dense_width"] == 16384


def test_ops_count_by_hand():
    """A parameter at a time, as the issue counts them, and one token through
    each kind of layer."""
    d, f, sq = 4096, 16384, 4096 * 4096
    swiglu = 3 * d * f
    assert swiglu == 201_326_592
    sparse_layer = 3 * sq + 2 * d * 256 + 256 + 2 * d + swiglu
    assert sparse_layer == 253_763_840
    lightning_layer = 5 * sq + 256 + d + 2 * d + swiglu
    assert lightning_layer == 285_225_216
    ends = 2 * 73448 * d + d
    assert ends == 601_690_112
    assert sparse_layer + 3 * lightning_layer + ends == PARAMETERS
    assert OPS.parameters(SIZES) == PARAMETERS
    assert OPS.mixer_projection_parameters(SIZES) == (
        3 * sq + 2 * d * 256, 5 * sq)
    # 2,219 MFLOP a token in the four layers: 145.4 TFLOP a step of 4 rows
    per_token = 2 * (3 * sq + 2 * d * 256 + 3 * 5 * sq + 4 * swiglu)
    assert 2.2185e9 < per_token < 2.2195e9
    assert 145.3e12 < 4 * 16384 * per_token < 145.5e12
    assert OPS.keys_read(SIZES) == KEYS_READ == 58_335_232
    assert 0.4345 < KEYS_READ / (16384 * 16385 // 2) < 0.4347
    parts = OPS.kernels(SIZES, rows=4, bytes_per_value=2)
    assert parts["sparse_attention"]["flops"] == \
        2 * 4 * 32 * 256 * KEYS_READ
    # q and the result at 32 heads, the window's keys and values once
    assert parts["sparse_attention"]["bytes"] == \
        4 * 16384 * 2 * (32 + 2) * 128 * 2
    # a query at t sees the pooled keys whose window of 32 ends at or before
    # it: (t - 31) // 16 + 1 of them from position 31 on
    seen = sum((t - 31) // 16 + 1 for t in range(31, 16384))
    assert parts["sparse_select"]["flops"] == 2 * 4 * 32 * 128 * seen
    assert parts["lightning_scan"]["flops"] == \
        2 * 3 * 65536 * 32 * (64 * 128 + 64 * 128 + 2 * 128 * 128)
    assert parts["lightning_scan"]["bytes"] == 3 * 65536 * 4 * 4096 * 2
    # the scan is bound by its bytes, the attention by its operations
    assert parts["lightning_scan"]["bytes"] / 819e9 \
        > parts["lightning_scan"]["flops"] / 197e12
    assert parts["sparse_attention"]["flops"] / 197e12 \
        > parts["sparse_attention"]["bytes"] / 819e9
    row = 16384 * per_token + sum(
        k["flops"] for k in OPS.kernels(SIZES, 1, 2).values()) + 2 * d * 73448
    assert OPS.flops_per_row(SIZES) == row
    got = OPS.counts(SIZES, rows=4, steps=1, bytes_per_value=2)
    assert got["flops"] == 4 * row and 150.0e12 < got["flops"] < 150.4e12
    assert got["bytes"] == 2 * PARAMETERS + 4 * 4 * (16384 + 73448)
    # a window of dense_len or less reads every causal key and selects none
    short = dict(SIZES, held=dict(SIZES["held"], sequence_length=8192))
    assert OPS.keys_read(short) == 8192 * 8193 // 2
    assert OPS.kernels(short, 1, 2)["sparse_select"]["flops"] == 0


def test_ops_parameters_are_the_programs():
    import jax

    from storm_tpu.models.registry import build_model

    for name, count in (("minicpm_sala", PARAMETERS),
                        ("minicpm_sala_tiny", None)):
        model = build_model(name)
        params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        held = sum(x.size for x in jax.tree.leaves(params))
        assert OPS.parameters(spec.config(name)["published"]) == held, name
        assert count in (None, held)


def test_rows_per_step_reads_the_window_shape():
    names = ["%while.16 = (s32[], bf16[4,16384,4096]{2,1,0}, "
             "bf16[4096,16384]{1,0}) while(%t)",
             "%fusion.2 = f32[4,32,128]{2,1,0} fusion()"]
    assert OPS.rows_per_step(names, SIZES) == 4
    assert OPS.rows_per_step(names[1:], SIZES) is None


# ---- every listed metric over a trace of this cell's shapes ------------------

MS = 1e6  # nanoseconds
DEV = "/device:TPU:0"
# one step's top-level operations, as the v5e compiler names them (a compile
# for the described chip): (name, op_name or None, start, duration)
QKV = "%fusion.9 = bf16[4,16384,4096]{2,1,0} fusion(%p), kind=kOutput"


def _loop(number, carried):
    """A ``while`` as a trace names it: its tuple type, then its operand's."""
    return (f"%while.{number} = ({carried}) while(({carried}) %tuple.3), "
            "condition=%c, body=%b")


SELECT = _loop(20, "s32[]{:T(128)}, pred[4,2,16384,256]{2,1,3,0:T(4,128)"
               "(4,1)}, bf16[4,32,16384,128]{3,2,1,0:T(8,128)(2,1)}, "
               "bf16[4,2,16384,128]{3,2,1,0:T(8,128)(2,1)}, s32[]{:T(128)}, "
               "f32[]{:T(128)}, f32[]{:T(128)}")
TOPK = "%sort.3 = (f32[2,1024,256], s32[2,1024,256]) sort(%a, %i)"
ATTEND = _loop(15, "s32[]{:T(128)}, bf16[4,32,16384,128]{3,2,1,0:T(8,128)"
               "(2,1)}, s32[4]{0:T(128)S(1)}, pred[4,2,16384,256]{2,3,1,0:"
               "T(8,128)(4,1)}, bf16[4,32,16384,128]{3,2,1,0:T(8,128)(2,1)}, "
               "bf16[4,2,16384,128]{3,2,1,0:T(8,128)(2,1)}")
KERNEL = ("%_kernel_row.3 = bf16[2,16,16384,128]{3,2,1,0:T(8,128)(2,1)} "
          "custom-call(%at, %q, %k, %v, %m)")
GATE = "%fusion.7 = bf16[4,16384,4096]{2,1,0} fusion(%o, %g), kind=kLoop"
FFN = _loop(16, "s32[]{:T(128)}, bf16[4,16384,4096]{2,1,0:T(8,128)(2,1)}, "
            "bf16[4,16384,4096]{2,1,0:T(8,128)(2,1)}, bf16[4096,16384]{1,0}, "
            "bf16[4096,16384]{1,0}, bf16[16384,4096]{1,0}")
TURN = "%fusion.3 = bf16[4,16384,32,128]{3,2,1,0} fusion(%q), kind=kLoop"
SCAN = _loop(21, "s32[]{:T(128)}, f32[4,32,1,128,128]{4,3,1,0,2:T(8,128)"
             "S(1)}, bf16[128,4,128,32,1,128]{5,4,3,2,1,0}")
COPY = "%copy.3 = bf16[128,4,128,32,1,128] copy(%w)"
STEP_OPS = [
    (QKV, "jit(fwd)/mix.elementwise/proj/dot_general", 0, 300),
    (SELECT, None, 300, 60),
    (TOPK, "jit(fwd)/mix.elementwise/mix.sparse_select/while/body/top_k",
     301, 5),
    (ATTEND, None, 360, 120),
    (KERNEL, "jit(fwd)/mix.elementwise/mix.sparse_attention/while/body/"
     "pallas_call", 361, 29),
    (GATE, "jit(fwd)/mix.elementwise/mul", 480, 50),
    (FFN, "jit(fwd)/proj/while", 530, 500),
    (TURN, "jit(fwd)/mix.elementwise/mix.rope/concatenate", 1030, 20),
    (SCAN, "jit(fwd)/mix.elementwise/mix.ssd_scan/while", 1050, 90),
    (COPY, None, 1140, 60),
]
STEP_MS = 1200.0
WANT = {"model_step_ms": STEP_MS, "lightning_rope_ms": 20.0,
        "sparse_select_ms": 60.0,
        "sparse_attention_ms": 120.0, "lightning_scan_ms": 90.0,
        "mixer_elementwise_ms": 50.0, "projections_ms": 800.0,
        "step_named_share": 100.0 * 1140 / 1200,
        # the two cut executions lack their first 360 ms of operations
        "device_idle_share": 100.0 * 2 * 360 / (6 * 1200),
        "batch_size_mean": 4.0, "cut_hold_mean_ms": 0.0,
        "sparse_keys_read_share": 100.0 * KEYS_READ / (16384 * 16385 // 2),
        "parse_ms_per_record": 0.1, "egress_ms_per_record": 10.0,
        "step_gap_max_ms": STEP_MS}


def _traced_run(steps=6):
    mods, ops, log = [], [], []
    for i in range(steps):  # the first and the last are cut: fewer operations
        at = i * STEP_MS
        cut = i in (0, steps - 1)
        mods.append(("jit_fwd(5)", at * MS, STEP_MS * MS))
        ops += [(n, (at + s) * MS, d * MS) for n, _o, s, d in
                STEP_OPS[3 * cut:]]
    planes = [(DEV, [("XLA Modules", mods), ("XLA Ops", ops)])]
    cell = spec.cell(BENCH, CELL)
    run = harness.Run(cell, CONFIG, {}, 0, 12.0)
    run.device = {"kind": "TPU v5 lite"}
    run.trace = xplane.reduce(planes)
    run._device_planes = planes
    run._trace_meta = {"op_names": {DEV: {n: o for n, o, _s, _d in STEP_OPS
                                          if o}}, "start_s": None}
    off = 7000.0  # the device's zero on the host's clock
    for n in range(16):  # steps 10.. are the traced executions
        ready = off + STEP_MS / 1e3 * (n - 10 + 1) + 2e-4
        log.append({"step": n, "engine": "minicpm_sala", "padded": 4,
                    "rows": 4, "sources": 2, "seen": True,
                    "t_first_enq": ready - 3.6, "t_cut": ready - 2.41,
                    "t_staged": ready - 2.405, "t_launched": ready - 2.4,
                    "t_ready": ready, "t_fetched": ready + 0.001,
                    "t_resolved": ready + 0.002})
    run._step_rows = log
    run.delivery_times = [off - 10 * STEP_MS / 1e3, off]
    run.delivered_in_window = 4 * 10
    hist = lambda count, total: {"count": count, "sum": total}  # noqa: E731
    run.registry_before = {"inference-bolt": {}, "kafka-bolt": {}}
    run.registry_after = {
        "inference-bolt": {
            "decode_ms": hist(40, 40 * 0.1), "batch_size": hist(10, 40.0),
            "encode_ms": hist(40, 40 * 9.0), "cut_hold_ms": hist(10, 0.0),
            "sparse_keys_read": 10 * 8 * KEYS_READ,
            "sparse_keys_skipped": 10 * 8 * (16384 * 16385 // 2 - KEYS_READ)},
        "kafka-bolt": {"produce_ms": hist(40, 40 * 1.0)}}
    return run


def test_the_new_entries_are_the_last_and_list_what_reads_here():
    cell = spec.cell(BENCH, CELL)
    assert cell == BENCH["workloads"][-1] and len(BENCH["workloads"]) == 6
    assert BENCH["configs"][-1]["name"] == "minicpm_sala"
    assert [w["name"] for w in BENCH["workloads"][:5]] == [
        "vit_g14.tensor_backlog", "vit_g14.json_paced",
        "kimi_linear_48b.tokens_backlog",
        "nemotron_3_nano_30b.tokens_backlog", "kimi_k2_6.tokens_backlog"]
    assert cell["chips"] == 1 and cell["traffic"] == "tokens16k_backlog"
    assert cell["config"] == "minicpm_sala" and len(cell["why"]) <= 200
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    assert BENCH["run_seconds"] == 20
    e2e = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end", cell)}
    assert e2e == {"records_per_s", "setup_s"}
    layer = {m["name"]: m for m in spec.metrics_for(BENCH, "per_layer", cell)}
    assert set(layer) == SHARED | NEW | {"compile_s", "cache_misses"}
    # loops and counters told by other models' shapes are not this cell's
    assert not [n for n in layer if n.startswith((
        "expert_", "moe_", "k2_", "kda_", "ssd_scan_", "mla_", "gqa_"))]
    for name in NEW:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["layer"] == "engine and model"
        assert layer[name]["moves"] == "records_per_s"
        assert layer[name]["unit"] == ("%" if name.endswith("_share")
                                       else "ms")
        assert layer[name]["source"] == (
            "program_counter" if name == "sparse_keys_read_share"
            else "device_trace")
    for name in SHARED:
        assert layer[name]["workloads"][-1] == CELL
    names = [m["name"] for m in BENCH["per_layer"]]
    assert set(names[-7:]) == NEW
    # ``rope_ms`` reads the same part, but an accepted test
    # (test_perfbench_kimi_k2.py) holds its list to kimi_k2_6's cell alone
    assert layer["lightning_rope_ms"]["workloads"] == [CELL]
    assert spec.metric("lightning_rope_ms")["args"] == \
        spec.metric("rope_ms")["args"]
    traffic = spec.traffic("tokens16k_backlog")
    assert (traffic["outstanding"], traffic["pool"], traffic["payload"],
            traffic["arrivals"], traffic["warmup_seconds"],
            traffic["trace_seconds"], traffic["drain_seconds"]) == (
        32, 16, "arrow_tensor", "closed_loop", 6, 8, 60)
    assert traffic["program"] == {"topology.spout_scheme": "raw"}
    # the mix the three other language cells share is as it was
    assert spec.traffic("tokens_backlog")["outstanding"] == 128


def test_every_listed_metric_reads_a_number_from_a_trace_of_its_shapes():
    run = _traced_run()
    cell = spec.cell(BENCH, CELL)
    listed = spec.metrics_for(BENCH, "per_layer", cell)
    got = harness.read_metrics(run, [m for m in listed if m["name"]
                                     not in ("compile_s", "cache_misses")])
    assert set(got) == SHARED | NEW
    for name, want in WANT.items():
        assert got[name]["value"] == pytest.approx(want, abs=1e-6), name
    for name in ("model_roofline_share", "sparse_attention_roofline_share",
                 "lightning_scan_roofline_share"):
        assert 0 < got[name]["value"] < 100 and math.isfinite(
            got[name]["value"])
    work = OPS.kernels(SIZES, 4, 2)
    assert got["sparse_attention_roofline_share"]["value"] == pytest.approx(
        100 * work["sparse_attention"]["flops"] / 197e12 / 0.120)
    assert got["lightning_scan_roofline_share"]["value"] == pytest.approx(
        100 * work["lightning_scan"]["bytes"] / 819e9 / 0.090)
    assert run.notes["kernels"]["sparse_attention"]["rows"] == 4
    assert got["model_roofline_share"]["value"] == pytest.approx(
        100 * 4 * OPS.flops_per_row(SIZES) / 197e12 / 1.2)
    assert run.roofline_bound == "compute"
    assert run.notes["parts"]["mix.sparse_select"] == pytest.approx(60.0)
    assert run.notes["part_loops"] == pytest.approx(
        {"mix.sparse_select": 60.0, "mix.sparse_attention": 120.0,
         "proj": 500.0, "mix.ssd_scan": 90.0})
    # the selection's loop carries no rows' indices and is not the
    # attention's; Nemotron's scan carries other states and is not this one
    for name, other in (("sparse_attention_ms", SELECT),
                        ("lightning_scan_ms",
                         "%while.4 = (s32[], f32[8,8,8,64,128]) while(%t)")):
        assert not re.search(spec.metric(name)["args"]["pattern"], other)
    # the line such a run prints is complete by the driver's own check
    got.update(compile_s={"value": 1.0, "unit": "s"},
               cache_misses={"value": 0.0, "unit": "count"})
    row = {"correct": True, "attempted": 1, "failed": 0, "metrics": got,
           "device": {}}
    assert check_line.problems(row, CELL, traced=True) == []


def test_a_program_without_the_new_parts_reads_nothing_and_raises_nothing():
    """The parent's programs, or another model's: each of the seven is left
    out of the line but ``sparse_select_ms`` and ``lightning_rope_ms``, which
    are 0.0 where the program has names and nothing under theirs."""
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
    run.registry_before = run.registry_after = {"inference-bolt": {}}
    for name in sorted(NEW):
        doc = spec.metric(name)
        value = spec.plugin("readers", doc["reader"]).read(run, **doc["args"])
        assert value is None or (name in (
            "sparse_select_ms", "lightning_rope_ms") and value == 0.0), name
    untraced = harness.Run(spec.cell(BENCH, CELL), CONFIG, {}, 0, 1.0)
    for name in sorted(NEW - {"sparse_keys_read_share"}):
        doc = spec.metric(name)
        assert spec.plugin("readers", doc["reader"]).read(
            untraced, **doc["args"]) is None


def test_the_windows_come_from_the_whole_vocabulary_and_a_kind_of_their_own():
    make = spec.plugin("inputs", "minicpm_sala_tokens").make
    a, b = make(3, (16384,), 4_500_000_019), make(3, (16384,), 4_500_000_019)
    assert (a == b).all() and a.shape == (3, 16384)
    assert a.min() >= 0 and 73000 < a.max() < 73448
    assert (a == a.round()).all()
    assert not (a == make(3, (16384,), 4_500_000_020)).all()
    assert make(3, (96,), 1).max() < 96
    with pytest.raises(ValueError):
        make(1, (4096,), 1)  # the other language models': other kinds'
    kinds = {}
    for name in sorted(os.listdir(os.path.join(spec.BENCH_DIR, "configs"))):
        doc = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", name))
        kinds.setdefault(doc["inputs"]["kind"], []).append(
            tuple(doc["model"]["input_shape"]))
    assert sorted(kinds["minicpm_sala_tokens"]) == [(96,), (16384,)]
    for shapes in kinds.values():  # no kind has one shape twice
        assert len(set(shapes)) == len(shapes)


@pytest.mark.timeout(115)
def test_rehearsal_of_the_tiny_cell_on_the_cpu(tmp_path):
    # a compile cache of its own: tests/test_infer.py watches the checkout's
    # while other workers run
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    command = [sys.executable if w == "python3" else w
               for w in BENCH["command"]]
    proc = subprocess.run(
        command + ["--workload", "minicpm_sala_tiny.tokens16k_backlog",
                   "--seed", "4500000029", "--seconds", "2", "--trace", "0",
                   "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=105)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    row = lines[-1]
    assert row["correct"] is True and row["failed"] == 0
    assert row["attempted"] > 0
    assert set(row["metrics"]) == {"records_per_s", "setup_s"}
    assert 0 < row["checks"]["farthest_output"][0] <= 1e-4  # float32 here
    every = [line for line in lines if line.get("phase") == "all_metrics"][0]
    layer = every["per_layer"]
    assert layer["batch_size_mean"] <= 4.0  # the one bucket: (4,)
    # 96 positions, 6 blocks of 8 a query: 13,248 of 18,624 keys a layer
    assert layer["sparse_keys_read_share"] == pytest.approx(
        100 * 13248 / 18624)


@pytest.mark.timeout(115)
def test_the_tolerances_two_readings_and_the_mixer_check_at_toy_sizes(
        tmp_path):
    """``tools/tolerance.py`` reaches the model's products through the shared
    ``matmul`` (the float8 control fails, the program does not), and
    ``tools/sparse_mixer_check.py`` holds the mixer to the reference either
    side of ``dense_len``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/tolerance.py", "--config",
         "minicpm_sala_tiny", "--rehearse", "5:f8"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=105)
    assert proc.returncode == 0, proc.stderr[-2000:]
    (row,) = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert row["program"]["correct"] is True
    assert row["program"]["rows_failed"] == 0 and row["program"]["max"] < 1e-5
    assert row["float8"]["correct"] is False
    assert row["float8"]["min"] > 100 * row["program"]["max"]
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/sparse_mixer_check.py", "--config",
         "minicpm_sala_tiny", "--rehearse", "--seed", "5", "24", "96"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=105)
    assert proc.returncode == 0, proc.stderr[-2000:]
    dense, sparse = (json.loads(line)
                     for line in proc.stdout.strip().splitlines())
    assert dense["forms"] == ["causal_attention=blocked-grouped"]
    assert sparse["forms"] == ["sparse_attention=blocked"]
    assert dense["rms_over_rms"] < 1e-5 and sparse["rms_over_rms"] < 1e-5
    assert sparse["queries"] == 192 and sparse["queries_flipped"] == 0
