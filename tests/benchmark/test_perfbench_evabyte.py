"""The benchmark's EvaByte files (PR 49): the configuration against the
catalog row it is cut from and the program's own parameter tree,
``ops/evabyte.py`` against counts by hand, every per-layer metric that lists
the new cell over a trace of its shapes made by hand, the new entries in
``BENCHMARK.json`` (found by name: neither how many cells there are nor which
is last is this file's business), the byte windows, and a rehearsal of
``evabyte_tiny.bytes16k_backlog`` on the CPU."""

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

CELL = "evabyte.bytes16k_backlog"
BENCH = spec.benchmark()
CONFIG = spec.config("evabyte")
SIZES = CONFIG["published"]
OPS = spec.plugin("ops", "evabyte")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMETERS = 2_238_107_648
# every width of the row: none may differ from the published value
WIDTHS = {"hidden_size": 4096, "intermediate_size": 11008,
          "num_attention_heads": 32, "num_key_value_heads": 32,
          "window_size": 2048, "chunk_size": 16, "vocab_size": 320,
          "num_pred_heads": 8, "rope_theta": 100000,
          "max_position_embeddings": 32768, "rms_norm_eps": 1e-05}
SHARED = {"parse_ms_per_record", "batch_size_mean", "model_step_ms",
          "model_roofline_share", "egress_ms_per_record", "device_idle_share",
          "cut_hold_mean_ms", "step_named_share", "mixer_elementwise_ms",
          "projections_ms", "step_gap_max_ms"}
NEW = {"eva_attention_ms", "eva_chunks_ms", "eva_attention_roofline_share",
       "eva_chunks_roofline_share", "eva_rope_ms",
       "eva_summarised_pairs_share"}
# what one head's queries read in a window of 16,384 positions: their own
# attention window's keys up to themselves, and 128 summaries for each of
# the attention windows before (0, 128, ..., 896)
KEYS = 8 * (2048 * 2049 // 2)
SUMMARIES = 2048 * 128 * (0 + 1 + 2 + 3 + 4 + 5 + 6 + 7)
CAUSAL = 16384 * 16385 // 2


def _entry(group, name):
    (found,) = [e for e in BENCH[group] if e["name"] == name]
    return found


def test_configuration_states_the_cut_and_keeps_every_width():
    held = SIZES["held"]
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert (CONFIG["num_hidden_layers"], SIZES["num_hidden_layers"]) == (11,
                                                                         32)
    assert (held["num_hidden_layers"], held["chips_per_layer"],
            held["sequence_length"], held["rows_per_step"],
            held["vocab_size"], held["num_pred_heads"],
            held["first_byte_id"]) == (11, 1, 16384, 4, 320, 8, 64)
    for key, value in WIDTHS.items():
        assert CONFIG[key] == SIZES[key] == value, key
    for key, value in SIZES.items():
        if key not in CONFIG["reduced"] and key != "held":
            assert CONFIG[key] == value, key
    assert "One chip holds each layer whole" in CONFIG["deployment"]
    assert "layers 0-10 of 32" in CONFIG["deployment"]
    assert CONFIG["model"] == {"name": "evabyte", "input_shape": [16384],
                               "num_classes": 2560, "dtype": "bfloat16"}
    assert CONFIG["model"]["num_classes"] == \
        SIZES["num_pred_heads"] * SIZES["vocab_size"]
    for key in ("pooling_scale", "rotary_before_pooling", "prediction_heads",
                "byte_ids", "unit_offset", "rotary", "weights", "stream",
                "inputs", "output", "ids", "tiles", "window_length"):
        assert CONFIG["assumed"][key], key
    assert "1 + g" in CONFIG["assumed"]["unit_offset"]
    assert CONFIG["on_device"]["parameters"] == PARAMETERS
    assert CONFIG["on_device"]["parameters_bytes"] == 2 * PARAMETERS
    # the memory floor for a new cell, on parameters alone
    assert CONFIG["on_device"]["parameters_bytes"] >= 4 * 2 ** 30
    assert CONFIG["on_device"]["parameters_bytes"] \
        + CONFIG["on_device"]["program_temporaries_bucket_4_bytes"] < 15e9
    assert CONFIG["inputs"] == {"kind": "evabyte_bytes", "decimals": 0,
                                "candidates": 8}
    assert "every earlier window" in CONFIG["guarantees"]["attention"]
    assert 0 < CONFIG["tolerance"]["relative_distance"] < 0.2
    assert "float8" in CONFIG["tolerance"]["why"]
    entry = _entry("configs", "evabyte")
    assert entry["file"] == "benchmarks/configs/evabyte.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] and len(entry["why"]) <= 200


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_in_the_file():
    rows = [json.loads(line) for line in open(CATALOG)]
    (row,) = [r for r in rows if r["name"] == "EvaByte"]
    assert CONFIG["source"] == row["source_url"]
    assert row["config"]["model_type"] == "evabyte"
    assert row["config"]["attention_class"] == "eva"
    for key, value in row["config"].items():
        assert SIZES[key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert row["hidden_size"] == 4096 and row["dense_width"] == 11008


def test_ops_count_by_hand():
    """A parameter at a time, as the issue counts them, and one position
    through a layer."""
    d, f, sq = 4096, 11008, 4096 * 4096
    assert 4 * sq == 67_108_864
    swiglu = 3 * d * f
    assert swiglu == 135_266_304
    layer = 4 * sq + swiglu + 2 * d + 2 * 32 * 128
    assert layer == 202_391_552
    ends = 320 * d + d * 2560 + d
    assert ends == 11_800_576
    assert 11 * layer + ends == PARAMETERS
    assert 32 * layer + ends == 6_488_330_240  # the published 6.5 B
    assert OPS.parameters(SIZES) == PARAMETERS
    assert 2 * PARAMETERS == 4_476_215_296  # 4.17 GiB in bfloat16
    assert 2 * (10 * layer + ends) < 4 * 2 ** 30 < 2 * PARAMETERS
    # 404.75 MFLOP a position and layer: 291.8 TFLOP a step of 4 rows
    per_token = 2 * (4 * sq + swiglu)
    assert per_token == 404_750_336
    assert 291.7e12 < 11 * 4 * 16384 * per_token < 291.9e12
    # the attention: 24,125,440 reads a row and layer
    assert (KEYS, SUMMARIES) == (16_785_408, 7_340_032)
    assert OPS.reads(SIZES) == (KEYS, SUMMARIES)
    assert KEYS + 16 * SUMMARIES == CAUSAL == 134_225_920
    assert 100 * 16 * SUMMARIES / CAUSAL == pytest.approx(87.49, abs=0.005)
    assert 100 * SUMMARIES / (KEYS + SUMMARIES) == pytest.approx(30.4,
                                                                  abs=0.05)
    work = OPS.kernels(SIZES, 4, 2)
    assert work["eva_attention"]["flops"] == \
        11 * 4 * 32 * 512 * (KEYS + SUMMARIES)
    assert 17.3e12 < work["eva_attention"]["flops"] < 17.5e12
    wide = 4 * 16384 * 4096
    assert work["eva_attention"]["bytes"] == 11 * 2 * (4 * wide
                                                       + 2 * wide // 16)
    assert work["eva_chunks"] == {
        "flops": 0, "bytes": 11 * 2 * (2 * wide + 2 * wide // 16)}
    # 1.07 GB of keys and values a layer and step
    assert 2 * 2 * wide == 1_073_741_824
    assert OPS.flops_per_row(SIZES) == (
        11 * 16384 * per_token + work["eva_attention"]["flops"] // 4
        + 2 * d * 2560)
    step = OPS.counts(SIZES, rows=4, steps=1, bytes_per_value=2)
    assert 309.0e12 < step["flops"] < 309.4e12  # 1.57 s at the chip's peak
    assert step["flops"] / 197e12 == pytest.approx(1.57, abs=0.005)
    assert step["bytes"] == 2 * PARAMETERS + 4 * 4 * (16384 + 2560)


def test_ops_parameters_are_the_programs():
    import jax

    from storm_tpu.models.registry import build_model

    for name, count in (("evabyte", PARAMETERS), ("evabyte_tiny", None)):
        model = build_model(name)
        params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        held = sum(x.size for x in jax.tree.leaves(params))
        sizes = spec.config(name)["published"]
        assert OPS.parameters(sizes) == held, name
        assert count in (None, held)
        assert model.num_classes == spec.config(name)["model"]["num_classes"]
        assert model.hyper["window"] == sizes["window_size"]
        assert model.hyper["chunk"] == sizes["chunk_size"]
        assert model.hyper["layers"] == sizes["held"]["num_hidden_layers"]


def test_rows_per_step_reads_the_window_shape():
    names = ["%while.16 = (s32[], bf16[4,16384,4096]{2,1,0}, "
             "bf16[4096,11008]{1,0}) while(%t)",
             "%fusion.2 = f32[4,8,320]{2,1,0} fusion()"]
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


TURN = ("%_turn_lanes.3 = (bf16[4,16384,4096]{2,1,0:T(8,128)(2,1)}, "
        "bf16[4,16384,4096]{2,1,0:T(8,128)(2,1)}) custom-call(%cos, %sin, "
        "%q, %k)")
CHUNKS = _loop(0, "s32[]{:T(128)}, bf16[4,1024,4096]{2,1,0:T(8,128)(2,1)"
               "S(1)}, bf16[4,1024,4096]{2,1,0:T(8,128)(2,1)}, "
               "s32[4]{0:T(128)}, f32[2,4096]{1,0:T(2,128)}, "
               "bf16[4,16384,4096]{2,1,0:T(8,128)(2,1)}, "
               "bf16[4,16384,4096]{2,1,0:T(8,128)(2,1)}, s32[]{:T(128)}")
POOL = ("%_chunks_row.5 = (bf16[4,1024,4096]{2,1,0}, bf16[4,1024,4096]"
        "{2,1,0}) fusion(%at, %k, %v, %w), kind=kCustom")
ATTEND = _loop(1, "s32[]{:T(128)}, bf16[4,16384,4096]{2,1,0:T(8,128)(2,1)}, "
               "s32[4]{0:T(128)}, bf16[4,16384,4096]{2,1,0:T(8,128)(2,1)}, "
               "bf16[4,16384,4096]{2,1,0:T(8,128)(2,1)}, "
               "bf16[4,16384,4096]{2,1,0:T(8,128)(2,1)}, "
               "bf16[4,1024,4096]{2,1,0:T(8,128)(2,1)S(1)}, "
               "bf16[4,1024,4096]{2,1,0:T(8,128)(2,1)S(1)}, s32[]{:T(128)}")
KERNEL = ("%_kernel_row.3 = bf16[4,16384,4096]{2,1,0:T(8,128)(2,1)} "
          "fusion(%at, %q, %k, %v, %kbar, %vbar), kind=kCustom")
COPY = "%copy.3 = bf16[4,16384,4096]{2,1,0} copy(%v)"
FFN = _loop(2, "s32[]{:T(128)}, bf16[4,16384,4096]{2,1,0:T(8,128)(2,1)}, "
            "bf16[4,16384,4096]{2,1,0:T(8,128)(2,1)}, bf16[4096,11008]{1,0}, "
            "bf16[4096,11008]{1,0}, bf16[11008,4096]{1,0}")
STEP_OPS = [
    (QKV, "jit(fwd)/mix.elementwise/proj/dot_general", 0, 300),
    (TURN, "jit(fwd)/mix.elementwise/mix.rope/jit(_turn_lanes)/pallas_call",
     300, 100),
    (CHUNKS, None, 400, 40),
    (POOL, "jit(fwd)/mix.elementwise/mix.eva_chunks/while/body/closed_call/"
     "jit(_chunks_row)/pallas_call", 401, 9),
    (ATTEND, None, 440, 240),
    (KERNEL, "jit(fwd)/mix.elementwise/mix.eva_attention/while/body/"
     "closed_call/jit(_kernel_row)/pallas_call", 441, 59),
    (COPY, "jit(fwd)/mix.elementwise/reshape", 680, 20),
    (FFN, "jit(fwd)/proj/while", 700, 1200),
    ("%copy.9 = f32[4,16384,4096] copy(%h)", None, 1900, 100),
]
STEP_MS = 2000.0
WANT = {"model_step_ms": STEP_MS, "eva_rope_ms": 100.0,
        "eva_chunks_ms": 40.0, "eva_attention_ms": 240.0,
        "mixer_elementwise_ms": 20.0, "projections_ms": 1500.0,
        "step_named_share": 100.0 * 1900 / 2000,
        # the two cut executions lack their first 440 ms of operations
        "device_idle_share": 100.0 * 2 * 440 / (6 * 2000),
        "batch_size_mean": 4.0, "cut_hold_mean_ms": 0.0,
        "eva_summarised_pairs_share": 100.0 * 16 * SUMMARIES / CAUSAL,
        "parse_ms_per_record": 0.1, "egress_ms_per_record": 10.0,
        "step_gap_max_ms": STEP_MS}


def _traced_run(steps=6):
    mods, ops, log = [], [], []
    for i in range(steps):  # the first and the last are cut: fewer operations
        at = i * STEP_MS
        cut = i in (0, steps - 1)
        mods.append(("jit_fwd(5)", at * MS, STEP_MS * MS))
        ops += [(n, (at + s) * MS, d * MS) for n, _o, s, d in
                STEP_OPS[4 * cut:]]
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
        log.append({"step": n, "engine": "evabyte", "padded": 4,
                    "rows": 4, "sources": 2, "seen": True,
                    "t_first_enq": ready - 6.0, "t_cut": ready - 4.01,
                    "t_staged": ready - 4.005, "t_launched": ready - 4.0,
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
            "eva_pairs_exact": 10 * 11 * 4 * KEYS,
            "eva_pairs_summarised": 10 * 11 * 4 * 16 * SUMMARIES},
        "kafka-bolt": {"produce_ms": hist(40, 40 * 1.0)}}
    return run


def test_the_new_entries_list_what_reads_here():
    """Found by name. How many cells the benchmark has and which comes last
    is no business of this file's: the next cell must not fail it."""
    cell = spec.cell(BENCH, CELL)
    assert cell in BENCH["workloads"]
    assert cell["chips"] == 1 and cell["traffic"] == "bytes16k_backlog"
    assert cell["config"] == "evabyte" and len(cell["why"]) <= 200
    assert BENCH["run_seconds"] == 20
    e2e = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end", cell)}
    assert e2e == {"records_per_s", "setup_s"}
    assert _entry("end_to_end", "records_per_s")["bound"] == 0.01
    assert _entry("end_to_end", "setup_s")["bound"] == 0.1
    layer = {m["name"]: m for m in spec.metrics_for(BENCH, "per_layer", cell)}
    assert set(layer) == SHARED | NEW | {"compile_s", "cache_misses"}
    # loops and counters told by other models' shapes are not this cell's
    assert not [n for n in layer if n.startswith((
        "expert_", "moe_", "k2_", "kda_", "ssd_scan_", "mla_", "gqa_",
        "sparse_", "lightning_", "rope_"))]
    for name in NEW:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["layer"] == "engine and model"
        assert layer[name]["moves"] == "records_per_s"
        assert layer[name]["unit"] == ("%" if name.endswith("_share")
                                       else "ms")
        assert layer[name]["source"] == (
            "program_counter" if name == "eva_summarised_pairs_share"
            else "device_trace")
    for name in SHARED:
        assert CELL in layer[name]["workloads"]
    # the shared metrics it joined are those minicpm_sala's cell is on
    sala = spec.cell(BENCH, "minicpm_sala.tokens16k_backlog")
    assert SHARED == {m["name"] for m in spec.metrics_for(
        BENCH, "per_layer", sala) if len(m.get("workloads", [])) > 1}
    # ``rope_ms`` reads the same part, but an accepted test
    # (test_perfbench_kimi_k2.py) holds its list to kimi_k2_6's cell alone
    assert spec.metric("eva_rope_ms")["args"] == \
        spec.metric("rope_ms")["args"]
    assert spec.metric("eva_attention_ms")["reader"] == \
        spec.metric("eva_chunks_ms")["reader"] == "trace_part_time"
    traffic = spec.traffic("bytes16k_backlog")
    assert (traffic["outstanding"], traffic["pool"], traffic["payload"],
            traffic["arrivals"], traffic["warmup_seconds"],
            traffic["trace_seconds"], traffic["drain_seconds"]) == (
        32, 8, "arrow_tensor", "closed_loop", 8, 12, 90)
    assert traffic["program"] == {"topology.spout_scheme": "raw"}
    # the two token mixes are as they were
    assert spec.traffic("tokens_backlog")["outstanding"] == 128
    assert spec.traffic("tokens16k_backlog")["pool"] == 16


def test_every_listed_metric_reads_a_number_from_a_trace_of_its_shapes():
    run = _traced_run()
    cell = spec.cell(BENCH, CELL)
    listed = spec.metrics_for(BENCH, "per_layer", cell)
    got = harness.read_metrics(run, [m for m in listed if m["name"]
                                     not in ("compile_s", "cache_misses")])
    assert set(got) == SHARED | NEW
    for name, want in WANT.items():
        assert got[name]["value"] == pytest.approx(want, abs=1e-6), name
    for name in ("model_roofline_share", "eva_attention_roofline_share",
                 "eva_chunks_roofline_share"):
        assert 0 < got[name]["value"] < 100 and math.isfinite(
            got[name]["value"])
    work = OPS.kernels(SIZES, 4, 2)
    assert got["eva_attention_roofline_share"]["value"] == pytest.approx(
        100 * work["eva_attention"]["flops"] / 197e12 / 0.240)
    assert got["eva_chunks_roofline_share"]["value"] == pytest.approx(
        100 * work["eva_chunks"]["bytes"] / 819e9 / 0.040)
    assert run.notes["kernels"]["eva_attention"]["rows"] == 4
    assert got["model_roofline_share"]["value"] == pytest.approx(
        100 * 4 * OPS.flops_per_row(SIZES) / 197e12 / 2.0)
    assert run.roofline_bound == "compute"
    # the roofline shares read the very events the parts' times read
    assert run.notes["parts"]["mix.eva_chunks"] == pytest.approx(40.0)
    assert run.notes["part_loops"] == pytest.approx(
        {"mix.eva_chunks": 40.0, "mix.eva_attention": 240.0, "proj": 1200.0})
    # each loop is told from the other, from the feed-forward's and from
    # another model's
    att, chunks = (spec.metric(f"eva_{k}_roofline_share")["args"]["pattern"]
                   for k in ("attention", "chunks"))
    assert re.search(att, ATTEND) and re.search(chunks, CHUNKS)
    for other in (CHUNKS, FFN,
                  "%while.4 = (s32[], f32[8,32,1,128,128]) while(%t)"):
        assert not re.search(att, other)
    for other in (ATTEND, FFN):
        assert not re.search(chunks, other)
    # the line such a run prints is complete by the driver's own check
    got.update(compile_s={"value": 1.0, "unit": "s"},
               cache_misses={"value": 0.0, "unit": "count"})
    row = {"correct": True, "attempted": 1, "failed": 0, "metrics": got,
           "device": {}}
    assert check_line.problems(row, CELL, traced=True) == []


def test_a_program_without_the_new_parts_reads_nothing_and_raises_nothing():
    """The parent's programs, or another model's: each of the six is left
    out of the line but the three parts' times, which are 0.0 where the
    program has names and nothing under theirs."""
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
            "eva_attention_ms", "eva_chunks_ms", "eva_rope_ms")
            and value == 0.0), name
    untraced = harness.Run(spec.cell(BENCH, CELL), CONFIG, {}, 0, 1.0)
    for name in sorted(NEW - {"eva_summarised_pairs_share"}):
        doc = spec.metric(name)
        assert spec.plugin("readers", doc["reader"]).read(
            untraced, **doc["args"]) is None


def test_the_windows_are_bytes_after_the_special_ids_and_a_kind_of_their_own():
    make = spec.plugin("inputs", "evabyte_bytes").make
    a, b = make(3, (16384,), 4_900_000_019), make(3, (16384,), 4_900_000_019)
    assert (a == b).all() and a.shape == (3, 16384)
    assert a.min() == 64 and a.max() == 319  # every byte, no special id
    assert len(set(a.ravel().tolist())) == 256
    assert (a == a.round()).all()
    assert not (a == make(3, (16384,), 4_900_000_020)).all()
    tiny = make(3, (96,), 1)
    assert tiny.min() >= 8 and tiny.max() < 40
    with pytest.raises(ValueError):
        make(1, (4096,), 1)  # the other language models': other kinds'
    kinds = {}
    for name in sorted(os.listdir(os.path.join(spec.BENCH_DIR, "configs"))):
        doc = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", name))
        kinds.setdefault(doc["inputs"]["kind"], []).append(
            tuple(doc["model"]["input_shape"]))
    assert sorted(kinds["evabyte_bytes"]) == [(96,), (16384,)]
    # the same window length as minicpm_sala's, another kind
    assert (16384,) in kinds["minicpm_sala_tokens"]
    for shapes in kinds.values():  # no kind has one shape twice
        assert len(set(shapes)) == len(shapes)


def test_reference_answers_eight_distributions_a_record():
    import jax
    import numpy as np

    from storm_tpu.models.registry import build_model

    reference = spec.plugin("references", "evabyte")
    tiny = spec.config("evabyte_tiny")
    model = build_model("evabyte_tiny")
    params, state = model.init(jax.random.PRNGKey(0))
    x = spec.plugin("inputs", "evabyte_bytes").make(2, (96,), 3)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference.forward(tiny["published"], params, state,
                                           x.astype(np.float32)))
    assert got.shape == (2, tiny["model"]["num_classes"]) == (2, 320)
    np.testing.assert_allclose(got.reshape(2, 8, 40).sum(-1), 1.0, atol=1e-5)
    with pytest.raises(ValueError):  # another depth than the file states
        reference.forward(tiny["published"],
                          dict(params, layers=params["layers"][:1]), state, x)


@pytest.mark.timeout(115)
def test_rehearsal_of_the_tiny_cell_on_the_cpu(tmp_path):
    # a compile cache of its own: tests/test_infer.py watches the checkout's
    # while other workers run
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    command = [sys.executable if w == "python3" else w
               for w in BENCH["command"]]
    proc = subprocess.run(
        command + ["--workload", "evabyte_tiny.bytes16k_backlog",
                   "--seed", "4900000029", "--seconds", "2", "--trace", "0",
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
    # 96 positions, windows of 32: 3,072 of 4,656 causal pairs by summaries
    assert layer["eva_summarised_pairs_share"] == pytest.approx(
        100 * 3072 / 4656)


@pytest.mark.timeout(115)
def test_the_tolerances_two_readings_and_the_mixer_check_at_toy_sizes(
        tmp_path):
    """``tools/evabyte_check.py`` reaches the model's products through the
    shared ``matmul`` (the float8 control fails, the program does not) and
    holds the mixer to the reference with and without summaries."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/evabyte_check.py", "--config",
         "evabyte_tiny", "--rehearse", "mixer:5:32,96", "5:f8"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=105)
    assert proc.returncode == 0, proc.stderr[-2000:]
    one, three, row = (json.loads(line)
                       for line in proc.stdout.strip().splitlines())
    assert one["forms"] == three["forms"] == [
        "rotary_turn=halves", "eva_chunks=xla", "eva_attention=blocked"]
    assert (one["length"], three["length"]) == (32, 96)
    for check in (one, three):
        assert check["pass"] and check["rms_over_rms"] < 1e-5
        assert check["kbar_rms_over_rms"] < 1e-5
        assert check["vbar_rms_over_rms"] < 1e-5
    assert row["program"]["correct"] is True
    assert row["program"]["rows_failed"] == 0 and row["program"]["max"] < 1e-5
    assert row["float8"]["correct"] is False
    assert row["float8"]["rows_failed"] == row["float8"]["rows"] == 8
    assert row["float8"]["min"] > 100 * row["program"]["max"]
