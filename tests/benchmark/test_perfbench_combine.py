"""``expert_combine_ms`` (PR 37): the metric file over ``trace_ops_time`` finds
the combine's loop of ``parallel/moe.py _combine_held`` in both language
models' programs by the float32 sums it alone carries, takes no other loop
for it, and reads nothing from a program without it."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.core import harness, spec  # noqa: E402

CELLS = {"nemotron_3_nano_30b.tokens_backlog": 2688,
         "kimi_linear_48b.tokens_backlog": 2304}
MS = 1e6  # nanoseconds
# the loops as the v5e compiler writes them for the two 8-row programs
COMBINE = ("%while.165 = (s32[], f32[32768,{dim}]{{1,0}}, s32[128]{{0}}, "
           "s32[197120]{{0}}, bf16[212993,{dim}]{{1,0}}, s32[197120]{{0}}) "
           "while(%tuple.9), condition=%c, body=%b")
OTHERS = [
    # the experts' tile loops: the buffer, the tokens, the stacked weights
    "%while.153 = (s32[], bf16[212993,2688]{1,0}, s32[197120]{0}, "
    "bf16[32768,2688]{1,0}, bf16[32,1856,2688]{2,1,0}, "
    "bf16[32,2688,1856]{1,2,0}, f32[197120]{0}) while(%t)",
    "%while.52 = (s32[], bf16[294913,2304]{1,0}, s32[263168]{0}, "
    "bf16[32768,2304]{1,0}, bf16[32,1024,2304]{2,1,0}, "
    "bf16[32,2304,1024]{1,2,0}, f32[263168]{0}) while(%t)",
    # Mamba-2's scan, both attentions, KDA's two loops
    "%while.175 = (s32[], f32[8,8,8,64,128]{4,3,2,1,0}, "
    "bf16[32,8,128,8,8,64]{2,5,4,3,1,0}) while(%t)",
    "%while.150 = (s32[], bf16[8,32,4096,128]{2,3,1,0}, "
    "bf16[8,2,4096,128]{3,2,1,0}) while(%t)",
    "%while.48 = (s32[], bf16[8,32,4096,128], bf16[8,32,4096,192]) while(%t)",
    "%while.45 = (s32[], bf16[8,32,64,64,128], f32[8,32,64,64,128], "
    "bf16[8,32,64,64,64]) while(%t)",
    "%while.66 = (s32[], f32[8,32,128,128], bf16[64,8,32,64,128], "
    "bf16[64,8,32,64,64]) while(%t)",
    # what is outside every loop: the zeroed sums, the last pass over them
    "%broadcast.7 = f32[32768,2688]{1,0} broadcast(%zero)",
    "%fusion.31 = f32[32768,2688]{1,0} fusion(%while.165), kind=kOutput",
    "%fusion.9 = bf16[8,4096,2688]{2,1,0} fusion(%p), kind=kLoop",
]
METRIC = spec.metric("expert_combine_ms")
READER = spec.plugin("readers", METRIC["reader"])


def _planes(dim, combine=True):
    """Three executions of one program, 100 ms each; the first is cut (it
    holds fewer operations than the others). A whole one: every other loop
    at 5 ms, and four combines of 4.5 ms with their block's product inside."""
    mods, ops = [], []
    for i, start in enumerate((0, 100, 200)):
        mods.append(("jit_fwd(7)", start * MS, 100 * MS))
        at = start * MS
        for j, op in enumerate(OTHERS[i == 0:]):
            ops.append((op, at + (1 + 5 * j) * MS, 5 * MS))
        for j in range(4 if combine else 0):
            begin = at + (60 + 5 * j) * MS
            ops += [(COMBINE.format(dim=dim), begin, 4.5 * MS),
                    (f"%convolution_add_fusion.2 = f32[256,{dim}] fusion()",
                     begin + MS, 2 * MS)]
    return [("/device:TPU:0", [("XLA Modules", mods), ("XLA Ops", ops)])]


def _run_over(planes, cell):
    config = spec.config(spec.cell(spec.benchmark(), cell)["config"])
    run = harness.Run({"name": cell}, config, {}, 0, 1.0)
    run.trace = {"busy_s": 0.2}
    run._device_planes = planes
    run.device = {"kind": "TPU v5 lite"}
    return run


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_metric_sums_the_combines_loops_and_no_other(cell):
    assert METRIC["args"]["prefix"] == "jit_fwd" and "kernel" not in \
        METRIC["args"]
    got = READER.read(_run_over(_planes(CELLS[cell]), cell), **METRIC["args"])
    assert got == pytest.approx(4 * 4.5)


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_program_without_the_loop_reads_nothing(cell):
    """The parent's programs, an untraced run: the metric is left out of the
    line and nothing is raised."""
    run = _run_over(_planes(CELLS[cell], combine=False), cell)
    assert READER.read(run, **METRIC["args"]) is None
    untraced = _run_over(_planes(CELLS[cell]), cell)
    untraced.trace = None
    assert READER.read(untraced, **METRIC["args"]) is None


def test_the_other_loops_metrics_do_not_take_the_combine_for_theirs():
    """With the combine's loops in the program, each accepted loop metric
    reads what it read without them: 5 ms."""
    for name, cell in [("relu2_expert_matmul_ms", "nemotron_3_nano_30b"),
                       ("ssd_scan_ms", "nemotron_3_nano_30b"),
                       ("gqa_attention_ms", "nemotron_3_nano_30b"),
                       ("expert_matmul_ms", "kimi_linear_48b"),
                       ("mla_attention_ms", "kimi_linear_48b")]:
        doc, cell = spec.metric(name), cell + ".tokens_backlog"
        for combine in (True, False):
            run = _run_over(_planes(CELLS[cell], combine), cell)
            assert spec.plugin("readers", doc["reader"]).read(
                run, **doc["args"]) == pytest.approx(5.0), name
    doc = spec.metric("kda_scan_ms")  # its two loops
    run = _run_over(_planes(2304), "kimi_linear_48b.tokens_backlog")
    assert spec.plugin("readers", doc["reader"]).read(
        run, **doc["args"]) == pytest.approx(10.0)


def test_both_language_cells_report_it_and_no_other_cell():
    bench = spec.benchmark()
    entry = [m for m in bench["per_layer"] if m["name"] == "expert_combine_ms"]
    assert len(entry) == 1
    assert entry[0] == {
        "name": "expert_combine_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "engine and model",
        "moves": "records_per_s", "workloads": [
            "kimi_linear_48b.tokens_backlog",
            "nemotron_3_nano_30b.tokens_backlog"]}
    for cell in bench["workloads"]:
        names = {m["name"] for m in spec.metrics_for(bench, "per_layer", cell)}
        assert ("expert_combine_ms" in names) == (cell["name"] in CELLS)
