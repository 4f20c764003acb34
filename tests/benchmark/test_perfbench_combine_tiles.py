"""``combine_tiles_written_share`` (PR 69): the metric file over
``registry_counter_share`` reads the two counters ``parallel/moe.py
observe_expert_counts`` adds to (the tiles of the combine that wrote their
block's sums, and those that read them back and added), is listed last, for
the five cells that hold a part of their router, reads nothing from a
program that counts neither, and is on the line a rehearsal of Granite's toy
cell prints, by the driver's own check."""

import inspect
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.core import harness, spec  # noqa: E402
from benchmarks.tools import check_line  # noqa: E402

BENCH = spec.benchmark()
NAME = "combine_tiles_written_share"
CELLS = ["kimi_linear_48b.tokens_backlog",
         "nemotron_3_nano_30b.tokens_backlog", "kimi_k2_6.tokens_backlog",
         "solar_open2_250b.tokens_backlog",
         "granite_4_h_small.tokens_backlog"]
ENTRY = {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "engine and model",
         "moves": "records_per_s", "workloads": CELLS}


def test_the_entry_is_the_last_and_names_the_five_cells_that_hold_a_part():
    assert BENCH["per_layer"][-1] == ENTRY
    assert [m["name"] for m in BENCH["per_layer"]].count(NAME) == 1
    listed = {w["name"]: w for w in BENCH["workloads"]}
    throughput = next(m for m in BENCH["end_to_end"]
                      if m["name"] == ENTRY["moves"])
    for cell in CELLS:
        assert listed[cell]["traffic"] == "tokens_backlog"
        assert cell in throughput["workloads"]
    # the cells that hold their whole router, or no router, do not list it
    for cell in set(listed) - set(CELLS):
        assert NAME not in {m["name"] for m in spec.metrics_for(
            BENCH, "per_layer", listed[cell])}


def test_the_metric_file_names_the_reader_and_the_programs_counters():
    doc = spec.metric(NAME)
    assert set(doc) == {"doc", "reader", "args"}
    assert doc["reader"] == "registry_counter_share"
    assert doc["args"] == {
        "component": "inference-bolt", "of": "combine_tiles_written",
        "among": ["combine_tiles_written", "combine_tiles_added"]}
    from storm_tpu.parallel import moe
    source = inspect.getsource(moe.observe_expert_counts)
    for counter in doc["args"]["among"]:
        assert f'"{counter}"' in source


def _run(before: dict, after: dict):
    return types.SimpleNamespace(
        registry_before={"inference-bolt": before},
        registry_after={"inference-bolt": after})


@pytest.mark.parametrize("cell", CELLS)
def test_the_share_is_the_windows_written_over_written_and_added(cell):
    """Ten expert layers of 342 blocks over 14 steps with 8 further tiles a
    layer and step, after a set-up that counted too: the window's increase
    alone; 100 where no tile was added; nothing where a program counts
    neither (the parent's) or the window counted nothing."""
    entry = next(m for m in spec.metrics_for(
        BENCH, "per_layer", spec.cell(BENCH, cell)) if m["name"] == NAME)
    written, added = 14 * 10 * 342, 14 * 10 * 8
    got = harness.read_metrics(_run(
        {"combine_tiles_written": 3420, "combine_tiles_added": 77},
        {"combine_tiles_written": 3420 + written,
         "combine_tiles_added": 77 + added}), [entry])
    assert got == {NAME: {"value": pytest.approx(100 * 342 / 350),
                          "unit": "%"}}
    got = harness.read_metrics(_run(
        {}, {"combine_tiles_written": written, "combine_tiles_added": 0}),
        [entry])
    assert got[NAME]["value"] == 100.0
    for before, after in (({}, {"expert_assignments_held": 5}),
                          ({"combine_tiles_written": 7,
                            "combine_tiles_added": 0},
                           {"combine_tiles_written": 7,
                            "combine_tiles_added": 0}),
                          ({}, {"combine_tiles_written": 7})):
        assert harness.read_metrics(_run(before, after), [entry]) == {}


@pytest.mark.timeout(115)
def test_check_line_finds_it_on_a_rehearsed_line_of_granites_toy_cell(
        tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"))
    command = [sys.executable if w == "python3" else w
               for w in BENCH["command"]]
    proc = subprocess.run(
        command + ["--workload", "granite_h_tiny.tokens_backlog", "--seed",
                   "3000000031", "--seconds", "2", "--trace", "1",
                   "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=105)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row["correct"] is True and row["failed"] == 0
    share = row["metrics"][NAME]
    assert share["unit"] == "%" and 50.0 < share["value"] <= 100.0
    # the CPU's line lacks what only a device trace gives, and not this
    found = check_line.problems(row, CELLS[-1], traced=True)
    assert found and not [p for p in found if NAME in p]
    del row["metrics"][NAME]
    assert f"metrics lacks {NAME}" in check_line.problems(
        row, CELLS[-1], traced=True)
