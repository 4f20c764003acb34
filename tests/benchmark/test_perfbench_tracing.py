"""The readers of PR 41 over traces and step logs made by hand: the time of a
step by the parts' names (``readers/trace_part_time.py``), the step log
alone (``readers/step_gap_max.py``) and log and trace on one clock
(``core/steplog.py``, ``readers/step_on_device_clock.py``,
``readers/idle_with_rows_share.py``).

A reader that returns nothing leaves its metric out of the result line, and
a line that lacks a listed metric is refused. So every new metric is read
here in every cell that lists it, over a run of that cell's kind, and must
give the number worked out by hand: a device never idle with the log's ring
full (the backlog cells), a paced one with an idle gap of each class, one
with a single whole execution, and one whose log is empty or whose events
carry no name."""

import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.core import harness, spec, steplog, xplane_meta  # noqa: E402
from benchmarks.tools import check_line  # noqa: E402

MS = 1e6  # nanoseconds
DEV = "/device:TPU:0"
BENCH = spec.benchmark()
NEW = ("step_named_share", "step_named_share.paced", "moe_routing_ms",
       "mixer_elementwise_ms", "projections_ms", "step_gap_max_ms",
       "step_gap_max_ms.paced", "cut_to_device_start_p50_ms.paced",
       "device_end_to_host_p50_ms.paced", "idle_with_rows_share.paced")
LISTED = [(m["name"], cell) for m in BENCH["per_layer"] if m["name"] in NEW
          for cell in m["workloads"]]

# one step's operations: (name, op_name or None, start ms, duration ms); a
# loop's event spans its body's, and carries no op_name of its own
PROJ = "%fusion.4 = bf16[8,4096,2688] fusion(%p), kind=kOutput"
LOOP = "%while.7 = (s32[], bf16[8,32,4096,128]) while(%t)"
BODY = "%fusion.14 = bf16[32,512,4096] fusion(%g), kind=kLoop"
SORT = "%sort.8 = (f32[32768,128], s32[32768,128]) sort(%a, %i)"
COPY = "%copy.3 = f32[8,4096,2688] copy(%w)"
TILE = "%fusion.9 = f32[8,4096,4096] fusion(%y), kind=kLoop"
STEP_OPS = [(PROJ, "jit(fwd)/mix.elementwise/proj/dot_general", 0, 30),
            (LOOP, None, 30, 40),
            (BODY, "jit(fwd)/mix.elementwise/mix.attention/while/body/dot", 31,
             9),
            (BODY, "jit(fwd)/mix.elementwise/mix.attention/while/body/dot", 40,
             9),
            (SORT, "jit(fwd)/moe.route/jit(sort)/sort", 70, 10),
            (COPY, None, 80, 5),
            (TILE, "jit(fwd)/mix.elementwise/reshape", 85, 15)]
OP_NAMES = {DEV: {name: op for name, op, _s, _d in STEP_OPS if op}}
PARTS = {"proj": 30.0, "mix.attention": 40.0, "moe.route": 10.0,
         "(none)": 5.0, "mix.elementwise": 15.0}


def _ops(at_ms, scale=1.0, cut=False):
    return [(name, (at_ms + s * scale) * MS, d * scale * MS)
            for name, _op, s, d in STEP_OPS[2 * cut:]]


def _row(step, padded, enq, cut, staged, launched, ready, fetched, resolved,
         seen=True, engine="m"):
    return {"step": step, "engine": engine, "padded": padded, "rows": padded,
            "sources": 2, "seen": seen, "t_first_enq": enq, "t_cut": cut,
            "t_staged": staged, "t_launched": launched, "t_ready": ready,
            "t_fetched": fetched, "t_resolved": resolved}


def _run(cell, planes, log, names=OP_NAMES, start_s=None, traced=True):
    """A run of ``cell`` that found these planes and this log; its window's
    deliveries span the backlog's last 4,000 steps, or the paced trace."""
    the_cell = spec.cell(BENCH, cell)
    run = harness.Run(the_cell, spec.config(the_cell["config"]), {}, 0, 14.0)
    run.trace = {"busy_s": 1.0, "window_s": 1.0} if traced else None
    run._device_planes = planes
    run._trace_meta = {"op_names": names, "start_s": start_s}
    run._step_rows = log
    run.delivery_times = [T0 + 0.03, T0 + 0.15] \
        if cell.endswith("_paced") else [OFF - 390.0, OFF + 20.0]
    return run


def _read(name, run):
    doc = spec.metric(name)
    return spec.plugin("readers", doc["reader"]).read(run,
                                                      **doc.get("args", {}))


# ---- a backlog: never idle, a step of 100 ms launched a step ahead ----------

OFF = 4000.0  # the device's zero on the host's clock


def _backlog():
    mods, ops = [], []
    for i in range(6):  # the first is cut: it holds fewer operations
        mods.append(("jit_fwd(7)", i * 100 * MS, 100 * MS))
        ops += _ops(i * 100, cut=i == 0)
    log = []
    for n in range(steplog_ring()):
        ready = OFF + 0.1 * (n - 4000 + 1) + 2e-4  # step 4000 is execution 0
        if n >= 4040:
            ready += 0.25  # one stall: the device took 350 ms over a step
        log.append(_row(n, 8, ready - 0.28, ready - 0.21, ready - 0.205,
                        ready - 0.2 - 0.25 * (n == 4040), ready,
                        ready + 0.001, ready + 0.002))
    return [(DEV, [("XLA Modules", mods), ("XLA Ops", ops)])], log


def steplog_ring():
    from storm_tpu.obs.profile import STEP_LOG

    return STEP_LOG


BACKLOG = {"step_named_share": 95.0, "moe_routing_ms": 10.0,
           "mixer_elementwise_ms": 15.0, "projections_ms": 30.0,
           "step_gap_max_ms": 350.0}

# ---- a paced cell: two programs interleave, the device idles between ------

# device ms: (program, padded, start, end), and the host's moments of the
# step on the same scale: first_enq, cut, launched (staged 0.5 before),
# ready - end, fetched - ready
PACED_STEPS = [("jit_fwd(8)", 8, 10, 38, 2, 5, 7, 0.0, 0.3),
               ("jit_fwd(32)", 32, 50, 78, 41, 44, 47, 0.2, 0.3),
               ("jit_fwd(8)", 8, 90, 118, 85, 86, 87, 0.2, 0.3),
               ("jit_fwd(32)", 32, 118, 146, 100, 110, 112, 0.2, 0.3)]
# idle 38-50: 3 no rows, 3 waiting, 3 cut, 3 launched; idle 78-90: 7 no rows,
# 1 waiting, 1 cut, 3 launched; the span 10-146
IDLE = {"no rows": 10.0, "rows waiting for the cut": 4.0,
        "cut->launched": 4.0, "launched->device start": 6.0}
PACED = {"step_named_share.paced": 95.0,
         "step_gap_max_ms.paced": 40.2,  # 38.0 -> 78.2
         "cut_to_device_start_p50_ms.paced": 5.5,  # 5, 6, 4, 8
         "device_end_to_host_p50_ms.paced": 0.5,  # 0.3, 0.5, 0.5, 0.5
         "idle_with_rows_share.paced": 100.0 * 14.0 / 136.0}
T0 = 5000.0  # the host's clock at the device's zero, planted


def _paced(steps=PACED_STEPS, before=3, after=2):
    mods, ops, log = [], [], []
    # steps before the trace began and after it ended, at uneven times
    for n in range(before):
        at = T0 - 0.3 + 0.07 * n + 0.011 * n * n
        log.append(_row(n, (8, 32, 32)[n % 3], at, at + .003, at + .0045,
                        at + .005, at + .034, at + .0343, at + .0345))
    for n, (prog, padded, start, end, enq, cut, launched, late, copy) \
            in enumerate(steps, before):
        mods.append((prog, start * MS, (end - start) * MS))
        ops += _ops(start, scale=(end - start) / 100.0)
        ready = T0 + (end + late) / 1e3
        log.append(_row(n, padded, T0 + enq / 1e3, T0 + cut / 1e3,
                        T0 + (launched - 0.5) / 1e3, T0 + launched / 1e3,
                        ready, ready + copy / 1e3, ready + (copy + .1) / 1e3))
    for n in range(after):
        at = T0 + 0.2 + 0.09 * n
        log.append(_row(before + len(steps) + n, 8, at, at + .004, at + .0055,
                        at + .006, at + .036, at + .0363, at + .0365))
    return [(DEV, [("XLA Modules", mods), ("XLA Ops", ops)])], log


def _kind(cell):
    if cell.endswith("_paced"):
        planes, log = _paced()
        return _run(cell, planes, log, start_s=T0 - 0.0015), PACED
    planes, log = _backlog()
    return _run(cell, planes, log, start_s=OFF - 0.0015), BACKLOG


@pytest.mark.parametrize("name,cell", LISTED)
def test_every_new_metric_is_a_number_in_every_cell_that_lists_it(name, cell):
    run, expected = _kind(cell)
    value = _read(name, run)
    assert value is not None and math.isfinite(value)
    assert value == pytest.approx(expected[name], abs=1e-6)


def test_the_listing_is_the_issues():
    cells = {m["name"]: m.get("workloads") for m in BENCH["per_layer"]}
    backlog = [w["name"] for w in BENCH["workloads"]
               if w["traffic"].endswith("backlog")]
    assert len(LISTED) == 17 and set(n for n, _ in LISTED) == set(NEW)
    for name in NEW:
        if name.endswith(".paced"):
            assert cells[name] == ["vit_g14.json_paced"]
            assert "records_per_s" not in [
                m["moves"] for m in BENCH["per_layer"] if m["name"] == name]
    assert cells["step_named_share"] == cells["step_gap_max_ms"] == backlog
    assert cells["moe_routing_ms"] == cells["projections_ms"] == \
        cells["mixer_elementwise_ms"] == backlog[1:]


def test_the_notes_hold_the_parts_and_where_a_gap_went():
    run, _ = _kind("kimi_linear_48b.tokens_backlog")
    for name in ("step_named_share", "step_gap_max_ms"):
        _read(name, run)
    assert run.notes["parts"] == pytest.approx(PARTS)
    assert sum(run.notes["parts"].values()) == pytest.approx(100.0)
    assert run.notes["unnamed_ops"] == [[COPY, pytest.approx(5.0)]]
    assert run.notes["part_loops"] == pytest.approx({"mix.attention": 40.0})
    gap = run.notes["step_gap"]
    assert (gap["before"]["step"], gap["after"]["step"]) == (4039, 4040)
    assert gap["interval"] == "launched->ready"
    assert gap["over_median_ms"] == pytest.approx(250.0)
    # in a traced run the clock is fitted in every cell: launched a step
    # ahead, ready 0.2 ms after the device ends
    assert run.notes["clock_offset_s"] == pytest.approx(OFF + 2e-4)
    clock = run.notes["clock"]
    assert clock["executions_whole_matched"] == 5
    assert clock["least_device_start_minus_launched_ms"] == pytest.approx(
        100.0, abs=1e-3)
    assert clock["violation_ms"] == 0.0


def test_a_loop_is_counted_once_and_takes_its_bodys_name():
    reader = spec.plugin("readers", "trace_part_time")
    from storm_tpu.ops.parts import part_of

    names = OP_NAMES[DEV]
    top = reader.top_level(_ops(0), lambda e: part_of(names[e])
                           if e in names else None)
    assert [(n, p, d / MS) for n, p, d in top] == [
        (PROJ, "proj", 30.0), (LOOP, "mix.attention", 40.0),
        (SORT, "moe.route", 10.0), (COPY, None, 5.0),
        (TILE, "mix.elementwise", 15.0)]


def test_the_fit_recovers_a_planted_offset_with_and_without_the_stamp():
    planes, log = _paced()
    execs = steplog.device_executions(planes, "jit_fwd")
    assert [e[3] for e in execs] == [True] * 4
    for hint in (T0 - 0.0015, None):
        found = steplog.match(execs, log, hint)
        assert found["offset_s"] == pytest.approx(T0, abs=1e-9)
        assert found["violation_s"] == 0.0
        # the least ``device start - t_launched``: the third step's 3 ms
        assert found["room_s"] == pytest.approx(0.003, abs=1e-9)


def test_steps_match_executions_in_order_when_two_buckets_interleave():
    planes, log = _paced()
    found = steplog.match(steplog.device_executions(planes, "jit_fwd"), log,
                          T0 - 0.0015)
    assert [(e[0], row["step"], row["padded"]) for e, row in
            found["pairs"]] == [("jit_fwd(8)", 3, 8), ("jit_fwd(32)", 4, 32),
                                ("jit_fwd(8)", 5, 8), ("jit_fwd(32)", 6, 32)]
    # no shift by one pairs a program with one bucket: the hint is not needed
    wrong = [r for r in log if r["step"] != 3]
    again = steplog.match(steplog.device_executions(planes, "jit_fwd"),
                          wrong, None)
    assert again is None or again["violation_s"] > 0.0


def test_the_idle_time_by_class_and_the_longest_gaps():
    run, _ = _kind("vit_g14.json_paced")
    _read("idle_with_rows_share.paced", run)
    idle = run.notes["idle"]
    assert idle["span_s"] == pytest.approx(0.136)
    assert {k: v * 1e3 for k, v in idle["classes"].items()} == \
        pytest.approx(IDLE)
    assert [(round(g[0] * 1e3, 6), g[1]) for g in idle["gaps"]] == [
        (12.0, "no rows"), (12.0, "no rows")] or \
        [g[1] for g in idle["gaps"]][1] == "no rows"
    assert idle["gaps"][0][0] == pytest.approx(0.012)


def test_a_single_whole_execution_is_enough():
    planes, log = _paced(PACED_STEPS[:3], before=1, after=0)
    # the trace cut the first and the last: they hold fewer operations
    lines = dict(planes[0][1])
    ops = [e for e in lines["XLA Ops"] if 50 * MS <= e[1] < 78 * MS]
    ops += [e for e in lines["XLA Ops"] if e[1] < 38 * MS][2:]
    ops += [e for e in lines["XLA Ops"] if e[1] >= 90 * MS][:3]
    planes = [(DEV, [("XLA Modules", lines["XLA Modules"]),
                     ("XLA Ops", ops)])]
    run = _run("vit_g14.json_paced", planes, log, start_s=T0 - 0.0015)
    assert [e[3] for e in steplog.device_executions(planes, "jit_fwd")] == \
        [False, True, False]
    assert _read("cut_to_device_start_p50_ms.paced", run) == \
        pytest.approx(6.0)
    assert _read("device_end_to_host_p50_ms.paced", run) == pytest.approx(0.5)
    assert _read("step_named_share.paced", run) == pytest.approx(95.0)
    assert math.isfinite(_read("idle_with_rows_share.paced", run))
    assert run.notes["clock"]["executions_whole_matched"] == 1


@pytest.mark.parametrize("cell", ["vit_g14.json_paced",
                                  "nemotron_3_nano_30b.tokens_backlog"])
def test_an_empty_log_and_events_without_names_read_what_the_issue_fixed(cell):
    planes, _log = _paced() if cell.endswith("_paced") else _backlog()
    run = _run(cell, planes, [], names={})
    paced = ".paced" if cell.endswith("_paced") else ""
    assert _read("step_named_share" + paced, run) == 0.0
    assert _read("step_gap_max_ms" + paced, run) == 14000.0
    if paced:
        assert _read("idle_with_rows_share.paced", run) == 0.0
        # these two need a step to set against an execution
        assert _read("cut_to_device_start_p50_ms.paced", run) is None
        assert _read("device_end_to_host_p50_ms.paced", run) is None
    else:
        for name in ("moe_routing_ms", "mixer_elementwise_ms",
                     "projections_ms"):
            assert _read(name, run) == 0.0


def test_a_program_from_before_the_log_or_a_run_without_a_trace_reads_nothing():
    planes, log = _paced()
    run = _run("vit_g14.json_paced", planes, None)  # no ``steps()`` there
    assert _read("step_gap_max_ms.paced", run) is None
    assert _read("idle_with_rows_share.paced", run) is None
    run = _run("vit_g14.json_paced", [], log, traced=False)
    for name in ("step_named_share.paced", "idle_with_rows_share.paced",
                 "cut_to_device_start_p50_ms.paced"):
        assert _read(name, run) is None
    # the step log needs no trace
    assert _read("step_gap_max_ms.paced", run) == pytest.approx(40.2)


def test_the_trace_files_own_fields_are_read(tmp_path):
    """``core/xplane_meta.py`` over an ``XSpace`` written by hand: a device
    plane whose event metadata carries ``tf_op``, and the profiler's start."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def field(number, payload):
        if isinstance(payload, int):
            return varint(number << 3) + varint(payload)
        return varint(number << 3 | 2) + varint(len(payload)) + payload

    def entry(key, value):
        return field(1, key) + field(2, value)

    stat_names = field(5, entry(1, field(1, 1) + field(2, b"tf_op"))) + \
        field(5, entry(2, field(1, 2) + field(2, b"profile_start_time")))
    meta = field(1, 9) + field(2, PROJ.encode()) + field(
        5, field(1, 1) + field(5, b"jit(fwd)/proj/dot_general:"))
    bare = field(1, 10) + field(2, LOOP.encode())
    device = field(2, DEV.encode()) + field(3, b"\x0a\x00") + \
        field(4, entry(9, meta)) + field(4, entry(10, bare)) + stat_names
    task = field(2, b"Task Environment") + stat_names + field(
        6, field(1, 2) + field(3, 1790736463242245641))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, device) + field(1, task))
    found = xplane_meta.read(str(path))
    assert found["op_names"] == {DEV: {PROJ: "jit(fwd)/proj/dot_general"}}
    assert found["start_s"] == pytest.approx(1790736463.242245641)


def test_check_line_refuses_a_line_that_lacks_a_listed_metric():
    cell = "vit_g14.json_paced"
    listed = spec.metrics_for(BENCH, "per_layer", spec.cell(BENCH, cell))
    row = {"correct": True, "attempted": 1, "failed": 0, "device": {},
           "metrics": {m["name"]: {"value": 1.0, "unit": m["unit"]}
                       for m in listed}}
    assert check_line.problems(row, cell, True) == []
    del row["metrics"]["step_gap_max_ms.paced"]
    row["metrics"]["idle_with_rows_share.paced"]["value"] = float("nan")
    assert check_line.problems(row, cell, True) == [
        "metrics lacks step_gap_max_ms.paced",
        "idle_with_rows_share.paced is nan, no finite number"]
    assert check_line.problems(row, cell, False)[0] == \
        "metrics lacks latency_p50_ms"
    assert check_line.problems(None, cell, True) == [
        "the last line is no JSON object"]
