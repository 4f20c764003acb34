"""The readers of PR 54 over a record log, a step log and a trace made by
hand (``core/recordlog.py``, ``readers/record_interval.py``,
``readers/idle_before_queue.py``), and over a rehearsal of the tiny paced
cell.

A known hole between a step's ``t_resolved`` and a record's ``t_egress`` must
read as that many milliseconds; a record that lies in the broker while the
device idles must read as ``idle_before_queue``, one appended after the gap
must not; a program without ``records()`` reads nothing and the line leaves
the metrics out."""

import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.core import harness, pairing, recordlog, spec  # noqa: E402

MS = 1e6  # nanoseconds
DEV = "/device:TPU:0"
T0 = 5000.0  # the host's clock at the device's zero, planted
BENCH = spec.benchmark()
CELL = "vit_g14.json_paced"
INTERVAL = {"append_to_poll_p50_ms.paced": ("t_append", "t_polled"),
            "emit_to_execute_p50_ms.paced": ("t_emitted", "t_exec"),
            "resolved_to_egress_p50_ms.paced": ("t_resolved", "t_egress"),
            "egress_to_produced_p50_ms.paced": ("t_egress", "t_produced")}
NEW = tuple(INTERVAL) + ("deliveries_logged_share.paced",
                         "idle_before_queue_share.paced")
LAYER = dict(zip(NEW, ["broker and spout"] * 2 + ["encode and sink"] * 3
                 + ["device"]))

# device ms: (program, padded, start, end), and the host's moments of the step
# on the same scale: first_enq, cut, launched (staged 0.5 before); ready 0.2
# after the end (the first at it), fetched 0.3 and resolved 0.4 after that. The device idles
# 38-50 (no step pending 38-41) and 78-90 (none pending 78-85); span 10-146.
STEPS = [("jit_fwd(8)", 8, 10, 38, 2, 5, 7),
         ("jit_fwd(32)", 32, 50, 78, 41, 44, 47),
         ("jit_fwd(8)", 8, 90, 118, 85, 86, 87),
         ("jit_fwd(32)", 32, 118, 146, 100, 110, 112)]
BEFORE = 3  # steps of the log from before the trace
# records: (step's place in STEPS, append ms, enq ms, hole ms between the
# step's t_resolved and the record's t_egress)
RECORDS = [(0, 1.0, 2.0, 1.0),    # appended before the trace began
           (1, 39.5, 41.0, 2.0),  # in the broker while the device idled
           (2, 79.0, 85.0, 3.0),  # likewise, 6 ms of the second gap
           (2, 84.0, 85.5, 3.0),  # inside the one before it
           (3, 95.0, 100.0, 4.0),  # appended after the gap: the device ran
           (3, 108.0, 109.0, 5.0)]
HELD_MS = 1.5 + 6.0
SPAN_MS = 136.0
NO_ROWS_MS = 10.0


def _step_row(step, padded, enq, cut, launched, end, late=0.2, engine="m"):
    ready = T0 + (end + late) / 1e3
    return {"step": step, "engine": engine, "padded": padded, "rows": padded,
            "sources": 2, "seen": True, "t_first_enq": T0 + enq / 1e3,
            "t_cut": T0 + cut / 1e3, "t_staged": T0 + (launched - .5) / 1e3,
            "t_launched": T0 + launched / 1e3, "t_ready": ready,
            "t_fetched": ready + 3e-4, "t_resolved": ready + 4e-4}


def _logs():
    mods, ops, steps = [], [], []
    for n in range(BEFORE):  # before the trace, at uneven times
        at = -300 + 70 * n + 11 * n * n
        steps.append(_step_row(n, (8, 32, 32)[n % 3], at, at + 3, at + 5,
                               at + 34))
    for n, (prog, padded, start, end, enq, cut, launched) in \
            enumerate(STEPS, BEFORE):
        mods.append((prog, start * MS, (end - start) * MS))
        ops += [(f"%fusion.{k} = bf16[8,64] fusion(%p)",
                 (start + k * (end - start) / 4) * MS, (end - start) / 4 * MS)
                for k in range(4)]
        # the first result is seen as the device ends: the fit's anchor
        steps.append(_step_row(n, padded, enq, cut, launched, end,
                               late=0.2 * (n > BEFORE)))
    records = []
    for place, append, enq, hole in RECORDS:
        step = steps[BEFORE + place]
        egress = step["t_resolved"] + hole / 1e3
        records.append({
            "records": 1, "t_append": T0 + append / 1e3,
            "t_polled": T0 + (append + 0.5) / 1e3,
            "t_emitted": T0 + (append + 0.6) / 1e3,
            "t_exec": T0 + (append + 0.6 + 0.1 * (place + 1)) / 1e3,
            "t_parsed": T0 + (enq - 0.01) / 1e3, "t_enq": T0 + enq / 1e3,
            "engine": "m", "step": step["step"], "t_egress": egress,
            "t_encoded": egress + 2e-4, "t_sink": egress + 5e-4,
            "t_produced": egress + 1.5e-3, "ended": "delivered"})
    planes = [(DEV, [("XLA Modules", mods), ("XLA Ops", ops)])]
    return planes, steps, records


def _run(records, steps, planes=(), traced=True):
    the_cell = spec.cell(BENCH, CELL)
    run = harness.Run(the_cell, spec.config(the_cell["config"]), {}, 0, 14.0)
    run.trace = {"busy_s": 1.0, "window_s": 1.0} if traced else None
    run._device_planes = list(planes)
    run._trace_meta = {"op_names": {}, "start_s": T0 - 0.0015}
    run._step_rows = steps
    run._record_rows = records
    # the output's broker stamp lies 50 us before the row's t_produced
    run.delivery_times = sorted(r["t_produced"] - 5e-5 for r in records or []
                                if r.get("t_produced") is not None)
    return run


def _read(name, run):
    doc = spec.metric(name)
    return spec.plugin("readers", doc["reader"]).read(run,
                                                      **doc.get("args", {}))


@pytest.mark.parametrize("name", NEW)
def test_the_entry_is_found_by_name_and_lists_the_paced_cell_alone(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "latency_p50_ms"
    assert entry["unit"] == ("%" if name.endswith("share.paced") else "ms")
    assert entry["better"] == ("higher" if name.startswith("deliveries")
                               else "lower")
    assert entry["source"] == ("device_trace" if name.startswith("idle")
                               else "program_span")
    assert entry["layer"] == LAYER[name]
    doc = spec.metric(name)
    assert os.path.isfile(os.path.join(spec.BENCH_DIR, "readers",
                                       doc["reader"] + ".py"))


@pytest.mark.parametrize("name", sorted(INTERVAL))
def test_an_interval_reads_the_milliseconds_planted(name):
    planes, steps, records = _logs()
    run = _run(records, steps, planes)
    a, b = INTERVAL[name]
    joined = recordlog.join(records, steps)
    want = pairing.quantile([(p[b] - p[a]) * 1e3 for p in joined], 0.5)
    assert _read(name, run) == pytest.approx(want, abs=1e-6)
    if name.startswith("resolved"):  # the holes: 1, 2, 3, 3, 4, 5 ms
        assert want == pytest.approx(3.0, abs=1e-6)
    if name.startswith("emit"):  # 0.1, 0.2, 0.3, 0.3, 0.4, 0.4
        assert want == pytest.approx(0.3, abs=1e-6)


def test_only_records_produced_inside_the_window_count():
    planes, steps, records = _logs()
    run = _run(records, steps, planes)
    run.delivery_times = run.delivery_times[2:5]  # holes 3, 3 and 4
    assert _read("resolved_to_egress_p50_ms.paced", run) == \
        pytest.approx(3.0, abs=1e-6)
    assert run.notes["record"]["in_window"] == 3
    assert run.notes["record"]["rows"] == 6


def test_the_log_covers_the_deliveries_seen_from_outside():
    planes, steps, records = _logs()
    run = _run(records, steps, planes)
    assert _read("deliveries_logged_share.paced", run) == 100.0
    # a delivery no row answers, a row whose step the log has lost, a row
    # without a moment, and one more than a millisecond from its output
    run = _run(records, steps, planes)
    run.delivery_times = sorted(run.delivery_times + [T0 + 0.5])
    assert _read("deliveries_logged_share.paced", run) == \
        pytest.approx(100.0 * 6 / 7)
    run = _run(records, [s for s in steps if s["step"] != BEFORE + 1], planes)
    assert _read("deliveries_logged_share.paced", run) == \
        pytest.approx(100.0 * 5 / 6)
    run = _run([dict(r, t_sink=None) if i == 0 else r
                for i, r in enumerate(records)], steps, planes)
    assert _read("deliveries_logged_share.paced", run) == \
        pytest.approx(100.0 * 5 / 6)
    run = _run(records, steps, planes)
    run.delivery_times = [t - 2e-3 * (i == 5)
                          for i, t in enumerate(run.delivery_times)]
    assert _read("deliveries_logged_share.paced", run) == \
        pytest.approx(100.0 * 5 / 6)
    # one row answers one delivery
    twice = [run.delivery_times[0]] * 2
    assert recordlog.logged_share(
        twice, recordlog.join(records, steps)) == 50.0


def test_the_notes_add_up_to_the_median_records_latency():
    planes, steps, records = _logs()
    run = _run(records, steps, planes)
    run.latencies_ms = None
    _read("append_to_poll_p50_ms.paced", run)
    note = run.notes["record"]
    assert note["without_step"] == 0 and note["not_delivered"] == 0
    assert set(note["intervals"]) == {n for n, _, _ in recordlog.INTERVALS}
    assert note["intervals"]["append->polled"]["p50"] == pytest.approx(0.5)
    mid = note["median_record"]
    assert sum(mid["intervals_ms"].values()) == \
        pytest.approx(mid["append_to_produced_ms"], abs=1e-6)
    assert mid["append_to_produced_ms"] == pytest.approx(
        note["log_append_to_produced_p50_ms"], rel=0.5)
    assert mid["row"]["t_cut"] is not None
    json.dumps(note)
    # a row whose step is not in the step log is counted
    run = _run(records, [s for s in steps if s["step"] != BEFORE + 1], planes)
    _read("append_to_poll_p50_ms.paced", run)
    assert run.notes["record"]["without_step"] == 1


def test_idle_while_the_host_held_a_record_and_not_after_the_gap():
    planes, steps, records = _logs()
    run = _run(records, steps, planes)
    value = _read("idle_before_queue_share.paced", run)
    assert value == pytest.approx(100.0 * HELD_MS / SPAN_MS, abs=1e-6)
    note = run.notes["idle_before_queue"]
    assert note["span_s"] == pytest.approx(SPAN_MS / 1e3)
    assert note["no_rows"] == pytest.approx(NO_ROWS_MS / 1e3)
    assert note["host_held"] == pytest.approx(HELD_MS / 1e3)
    assert note["nothing_appended"] == pytest.approx(
        (NO_ROWS_MS - HELD_MS) / 1e3)
    # the step log's own classes are what they were: the run's rows are
    # not written to
    assert _read("idle_with_rows_share.paced", run) == \
        pytest.approx(100.0 * 14.0 / SPAN_MS, abs=1e-6)
    # without the records that lay in the broker, nothing was held; the one
    # appended after the gap (95 ms, the device running) adds nothing
    run = _run([r for r in records if r["t_append"] > T0 + 0.09], steps,
               planes)
    assert _read("idle_before_queue_share.paced", run) == 0.0
    # every record from its step's first entry on: held for no moment
    run = _run([dict(r, t_append=r["t_enq"]) for r in records], steps, planes)
    assert _read("idle_before_queue_share.paced", run) == \
        pytest.approx(0.0, abs=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_log_reads_nothing(name):
    planes, steps, _ = _logs()
    run = _run(None, steps, planes)  # no ``records()`` there
    assert _read(name, run) is None
    assert "record" not in run.notes
    # and so the line leaves the metric out, whatever else it holds
    listed = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert harness.read_metrics(run, listed) == {}


def test_without_a_trace_or_a_matched_step_the_idle_share_says_so():
    planes, steps, records = _logs()
    run = _run(records, steps, [], traced=False)
    assert _read("idle_before_queue_share.paced", run) is None
    for name in INTERVAL:  # the log needs no trace
        assert math.isfinite(_read(name, run))
    run = _run(records, [], planes)
    assert _read("idle_before_queue_share.paced", run) == 0.0
    assert run.notes["idle_before_queue"] == {"why": "no step matched"}
    run = _run([], steps, planes)  # a log that is there and empty
    assert _read("append_to_poll_p50_ms.paced", run) is None
    assert _read("deliveries_logged_share.paced", run) is None
    assert _read("idle_before_queue_share.paced", run) == 0.0


def test_the_yardsticks_path_is_the_programs():
    from storm_tpu.obs.profile import RECORD_INTERVALS, RECORD_PATH

    assert recordlog.PATH == RECORD_PATH
    assert recordlog.INTERVALS == RECORD_INTERVALS


@pytest.mark.timeout(110)
def test_every_new_metric_is_a_number_on_a_rehearsal_of_the_paced_cell():
    """The command at toy size on the CPU: the five metrics the log alone
    gives are numbers, every delivery has its row, and the two medians lie
    together. The sixth needs a device's planes, which a CPU's trace has
    not: it is read over a trace made by hand above."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    command = [sys.executable if w == "python3" else w
               for w in bench["command"]]
    proc = subprocess.run(
        command + ["--workload", "vit_tiny.json_paced", "--seed",
                   "3000000031", "--seconds", "3", "--trace", "0",
                   "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=100)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True
    (every,) = [x for x in lines if x.get("phase") == "all_metrics"]
    for name in NEW[:-1]:
        assert math.isfinite(every["per_layer"][name]), name
    assert "idle_before_queue_share.paced" not in every["per_layer"]
    assert every["per_layer"]["deliveries_logged_share.paced"] >= 99.0
    note = every["notes"]["record"]
    assert note["without_step"] == 0 and note["not_delivered"] == 0
    assert len(note["intervals"]) == len(recordlog.INTERVALS)
    mid = note["median_record"]
    assert sum(mid["intervals_ms"].values()) == \
        pytest.approx(mid["append_to_produced_ms"], abs=1e-6)
    assert note["log_append_to_produced_p50_ms"] == pytest.approx(
        note["outside_latency_less_late_p50_ms"], rel=0.25)
