"""BENCHMARK.json against its contract, and every name in it against the
files under benchmarks/: a later PR adds files and entries and edits none."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.core import harness, spec  # noqa: E402

BENCH = spec.benchmark()
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]
TEXT_RE = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    assert 1 <= len(BENCH["command"]) <= 32
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for path in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path)
        assert not path.startswith("/") and ".." not in path.split("/")
        assert os.path.isdir(os.path.join(ROOT, path))
    for word in BENCH["command"]:
        assert TEXT_RE.match(word) and not word.startswith("/")
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])


def test_files_under_paths_have_permitted_names():
    for path in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                if name.endswith(".pyc"):
                    continue
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_resolves(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert spec.NAME_RE.match(entry["name"])
    assert TEXT_RE.match(entry["source"]) and TEXT_RE.match(entry["why"])
    assert len(entry["reduced"]) <= 16
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    assert entry["file"] == f"benchmarks/configs/{entry['name']}.json"
    doc = spec.config(entry["name"])
    assert doc["name"] == entry["name"]
    assert doc["reduced"] == entry["reduced"]
    for kind in ("runner", "reference"):
        spec.plugin(kind + "s", doc[kind])
    spec.plugin("inputs", doc["inputs"]["kind"])
    if "ops" in doc:
        spec.plugin("ops", doc["ops"])
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
    assert doc["guarantees"]["offsets"].startswith("policy earliest")
    assert doc["program"] == {"offsets.policy": "earliest",
                              "offsets.max_behind": None}
    assert 0 < doc["tolerance"]["relative_distance"] <= 0.2


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_and_reports_enough(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert spec.NAME_RE.match(cell["name"])
    assert cell["chips"] in (1, 4) and TEXT_RE.match(cell["why"])
    assert cell["config"] in [c["name"] for c in BENCH["configs"]]
    traffic = spec.traffic(cell["traffic"])
    spec.plugin("arrivals", traffic["arrivals"])
    spec.plugin("payloads", traffic["payload"])
    assert traffic["pool"] >= 1 and traffic["why"]
    e2e = [m["name"] for m in spec.metrics_for(BENCH, "end_to_end", cell)]
    layer = spec.metrics_for(BENCH, "per_layer", cell)
    assert "setup_s" in e2e and len(e2e) >= 2 and len(layer) >= 1
    for m in layer:  # what a metric moves is reported wherever it is
        assert m["moves"] in e2e, (m["name"], cell["name"])


def test_cells_are_distinct_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs) and 1 <= len(pairs) <= 24
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(pairs) // 4)


@pytest.mark.parametrize("entry", METRICS, ids=lambda m: m["name"])
def test_metric_entry_resolves(entry):
    end_to_end = entry in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert keys <= set(entry) <= keys | {"workloads"}
    assert spec.NAME_RE.match(entry["name"])
    assert spec.UNIT_RE.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in SOURCES
    if end_to_end:
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.1
    else:
        assert TEXT_RE.match(entry["layer"])
        assert entry["moves"] in [m["name"] for m in BENCH["end_to_end"]]
    for cell in entry.get("workloads", []):
        assert cell in CELLS
    doc = spec.metric(entry["name"])
    reader = spec.plugin("readers", doc["reader"])
    assert callable(reader.read)
    if entry["name"].endswith("_roofline") or "mfu" in entry["name"]:
        assert entry["unit"] == "%"


def test_names_are_unique():
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        names = [e["name"] for e in group]
        assert len(set(names)) == len(names)
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    layers = {m["layer"] for m in BENCH["per_layer"]}
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:  # PERF.md's list of layers has each, letter for letter
        assert f"**{layer}**" in perf, layer


def test_a_bad_name_and_a_missing_file_are_errors():
    with pytest.raises(spec.SpecError):
        spec.config("no such/config")
    with pytest.raises(spec.SpecError):
        spec.plugin("readers", "does_not_exist")
    with pytest.raises(spec.SpecError):
        spec.cell(BENCH, "vit_g14.no_such_mix")
    assert spec.cell(BENCH, "vit_tiny.tensor_backlog",
                     rehearse=True)["config"] == "vit_tiny"


def test_peaks_table_and_an_unlisted_device():
    table = spec.load_json(os.path.join(spec.BENCH_DIR, "peaks.json"))
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                   "hbm_bytes": 16e9}
    assert "Google Cloud" in table["source"]
    run = harness.Run({"name": "c"}, {"model": {"dtype": "bfloat16"}}, {},
                      0, 1.0)
    run.device = {"kind": "TPU v5 lite"}
    assert run.peaks()["bf16_flops_per_s"] == 197e12
    run.device = {"kind": "cpu"}
    with pytest.raises(spec.SpecError):
        run.peaks()


def _fake_run():
    run = harness.Run({"name": "c"}, {"model": {"dtype": "bfloat16"}}, {},
                      0, 2.0)
    run.delivered_in_window = 100
    run.registry_before = {"some-bolt": {"work_ms": {"count": 10, "sum": 5.0},
                                         "things": 3}}
    run.registry_after = {"some-bolt": {
        "work_ms": {"count": 60, "sum": 30.0, "p50": 0.4}, "things": 53}}
    return run


def test_a_throw_away_metric_is_a_file_and_an_entry(tmp_path, monkeypatch):
    """A registry-backed metric of a later PR: one new file under metrics/,
    one new entry in BENCHMARK.json, no edit to any file that is there."""
    bench_dir = tmp_path / "benchmarks"
    (bench_dir / "metrics").mkdir(parents=True)
    os.symlink(os.path.join(spec.BENCH_DIR, "readers"), bench_dir / "readers")
    (bench_dir / "metrics" / "throwaway_ms.json").write_text(json.dumps({
        "reader": "registry_histogram",
        "args": {"terms": [["some-bolt", "work_ms"]],
                 "stat": "sum_per_record"}}))
    (bench_dir / "metrics" / "throwaway_count.json").write_text(json.dumps({
        "reader": "registry_counter",
        "args": {"component": "some-bolt", "name": "things"}}))
    (bench_dir / "metrics" / "nothing_there.json").write_text(json.dumps({
        "reader": "registry_counter",
        "args": {"component": "some-bolt", "name": "absent"}}))
    monkeypatch.setattr(spec, "BENCH_DIR", str(bench_dir))
    entries = [{"name": "throwaway_ms", "unit": "ms"},
               {"name": "throwaway_count", "unit": "count"},
               {"name": "nothing_there", "unit": "count"}]
    got = harness.read_metrics(_fake_run(), entries)
    assert got == {"throwaway_ms": {"value": 0.25, "unit": "ms"},
                   "throwaway_count": {"value": 50.0, "unit": "count"}}


def test_registry_histogram_stats():
    reader = spec.plugin("readers", "registry_histogram")
    run = _fake_run()
    terms = [["some-bolt", "work_ms"]]
    assert reader.read(run, terms, "mean") == 0.5
    assert reader.read(run, terms, "p50") == 0.4
    assert reader.read(run, [["some-bolt", "absent"]], "mean") is None
