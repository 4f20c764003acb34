"""Finding everything by name.

``BENCHMARK.json`` at the root names cells, configurations and metrics. Each
name resolves to a file under ``benchmarks/`` and nothing is registered in
code, so a later PR adds a cell, a configuration, a traffic mix, a metric or
a reader by adding files and entries and edits none:

    configs/<config>.json      traffic/<mix>.json      metrics/<metric>.json
    readers/<reader>.py        arrivals/<kind>.py      payloads/<encoding>.py
    runners/<builder>.py       ops/<model>.py          references/<model>.py
    inputs/<kind>.py           models/<family>.py

A cell is ``<config>.<mix>``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PLUGIN_KINDS = ("readers", "arrivals", "payloads", "inputs", "runners",
                "ops", "references", "models")


class SpecError(Exception):
    """A name that resolves to nothing, or a data file that is malformed."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError as e:
        raise SpecError(f"{os.path.relpath(path, ROOT)}: {e.strerror}") from e
    except ValueError as e:
        raise SpecError(f"{os.path.relpath(path, ROOT)}: {e}") from e
    if not isinstance(doc, dict):
        raise SpecError(f"{os.path.relpath(path, ROOT)}: not a JSON object")
    return doc


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def data_file(kind: str, name: str) -> str:
    if not NAME_RE.match(name):
        raise SpecError(f"{kind} name {name!r} has characters outside "
                        "letters, digits, '_', '.', '-'")
    return os.path.join(BENCH_DIR, kind, name + ".json")


def config(name: str) -> dict:
    return load_json(data_file("configs", name))


def traffic(name: str) -> dict:
    return load_json(data_file("traffic", name))


def metric(name: str) -> dict:
    doc = load_json(data_file("metrics", name))
    if "reader" not in doc:
        raise SpecError(f"metrics/{name}.json names no reader")
    return doc


def plugin(kind: str, name: str):
    """The module ``benchmarks/<kind>/<name>.py``, loaded by file so that a
    module called ``json`` or ``standard`` shadows nothing."""
    if kind not in PLUGIN_KINDS:
        raise SpecError(f"no plugin kind {kind!r}")
    if not NAME_RE.match(name):
        raise SpecError(f"{kind} name {name!r} is not a name")
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"{kind}/{name}.py does not exist (named by a data "
                        "file; add the file, edit none)")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def split_cell(cell: str) -> tuple:
    config_name, dot, mix = cell.partition(".")
    if not dot or not config_name or not mix:
        raise SpecError(f"cell {cell!r} is not <config>.<mix>")
    return config_name, mix


def cell(bench: dict, name: str, rehearse: bool = False) -> dict:
    """The ``workloads`` entry called ``name``. A rehearsal may run a cell
    that ``BENCHMARK.json`` does not list (a toy configuration under a real
    traffic mix): its files are found by the same names."""
    for w in bench["workloads"]:
        if w["name"] == name:
            return dict(w)
    if not rehearse:
        raise SpecError(f"BENCHMARK.json has no workload {name!r} (known: "
                        f"{[w['name'] for w in bench['workloads']]})")
    config_name, mix = split_cell(name)
    return {"name": name, "config": config_name, "traffic": mix, "chips": 1,
            "why": "rehearsal"}


def metrics_for(bench: dict, group: str, the_cell: dict) -> list:
    """Entries of ``end_to_end`` or ``per_layer`` that this cell reports: those
    that list it, and those with no ``workloads`` key. A rehearsal cell takes
    the metrics of the listed cells that share its traffic mix."""
    listed = {w["name"]: w for w in bench["workloads"]}
    out = []
    for m in bench[group]:
        cells = m.get("workloads")
        if cells is None or the_cell["name"] in cells:
            out.append(m)
        elif the_cell["name"] not in listed and any(
                listed.get(c, {}).get("traffic") == the_cell["traffic"]
                for c in cells):
            out.append(m)
    return out
