"""Citation honesty for the documents and for the package's own words.

A cited file IS the evidence; citing a file that isn't in the tree is a
false claim the reader can't audit. A deletion that leaves the words
behind (a docstring that sends the reader to a script that is gone, an
error message that tells an operator to run it) is the same claim, so the
gate reads every document of the tree as it is and every comment,
docstring and string literal of ``storm_tpu/``. What counts as a citation:

* a path that starts with one of ``PREFIXES``: it must exist;
* a name in the root's own convention (``README.md``,
  ``PERF_LEDGER.jsonl``): it must exist at the root. Lower-case names
  (``profile.json``, ``metrics.jsonl``) are an operator's own files;
* a ``name.py``, bare or with directories before it: it must be the end of
  the path of a file at the root, beside the citing file, or under
  ``PY_ROOTS``;
* the phrase ``ROADMAP item <n>`` / ``ROADMAP-<n>``: no roadmap since PR 21
  is numbered that way, so it never holds.

A trailing ``:line``, ``::test`` or dotted name is cut. A token that holds
``*``, ``<``, ``{`` or ``$``, or that hangs from a root of its own
(``/tmp/x.py``, ``<dir>/pkg/worker.py``), is a pattern or somebody else's
file, not a citation. ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are
histories that rightly name files that are gone, and are not read.
"""

from __future__ import annotations

import functools
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCS = ("README.md", "PARITY.md", "docs/ARCHITECTURE.md",
        "docs/OPERATIONS.md", "docs/MIGRATION.md", "docs/JVM_CLIENT.md",
        "examples/README.md", ".claude/skills/verify/SKILL.md")
PACKAGES = sorted(p.name for p in (REPO / "storm_tpu").iterdir()
                  if p.is_dir() and p.name != "__pycache__")
PREFIXES = ("storm_tpu", "tests", "benchmarks", "docs", "examples",
            "checkpoints")
PY_ROOTS = ("storm_tpu", "tests", "benchmarks", "examples")

_NOT_INSIDE = r"(?<![\w./<>{}$*~-])"
_PREFIXED = re.compile(
    _NOT_INSIDE + r"(?:%s)/[\w./*<>{}$-]*" % "|".join(PREFIXES))
_ROOT_NAME = re.compile(
    _NOT_INSIDE + r"[A-Z][A-Za-z0-9_]*\.(?:md|jsonl|json)\b")
_PY_NAME = re.compile(_NOT_INSIDE + r"(?:[\w-]+/)*[\w-]+\.py\b")
_ROADMAP_ITEM = re.compile(r"ROADMAP(?:\s+item\s+|-)\d+")
_PATTERN_CHARS = set("*<>{}$")


@functools.cache
def _py_names():
    """Every way to cite a ``*.py`` of the tree: its path from the root and
    each shorter end of it (``infer/engine.py``, ``engine.py``)."""
    files = [p.name for p in REPO.glob("*.py")]
    for root in PY_ROOTS:
        files += [p.relative_to(REPO).as_posix()
                  for p in (REPO / root).rglob("*.py")]
    return {f.split("/", i)[-1] for f in files for i in range(f.count("/") + 1)}


def _prefixed_exists(token: str) -> bool:
    """``tests/kafka_stub.KafkaStubBroker`` cites ``tests/kafka_stub.py``:
    dotted names are cut from the end until something exists."""
    while True:
        if (REPO / token).exists() or (REPO / (token + ".py")).is_file():
            return True
        head, dot, _ = token.rpartition(".")
        if not dot or "/" in token[len(head):]:
            return False
        token = head


def _py_exists(token: str, beside: Path) -> bool:
    return token in _py_names() or (beside / token).is_file()


def citations(text: str, beside: Path = REPO):
    """Yield ``(line, citation, holds)`` for every citation in ``text``."""
    text = text.replace("/root/repo/", "")
    seen = []
    for m in _PREFIXED.finditer(text):
        token = m.group().rstrip("./-")
        if _PATTERN_CHARS & set(token) or ".." in token:
            continue
        seen.append((m.start(), token, _prefixed_exists(token)))
    for m in _ROOT_NAME.finditer(text):
        seen.append((m.start(), m.group(), (REPO / m.group()).is_file()))
    for m in _PY_NAME.finditer(text):
        if m.group().split("/")[0] not in PREFIXES:
            seen.append((m.start(), m.group(),
                         _py_exists(m.group(), beside)))
    for m in _ROADMAP_ITEM.finditer(text):
        seen.append((m.start(), " ".join(m.group().split()), False))
    for at, token, holds in sorted(seen):
        yield text.count("\n", 0, at) + 1, token, holds


def _missing(path: Path):
    return [f"{path.relative_to(REPO)}:{line} cites {token}"
            for line, token, holds in
            citations(path.read_text(encoding="utf-8"), path.parent)
            if not holds]


def _report(missing):
    assert not missing, (
        "citations of files that are not in the tree:\n  "
        + "\n  ".join(missing)
        + "\n(cite what exists, or say in words where the number came from)")


@pytest.mark.parametrize("doc", DOCS)
def test_cited_paths_exist(doc):
    _report(_missing(REPO / doc))


@pytest.mark.parametrize("package", PACKAGES + ["top-level modules"])
def test_code_cites_only_present_files(package):
    """Comments, docstrings and string literals alike: an error message or
    a ``help=`` that names a dead script fails as a docstring does."""
    if package in PACKAGES:
        files = [p for ext in ("*.py", "*.json")
                 for p in (REPO / "storm_tpu" / package).rglob(ext)]
    else:
        files = list((REPO / "storm_tpu").glob("*.py"))
    assert files, package
    _report([m for path in sorted(files) for m in _missing(path)])


def test_the_helper_sees_citations():
    """Guard the guard: if a naming convention changes and the helper goes
    blind, this fails instead of the two above passing on nothing."""
    text = ("see storm_tpu/config.py and storm_tpu/gone/away.py,\n"
            "engine.py:123, tests/test_plan.py::test_y, README.md,\n"
            "GONE_r07.json, a pattern tests/test_*.py, /tmp/drive.py,\n"
            "an operator's profile.json and ROADMAP item 3\n")
    assert list(citations(text)) == [
        (1, "storm_tpu/config.py", True),
        (1, "storm_tpu/gone/away.py", False),
        (2, "engine.py", True),
        (2, "tests/test_plan.py", True),
        (2, "README.md", True),
        (3, "GONE_r07.json", False),
        (4, "ROADMAP item 3", False),
    ]
    found = [c for doc in DOCS
             for c in citations((REPO / doc).read_text(encoding="utf-8"))]
    assert len(found) >= 50
