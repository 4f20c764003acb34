"""Tier-1 gate: `storm-tpu lint` must run clean on the real tree.

"Clean" means zero NON-BASELINED findings — the baseline
(storm_tpu/analysis/baseline.json) holds the reviewed-and-accepted holds
(engine dispatch-order device_put, controller recovery transactions, the
Kafka per-partition send serialization), each with a justification. A new
finding here means new code violated a checked invariant OR a checker
regressed; either way it fails tier-1 until fixed or reviewed into the
baseline. docs/OPERATIONS.md "Static analysis" is the runbook.
"""

import ast
import json
import os
import re
import textwrap

from storm_tpu.analysis import filter_new, load_baseline, load_config, run_lint
from storm_tpu.analysis.callgraph import CallGraph, module_of
from storm_tpu.analysis.core import dotted_name, iter_python_files, parse_source

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(ROOT, "storm_tpu", "analysis", "baseline.json")


def test_tree_has_no_new_findings():
    config = load_config(ROOT)
    findings = run_lint(["storm_tpu"], ROOT, config)
    new = filter_new(findings, load_baseline(BASELINE))
    assert new == [], "new lint findings (fix or baseline with a why):\n" + \
        "\n".join(f.render() for f in new)


def test_baseline_entries_are_justified():
    # every accepted finding carries a real reviewed justification, not
    # the --update-baseline placeholder
    data = json.load(open(BASELINE))
    for row in data["findings"]:
        why = row.get("why", "")
        assert why and "accepted via --update-baseline" not in why, \
            f"baseline entry needs a justification: {row['key']}"


def test_baseline_has_no_stale_entries():
    # entries whose finding no longer exists should be pruned — a stale
    # key silently suppresses a future regression at the same site
    config = load_config(ROOT)
    live = {f.key() for f in run_lint(["storm_tpu"], ROOT, config)}
    stale = [k for k in load_baseline(BASELINE) if k not in live]
    assert stale == [], f"baseline entries with no live finding: {stale}"


def _tree_files():
    files = []
    for rel in iter_python_files(["storm_tpu"], ROOT):
        with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
            sf = parse_source(f.read(), rel)
        if sf is not None:
            files.append(sf)
    return files


def test_metric_registry_is_fresh():
    # the committed metric_names.py must match what --regen-metric-registry
    # would produce from today's call sites
    from storm_tpu.analysis.observability import generate_registry

    committed = open(os.path.join(
        ROOT, "storm_tpu", "analysis", "metric_names.py")).read()
    assert generate_registry(_tree_files()) == committed, \
        "metric registry is stale: run `storm-tpu lint " \
        "--regen-metric-registry` and commit the result"


def test_protocol_registry_is_fresh():
    # same gate for protocol_names.py: control commands, journal kinds and
    # flight events checked by PRT001-003 must be regenerated whenever a
    # call site changes
    from storm_tpu.analysis.protocol import generate_registry

    committed = open(os.path.join(
        ROOT, "storm_tpu", "analysis", "protocol_names.py")).read()
    assert generate_registry(_tree_files()) == committed, \
        "protocol registry is stale: run `storm-tpu lint " \
        "--regen-protocol-registry` and commit the result"


def test_lint_wall_clock_budget():
    # the whole-tree run (parse + per-file rules + call graph + the
    # interprocedural tier) has to stay cheap enough for tier-1 and for
    # pre-commit use; --profile prints the same numbers for humans
    timings = {}
    config = load_config(ROOT)
    run_lint(["storm_tpu"], ROOT, config, timings=timings)
    assert timings["total_s"] < 10.0, \
        f"lint took {timings['total_s']:.1f}s (budget 10s): {timings}"


# ---------------------------------------------------------------------------
# weakref.finalize callbacks: no lock their own thread may hold
# ---------------------------------------------------------------------------

# A finalizer runs wherever the collector does: on any thread, inside any
# allocation, also one made under a lock. So the callable handed to
# weakref.finalize may acquire only re-entrant locks — or it is listed here,
# reviewed, with the reason it stands.
_REVIEWED_FINALIZERS = {
    ("storm_tpu/infer/engine.py", "self._fetch_q.put"):
        "_fetch_q is a queue.SimpleQueue, whose put() is documented as "
        "re-entrant and made for destructors and weakref callbacks (the "
        "call graph cannot see into the stdlib). A queue.Queue there would "
        "wedge: its put() takes a plain mutex. ROADMAP.md, Design item 11.",
}


def _reentrant(sf, key):
    """Whether every lock of that name in the file is an RLock, or a
    Condition on its default lock (which is an RLock)."""
    name = re.split(r"[.:#]", key)[-1]
    kinds = []
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call) \
                and dotted_name(node.targets[0]).rsplit(".", 1)[-1] == name:
            ctor = dotted_name(node.value.func).rsplit(".", 1)[-1]
            kinds.append(ctor == "RLock" or (
                ctor == "Condition" and not node.value.args
                and not node.value.keywords))
    return bool(kinds) and all(kinds)


def _unsafe_finalizers(files, reviewed=()):
    """(site, why) for every ``weakref.finalize(obj, fn, ...)`` whose ``fn``
    the call graph cannot resolve, or resolves to code that may take a
    non-re-entrant lock; plus the sites seen, to catch a stale review."""
    graph = CallGraph(files, load_config(ROOT))
    by_module = {module_of(sf.path): sf for sf in files}
    seen, bad = set(), []
    for fn in graph.functions.values():
        lines = {rec.line for rec in fn.calls
                 if rec.raw in ("weakref.finalize", "finalize")}
        if not lines:
            continue
        for node in ast.walk(by_module[fn.module].tree):
            if not (isinstance(node, ast.Call) and node.lineno in lines
                    and dotted_name(node.func).endswith("finalize")):
                continue
            raw = dotted_name(node.args[1])
            site = (fn.path, raw)
            seen.add(site)
            if site in reviewed:
                continue
            target = graph.resolve(fn.module, fn.scope, raw, fn)
            if target is None:
                bad.append((site, "not resolved: review it"))
                continue
            plain = sorted(
                k for k in graph.functions[target].trans_acquires
                if not _reentrant(by_module[k.split(":")[0]], k))
            if plain:
                bad.append((site, f"may take {plain}"))
    return bad, seen


def test_finalizers_take_no_plain_lock():
    bad, seen = _unsafe_finalizers(_tree_files(), _REVIEWED_FINALIZERS)
    assert bad == [], \
        "a weakref.finalize callback may take a lock its thread holds: " \
        f"{bad}"
    assert ("storm_tpu/infer/continuous.py", "new.close") in seen, \
        "the continuous queue's finalizer is no longer checked"
    assert set(_REVIEWED_FINALIZERS) <= seen, \
        f"reviewed finalizers no longer in the tree: " \
        f"{set(_REVIEWED_FINALIZERS) - seen}"


def test_finalizer_check_catches_a_registry_lock():
    # the shape that wedged the tier-1 suite: the finalizer takes the plain
    # lock that the registering call holds while it allocates
    src = textwrap.dedent("""
        import threading, weakref
        _REGISTRY = {}
        _REGISTRY_LOCK = threading.Lock()
        def queue_for(engine):
            with _REGISTRY_LOCK:
                def _drop(k=id(engine)):
                    with _REGISTRY_LOCK:
                        _REGISTRY.pop(k, None)
                weakref.finalize(engine, _drop)
    """)
    bad, _ = _unsafe_finalizers([parse_source(src, "pkg/registry.py")])
    assert bad == [(("pkg/registry.py", "_drop"),
                    "may take ['pkg.registry:_REGISTRY_LOCK']")]
