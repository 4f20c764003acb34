"""Device milliseconds a step spends in the operations whose trace names
match ``pattern``, or with ``kernel`` that time's share of its roofline.

The trace's ``XLA Ops`` line names an operation by its HLO text, shapes
included, and nothing else: no scope, no source line. What it can name of a
program XLA compiled is therefore a computation kept whole in one operation
(a loop is one ``%while`` event spanning its iterations, beside the events of
the operations inside it) told by the shapes it carries. ``pattern`` is a
regular expression searched in each event's name; the durations of the
matches inside one whole execution of a program whose name starts with
``prefix`` are summed, and the mean over the whole executions is returned
(those the profiler cut at the trace's ends are left out, as everywhere:
``core/xplane.py executions``). ``core/xplane.py reduce`` keeps the ten
costliest operations only, so this reader loads the trace itself, once a run.

With ``kernel``: the least time of that kernel by ``kernels()`` of the
configuration's ``ops`` file and ``peaks.json``, over the time read, in
percent. For ``expert_matmul`` the assignments are the number the program
counted (registry ``expert_assignments_held`` over the steps of the window),
the expected number where it counted none.

None where the trace has no such operation (a program without it, a run on
no TPU): the metric is then left out of the line.
"""

import os
import re

from benchmarks.core import spec, xplane


def _planes(run):
    planes = getattr(run, "_device_planes", None)
    if planes is None:
        path = xplane.find_trace(os.path.join(
            spec.ROOT, "bench_out", "trace", run.cell["name"]))
        planes = [(n, ls) for n, ls in (xplane.load(path) if path else [])
                  if n.startswith(xplane.DEVICE_PREFIX)]
        run._device_planes = planes
    return planes


def step_times(planes: list, prefix: str, pattern: str):
    """``(seconds, op_names)``: for each whole execution of a ``prefix``
    program the summed duration of its matching operations, and the names of
    one execution's operations (for ``rows_per_step``)."""
    wanted = re.compile(pattern)
    seconds, names = [], []
    for _, lines in planes:
        mods = xplane._line(lines, xplane.MODULE_LINE)
        ops = sorted(xplane._line(lines, xplane.OP_LINE), key=lambda e: e[1])
        for name, _s, _d, a, b, whole in xplane.executions(
                mods, [e[1] for e in ops]):
            if not whole or not name.startswith(prefix):
                continue
            seconds.append(sum(d for n, _, d in ops[a:b]
                               if wanted.search(n)) / 1e9)
            names = names or [e[0] for e in ops[a:b]]
    return seconds, names


def _assignments_per_step(run):
    after, before = (r.get("inference-bolt", {})
                     for r in (run.registry_after, run.registry_before))
    held = after.get("expert_assignments_held")
    steps = (after.get("batch_size") or {}).get("count")
    if not held or not steps:
        return None
    held -= before.get("expert_assignments_held") or 0
    steps -= (before.get("batch_size") or {}).get("count", 0)
    return held / steps if held > 0 and steps > 0 else None


def read(run, prefix, pattern, kernel=None, **_):
    if not run.trace:
        return None
    seconds, names = step_times(_planes(run), prefix, pattern)
    if not seconds or not sum(seconds):
        return None
    mean_s = sum(seconds) / len(seconds)
    if kernel is None:
        return mean_s * 1e3
    ops = spec.plugin("ops", run.config["ops"])
    rows = ops.rows_per_step(names, run.config["published"])
    if rows is None:
        return None
    work = ops.kernels(run.config["published"], rows, run.bytes_per_value,
                       assignments=_assignments_per_step(run)
                       if kernel == "expert_matmul" else None)[kernel]
    peaks = run.peaks()
    least = max(work["flops"] / peaks["bf16_flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    run.notes.setdefault("kernels", {})[kernel] = {
        "ms": mean_s * 1e3, "least_ms": least * 1e3, "rows": rows}
    return 100.0 * least / mean_s
