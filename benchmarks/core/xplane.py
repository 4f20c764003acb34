"""From a profiler trace to numbers.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it: planes, their lines, events with a start and a duration in nanoseconds.
A TPU is a plane ``/device:TPU:<n>`` whose line ``XLA Modules`` has one event
per executed program (``jit_fwd(...)`` is the engine's; each bucket is a
program of its own) and whose line ``XLA Ops`` has one per operation, named
by its HLO text, shapes included. The reduction works on plain tuples so
that a test can hand it a trace made by hand:

    planes = [(plane_name, [(line_name, [(event_name, start_ns, dur_ns)])])]
"""

from __future__ import annotations

import bisect
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
KEEP = 10


def find_trace(trace_dir: str):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def load(path: str) -> list:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(e.name, float(e.start_ns),
                                       float(e.duration_ns))
                                      for e in line.events]))
        planes.append((plane.name, lines))
    return planes


def union(intervals: list) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _line(lines: list, name: str) -> list:
    for line_name, events in lines:
        if line_name == name:
            return events
    return []


def executions(mods: list, starts: list) -> list:
    """One device's ``XLA Modules`` events in order of start, each as
    ``(name, start, duration, first_op, end_op, whole)``: its operations
    are ``first_op:end_op`` of the ``XLA Ops`` events whose sorted starts are
    ``starts``, and ``whole`` says whether the trace holds all of it.

    The profiler cuts the execution that is running when the trace starts
    and the one running when it stops, and writes each as an event of what
    is left of it. Only the line's first and last events can be such, and
    no time tells: the span is theirs. Their operations do: a whole execution
    holds as many ``XLA Ops`` events as the program's executions between
    them. So the first and the last are whole only where they hold as many
    as another execution of their program that is neither; a program seen at
    an edge alone has nothing to show that it is whole."""
    spans = []
    for name, start, d in sorted(mods, key=lambda e: e[1]):
        spans.append((name, start, d, bisect.bisect_left(starts, start),
                      bisect.bisect_left(starts, start + d)))
    inner: dict = {}
    for name, _s, _d, a, b in spans[1:-1]:
        inner[name] = max(inner.get(name, 0), b - a)
    return [sp + (0 < i < len(spans) - 1
                  or 0 < inner.get(sp[0], 0) <= sp[4] - sp[3],)
            for i, sp in enumerate(spans)]


def reduce(planes: list) -> dict:
    """Busy time, program times, the costliest operations and the longest
    idle gaps of the device planes. The traced window spans every event of
    every plane (the harness traces the device's planes only, so that is
    from the first operation to the end of the last). Program times
    (``modules``) are of whole executions only (``executions``;
    ``cut_modules`` counts those left out); busy time, operations and gaps
    are of every event. Returns ``{}`` where no device plane has an event:
    nothing to read."""
    devices = [(n, ls) for n, ls in planes if n.startswith(DEVICE_PREFIX)]
    if not any(evs for _, ls in devices for _, evs in ls):
        return {}
    every = [e for _, ls in planes for _, evs in ls for e in evs]
    w0 = min(s for _, s, _d in every)
    w1 = max(s + d for _, s, d in every)

    busy_ns = []
    modules: dict = {}
    module_ops: dict = {}
    cut: dict = {}
    ops: dict = {}
    gaps = []
    for _, lines in devices:
        mods = _line(lines, MODULE_LINE)
        work = _line(lines, OP_LINE) or mods
        merged = union([[max(s, w0), min(s + d, w1)] for _, s, d in work
                        if s < w1 and s + d > w0])
        busy_ns.append(sum(e - s for s, e in merged))
        all_ops = sorted(_line(lines, OP_LINE), key=lambda e: e[1])
        for name, _s, d, a, b, whole in executions(
                mods, [e[1] for e in all_ops]):
            if not whole:
                cut[name] = cut.get(name, 0) + 1
                continue
            if name not in modules:  # the operations of its first execution
                module_ops[name] = [e[0] for e in all_ops[a:b]]
            modules.setdefault(name, []).append(d / 1e9)
        for name, _s, d in all_ops:
            ops[name] = ops.get(name, 0.0) + d / 1e9
        edges = [w0] + [x for pair in merged for x in pair] + [w1]
        ends = sorted((s + d, name) for name, s, d in mods)
        gaps += [(b - a, a, b, ends)
                 for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: -g[0])
    idle_gaps = []
    for length, a, b, ends in gaps[:KEEP]:
        # A gap is named after the program that ran before it: what the
        # host did meanwhile is not in a trace of the device's planes.
        at = bisect.bisect_right(ends, (a + 1.0, "\uffff"))
        before = ends[at - 1][1] if at else "window start"
        idle_gaps.append([f"after {before}"[:120], length / 1e9])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:KEEP]
    return {
        "lines": {f"{n}|{ln}": len(evs) for n, ls in planes for ln, evs in ls
                  if evs},
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "devices": len(devices),
        "modules": modules,
        "cut_modules": cut,
        "module_ops": module_ops,
        "device_ops": [[name[:120], secs] for name, secs in top_ops],
        "idle_gaps": idle_gaps,
    }


def module_times(reduced: dict, prefix: str) -> list:
    """Durations in seconds of every whole execution of the programs whose
    name starts with ``prefix``."""
    return [d for name, ds in reduced.get("modules", {}).items()
            if name.startswith(prefix) for d in ds]
