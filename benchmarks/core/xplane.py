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


def reduce(planes: list) -> dict:
    """Busy time, program times, the costliest operations and the longest
    idle gaps of the device planes. The traced window spans every event of
    every plane (the harness traces the device's planes only, so that is
    from the first operation to the end of the last). Returns ``{}`` where
    no device plane has an event: nothing to read."""
    devices = [(n, ls) for n, ls in planes if n.startswith(DEVICE_PREFIX)]
    if not any(evs for _, ls in devices for _, evs in ls):
        return {}
    every = [e for _, ls in planes for _, evs in ls for e in evs]
    w0 = min(s for _, s, _d in every)
    w1 = max(s + d for _, s, d in every)

    busy_ns = []
    modules: dict = {}
    module_ops: dict = {}
    ops: dict = {}
    gaps = []
    for _, lines in devices:
        mods = _line(lines, MODULE_LINE)
        work = _line(lines, OP_LINE) or mods
        merged = union([[max(s, w0), min(s + d, w1)] for _, s, d in work
                        if s < w1 and s + d > w0])
        busy_ns.append(sum(e - s for s, e in merged))
        all_ops = sorted(_line(lines, OP_LINE), key=lambda e: e[1])
        starts = [e[1] for e in all_ops]
        for name, start, d in mods:
            if name not in modules:  # the operations of its first execution
                a = bisect.bisect_left(starts, start)
                b = bisect.bisect_right(starts, start + d)
                module_ops[name] = [e[0] for e in all_ops[a:b]]
            modules.setdefault(name, []).append(d / 1e9)
        for name, _s, d in _line(lines, OP_LINE):
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
        "module_ops": module_ops,
        "device_ops": [[name[:120], secs] for name, secs in top_ops],
        "idle_gaps": idle_gaps,
    }


def module_times(reduced: dict, prefix: str) -> list:
    """Durations in seconds of every execution of the programs whose name
    starts with ``prefix``."""
    return [d for name, ds in reduced.get("modules", {}).items()
            if name.startswith(prefix) for d in ds]
