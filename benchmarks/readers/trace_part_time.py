"""Device milliseconds a step under one of the parts' names, or the share of
a step under any of them.

The models wrap their parts in ``jax.named_scope`` (names:
``storm_tpu/ops/parts.py``); the name rides an operation's ``op_name`` into
the trace (``core/xplane_meta.py`` reads it), and an operation's part is the
innermost name of its path that is of the vocabulary. The ``XLA Ops`` line
nests: a ``%while`` event spans its iterations beside the events of the
operations inside it. So only top-level events are added (one that lies
wholly inside another is its child), each with its whole duration, and the parts of
an execution sum to its busy time with no loop counted twice. A top-level
event that carries no name takes its first named child's (a ``%while`` has no
``op_name`` of its own on a TPU; its body's operations have the loop's).

``part``: the mean, over the whole executions of the programs called
``prefix...``, of the milliseconds under that part (0.0 where the program
has the names and nothing under this one). ``share``: named over all, in
percent (0.0 where no operation carries a name). Beside the number, in
``run.notes["parts"]``, the milliseconds a step by part (``(none)``: under
no name), in ``run.notes["part_loops"]`` how much of each is its top-level
``%while`` events (what the shape-pattern metrics of ``trace_ops_time`` read:
the cross-check that both see the same events) and in
``run.notes["unnamed_ops"]`` the ten costliest operations under none. None
where there is no trace, no whole execution, or a program from before the
names (no ``ops/parts.py``).
"""

from benchmarks.core import xplane, xplane_meta

NONE = "(none)"
KEEP = 10


def top_level(ops: list, part_of) -> list:
    """``(name, part, duration_ns)`` of the events of ``ops`` (one
    execution's ``(name, start, duration)``) that lie inside no other."""
    out = []
    end = -1.0
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        part = part_of(name)
        if start + dur <= end:  # wholly inside the last top-level event
            if out[-1][1] is None and part is not None:
                out[-1][1] = part
            continue
        out.append([name, part, dur])
        end = start + dur
    return out


def by_part(planes: list, op_names: dict, prefix: str, part_of_op):
    """``(parts, unnamed, steps, loops)``: mean milliseconds a whole
    execution by part, the costliest operations under none as ``[name, ms a
    step]``, how many whole executions there were, and of each part the
    milliseconds in top-level ``%while`` events."""
    totals, unnamed, steps, loops = {}, {}, 0, {}
    for plane, lines in planes:
        names = op_names.get(plane, {})

        def part_of(event, names=names):
            op = names.get(event)
            return part_of_op(op) if op else None

        mods = xplane._line(lines, xplane.MODULE_LINE)
        ops = sorted(xplane._line(lines, xplane.OP_LINE), key=lambda e: e[1])
        for name, _s, _d, a, b, whole in xplane.executions(
                mods, [e[1] for e in ops]):
            if not whole or not name.startswith(prefix):
                continue
            steps += 1
            for event, part, dur in top_level(ops[a:b], part_of):
                totals[part or NONE] = totals.get(part or NONE, 0.0) + dur
                if part is None:
                    unnamed[event] = unnamed.get(event, 0.0) + dur
                if event.startswith("%while"):
                    loops[part or NONE] = loops.get(part or NONE, 0.0) + dur
    if not steps:
        return {}, [], 0, {}
    worst = sorted(unnamed.items(), key=lambda kv: -kv[1])[:KEEP]
    return ({p: ns / steps / 1e6 for p, ns in totals.items()},
            [[name[:160], ns / steps / 1e6] for name, ns in worst], steps,
            {p: ns / steps / 1e6 for p, ns in loops.items()})


def read(run, prefix, part=None, share=False, **_):
    if not run.trace:
        return None
    try:
        from storm_tpu.ops import parts as vocabulary
    except ImportError:
        return None
    found = getattr(run, "_parts", None)
    if found is None:
        found = run._parts = by_part(
            xplane_meta.device_planes(run), xplane_meta.meta(run)["op_names"],
            prefix, vocabulary.part_of)
        parts, unnamed, steps, loops = found
        if steps:
            run.notes["parts"] = parts
            run.notes["part_loops"] = loops
            run.notes["unnamed_ops"] = unnamed
    parts, _unnamed, steps, _loops = found
    if not steps:
        return None
    if share:
        total = sum(parts.values())
        return 100.0 * (total - parts.get(NONE, 0.0)) / total if total else None
    return parts.get(part, 0.0)
