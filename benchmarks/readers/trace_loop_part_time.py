"""Device milliseconds a step under one of the parts' names, read **inside
the loops** of a step that is one: ``trace_part_time`` for a program whose
layers run in a ``%while`` (a looped model's passes, a scan over blocks).

``trace_part_time`` adds top-level events only, and gives one that carries no
name its first named child's part: a step that is one top-level ``%while``
then reads as one part. Here the events of an execution are laid out as the
tree they are (an event that lies wholly inside another is its child, to any
depth), and an event is booked by what lies under it:

- no event under it carries a part's name, or all that do carry the same:
  its whole duration under its own part, else that one, else ``(none)``
  (a row loop of the attention kernel is ``mix.attention`` whole, its
  counter's increments with it: what ``trace_part_time`` reads);
- events of more than one part lie under it: it is read as its children,
  each by this rule, and what is left of its own duration (the loop's
  condition, its copies, the gaps between its children) is booked under
  ``(loop)``.

An event is clipped to what lies past the end of the last event counted
before it, so two operations that overlap are counted once where they do and
the parts of an execution, ``(loop)`` and ``(none)`` among them, sum to the
time some operation of it ran (ROADMAP Design 10 (o): ``trace_part_time``
counts such an event whole; this reader has no history to stay comparable
with). On a program with no such loop and no overlap it reads what
``trace_part_time`` reads.

``part``: the mean, over the whole executions of the programs called
``prefix...``, of the milliseconds under that part (0.0 where the program
has the names and nothing under this one). With ``kernel`` besides: the
least time of ``kernels()[kernel]`` of the configuration's ``ops`` file by
``peaks.json`` over those milliseconds, in percent (``trace_part_share``'s
number from this reader's time). Beside the number, in
``run.notes["loop_parts"]``: the milliseconds a step by part, ``busy_ms``
(the union of the execution's operations: what the parts sum to),
``named_share`` (what lies under a part's name over all, in percent),
``descended`` (the events read as their children: name and milliseconds a
step) and the whole executions counted. None where there is no
trace, no whole execution, or a program from before the names.
"""

from benchmarks.core import spec, xplane, xplane_meta

NONE = "(none)"
LOOP = "(loop)"


def forest(ops: list) -> list:
    """The events of ``ops`` (one execution's ``(name, start, duration)``)
    as trees ``[name, start, end, children]``: an event that lies wholly
    inside another is its child."""
    roots, stack = [], []
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        node = [name, start, start + dur, []]
        while stack and node[2] > stack[-1][2]:
            stack.pop()
        (stack[-1][3] if stack else roots).append(node)
        stack.append(node)
    return roots


def parts_under(node: list, part_of, memo: dict) -> frozenset:
    """The parts' names that the events under ``node`` carry (not its
    own)."""
    found = memo.get(id(node))
    if found is None:
        found = frozenset().union(*(
            parts_under(kid, part_of, memo) | {part_of(kid[0])}
            for kid in node[3])) - {None}
        memo[id(node)] = found
    return found


def book(node: list, part_of, cursor: float, totals: dict, descended: dict,
         memo: dict) -> float:
    """Add ``node``'s time past ``cursor`` to ``totals`` by part; returns the
    new cursor (the end of what is counted so far)."""
    name, start, end, kids = node
    start = max(start, cursor)
    if end <= start:
        return cursor
    below = parts_under(node, part_of, memo)
    if len(below) <= 1:
        part = part_of(name) or next(iter(below), None) or NONE
        totals[part] = totals.get(part, 0.0) + end - start
        return end
    at, before = start, sum(totals.values())
    for kid in kids:
        at = book(kid, part_of, at, totals, descended, memo)
    inside = sum(totals.values()) - before
    totals[LOOP] = totals.get(LOOP, 0.0) + (end - start) - inside
    descended[name] = descended.get(name, 0.0) + end - start
    return end


def by_part(planes: list, op_names: dict, prefix: str, part_of_op):
    """``(parts, busy, descended, steps)``: mean milliseconds a whole
    execution by part, the mean union of its operations' intervals, the
    events read as their children as ``[name, ms a step]``, and how many
    whole executions there were."""
    totals, descended, busy, steps = {}, {}, 0.0, 0
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
            cursor, memo = -1.0, {}
            for root in forest(ops[a:b]):
                cursor = book(root, part_of, cursor, totals, descended, memo)
            busy += sum(e - s for s, e in xplane.union(
                [[s, s + d] for _, s, d in ops[a:b]]))
    if not steps:
        return {}, 0.0, [], 0
    return ({p: ns / steps / 1e6 for p, ns in totals.items()},
            busy / steps / 1e6,
            [[name[:160], ns / steps / 1e6] for name, ns in sorted(
                descended.items(), key=lambda kv: -kv[1])], steps)


def read(run, prefix, part, kernel=None, **_):
    if not run.trace:
        return None
    try:
        from storm_tpu.ops import parts as vocabulary
    except ImportError:
        return None
    found = getattr(run, "_loop_parts", None)
    if found is None:
        found = run._loop_parts = by_part(
            xplane_meta.device_planes(run), xplane_meta.meta(run)["op_names"],
            prefix, vocabulary.part_of)
        parts, busy, descended, steps = found
        if steps:
            total = sum(parts.values())
            run.notes["loop_parts"] = {
                "parts": parts, "busy_ms": busy, "descended": descended,
                "steps": steps, "named_share": 100.0 * (
                    total - parts.get(NONE, 0.0) - parts.get(LOOP, 0.0))
                / total if total else 0.0}
    parts, _busy, _descended, steps = found
    if not steps:
        return None
    ms = parts.get(part, 0.0)
    if kernel is None:
        return ms
    if not ms:
        return None
    ops = spec.plugin("ops", run.config["ops"])
    rows = ops.rows_per_step(
        [op for module, ns in run.trace.get("module_ops", {}).items()
         if module.startswith(prefix) for op in ns], run.config["published"])
    if rows is None:
        return None
    work = ops.kernels(run.config["published"], rows,
                       run.bytes_per_value)[kernel]
    peaks = run.peaks()
    least_ms = 1e3 * max(work["flops"] / peaks["bf16_flops_per_s"],
                         work["bytes"] / peaks["hbm_bytes_per_s"])
    run.notes.setdefault("kernels", {})[kernel] = {
        "ms": ms, "least_ms": least_ms, "rows": rows}
    return 100.0 * least_ms / ms
