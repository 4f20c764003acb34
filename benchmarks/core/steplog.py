"""The program's step log (``storm_tpu/obs/profile.py ProfileStore.steps()``:
one row a device step with seven moments on ``time.time()``), alone and
beside a device trace.

Alone it says how far apart two steps' results were, and to which interval of
a step the time of a stall went (``longest_gap``, the program's own reading
of the window's rows): no trace needed, so every run has it. Beside a trace it puts host and device on one clock: the
``XLA Modules`` events of the engine's programs are the steps' executions,
in the order the steps were launched (one engine launches in order onto one
stream), and for every execution

    t_launched <= offset + device start      offset + device end <= t_ready

must hold. The device's clock is not the host's (its zero lies a
millisecond or two after the ``profile_start_time`` the profiler stamps), so
``match`` looks for the alignment of the two sequences and the one constant
``offset`` under which all of these hold, and ``idle_classes`` then says for
every moment the device idled what the host was at.

Every function takes plain rows and ``core/xplane.py``'s tuples, so a test
hands it a log and a trace made by hand.
"""

from __future__ import annotations

from benchmarks.core import xplane

IDLE_CLASSES = ("no rows", "rows waiting for the cut", "cut->launched",
                "launched->device start")
KEEP = 10


def rows(run):
    """The step log at the run's end, oldest first, once a run. None where
    the program keeps none (a commit before the log: nothing to read)."""
    if not hasattr(run, "_step_rows"):
        try:
            from storm_tpu.obs.profile import profile_store

            run._step_rows = profile_store().steps()
        except (ImportError, AttributeError):
            run._step_rows = None
    return run._step_rows


def busiest_engine(log: list, key: str = "t_ready") -> list:
    """The rows of the engine with the most rows that have ``key``, sorted
    by it."""
    per: dict = {}
    for r in log:
        if r.get(key) is not None:
            per.setdefault(r.get("engine"), []).append(r)
    if not per:
        return []
    return sorted(max(per.values(), key=len), key=lambda r: r[key])


def longest_gap(log: list, t0: float, t1: float):
    """Over the steps of the busiest engine whose ``t_ready`` lies in
    ``[t0, t1]``: ``(gap seconds, note)`` of the two consecutive ones
    farthest apart; the note is the program's own reading of those rows
    (``obs/profile.py longest_gap``: both rows, and the interval of the
    later one that exceeds its median over them by most). None under two
    such steps."""
    from storm_tpu.obs.profile import longest_gap as read

    steps = [r for r in busiest_engine(log) if t0 <= r["t_ready"] <= t1]
    note = read(steps)
    if note is None:
        return None
    return note["gap_ms"] / 1e3, dict(note, steps=len(steps))


def device_executions(planes: list, prefix: str) -> list:
    """``(name, start_s, end_s, whole)`` of the first device plane's
    executions of the programs called ``prefix...``, in order of start, on
    the device's clock."""
    for _, lines in planes:
        mods = xplane._line(lines, xplane.MODULE_LINE)
        if not mods:
            continue
        starts = sorted(e[1] for e in xplane._line(lines, xplane.OP_LINE))
        return [(name, s / 1e9, (s + d) / 1e9, whole)
                for name, s, d, _a, _b, whole in xplane.executions(mods,
                                                                   starts)
                if name.startswith(prefix)]
    return []


def match(execs: list, log: list, hint_s=None):
    """Align ``execs`` (``device_executions``) with the busiest engine's
    steps in order of launch. Returns ``{"pairs": [(execution, row)],
    "offset_s", "room_s", "hint_off_s", "violation_s"}`` or None where there
    is no execution or no launched step.

    A shift pairs execution ``i`` with step ``k + i``. It is admissible
    where every program goes with one padded bucket and every bucket with
    one program; under it the offset may lie between ``lo`` (the latest
    ``t_launched - device start``) and ``hi`` (the earliest ``t_ready -
    device end`` over steps whose readiness the fetch thread saw; a cut
    execution's missing edge is left out). Of the admissible shifts the
    one whose ``hi`` lies nearest the hint is taken (the profiler's start
    stamp: in a steady backlog every shift by one step fits as well), and
    without a hint the one that violates least, then the narrowest. The
    offset is ``hi`` (a result is seen ready within a tenth of a
    millisecond, while an input's transfer lies between a launch and its
    start): ``room_s = hi - lo`` is then the least ``device start -
    t_launched``."""
    steps = busiest_engine(log, "t_launched")
    if not execs or not steps:
        return None
    n, m = len(execs), len(steps)
    inf = float("inf")
    best = None
    for k in range(1 - n, m):
        lo, hi, names, buckets, ok, paired = -inf, inf, {}, {}, True, 0
        for i, (name, start, end, whole) in enumerate(execs):
            j = k + i
            if not 0 <= j < m:
                if whole:
                    ok = False  # every whole execution has its step
                    break
                continue
            row = steps[j]
            if names.setdefault(name, row["padded"]) != row["padded"] or \
                    buckets.setdefault(row["padded"], name) != name:
                ok = False
                break
            paired += 1
            if whole or i > 0:
                lo = max(lo, row["t_launched"] - start)
            if (whole or i < n - 1) and row.get("seen") \
                    and row.get("t_ready") is not None:
                hi = min(hi, row["t_ready"] - end)
        if not ok or not paired or lo == -inf:
            continue
        top = hi if hi < inf else lo
        violation = max(0.0, lo - top)
        if hint_s is not None:
            rank = (violation + abs(top - hint_s), violation)
        else:
            rank = (violation, top - lo)
        if best is None or rank < best[0]:
            best = (rank, k, lo, top, violation)
    if best is None:
        return None
    _, k, lo, top, violation = best
    pairs = [(e, steps[k + i]) for i, e in enumerate(execs)
             if 0 <= k + i < m]
    return {"pairs": pairs, "offset_s": top, "room_s": top - lo,
            "hint_off_s": None if hint_s is None else top - hint_s,
            "violation_s": violation}


def idle_classes(planes: list, log: list, found: dict):
    """``(traced span seconds, {class: idle seconds}, longest gaps)`` of the
    first device plane: every stretch in which no ``XLA Ops`` event ran,
    cut where a step of the log passes a moment, each piece by what the
    host was at: ``launched->device start`` where a launched step had not
    begun on the device, else ``cut->launched`` where a batch was cut, else
    ``rows waiting for the cut`` where a row was in the queue, else ``no
    rows``. A step's device start is its execution's under ``found``
    (``match``); a step launched after the last execution paired begins
    after the trace. The gaps are ``[seconds, class of its longest piece,
    seconds from the span's start]``, longest first."""
    offset = found["offset_s"]
    events = [e for _, lines in planes[:1] for _, evs in lines for e in evs]
    work = [e for _, lines in planes[:1]
            for e in (xplane._line(lines, xplane.OP_LINE)
                      or xplane._line(lines, xplane.MODULE_LINE))]
    if not events:
        return 0.0, dict.fromkeys(IDLE_CLASSES, 0.0), []
    w0 = min(s for _, s, _d in events) / 1e9 + offset
    w1 = max(s + d for _, s, d in events) / 1e9 + offset
    busy = xplane.union([[s / 1e9 + offset, (s + d) / 1e9 + offset]
                         for _, s, d in work])
    edges = [w0] + [x for pair in busy for x in pair] + [w1]
    begun = {id(row): start + offset for (_, start, _e, _w), row
             in found["pairs"]}
    last = max((r["t_launched"] for _, r in found["pairs"]), default=0.0)
    pending = []  # (first_enq or cut or launch, cut, launched, device start)
    for r in log:
        if r.get("t_launched") is None or r.get("engine") != \
                found["pairs"][0][1].get("engine"):
            continue
        start = begun.get(id(r))
        if start is None:
            if r["t_launched"] <= last:
                continue  # before the trace, or not the engine's stream
            start = float("inf")
        cut = r["t_cut"] if r.get("t_cut") is not None else r["t_launched"]
        enq = r["t_first_enq"] if r.get("t_first_enq") is not None else cut
        pending.append((enq, cut, r["t_launched"], start))

    def state(t: float, near: list) -> int:
        worst = 0
        for enq, cut, launched, start in near:
            if enq <= t < start:
                worst = max(worst, 3 if t >= launched else
                            2 if t >= cut else 1)
        return worst

    total = dict.fromkeys(IDLE_CLASSES, 0.0)
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        near = [p for p in pending if p[0] < b and p[3] > a]
        cuts = sorted({a, b} | {t for p in near for t in p if a < t < b})
        pieces = {}
        for x, y in zip(cuts, cuts[1:]):
            name = IDLE_CLASSES[state((x + y) / 2, near)]
            pieces[name] = pieces.get(name, 0.0) + (y - x)
        for name, seconds in pieces.items():
            total[name] += seconds
        gaps.append([b - a, max(pieces, key=pieces.get), a - w0])
    gaps.sort(key=lambda g: -g[0])
    return w1 - w0, total, gaps[:KEEP]


def on_trace_clock(run, prefix: str):
    """``match`` of the run's trace and step log, once a run, with what it
    found left in ``run.notes`` (``clock_offset_s`` and how much room the
    fit had). None where either is missing."""
    if hasattr(run, "_steps_on_trace"):
        return run._steps_on_trace
    from benchmarks.core import xplane_meta

    found = None
    log = rows(run)
    if run.trace and log:
        execs = device_executions(xplane_meta.device_planes(run), prefix)
        found = match(execs, log, xplane_meta.meta(run).get("start_s"))
    if found:
        whole = [(e, r) for e, r in found["pairs"] if e[3]]
        run.notes["clock_offset_s"] = found["offset_s"]
        run.notes["clock"] = {
            "executions_whole_matched": len(whole),
            "least_device_start_minus_launched_ms": found["room_s"] * 1e3,
            "least_ready_minus_device_end_ms": min(
                ((r["t_ready"] - e[2] - found["offset_s"]) * 1e3
                 for e, r in whole
                 if r.get("seen") and r.get("t_ready") is not None),
                default=None),
            "offset_minus_profile_start_ms":
                None if found["hint_off_s"] is None
                else found["hint_off_s"] * 1e3,
            "violation_ms": found["violation_s"] * 1e3}
    run._steps_on_trace = found
    return found
