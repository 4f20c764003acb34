"""The program's record log (``storm_tpu/obs/profile.py
ProfileStore.records()``: one row a root tuple, ten moments of its way through
the host on ``time.time()``, and the key of the step that took it), joined
with the step log (``core/steplog.py``) and set beside a device trace.

Joined, a record's moments tile its latency from the broker's append to the
broker's produce (``PATH``: each consecutive pair is one interval and every
millisecond lies in exactly one), so what a stage costs a record is read from
inside, record by record, and the intervals add up. Beside a trace, on the
offset ``core/steplog.py on_trace_clock`` fits, the log says of the device's
idle time whether a record was already on the host (``host_held``).

Every function takes plain rows, so a test hands it logs made by hand.
"""

from __future__ import annotations

from benchmarks.core import pairing, steplog

# a record's way: its own moments, and its step's between t_enq and t_egress
# (the yardstick's own copy: a program that renames one reads nothing here)
PATH = ("t_append", "t_polled", "t_emitted", "t_exec", "t_parsed", "t_enq",
        "t_cut", "t_staged", "t_launched", "t_ready", "t_fetched",
        "t_resolved", "t_egress", "t_encoded", "t_sink", "t_produced")
OF_STEP = PATH[6:12]
INTERVALS = tuple((f"{a[2:]}->{b[2:]}", a, b) for a, b in zip(PATH, PATH[1:]))
WITHIN_S = 1e-3  # a row's t_produced and its output's broker timestamp


def rows(run):
    """The record log at the run's end, oldest first, once a run. None where
    the program keeps none (a commit before the log: nothing to read)."""
    if not hasattr(run, "_record_rows"):
        try:
            from storm_tpu.obs.profile import profile_store

            run._record_rows = profile_store().records()
        except (ImportError, AttributeError):
            run._record_rows = None
    return run._record_rows


def join(records: list, steps: list) -> list:
    """Each record's row with its step's moments beside its own (None where
    the step log does not hold the step, or the record met none)."""
    by_key = {(s.get("engine"), s.get("step")): s for s in steps}
    out = []
    for r in records:
        step = by_key.get((r.get("engine"), r.get("step"))) \
            if r.get("step") is not None else None
        out.append(dict(r, **{m: (step or {}).get(m) for m in OF_STEP}))
    return out


def paths(run):
    """``join`` of the run's two logs, once a run; None without a record
    log."""
    if not hasattr(run, "_record_paths"):
        log = rows(run)
        run._record_paths = None if log is None else \
            join(log, steplog.rows(run) or [])
    return run._record_paths


def in_window(run) -> list:
    """The paths that count: ``t_produced`` between the window's first and
    last delivery (in a traced run that is the part before the profiler
    started), as ``readers/step_gap_max.py`` counts steps. A row's
    ``t_produced`` is its output's broker stamp to ``WITHIN_S``, and later:
    the last delivery's own row counts."""
    found = paths(run)
    if not found or not len(run.delivery_times):
        return []
    first, last = float(run.delivery_times[0]), float(run.delivery_times[-1])
    return [p for p in found if p.get("t_produced") is not None
            and first <= p["t_produced"] <= last + WITHIN_S]


def spans_ms(found: list, a: str, b: str) -> list:
    return [(p[b] - p[a]) * 1e3 for p in found
            if p.get(a) is not None and p.get(b) is not None]


def whole(p: dict) -> bool:
    """Every moment of its way set: its own, and its step's in the step
    log."""
    return all(p.get(m) is not None for m in PATH)


def logged_share(deliveries, found: list, within_s: float = WITHIN_S):
    """Of ``deliveries`` (sorted output timestamps), the share in percent
    that has a whole path of its own whose ``t_produced`` lies within
    ``within_s``: one row answers one delivery. None without deliveries."""
    produced = sorted(p["t_produced"] for p in found if whole(p))
    hit = j = 0
    for d in deliveries:
        while j < len(produced) and produced[j] < d - within_s:
            j += 1
        if j < len(produced) and produced[j] <= d + within_s:
            hit += 1
            j += 1
    return 100.0 * hit / len(deliveries) if len(deliveries) else None


def note(run) -> None:
    """``run.notes["record"]``, once a run: every interval's median, 90th
    percentile and mean over the window's records, how many rows have no
    step, the record whose latency is the window's median with its intervals
    (they add up to its latency), and the log's median latency beside the
    outside's."""
    if "record" in run.notes or paths(run) is None:
        return
    found = in_window(run)
    out = run.notes["record"] = {
        "rows": len(paths(run)), "in_window": len(found),
        "not_delivered": sum(p.get("ended") != "delivered"
                             for p in paths(run)),
        "without_step": sum(p.get("t_cut") is None for p in found),
        "intervals": {}}
    for name, a, b in INTERVALS:
        spans = spans_ms(found, a, b)
        if spans:
            out["intervals"][name] = {
                "p50": pairing.quantile(spans, 0.5),
                "p90": pairing.quantile(spans, 0.9),
                "mean": sum(spans) / len(spans), "count": len(spans)}
    # medians of stages do not add up to the median of their sum; means do
    for stat in ("p50", "mean"):
        out[f"sum_of_{stat}s_ms"] = sum(v[stat] for v in
                                        out["intervals"].values())
    timed = sorted((p for p in found if p.get("t_append") is not None),
                   key=lambda p: p["t_produced"] - p["t_append"])
    if timed:
        mid = timed[len(timed) // 2]
        whole_way = spans_ms(timed, "t_append", "t_produced")
        out["log_append_to_produced_p50_ms"] = pairing.quantile(whole_way, 0.5)
        out["log_append_to_produced_mean_ms"] = sum(whole_way) / len(whole_way)
        out["median_record"] = {
            "append_to_produced_ms":
                (mid["t_produced"] - mid["t_append"]) * 1e3,
            "intervals_ms": {name: (mid[b] - mid[a]) * 1e3
                             for name, a, b in INTERVALS
                             if mid.get(a) is not None
                             and mid.get(b) is not None},
            "row": mid}
    if run.latencies_ms is not None and len(run.latencies_ms):
        out["outside_latency_less_late_p50_ms"] = pairing.quantile(
            run.latencies_ms - run.late_ms, 0.5)


def steps_from_append(log: list, found: dict, records: list):
    """``(log, found)`` as ``core/steplog.py idle_classes`` takes them, with
    every step's ``t_first_enq`` moved back to the earliest broker append of
    the records it took: under it a moment is ``no rows`` only where no
    record of a coming step had been appended either. Copies: the run's own
    rows stay as they are."""
    first: dict = {}
    for r in records:
        if r.get("step") is not None and (r.get("t_append") or 0) > 0:
            key = (r.get("engine"), r["step"])
            first[key] = min(first.get(key, r["t_append"]), r["t_append"])
    copies = {}
    for s in log:
        copy = copies[id(s)] = dict(s)
        appended = first.get((s.get("engine"), s.get("step")))
        enq = next((s[m] for m in ("t_first_enq", "t_cut", "t_launched")
                    if s.get(m) is not None), None)
        if appended is not None and enq is not None:
            copy["t_first_enq"] = min(appended, enq)
    return ([copies[id(s)] for s in log],
            dict(found, pairs=[(e, copies[id(r)])
                               for e, r in found["pairs"]]))


def host_held(planes: list, log: list, found: dict, records: list):
    """``(traced span seconds, {"no_rows", "host_held", "nothing_appended"}
    seconds)``: the idle time the step log classes ``no rows``
    (``idle_classes``), and how much of it some record spent between its
    broker append and its entry to the queue."""
    span, classes, _ = steplog.idle_classes(planes, log, found)
    _, under, _ = steplog.idle_classes(
        planes, *steps_from_append(log, found, records))
    return span, {"no_rows": classes["no rows"],
                  "host_held": classes["no rows"] - under["no rows"],
                  "nothing_appended": under["no rows"]}
