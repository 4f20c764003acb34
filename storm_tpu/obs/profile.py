"""Online cost profiler: per-(engine, bucket) stage-cost curves.

Every completed device batch already carries per-phase wall-clock
attribution (``InflightBatch.timings`` — h2d/compute/d2h, filled by the
split-phase pipeline), and every cold bucket shape fires the engine's
``on_compile`` hook. Those numbers were only ever *observed* into flat
per-component histograms, which average away the one axis a planner
needs: batch size. The :class:`ProfileStore` keys the same stream by
(engine, padded bucket), turning the runtime's own traffic into the
per-stage latency/throughput curves the planner (storm_tpu/plan/) consumes —
InferLine's offline profiler, made continuous.

Wiring: the engine layer exposes ``set_profile_sink`` (a module-level
hook, same shape as ``on_compile`` but process-wide); ``ensure_installed``
points it at the process singleton. Recording is one lock + a couple of
dict/histogram updates per BATCH (not per record), on the engine's fetch
thread — what the step and record logs cost on the chip is PERF.md §6,
PRs 41 and 54.

Beside the curves the store keeps three logs, on one clock (``time.time()``)
and under one switch (:func:`set_enabled`): the step log (a row a device
step), the record log (a row a root tuple) and the set-up log (a row a span
of a start: :func:`setup_span` where the program's own work happens, and
every trace, lowering and backend compile JAX reports, with its cache
look-up, under the span that caused it). The set-up log runs no line per
step or per record.

The snapshot round-trips: ``storm-tpu profile <topology> --json`` writes
it as versioned JSON, and a later run loads
that file back as the regression sentinel's baseline
(:meth:`ProfileStore.load_baseline` + :meth:`ProfileStore.regressions`).
"""

from __future__ import annotations

import contextvars
import itertools
import threading
import time
from collections import deque
from operator import attrgetter
from typing import Dict, List, Optional

from storm_tpu.runtime.metrics import Histogram

# Stage keys tracked per (engine, bucket). device_ms is the synthetic
# whole-batch stage (sum of the split phases) so throughput math and the
# sentinel have one total-cost row even when a backend reports only some
# phases.
STAGE_KEYS = ("h2d_ms", "compute_ms", "d2h_ms", "device_ms")

# Reservoir per (engine, bucket, stage): small — a profile tracks the
# recent cost distribution, not history (the artifact snapshots it).
_RING = 512

# The step log: the last this many steps of the process, one row a step,
# whatever their engine.
STEP_LOG = 4096

# The moments of a step, in the order they pass (docs/OPERATIONS.md, "Reading
# the step log"). All ``time.time()`` seconds, the clock the broker stamps
# records with and a device trace is brought onto; one taken on
# ``perf_counter`` is converted by an offset read beside it, on the same
# thread for the same row. None where a path does not pass the moment.
STEP_MOMENTS = ("t_first_enq", "t_cut", "t_staged", "t_launched", "t_ready",
                "t_fetched", "t_resolved")
# the intervals between them, as ``longest_gap`` names them: ``cut->staged``
STEP_INTERVALS = tuple((f"{a[2:]}->{b[2:]}", a, b)
                       for a, b in zip(STEP_MOMENTS, STEP_MOMENTS[1:]))


# The record log: the last this many records of the process, one row a root
# tuple (a broker record; a chunk of them where the spout chunks).
RECORD_LOG = 16384

# The moments of a record on the host, in the order they pass
# (docs/OPERATIONS.md, "Reading the record log"), on the step log's clock.
# Between ``t_enq`` and ``t_egress`` lie the moments of the step that took
# it, which a row names by ``engine`` and ``step`` and does not copy.
RECORD_MOMENTS = ("t_append", "t_polled", "t_emitted", "t_exec", "t_parsed",
                  "t_enq", "t_egress", "t_encoded", "t_sink", "t_produced")
# a row of the log, in this order
RECORD_FIELDS = ("records",) + RECORD_MOMENTS[:6] + ("engine", "step") \
    + RECORD_MOMENTS[6:] + ("ended",)
# A record's whole way, broker append to broker produce: its own moments with
# its step's between them, so that consecutive ones tile its latency.
RECORD_PATH = RECORD_MOMENTS[:6] + STEP_MOMENTS[1:] + RECORD_MOMENTS[6:]
# the intervals, named as the step's are: ``append->polled``; ``enq->cut``
# and ``resolved->egress`` cross from one log to the other through the key
RECORD_INTERVALS = tuple((f"{a[2:]}->{b[2:]}", a, b)
                         for a, b in zip(RECORD_PATH, RECORD_PATH[1:]))


# The set-up log: the last this many spans of the process's starts, one row a
# span, written where the span ends (so a child stands before its parent).
SETUP_LOG = 1024
# a row of the log, in this order (docs/OPERATIONS.md, "Reading the set-up
# log"): ``span`` counts the process's spans, ``parent`` is the span that
# was open in the calling context when this one began (None: a root),
# ``t_start`` and ``t_end`` are on the step log's clock, ``attrs`` is a small
# flat dict
SETUP_FIELDS = ("span", "parent", "name", "t_start", "t_end", "thread",
                "attrs")
# JAX's own time spans (its dispatch module's), by the name of their row
_JAX_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_JAX_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_JAX_COMPILE = "/jax/core/compile/backend_compile_duration"
JAX_SPANS = {_JAX_TRACE: "jax.trace", _JAX_LOWER: "jax.lower",
             _JAX_COMPILE: "jax.backend_compile"}
# and its persistent cache's events (its compiler's and its compilation
# cache's), stamped onto the backend compile that closes next on their thread
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITTEN = "/jax/compilation_cache/cache_misses"  # where an entry is written
_CACHE_SECONDS = {"/jax/compilation_cache/cache_retrieval_time_sec":
                  "retrieval_s",
                  "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}


class RecordRow:
    """What one root tuple carries through the topology (``Tuple.record``,
    following anchoring as ``root_ts`` does): each site on its way stamps
    its moment here, and :func:`end_record` writes the row where the record
    ends. ``left`` counts what is still on its way: records not yet emitted
    by the operator and outputs not yet delivered by the sink."""

    __slots__ = RECORD_FIELDS + ("left",)
    row = property(attrgetter(*RECORD_FIELDS),
                   doc="its row of the log: a tuple in RECORD_FIELDS order")

    def __init__(self, t_append: float, t_polled: float,
                 records: int = 1) -> None:
        self.records = self.left = records
        self.t_append = t_append
        self.t_polled = t_polled
        self.t_emitted = self.t_exec = self.t_parsed = self.t_enq = None
        self.engine = self.step = None
        self.t_egress = self.t_encoded = self.t_sink = self.t_produced = None
        self.ended = None


def new_record_row(t_append: float, t_polled: Optional[float] = None,
                   records: int = 1) -> Optional[RecordRow]:
    """The holder a spout makes beside ``root_ts``: ``t_append`` is the
    first record's broker timestamp as the broker stamped it, ``t_polled``
    when the spout had it off the broker (now where it is not said: a
    replay). None while the profiler is off: every site then pays one check."""
    if not _ENABLED:
        return None
    return RecordRow(t_append,
                     time.time() if t_polled is None else t_polled, records)


def end_record(rec: RecordRow, how: str) -> None:
    """One record of ``rec`` ended ``dead_lettered`` at the operator, one of
    its outputs ``delivered`` at the sink, or its tuple ``failed`` (the
    collector's ``fail``: the whole tree replays): the row is written, once,
    when nothing of it is left on its way. A row that did not end delivered
    says so, whatever ended last."""
    if rec.left <= 0:
        return  # written
    rec.left = 0 if how == "failed" else rec.left - 1
    if rec.ended is None and (how != "delivered" or not rec.left):
        rec.ended = how
    if not rec.left:
        _STORE.log_record(rec.row)


def record_paths(records: List[dict], steps: List[dict]) -> List[dict]:
    """Each row of ``records`` (``ProfileStore.records()``) with the moments
    of its step beside its own (``RECORD_PATH``; None where ``steps`` no
    longer holds the row, or the record met no step)."""
    by_key = {(s["engine"], s["step"]): s for s in steps}
    out = []
    for r in records:
        s = by_key.get((r["engine"], r["step"])) or {}
        out.append(dict(r, **{m: s.get(m) for m in STEP_MOMENTS[1:]}))
    return out


def record_intervals(paths: List[dict]) -> Dict[str, dict]:
    """Over ``paths`` (:func:`record_paths`), each interval of
    ``RECORD_INTERVALS`` that some row passed: how many did, and the
    median and 90th percentile of its milliseconds."""
    out = {}
    for name, a, b in RECORD_INTERVALS:
        spans = sorted((r[b] - r[a]) * 1e3 for r in paths
                       if r.get(a) is not None and r.get(b) is not None)
        if spans:
            out[name] = {"count": len(spans),
                         "p50": spans[len(spans) // 2],
                         "p90": spans[min(len(spans) - 1,
                                          len(spans) * 9 // 10)]}
    return out


def new_step_row(step: int, engine: str, padded: int, rows: int,
                 queued: Optional[dict] = None) -> dict:
    """One row of the step log, built by ``InferenceEngine.dispatch``:
    ``step`` counts an engine's dispatches, ``queued`` is what the queue
    that cut the batch knows of it (``t_first_enq``, ``t_cut``, ``sources``;
    None for a direct ``predict``). ``seen``: whether the fetch thread
    watched the result become ready (``t_ready`` is then when it did, else
    when the thread came to it)."""
    row = {"step": step, "engine": engine, "padded": padded, "rows": rows,
           "sources": None, "seen": False}
    row.update(dict.fromkeys(STEP_MOMENTS))
    if queued:
        row.update(queued)
    return row


def longest_gap(rows: List[dict]) -> Optional[dict]:
    """Of ``rows`` (``ProfileStore.steps()``: any stretch of the log), the
    two consecutive steps of one engine whose results became ready farthest
    apart, and which interval of the later one exceeds its median over the
    rows by most: where the time of a stall went. None under two steps
    with ``t_ready``."""
    done: Dict[str, List[dict]] = {}
    for r in rows:
        if r.get("t_ready") is not None:
            done.setdefault(r["engine"], []).append(r)
    best = None
    for per in done.values():
        per.sort(key=lambda r: r["t_ready"])
        for a, b in zip(per, per[1:]):
            gap = b["t_ready"] - a["t_ready"]
            if best is None or gap > best[0]:
                best = (gap, a, b, per)
    if best is None:
        return None
    gap, a, b, per = best
    over = {}
    for name, t0, t1 in STEP_INTERVALS:
        spans = sorted(r[t1] - r[t0] for r in per
                       if r.get(t0) is not None and r.get(t1) is not None)
        if spans and b.get(t0) is not None and b.get(t1) is not None:
            over[name] = (b[t1] - b[t0]) - spans[len(spans) // 2]
    worst = max(over, key=over.get) if over else None
    return {"gap_ms": gap * 1e3, "before": dict(a), "after": dict(b),
            "interval": worst,
            "over_median_ms": over[worst] * 1e3 if worst else None}


class setup_span:
    """One span of a start: ``with setup_span("parameters", source="seed")
    as span`` (the names are in docs/OPERATIONS.md, "Reading the set-up
    log"). It stamps ``time.time()`` on the way in and on the way out,
    is the parent of what begins in its context while it is open (a
    ``contextvars`` variable, so it follows ``asyncio.to_thread`` and the
    tasks a coroutine makes), and writes its row of the set-up log as it
    ends. ``attrs`` may be added to until then; ``ms`` is its length, for a
    caller that reports it elsewhere. ``under`` names its parent where that
    is not the span open in the calling context: an engine's warm-up under
    the build that made the engine, whoever calls it. With the store off it
    is a pair of stamps: no number, no parent, no row."""

    __slots__ = ("span", "parent", "name", "t_start", "t_end", "attrs",
                 "_above", "_before")

    def __init__(self, name: str, under: "Optional[setup_span]" = None,
                 **attrs) -> None:
        self.name, self.attrs = name, attrs
        self.span = self.parent = self.t_end = None
        self._above = under

    def __enter__(self) -> "setup_span":
        self.t_start = time.time()
        if _ENABLED:
            above = self._above if self._above is not None else _open_span()
            self.span = next(_SPAN_IDS)
            self.parent = None if above is None else above.span
            self._above = above
            self._before = _CURRENT.get()
            _CURRENT.set(self)
        return self

    def __exit__(self, *exc) -> bool:
        self.t_end = time.time()
        if self.span is not None:
            _CURRENT.set(self._before)
            _STORE.log_setup((self.span, self.parent, self.name, self.t_start,
                              self.t_end, threading.current_thread().name,
                              self.attrs))
        return False

    @property
    def ms(self) -> float:
        return (self.t_end - self.t_start) * 1e3


_SPAN_IDS = itertools.count(1)
_CURRENT: "contextvars.ContextVar[Optional[setup_span]]" = \
    contextvars.ContextVar("storm_tpu_setup_span", default=None)


def _open_span() -> Optional[setup_span]:
    """The innermost span of the calling context that is still open. A
    context copied while a span was open (a task made in ``prepare``) keeps
    naming it after it has ended: what begins there later has no cause among
    the spans, and is a root."""
    span = _CURRENT.get()
    while span is not None and span.t_end is not None:
        span = span._above
    return span


def union_seconds(rows: List[dict]) -> float:
    """The seconds some row of ``rows`` covers: overlapping and nested rows
    count once."""
    total, end = 0.0, float("-inf")
    for a, b in sorted((r["t_start"], r["t_end"]) for r in rows):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def setup_under(rows: List[dict], root: Optional[int]) -> List[dict]:
    """The rows of ``rows`` that descend from the span ``root``, itself
    among them (all of them where ``root`` is None)."""
    if root is None:
        return list(rows)
    parent = {r["span"]: r["parent"] for r in rows}

    def below(span) -> bool:
        while span is not None and span != root:
            span = parent.get(span)
        return span == root

    return [r for r in rows if below(r["span"])]


def setup_summary(rows: List[dict], root: Optional[int] = None) -> dict:
    """What a start cost, by what it went to, over the rows under ``root``
    (``ProfileStore.setup()``; a ``topology.submit`` span, or None for the
    whole log): ``total_s`` (the root's seconds; the union of the rows
    without one), ``parameters_s`` (initialising or restoring them),
    ``serve_s`` (casting, arranging and placing them), ``programs_s`` (a
    cold bucket's stage, put, trace, lowering, cache look-up or compile, and
    launch, inside a warm-up), ``programs_loaded`` and ``programs_compiled``
    (those whose backend compiles were all cache hits, and those with one
    that was not), ``first_runs_s`` (a warm-up's bucket less its program:
    the first execution and its fetch), ``cold_in_traffic`` (programs that
    no warm-up met: a root ``program`` row, the cliff) and ``other_s``.
    Every figure is a union of intervals, so spans that overlap on two
    threads count once."""
    rows = setup_under(rows, root)
    by_span = {r["span"]: r for r in rows}

    def named(name):
        return [r for r in rows if r["name"] == name]

    def ancestors(r):
        seen = by_span.get(r["parent"])
        while seen is not None:
            yield seen
            seen = by_span.get(seen["parent"])

    head = by_span.get(root)
    warm = named("warmup.bucket")
    programs = [r for r in named("program")
                if any(a["name"] == "warmup.bucket" for a in ancestors(r))]
    all_hits: Dict[int, bool] = {}  # a program's backend compiles, by span
    for r in named("jax.backend_compile"):
        for a in ancestors(r):
            if a["name"] == "program":
                all_hits[a["span"]] = all_hits.get(a["span"], True) \
                    and r["attrs"].get("cache") == "hit"
    out = {
        "total_s": (head["t_end"] - head["t_start"]) if head
        else union_seconds(rows),
        "parameters_s": union_seconds(named("parameters")),
        "serve_s": union_seconds(named("parameters.serve")),
        "programs_s": union_seconds(programs),
        "programs_loaded": sum(all_hits.get(r["span"]) is True
                               for r in programs),
        "programs_compiled": sum(all_hits.get(r["span"]) is False
                                 for r in programs),
        "first_runs_s": union_seconds(warm) - union_seconds(programs),
        "cold_in_traffic": sum(r["parent"] is None
                               for r in named("program")),
    }
    out["other_s"] = out["total_s"] - union_seconds(
        named("parameters") + named("parameters.serve") + warm)
    return out


def setup_line(summary: dict) -> str:
    """:func:`setup_summary` in the one line ``submit`` logs."""
    return ("set-up {total_s:.1f} s: parameters {parameters_s:.1f}, serve "
            "{serve_s:.1f}, programs {programs_s:.1f} ({programs_loaded} "
            "loaded, {programs_compiled} compiled), first runs "
            "{first_runs_s:.1f}, other {other_s:.1f}").format(**summary)


def setup_tree(rows: List[dict]) -> List[tuple]:
    """``(depth, row)`` of every row in the order of a tree: a root, then
    what it caused, each level by ``t_start``. A row whose parent the log no
    longer holds stands as a root."""
    held = {r["span"] for r in rows}
    below: Dict[Optional[int], List[dict]] = {}
    for r in sorted(rows, key=lambda r: r["t_start"]):
        below.setdefault(r["parent"] if r["parent"] in held else None,
                         []).append(r)
    out, stack = [], [(0, r) for r in reversed(below.get(None, []))]
    while stack:
        depth, r = stack.pop()
        out.append((depth, r))
        stack.extend((depth + 1, c) for c in reversed(below.get(r["span"],
                                                                [])))
    return out


class _Bucket:
    __slots__ = ("stages", "batches", "rows")

    def __init__(self) -> None:
        self.stages: Dict[str, Histogram] = {
            k: Histogram(_RING) for k in STAGE_KEYS}
        self.batches = 0
        self.rows = 0


class ProfileStore:
    """Per-process cost profile: ``engines[key].buckets[padded]`` curves
    plus XLA compile cost per shape. Thread-safe (engine fetch threads
    write; the UI/bench/sentinel read)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # engine key -> {padded: _Bucket}
        self._buckets: Dict[str, Dict[int, _Bucket]] = {}
        # engine key -> {padded: {"count": n, "sum_ms": s, "last_ms": x}}
        self._compiles: Dict[str, Dict[int, Dict[str, float]]] = {}
        self._baseline: Optional[dict] = None
        self._steps: deque = deque(maxlen=STEP_LOG)
        self._records: deque = deque(maxlen=RECORD_LOG)
        self._setup: deque = deque(maxlen=SETUP_LOG)

    # ---- the write path (engine layer) ---------------------------------------

    def record_batch(self, key: str, padded: int, rows: int,
                     timings: Dict[str, float],
                     step: Optional[dict] = None) -> None:
        """One completed device batch: ``timings`` is the engine's
        per-phase dict (any subset of h2d/compute/d2h), ``step`` its row of
        the step log (kept as it is: the queue may still stamp it)."""
        if not timings:
            return
        with self._lock:
            if step is not None:
                self._steps.append(step)
            per = self._buckets.setdefault(key, {})
            b = per.get(int(padded))
            if b is None:
                b = per[int(padded)] = _Bucket()
            b.batches += 1
            b.rows += int(rows)
        total = 0.0
        for stage in ("h2d_ms", "compute_ms", "d2h_ms"):
            v = timings.get(stage)
            if v is None:
                continue
            total += float(v)
            b.stages[stage].observe(float(v))
        b.stages["device_ms"].observe(total)

    def log_record(self, row: tuple) -> None:
        """One ended record (:func:`end_record`): its row of the record log,
        in ``RECORD_FIELDS`` order."""
        with self._lock:
            self._records.append(row)

    def log_setup(self, row: tuple) -> None:
        """One ended span of a start (:class:`setup_span`, or one of JAX's
        own): its row of the set-up log, in ``SETUP_FIELDS`` order."""
        with self._lock:
            self._setup.append(row)

    def record_compile(self, key: str, padded: int, ms: float) -> None:
        """A cold bucket's first dispatch: ``ms`` is its ``program`` span's
        (``infer/engine.py``), the one timer of it."""
        with self._lock:
            per = self._compiles.setdefault(key, {})
            c = per.get(int(padded))
            if c is None:
                c = per[int(padded)] = {"count": 0, "sum_ms": 0.0,
                                        "last_ms": 0.0}
            c["count"] += 1
            c["sum_ms"] += float(ms)
            c["last_ms"] = float(ms)

    def reset(self) -> None:
        with self._lock:
            self._buckets.clear()
            self._compiles.clear()
            self._steps.clear()
            self._records.clear()
            self._setup.clear()

    # ---- the read path -------------------------------------------------------

    def steps(self) -> List[dict]:
        """The step log, oldest first: a copy of each of the last
        ``STEP_LOG`` rows."""
        with self._lock:
            return [dict(r) for r in self._steps]

    def records(self) -> List[dict]:
        """The record log, oldest first: the last ``RECORD_LOG`` rows, each
        a dict of ``RECORD_FIELDS``. ``(engine, step)`` is the key of the
        row of :meth:`steps` that took the record."""
        with self._lock:
            rows = list(self._records)
        return [dict(zip(RECORD_FIELDS, r)) for r in rows]

    def setup(self) -> List[dict]:
        """The set-up log, oldest first by where a span ended: the last
        ``SETUP_LOG`` rows, each a dict of ``SETUP_FIELDS`` (``attrs`` a
        copy)."""
        with self._lock:
            rows = list(self._setup)
        return [dict(zip(SETUP_FIELDS, r[:-1] + (dict(r[-1]),)))
                for r in rows]

    def snapshot(self) -> dict:
        """JSON-safe curves: per engine, per padded bucket, per stage
        {count, mean, p50, p95, max} plus rows/s throughput; compile cost
        per shape. Bucket keys are stringified ints (JSON round-trip).
        ``steps``: how many rows the step log holds, its last eight, and
        the longest gap between two steps with the interval it went to
        (:func:`longest_gap`). ``records``: how many rows the record log
        holds, every interval's median and 90th percentile over them
        (:func:`record_intervals`), and the delivered record that took
        longest from append to produce, with its step's moments. ``setup``:
        the set-up log's rows, per name of a span how many there are and the
        seconds they cover (:func:`union_seconds`: rows that overlap count
        once), and what the starts cost by what it went to
        (:func:`setup_summary` over the whole log)."""
        rows = self.steps()
        spans = self.setup()
        by_name: Dict[str, List[dict]] = {}
        for r in spans:
            by_name.setdefault(r["name"], []).append(r)
        paths = record_paths(self.records(), rows)
        whole = [r for r in paths if r["t_produced"] is not None]
        return {"engines": self._engines(),
                "steps": {"count": len(rows), "last": rows[-8:],
                          "longest_gap": longest_gap(rows)},
                "records": {"count": len(paths),
                            "intervals": record_intervals(paths),
                            "slowest": max(
                                whole, default=None, key=lambda r:
                                r["t_produced"] - r["t_append"])},
                "setup": {"count": len(spans), "rows": spans,
                          "by_name": {n: {"count": len(rs),
                                          "seconds": union_seconds(rs)}
                                      for n, rs in sorted(by_name.items())},
                          "summary": setup_summary(spans)}}

    def _engines(self) -> Dict[str, dict]:
        """The curves of :meth:`snapshot`, alone."""
        with self._lock:
            buckets = {k: dict(v) for k, v in self._buckets.items()}
            compiles = {k: {str(n): dict(c) for n, c in v.items()}
                        for k, v in self._compiles.items()}
        engines: Dict[str, dict] = {}
        for key in sorted(set(buckets) | set(compiles)):
            rows_out: Dict[str, dict] = {}
            for padded in sorted(buckets.get(key, ())):
                b = buckets[key][padded]
                stages = {}
                for stage, h in b.stages.items():
                    s = h.snapshot()
                    if not s["count"]:
                        continue
                    stages[stage] = {
                        "count": s["count"], "mean": round(s["mean"], 4),
                        "p50": round(s["p50"], 4), "p95": round(s["p95"], 4),
                        "max": round(s["max"], 4)}
                dev = stages.get("device_ms")
                thr = (b.rows / (dev["mean"] * dev["count"] / 1e3)
                       if dev and dev["mean"] else None)
                rows_out[str(padded)] = {
                    "batches": b.batches,
                    "rows": b.rows,
                    "ms_per_row": (round(dev["mean"] / padded, 5)
                                   if dev else None),
                    "throughput_rows_s": (round(thr, 1)
                                          if thr is not None else None),
                    "stages": stages,
                }
            engines[key] = {"buckets": rows_out,
                            "compiles": compiles.get(key, {})}
        return engines

    def cost_of(self, key: str,
                min_samples: int = 1) -> Optional[dict]:
        """Live per-row cost summary for one engine (the cascade
        inventory's measured-cost column): cheapest observed bucket view
        — mean device ms/row at the largest profiled bucket (marginal
        cost is what tier ordering cares about).

        Returns ``None`` when the curve can't answer; callers that need
        to know *why* (cold curve vs never-seen key) use
        :meth:`coverage`, which reports a per-(engine, bucket) status
        instead of collapsing both cases into ``None``."""
        with self._lock:
            per = self._buckets.get(key)
            if not per:
                return None
            padded = max(per)
            b = per[padded]
        s = b.stages["device_ms"].snapshot()
        if s["count"] < max(1, int(min_samples)):
            return None
        return {"bucket": padded, "batches": b.batches,
                "device_ms_mean": round(s["mean"], 4),
                "ms_per_row": round(s["mean"] / padded, 5)}

    def coverage(self, min_samples: int = 1) -> dict:
        """Which curves exist and which are trustworthy — the planner's
        answer to ``cost_of`` returning a bare ``None``.

        Per engine, per padded bucket: ``samples`` (device-stage
        observations) and ``status`` — ``"ok"`` at or above
        ``min_samples``, ``"cold"`` below it. A key absent from the
        returned mapping entirely is *unknown* (never profiled), the
        third state ``None`` used to hide. ``compile_known`` lists the
        shapes with a recorded XLA compile cost."""
        with self._lock:
            buckets = {k: dict(v) for k, v in self._buckets.items()}
            compiles = {k: sorted(v) for k, v in self._compiles.items()}
        need = max(1, int(min_samples))
        out: Dict[str, dict] = {}
        for key in sorted(set(buckets) | set(compiles)):
            rows = {}
            for padded in sorted(buckets.get(key, ())):
                n = buckets[key][padded].stages["device_ms"].snapshot()["count"]
                rows[str(padded)] = {
                    "samples": n,
                    "status": "ok" if n >= need else "cold"}
            out[key] = {"buckets": rows,
                        "compile_known": [str(p) for p in
                                          compiles.get(key, [])]}
        return out

    # ---- baseline / regression sentinel --------------------------------------

    def load_baseline(self, snap: dict) -> None:
        """Adopt a previously-snapshotted profile as the sentinel's
        comparison baseline. Accepts either a raw :meth:`snapshot` dict
        or what ``storm-tpu profile <topology> --json`` printed (which wraps
        the snapshot under its ``profile`` key — so ``obs.baseline_path`` can
        point straight at the saved file)."""
        if isinstance(snap, dict) and isinstance(snap.get("profile"), dict) \
                and isinstance(snap["profile"].get("engines"), dict):
            snap = snap["profile"]
        if not isinstance(snap, dict) \
                or not isinstance(snap.get("engines"), dict):
            raise ValueError("baseline must be a ProfileStore snapshot "
                             "(dict with an 'engines' mapping) or a "
                             "`profile --json` document wrapping one")
        with self._lock:
            self._baseline = snap

    @property
    def baseline(self) -> Optional[dict]:
        with self._lock:
            return self._baseline

    def regressions(self, factor: float = 1.5,
                    min_samples: int = 20) -> List[dict]:
        """Stage costs drifted beyond ``factor`` x the loaded baseline.

        Compares mean stage cost per (engine, bucket, stage) between the
        live curves and the baseline snapshot, skipping cells with fewer
        than ``min_samples`` live observations (cold curves flap). Empty
        list when no baseline is loaded or nothing drifted."""
        base = self.baseline
        if base is None:
            return []
        live = self._engines()
        out: List[dict] = []
        for key, eng in base.get("engines", {}).items():
            for bucket, row in eng.get("buckets", {}).items():
                lrow = live.get(key, {}).get("buckets", {}).get(bucket)
                if lrow is None:
                    continue
                for stage, bs in row.get("stages", {}).items():
                    ls = lrow.get("stages", {}).get(stage)
                    if ls is None or ls["count"] < min_samples:
                        continue
                    b_mean = bs.get("mean") or 0.0
                    if b_mean <= 0:
                        continue
                    ratio = ls["mean"] / b_mean
                    if ratio > factor:
                        out.append({
                            "engine": key, "bucket": bucket, "stage": stage,
                            "live_ms": ls["mean"], "baseline_ms": b_mean,
                            "ratio": round(ratio, 3)})
        return out


# ---- process singleton + engine-layer wiring ---------------------------------

_STORE = ProfileStore()
_ENABLED = True


def profile_store() -> ProfileStore:
    """The process-wide store (engines are process-cached via
    ``shared_engine``, so their cost curves are process-scoped too)."""
    return _STORE


# what JAX's listeners keep of a thread between two of its events: how deep
# in traces it is, and the cache events since its last backend compile
_JAX_THREAD = threading.local()
_JAX_LISTENING = False


def _jax_enter(event: str, _start: float, **_kw) -> None:
    # JAX's scalar event as one of its spans begins. A program's trace holds
    # one trace of every jitted function it calls (816 of them in a four-
    # bucket warm-up of the toy ViT), and a lowering traces what its rules
    # call: only the outermost of them can become a row.
    if event != _JAX_TRACE and event != _JAX_LOWER:
        return
    mine = _JAX_THREAD.__dict__
    mine["depth"] = mine.get("depth", 0) + 1
    if event == _JAX_LOWER:
        # An eager operation whose executable is in memory is traced again
        # at every call (217 times while the toy LeNet's parameters are
        # made, for 23 compiles): a trace becomes a row where a lowering
        # follows it on its thread, and is forgotten at the next trace.
        if "trace" in mine:
            _STORE.log_setup((next(_SPAN_IDS),) + mine.pop("trace"))


def _jax_span(event: str, t_start: float, t_end: float, **kw) -> None:
    name = JAX_SPANS.get(event)
    if name is None:
        return
    mine = _JAX_THREAD.__dict__
    if event != _JAX_COMPILE:
        mine["depth"] = depth = max(0, mine.get("depth", 1) - 1)
        if depth:
            return
    attrs = {"fun_name": kw.get("fun_name")}
    if event == _JAX_COMPILE:
        attrs.update(mine.pop("cache", None) or {"cache": "none"})
    if not _ENABLED:
        mine.pop("trace", None)
        return
    above = _open_span()
    row = (None if above is None else above.span, name, t_start, t_end,
           threading.current_thread().name, attrs)
    if event == _JAX_TRACE:
        mine["trace"] = row  # a row once its lowering begins
    else:
        _STORE.log_setup((next(_SPAN_IDS),) + row)


def _jax_cache_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT or event == _CACHE_WRITTEN:
        _JAX_THREAD.__dict__.setdefault("cache", {})["cache"] = \
            "hit" if event == _CACHE_HIT else "written"


def _jax_cache_seconds(event: str, seconds: float, **_kw) -> None:
    key = _CACHE_SECONDS.get(event)
    if key is not None:
        _JAX_THREAD.__dict__.setdefault("cache", {})[key] = seconds


def _listen_to_jax() -> None:
    """Register the set-up log's listeners with ``jax.monitoring``, once a
    process: they stay whatever the switch says later (``jax.monitoring``
    takes none back), and off, each returns at its first test."""
    global _JAX_LISTENING
    if _JAX_LISTENING:
        return
    _JAX_LISTENING = True
    import jax.monitoring as mon

    mon.register_scalar_listener(_jax_enter)
    mon.register_event_time_span_listener(_jax_span)
    mon.register_event_listener(_jax_cache_event)
    mon.register_event_duration_secs_listener(_jax_cache_seconds)


def ensure_installed() -> ProfileStore:
    """Point the engine layer's profile sink at the singleton and the
    set-up log's listeners at JAX's compile and cache events (idempotent).
    Called where a process places its compile cache
    (``infer/engine.py enable_compile_cache``: before its first compile),
    from the inference operator's ``prepare`` and from bench — importing the
    engine module lazily so ``obs`` stays importable without pulling jax
    in."""
    from storm_tpu.infer import engine as _engine

    _engine.set_profile_sink(_STORE if _ENABLED else None)
    if _ENABLED:
        _listen_to_jax()
    return _STORE


def set_enabled(flag: bool) -> None:
    """Profiling kill switch (the overhead A/B's off arm): detaches the
    engine sink so the hot path pays a single None check per batch, and
    the set-up log writes no row."""
    global _ENABLED
    _ENABLED = bool(flag)
    ensure_installed()


def enabled() -> bool:
    return _ENABLED
