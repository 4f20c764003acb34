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

The snapshot round-trips: ``storm-tpu profile <topology> --json`` writes
it as versioned JSON, and a later run loads
that file back as the regression sentinel's baseline
(:meth:`ProfileStore.load_baseline` + :meth:`ProfileStore.regressions`).
"""

from __future__ import annotations

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

    def record_compile(self, key: str, padded: int, ms: float) -> None:
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

    def snapshot(self) -> dict:
        """JSON-safe curves: per engine, per padded bucket, per stage
        {count, mean, p50, p95, max} plus rows/s throughput; compile cost
        per shape. Bucket keys are stringified ints (JSON round-trip).
        ``steps``: how many rows the step log holds, its last eight, and
        the longest gap between two steps with the interval it went to
        (:func:`longest_gap`). ``records``: how many rows the record log
        holds, every interval's median and 90th percentile over them
        (:func:`record_intervals`), and the delivered record that took
        longest from append to produce, with its step's moments."""
        rows = self.steps()
        paths = record_paths(self.records(), rows)
        whole = [r for r in paths if r["t_produced"] is not None]
        return {"engines": self._engines(),
                "steps": {"count": len(rows), "last": rows[-8:],
                          "longest_gap": longest_gap(rows)},
                "records": {"count": len(paths),
                            "intervals": record_intervals(paths),
                            "slowest": max(
                                whole, default=None, key=lambda r:
                                r["t_produced"] - r["t_append"])}}

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


def ensure_installed() -> ProfileStore:
    """Point the engine layer's profile sink at the singleton (idempotent).
    Called from the inference operator's ``prepare`` and from bench —
    importing the engine module lazily so ``obs`` stays importable
    without pulling jax in."""
    from storm_tpu.infer import engine as _engine

    _engine.set_profile_sink(_STORE if _ENABLED else None)
    return _STORE


def set_enabled(flag: bool) -> None:
    """Profiling kill switch (the overhead A/B's off arm): detaches the
    engine sink so the hot path pays a single None check per batch."""
    global _ENABLED
    _ENABLED = bool(flag)
    ensure_installed()


def enabled() -> bool:
    return _ENABLED
