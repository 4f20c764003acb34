"""Online cost profiler: per-(engine, bucket) stage-cost curves.

Every completed device batch already carries per-phase wall-clock
attribution (``InflightBatch.timings`` — h2d/compute/d2h, filled by the
split-phase pipeline), and every cold bucket shape fires the engine's
``on_compile`` hook. Those numbers were only ever *observed* into flat
per-component histograms, which average away the one axis a planner
needs: batch size. The :class:`ProfileStore` keys the same stream by
(engine, padded bucket), turning the runtime's own traffic into the
per-stage latency/throughput curves ROADMAP item 1's planner consumes —
InferLine's offline profiler, made continuous.

Wiring: the engine layer exposes ``set_profile_sink`` (a module-level
hook, same shape as ``on_compile`` but process-wide); ``ensure_installed``
points it at the process singleton. Recording is one lock + a couple of
dict/histogram updates per BATCH (not per record), on the engine's fetch
thread — the profiling-on/off interleaved A/B is committed as
``BENCH_OBS_OVERHEAD_r11.json``.

The snapshot round-trips: ``bench.py --profile`` writes it as a
versioned JSON artifact (``PROFILE_r11.json``), and a later run loads
that file back as the regression sentinel's baseline
(:meth:`ProfileStore.load_baseline` + :meth:`ProfileStore.regressions`).
"""

from __future__ import annotations

import threading
from collections import deque
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

    # ---- the read path -------------------------------------------------------

    def steps(self) -> List[dict]:
        """The step log, oldest first: a copy of each of the last
        ``STEP_LOG`` rows."""
        with self._lock:
            return [dict(r) for r in self._steps]

    def snapshot(self) -> dict:
        """JSON-safe curves: per engine, per padded bucket, per stage
        {count, mean, p50, p95, max} plus rows/s throughput; compile cost
        per shape. Bucket keys are stringified ints (JSON round-trip).
        ``steps``: how many rows the step log holds, its last eight, and
        the longest gap between two steps with the interval it went to
        (:func:`longest_gap`)."""
        rows = self.steps()
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
        return {"engines": engines,
                "steps": {"count": len(rows), "last": rows[-8:],
                          "longest_gap": longest_gap(rows)}}

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
        or a committed ``PROFILE_*.json`` bench artifact (which wraps the
        snapshot under its ``profile`` key — so ``obs.baseline_path`` can
        point straight at the committed file)."""
        if isinstance(snap, dict) and isinstance(snap.get("profile"), dict) \
                and isinstance(snap["profile"].get("engines"), dict):
            snap = snap["profile"]
        if not isinstance(snap, dict) \
                or not isinstance(snap.get("engines"), dict):
            raise ValueError("baseline must be a ProfileStore snapshot "
                             "(dict with an 'engines' mapping) or a "
                             "PROFILE_*.json artifact wrapping one")
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
        live = self.snapshot()["engines"]
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
