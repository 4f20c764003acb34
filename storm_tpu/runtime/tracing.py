"""Tracing: per-record distributed traces, per-stage spans, flight recorder.

The reference's observability is whatever Storm UI exposes (SURVEY.md §5.1);
here spans are first-class: a sampled record carries a ``TraceContext``
(W3C ``traceparent`` ids) from spout ingress through batching, device
execution (one shared batch span linked to every member record's span),
and sink egress, so queue-wait vs. device time is separable per record.
Completed trees live in an in-process ring buffer (``TraceStore``) served
by the UI; structured pipeline events (batch formed, SLO breach, autoscale
decision, chaos injection) go to a bounded JSONL ``FlightRecorder`` for
post-mortem debugging of soak/chaos runs.

Usage::

    with span(metrics, "inference-bolt", "decode"):
        ...                      # records decode_ms histogram

    with device_trace("/tmp/trace"):   # TensorBoard-loadable profile
        engine.predict(x)

    ctx = tracer.maybe_trace()         # None unless sampled (zero-alloc path)
    if ctx is not None:
        tracer.record(ctx, "ingress", "spout", t0, t1)
"""

from __future__ import annotations

import collections
import contextlib
import json
import logging
import os
import random
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

from storm_tpu.runtime.metrics import MetricsRegistry

log = logging.getLogger("storm_tpu.tracing")

_event_names_checked: set = set()


def _check_event_name(kind: str) -> None:
    """Warn once per flight-event name missing from the generated protocol
    registry (``storm_tpu/analysis/protocol_names.py``). The static side
    is lint rule PRT003; this runtime side catches names built from
    variables or f-strings the AST pass can't resolve. A typo'd event name
    is otherwise invisible: the recorder happily stores it while every
    reader (dashboards, fleet scorecard, chaos drills) filters on the
    spelling that never arrives."""
    if kind in _event_names_checked:
        return
    _event_names_checked.add(kind)
    try:
        from storm_tpu.analysis.protocol_names import is_known_event
    except ImportError:  # registry not generated in this checkout
        return
    if not is_known_event(kind):
        log.warning(
            "flight event %r is not in the generated protocol registry — "
            "typo, or run `storm-tpu lint --regen-protocol-registry` "
            "(PRT003)", kind)

#: Split-phase pipeline substages of one device round trip, in execution
#: order: ``(histogram/timing key, stage label)``. Single source of truth —
#: the engine's InflightBatch.timings keys, the inference operator's
#: substage histograms and the ``device_execute`` span sub-attrs
#: all derive from this tuple.
#: h2d = staging-buffer write + host->device transfer + async jit launch,
#: compute = launch -> device ready, d2h = blocking device->host copy.
DEVICE_SUBSTAGES: Tuple[Tuple[str, str], ...] = (
    ("h2d_ms", "h2d"),
    ("compute_ms", "compute"),
    ("d2h_ms", "d2h"),
)


@contextlib.contextmanager
def span(metrics: Optional[MetricsRegistry], component: str, name: str) -> Iterator[None]:
    """Time a stage into the ``<name>_ms`` histogram of ``component``."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        if metrics is not None:
            metrics.histogram(component, f"{name}_ms").observe(
                (time.perf_counter() - t0) * 1e3
            )


@contextlib.contextmanager
def device_trace(log_dir: str) -> Iterator[None]:
    """JAX/XLA profiler trace (host + device timelines) into ``log_dir``."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# Per-record distributed tracing
# ---------------------------------------------------------------------------

# Id source deliberately separate from tuples._rng: tuple ids are
# worker-tagged (top byte = owner) for ack routing; trace/span ids must be
# globally uniform randomness per W3C trace-context.
_rng = random.Random(os.urandom(16))


#: Sentinel for ``OutputCollector.emit(trace=...)``: the sampling decision
#: was already made upstream (and missed) — do NOT re-roll in the collector,
#: or spout-minting components would double the effective sample rate.
NOT_SAMPLED = object()


def _new_trace_id() -> str:
    return f"{_rng.getrandbits(128):032x}"


def _new_span_id() -> str:
    return f"{_rng.getrandbits(64):016x}"


class TraceContext:
    """W3C-trace-context-shaped identity a sampled tuple carries.

    Only ever attached to SAMPLED records — unsampled tuples carry
    ``trace=None`` so the sampling-off hot path allocates nothing beyond
    the (always-present) field.
    """

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def traceparent(self) -> str:
        # version 00, sampled flag always 01: an unsampled record has no
        # context object at all.
        return f"00-{self.trace_id}-{self.span_id}-01"

    @classmethod
    def from_traceparent(cls, header: Optional[str]) -> Optional["TraceContext"]:
        """Parse ``00-<32hex>-<16hex>-<2hex>``; None on anything malformed
        (a garbage header must never take down the deliver path)."""
        if not header or not isinstance(header, str):
            return None
        parts = header.split("-")
        if len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
            return None
        try:
            int(parts[1], 16), int(parts[2], 16)
        except ValueError:
            return None
        return cls(parts[1], parts[2])

    def to_bytes(self) -> Optional[bytes]:
        """24 raw bytes (16 trace id + 8 span id) for the binary dist wire.

        None on a non-hex context (same garbage-tolerance contract as
        :meth:`from_traceparent` — the sender drops the trace rather than
        failing the frame)."""
        try:
            return bytes.fromhex(self.trace_id) + bytes.fromhex(self.span_id)
        except ValueError:
            return None

    @classmethod
    def from_bytes(cls, raw: bytes) -> Optional["TraceContext"]:
        """Inverse of :meth:`to_bytes`; None on anything but 24 bytes."""
        if len(raw) != 24:
            return None
        return cls(raw[:16].hex(), raw[16:].hex())

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"TraceContext({self.traceparent()})"


class Span:
    """One timed operation inside a trace. ``links`` carries the span ids
    of OTHER spans causally tied to this one without being its children —
    the fan-in of N record spans into one shared device-execution span."""

    __slots__ = ("name", "component", "span_id", "parent_id", "start",
                 "duration_ms", "attrs", "links")

    def __init__(self, name: str, component: str, span_id: str,
                 parent_id: Optional[str], start: float, duration_ms: float,
                 attrs: Optional[dict] = None,
                 links: Optional[Tuple[str, ...]] = None):
        self.name = name
        self.component = component
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = start  # perf_counter domain of the recording process
        self.duration_ms = duration_ms
        self.attrs = attrs
        self.links = links

    def to_dict(self, t0: float) -> dict:
        d = {
            "name": self.name,
            "component": self.component,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "offset_ms": round((self.start - t0) * 1e3, 3),
            "duration_ms": round(self.duration_ms, 3),
        }
        if self.attrs:
            d["attrs"] = self.attrs
        if self.links:
            d["links"] = list(self.links)
        return d


class TraceStore:
    """In-process ring buffer of trace records.

    ``open`` starts a record for a root; spans append to it; ``finish``
    moves it to the completed ring (``deque(maxlen=capacity)``). Records
    abandoned by failed/timed-out tuple trees are evicted oldest-first
    once the open map exceeds 4x capacity, so a lossy pipeline can't grow
    the store unboundedly. Thread-safe: spans arrive from the event loop,
    readers (UI) from executor threads.
    """

    def __init__(self, capacity: int = 256):
        self.capacity = max(1, int(capacity))
        self._lock = threading.Lock()
        # trace_id -> record; insertion-ordered for oldest-first eviction
        self._open: Dict[str, dict] = {}
        self._done: collections.deque = collections.deque(maxlen=self.capacity)
        self.dropped = 0  # evicted-while-open (orphans)

    def _open_locked(self, trace_id: str) -> dict:
        rec = self._open.get(trace_id)
        if rec is None:
            rec = {
                "trace_id": trace_id,
                "opened_at": time.time(),
                "t0": time.perf_counter(),
                "spans": [],
            }
            self._open[trace_id] = rec
            while len(self._open) > 4 * self.capacity:
                self._open.pop(next(iter(self._open)))
                self.dropped += 1
        return rec

    def open(self, trace_id: str, t0: Optional[float] = None) -> None:
        with self._lock:
            rec = self._open_locked(trace_id)
            if t0 is not None:
                rec["t0"] = t0

    def add_span(self, trace_id: str, sp: Span) -> None:
        """Append a span, auto-opening a partial record: on a remote
        worker the trace arrived mid-flight and was never ``open``-ed."""
        with self._lock:
            rec = self._open_locked(trace_id)
            if sp.start < rec["t0"]:
                rec["t0"] = sp.start
            rec["spans"].append(sp)

    def finish(self, trace_id: str, duration_ms: float) -> None:
        with self._lock:
            rec = self._open.pop(trace_id, None)
            if rec is None:
                return
            rec["duration_ms"] = round(duration_ms, 3)
            self._done.append(rec)

    # ---- read side --------------------------------------------------------

    @staticmethod
    def _render(rec: dict) -> dict:
        t0 = rec["t0"]
        return {
            "trace_id": rec["trace_id"],
            "opened_at": rec["opened_at"],
            "duration_ms": rec.get("duration_ms"),
            "spans": [s.to_dict(t0) for s in rec["spans"]],
        }

    def get(self, trace_id: str) -> Optional[dict]:
        with self._lock:
            for rec in self._done:
                if rec["trace_id"] == trace_id:
                    return self._render(rec)
            rec = self._open.get(trace_id)
            return self._render(rec) if rec else None

    def recent(self, n: int = 20) -> List[dict]:
        with self._lock:
            recs = list(self._done)[-n:]
        return [self._render(r) for r in reversed(recs)]

    def open_records(self, n: int = 20) -> List[dict]:
        """Still-open records (no ``finish`` yet), newest first. On a dist
        worker that doesn't host the sink, EVERY record stays open — this
        is the slice the controller merges with the sink worker's finished
        ones. Rendered under the lock: open span lists still mutate."""
        with self._lock:
            return [self._render(r)
                    for r in reversed(list(self._open.values())[-n:])]

    def slowest(self, n: int = 20) -> List[dict]:
        with self._lock:
            recs = sorted(self._done,
                          key=lambda r: r.get("duration_ms") or 0.0,
                          reverse=True)[:n]
        return [self._render(r) for r in recs]

    def stats(self) -> dict:
        with self._lock:
            return {"open": len(self._open), "done": len(self._done),
                    "dropped": self.dropped, "capacity": self.capacity}


class Tracer:
    """Sampling decision + span recording for one runtime.

    Contract with the hot path: when ``sample_rate`` is 0 (the default)
    ``maybe_trace`` returns None without allocating, and every call site
    guards span work behind ``tuple.trace is not None`` — so tracing-off
    adds no per-tuple cost beyond the Tuple field itself.
    """

    def __init__(self, sample_rate: float = 0.0, store_capacity: int = 256):
        self.sample_rate = float(sample_rate)
        self.store = TraceStore(store_capacity)

    @property
    def active(self) -> bool:
        return self.sample_rate > 0.0

    def maybe_trace(self) -> Optional[TraceContext]:
        """A fresh sampled root context, or None (sampling miss / off)."""
        r = self.sample_rate
        if r <= 0.0 or (r < 1.0 and _rng.random() >= r):
            return None
        ctx = TraceContext(_new_trace_id(), _new_span_id())
        self.store.open(ctx.trace_id)
        return ctx

    def adopt(self, ctx: TraceContext) -> None:
        """Register a context minted elsewhere (remote worker side)."""
        self.store.open(ctx.trace_id)

    @staticmethod
    def new_span_id() -> str:
        """A fresh span id for spans shared across traces (the batch's
        device-execution span carries ONE id in every member trace)."""
        return _new_span_id()

    def record(self, ctx: TraceContext, name: str, component: str,
               start: float, end: float, *, parent_id: Optional[str] = None,
               span_id: Optional[str] = None, attrs: Optional[dict] = None,
               links: Optional[Tuple[str, ...]] = None) -> str:
        """Record a completed span under ``ctx``'s trace; returns its id."""
        sid = span_id or _new_span_id()
        self.store.add_span(ctx.trace_id, Span(
            name, component, sid,
            ctx.span_id if parent_id is None else parent_id,
            start, (end - start) * 1e3, attrs, links))
        return sid

    def finish(self, ctx: TraceContext, duration_ms: float) -> None:
        self.store.finish(ctx.trace_id, duration_ms)


class FlightRecorder:
    """Bounded structured-event log (the pipeline's black box).

    Events always land in an in-memory ring (``tail`` serves the UI); when
    ``path`` is set they are also appended as JSONL with size-based
    rotation (``path`` -> ``path.1`` -> ... up to ``max_files``), so a
    week-long soak run cannot fill the disk. Thread-safe; a failing disk
    must never take down the pipeline, so write errors disable the file
    sink and keep the ring.
    """

    def __init__(self, path: str = "", capacity: int = 512,
                 max_bytes: int = 4 * 1024 * 1024, max_files: int = 3):
        self.path = path or ""
        self.max_bytes = max(4096, int(max_bytes))
        self.max_files = max(1, int(max_files))
        self._ring: collections.deque = collections.deque(
            maxlen=max(16, int(capacity)))
        self._lock = threading.Lock()
        self._fh = None
        self._size = 0
        self._last: Dict[str, float] = {}  # kind -> last wall ts (throttle)
        if self.path:
            try:
                self._fh = open(self.path, "a", encoding="utf-8")
                self._size = self._fh.tell()
            except OSError:
                self._fh = None

    def _rotate_locked(self) -> None:
        self._fh.close()
        for i in range(self.max_files - 1, 0, -1):
            src = self.path if i == 1 else f"{self.path}.{i - 1}"
            try:
                os.replace(src, f"{self.path}.{i}")
            except OSError:
                pass
        self._fh = open(self.path, "a", encoding="utf-8")
        self._size = 0

    def event(self, kind: str, *, throttle_s: float = 0.0, **fields: Any) -> bool:
        """Record one event; returns False when throttled away.

        ``throttle_s`` suppresses repeats of the same ``kind`` within the
        window (SLO breaches arrive per-record; one per second is plenty).
        """
        _check_event_name(kind)  # once per kind: off the hot path
        now = time.time()
        with self._lock:
            if throttle_s > 0.0:
                last = self._last.get(kind, 0.0)
                if now - last < throttle_s:
                    return False
                self._last[kind] = now
            ev = {"ts": round(now, 3), "kind": kind}
            ev.update(fields)
            self._ring.append(ev)
            if self._fh is not None:
                try:
                    line = json.dumps(ev, default=str) + "\n"
                    if self._size + len(line) > self.max_bytes:
                        self._rotate_locked()
                    self._fh.write(line)
                    self._fh.flush()
                    self._size += len(line)
                except (OSError, ValueError):
                    self._fh = None  # disk trouble: keep the ring, drop file
        return True

    def tail(self, n: int = 50) -> List[dict]:
        with self._lock:
            return list(self._ring)[-n:]

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                except OSError:
                    pass
                self._fh = None
