"""Per-executor utilization and per-edge lag watermarks.

The measurement half of the bottleneck observatory (the fusion half is
:mod:`storm_tpu.obs.bottleneck`):

- :class:`CapacityTracker` — samples the executors' busy/wait/flush
  wall-time accumulators (``runtime/executor.py``) into Storm-style
  ``capacity = busy / window`` per component. Cursors are *named* (the
  ``Histogram.window`` contract): the Observatory, the dist ``utilization``
  control command, and any bench sampler each advance their own cursor,
  so independent consumers never steal each other's deltas.
- :class:`EdgeLagTracker` — inbox depth AND growth rate per (src -> dst)
  edge from the routing table, dist transport outbound depth per peer,
  and spout ingress lag (cursor vs. available) from
  ``BrokerSpout.ingress_lag``. (An engine queue's depth and oldest age
  are ``Observatory.occupancy``'s.)
- :func:`utilization_snapshot` — the per-process entry point the dist
  worker's ``utilization`` control command calls; the controller merges
  the per-worker results (``dist/controller.merge_utilization``).

Everything reads plain per-executor floats updated on the owning loop
and queue sizes — no locks taken on any hot path.
"""

from __future__ import annotations

import time
from typing import Dict, Iterator, List, Optional, Tuple

__all__ = ["CapacityTracker", "EdgeLagTracker", "utilization_snapshot"]


class CapacityTracker:
    """Windowed busy/wait/flush fractions per component.

    ``sample(key)`` returns, per component, the deltas since the last
    ``sample`` with the same key plus derived figures::

        {"component", "tasks", "busy_s", "wait_s", "flush_s", "dt_s",
         "capacity",                    # busy / (tasks * wallclock window)
         "busy_frac", "wait_frac", "flush_frac"}  # of *accounted* time

    ``capacity`` is the Storm UI number (1.0 = every task executing for
    the whole window); the fractions normalize over accounted time so
    they sum to ~1 regardless of scheduler gaps. First call with a key
    (or a task added by rebalance) reports nothing for that task — the
    zero-length-window contract of ``Histogram.window``.
    """

    def __init__(self, runtime, clock=time.monotonic) -> None:
        self.rt = runtime
        self.clock = clock
        # key -> {(component, task): (busy, wait, flush, t)} at last read
        self._cursors: Dict[str, Dict[Tuple[str, int], tuple]] = {}
        # Latest per-component rows from the most recent sample() — the
        # attributor and the UI /bottleneck route read this.
        self.last: Dict[str, dict] = {}

    def _executors(self) -> Iterator[Tuple[str, object]]:
        for comp, execs in {**(getattr(self.rt, "spout_execs", None) or {}),
                            **(getattr(self.rt, "bolt_execs", None) or {}),
                            }.items():
            for e in execs:
                yield comp, e

    def sample(self, key: str = "default",
               publish: bool = True) -> Dict[str, dict]:
        now = self.clock()
        cur = self._cursors.setdefault(key, {})
        per_comp: Dict[str, dict] = {}
        seen = set()
        for comp, e in self._executors():
            tkey = (comp, getattr(e, "task_index", 0))
            seen.add(tkey)
            busy = float(getattr(e, "busy_s", 0.0))
            wait = float(getattr(e, "wait_s", 0.0))
            flush = float(getattr(e, "flush_s", 0.0))
            prev = cur.get(tkey)
            cur[tkey] = (busy, wait, flush, now)
            if prev is None:
                continue  # zero-length first window for this task
            row = per_comp.setdefault(comp, {
                "component": comp, "tasks": 0, "busy_s": 0.0,
                "wait_s": 0.0, "flush_s": 0.0, "dt_s": 0.0})
            row["tasks"] += 1
            row["busy_s"] += max(0.0, busy - prev[0])
            row["wait_s"] += max(0.0, wait - prev[1])
            row["flush_s"] += max(0.0, flush - prev[2])
            row["dt_s"] = max(row["dt_s"], max(0.0, now - prev[3]))
        # Rebalance removed a task: drop its tuple from EVERY named cursor,
        # not just the one being sampled — the sampled key self-heals on its
        # next call, but an idle consumer's key (a finished scorecard cell,
        # a paused dist poller) would otherwise pin stale (comp, task)
        # state for the tracker's lifetime. The executor set is a property
        # of the runtime, so `seen` is valid for all keys at once.
        for ckey, cdict in list(self._cursors.items()):
            for tkey in [k for k in cdict if k not in seen]:
                del cdict[tkey]
            if not cdict and ckey != key:
                del self._cursors[ckey]
        for row in per_comp.values():
            _finish_row(row)
        self.last = per_comp
        if publish:
            g = self.rt.metrics.gauge
            for comp, row in per_comp.items():
                if row["capacity"] is not None:
                    g(comp, "capacity").set(row["capacity"])
                g(comp, "busy_frac").set(row["busy_frac"])
                g(comp, "wait_frac").set(row["wait_frac"])
                g(comp, "flush_frac").set(row["flush_frac"])
        return per_comp

    def drop(self, key: str) -> bool:
        """Forget a named cursor wholesale — the tracker-side twin of
        ``Histogram.drop_window``. A consumer whose lifetime is shorter
        than the topology's (one scorecard cell, a one-shot bench probe)
        calls this on exit; without it each retired key keeps a
        per-(component, task) tuple dict alive forever."""
        return self._cursors.pop(key, None) is not None

    def cursor_keys(self) -> tuple:
        """Live cursor names (leak check for long-running harnesses)."""
        return tuple(self._cursors)


def _finish_row(row: dict) -> None:
    """Derive capacity + accounted-time fractions in place (shared with
    the controller's cross-worker merge, which re-derives after summing)."""
    denom = row["tasks"] * row["dt_s"]
    row["capacity"] = (round(min(1.0, row["busy_s"] / denom), 4)
                       if denom > 0 else None)
    acct = row["busy_s"] + row["wait_s"] + row["flush_s"]
    for k, frac in (("busy_s", "busy_frac"), ("wait_s", "wait_frac"),
                    ("flush_s", "flush_frac")):
        row[frac] = round(row[k] / acct, 4) if acct > 0 else 0.0
    for k in ("busy_s", "wait_s", "flush_s", "dt_s"):
        row[k] = round(row[k], 6)


class EdgeLagTracker:
    """Queue watermarks: where records are piling up, and how fast.

    ``sample()`` returns::

        {"edges":   [{edge, src, dst, stream, depth, growth_per_s}],
         "ingress": [{component, task, records_behind, partitions}],
         "transport": {peer_<idx>: outbound_depth}}

    Depth growth is a windowed delta (one cursor per edge; first sample
    reports ``growth_per_s: None``).
    """

    def __init__(self, runtime, clock=time.monotonic) -> None:
        self.rt = runtime
        self.clock = clock
        self._prev: Dict[str, tuple] = {}  # edge -> (depth, t)
        self.last: dict = {}

    def sample(self) -> dict:
        now = self.clock()
        edges: List[dict] = []
        seen_edges = set()
        router = getattr(self.rt, "router", None)
        for src, stream, group in (router.edges() if router is not None
                                   else ()):
            dst = getattr(group, "component_id", "?")
            ekey = f"{src}->{dst}" + ("" if stream == "default"
                                      else f"[{stream}]")
            if ekey in seen_edges:  # two groupings on one edge: one row
                continue
            seen_edges.add(ekey)
            depth = 0
            for q in getattr(group, "inboxes", []):
                try:
                    depth += q.qsize()
                except Exception:
                    pass  # remote proxy without a size
            prev = self._prev.get(ekey)
            self._prev[ekey] = (depth, now)
            growth = None
            if prev is not None:
                dt = now - prev[1]
                growth = round((depth - prev[0]) / dt, 3) if dt > 0 else 0.0
            edges.append({"edge": ekey, "src": src, "dst": dst,
                          "stream": stream, "depth": depth,
                          "growth_per_s": growth})
        for ekey in [k for k in self._prev if k not in seen_edges]:
            del self._prev[ekey]

        ingress: List[dict] = []
        for comp, execs in (getattr(self.rt, "spout_execs", None) or {}).items():
            for e in execs:
                lag_fn = getattr(getattr(e, "spout", None),
                                 "ingress_lag", None)
                if lag_fn is None:
                    continue
                try:
                    lag = lag_fn()
                except Exception:
                    continue
                ingress.append({"component": comp,
                                "task": getattr(e, "task_index", 0), **lag})

        out = {"edges": edges, "ingress": ingress,
               "transport": transport_depths(self.rt)}
        self.last = out
        g = getattr(getattr(self.rt, "metrics", None), "gauge", None)
        if g is not None:
            for row in edges:
                g("obs", f"edge_depth_{row['edge']}").set(row["depth"])
                if row["growth_per_s"] is not None:
                    g("obs", f"edge_growth_{row['edge']}").set(
                        row["growth_per_s"])
            behind = sum(r["records_behind"] for r in ingress
                         if r.get("records_behind") is not None)
            g("obs", "spout_records_behind").set(behind)
        return out


def transport_depths(rt) -> Dict[str, int]:
    """Outbound dist-transport queue depth per peer (empty single-host).

    The PeerSender queue is the only unbounded queue in the system —
    depth growth there means the *wire or the receiving worker* is the
    limiter, which no local capacity number would show."""
    out: Dict[str, int] = {}
    for idx, sender in (getattr(rt, "senders", None) or {}).items():
        q = getattr(sender, "queue", None)
        if q is not None:
            out[f"peer_{idx}"] = q.qsize()
    return out


def utilization_snapshot(rt, key: str = "dist") -> dict:
    """Windowed per-component utilization for one runtime/process — the
    dist worker's ``utilization`` control command. The tracker is cached
    on the runtime so repeated calls advance cursors instead of
    re-priming them."""
    tr = getattr(rt, "_capacity_tracker", None)
    if tr is None:
        tr = CapacityTracker(rt)
        rt._capacity_tracker = tr
    return {"components": tr.sample(key=key, publish=False),
            "transport": transport_depths(rt)}
