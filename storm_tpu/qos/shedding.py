"""Adaptive load shedding: a hysteresis controller that drops best-effort
traffic *before* the autoscaler reacts.

Same structural shape as :class:`~storm_tpu.runtime.autoscale.Autoscaler`
(start/stop/step loop, ``decisions`` ledger, flight-recorder breadcrumbs),
but faster (1 s interval vs the autoscaler's 5 s) and cheaper (no
rebalance — it just moves a gauge). Signals, all read from the shared
metrics registry and the runtime's executors:

- **inbox occupancy** of the inference component (backpressure already
  materialized);
- **batch-wait p95** — the operator's in-batcher queueing stage, the
  metrics twin of PR 1's per-record ``queue_wait`` spans;
- **SLO-breach rate** — the sink's ``slo_breaches`` counter delta per
  interval (the counter is incremented on the same condition that fires
  PR 1's ``slo_breach`` flight events).

Hysteresis: ``hot_steps`` consecutive intervals with any signal above its
threshold raise the shed level by one; ``calm_steps`` consecutive
intervals with every signal below *half* its threshold lower it. The
level is published as gauge ``("qos", "shed_level")`` in the topology's
registry — the spout's admission controller and the inference operator
read it from there, so shedding needs no new plumbing through
TopologyContext and shows up in ``/metrics`` and UI snapshots for free.

Shed-first/scale-second: the autoscaler accepts ``shedder=`` and defers
its first scale-up while the shedder has not yet reacted, so cheap load
shedding gets one control step's head start over expensive scale-out.
"""

from __future__ import annotations

import asyncio
import logging
from dataclasses import dataclass
from typing import Optional

from storm_tpu.config import QosConfig
from storm_tpu.runtime.frames import RecordFrame

log = logging.getLogger("storm_tpu.qos")


@dataclass
class ShedPolicy:
    """Control-loop wiring + thresholds (defaults mirror QosConfig)."""

    component: str = "inference-bolt"   # whose inbox/batch-wait to watch
    latency_source: str = "kafka-bolt"  # whose slo_breaches counter to watch
    interval_s: float = 1.0
    inbox_frac: float = 0.5    # hot when inference inbox above this fraction
    wait_ms: float = 0.0       # hot when batch_wait p95 above this (0 = off)
    breach_rate: float = 1.0   # hot when sink SLO breaches/sec above this
    hot_steps: int = 2
    calm_steps: int = 5
    max_level: int = 2         # usually len(qos.lanes) - 1

    @classmethod
    def from_qos(cls, qos: QosConfig, component: str = "inference-bolt",
                 latency_source: str = "kafka-bolt") -> "ShedPolicy":
        return cls(
            component=component,
            latency_source=latency_source,
            interval_s=qos.shed_interval_s,
            inbox_frac=qos.shed_inbox_frac,
            wait_ms=qos.shed_wait_ms,
            breach_rate=qos.shed_breach_rate,
            hot_steps=qos.shed_hot_steps,
            calm_steps=qos.shed_calm_steps,
            max_level=qos.max_shed_level,
        )


class LoadShedController:
    def __init__(self, runtime, policy: Optional[ShedPolicy] = None) -> None:
        self.rt = runtime
        self.policy = policy or ShedPolicy()
        self.level = 0
        self.decisions: list = []  # ("shed"|"restore", old, new) per change
        self._task: Optional[asyncio.Task] = None
        self._hot = 0
        self._calm = 0
        self._prev_breaches: Optional[int] = None
        self._gauge = runtime.metrics.gauge("qos", "shed_level")
        self._gauge.set(0.0)
        # Optional SLO burn-rate tracker (storm_tpu/obs/slo.py): when the
        # observatory attaches one, its fast+slow-window trip is an
        # additional HOT signal — burn integrates breaches over a window,
        # so it rises before the raw per-interval breach-rate threshold
        # does (seen in a CPU-host overload run of round 11; no ledger line).
        self.burn = None
        # Expose ourselves so the UI's /qos route can serve decisions.
        runtime.qos = self

    def start(self) -> "LoadShedController":
        self._task = asyncio.get_event_loop().create_task(self._loop())
        return self

    async def stop(self) -> None:
        if self._task:
            self._task.cancel()
            try:
                await self._task
            except (asyncio.CancelledError, Exception):
                pass

    # ---- the control loop ----------------------------------------------------

    async def _loop(self) -> None:
        while True:
            await asyncio.sleep(self.policy.interval_s)
            try:
                self.step()
            except Exception as e:  # pragma: no cover
                log.warning("shed step failed: %s", e)

    def _signals(self) -> dict:
        p = self.policy
        execs = self.rt.bolt_execs.get(p.component, [])
        inbox_frac = max(
            (self._inbox_rows(e.inbox) / max(1, e.inbox.maxsize)
             for e in execs),
            default=0.0)
        wait = self.rt.metrics.histogram(p.component, "batch_wait_ms")
        wait_p95 = wait.percentile(95) if wait.count else 0.0
        breaches = self.rt.metrics.counter(
            p.latency_source, "slo_breaches").value
        if self._prev_breaches is None:
            delta = 0
        else:
            delta = max(0, breaches - self._prev_breaches)
        self._prev_breaches = breaches
        burn = self.burn
        return {
            "inbox_frac": inbox_frac,
            "wait_p95_ms": wait_p95,
            "breach_rate": delta / p.interval_s,
            "burn_rate": burn.fast_burn if burn is not None else 0.0,
            "burn_tripped": burn.tripped if burn is not None else False,
        }

    @staticmethod
    def _inbox_rows(inbox) -> int:
        """Queued RECORDS, not queued tuples. Batch-native ingress parks
        RecordFrames on the inbox — one tuple carrying hundreds of rows —
        so qsize() under-reads pressure by the frame fan-in factor and
        de-sensitizes every inbox-driven shed trigger (r19 OPERATIONS
        note, fixed round 20). Reads the asyncio.Queue's internal deque:
        a point-in-time sweep on the event-loop thread, no lock needed."""
        rows = 0
        for item in getattr(inbox, "_queue", ()):
            payload = (item.values[0]
                       if getattr(item, "values", None) else None)
            if isinstance(payload, (RecordFrame, list, tuple)):
                rows += len(payload)
            else:
                rows += 1
        return rows

    def step(self) -> Optional[int]:
        """One evaluation (synchronous — all signals are in-process reads);
        returns the new shed level if it changed."""
        p = self.policy
        s = self._signals()
        hot = (s["inbox_frac"] > p.inbox_frac
               or (p.wait_ms > 0 and s["wait_p95_ms"] > p.wait_ms)
               or s["breach_rate"] > p.breach_rate
               or s["burn_tripped"])
        calm = (s["inbox_frac"] < p.inbox_frac / 2
                and (p.wait_ms <= 0 or s["wait_p95_ms"] < p.wait_ms / 2)
                and s["breach_rate"] < p.breach_rate / 2
                and not s["burn_tripped"])
        if hot:
            self._hot += 1
            self._calm = 0
        elif calm:
            self._calm += 1
            self._hot = 0
        else:
            self._hot = 0
            self._calm = 0

        if self._hot >= p.hot_steps and self.level < p.max_level:
            return self._set_level(self.level + 1, "shed", s)
        if self._calm >= p.calm_steps and self.level > 0:
            return self._set_level(self.level - 1, "restore", s)
        return None

    def _set_level(self, new: int, direction: str, signals: dict) -> int:
        old = self.level
        self.level = new
        self._gauge.set(float(new))
        self._hot = 0
        self._calm = 0
        self.decisions.append((direction, old, new))
        self.rt.metrics.counter("qos", "shed_decisions").inc()
        log.info(
            "shed level %d->%d (%s): inbox=%.0f%% wait_p95=%.1fms "
            "breaches/s=%.1f", old, new, direction,
            signals["inbox_frac"] * 100, signals["wait_p95_ms"],
            signals["breach_rate"])
        flight = getattr(self.rt, "flight", None)
        if flight is not None:
            flight.event(
                "shed_decision", component=self.policy.component,
                direction=direction, level=(old, new),
                inbox_frac=round(signals["inbox_frac"], 3),
                wait_p95_ms=round(signals["wait_p95_ms"], 3),
                breach_rate=round(signals["breach_rate"], 3),
                burn_rate=round(signals.get("burn_rate", 0.0), 3),
            )
        return new
