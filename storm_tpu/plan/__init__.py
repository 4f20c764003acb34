"""SLO-aware joint planner: offline cost-model solve + online corrector.

InferLine's two halves (PAPERS.md) built on what the
observability PRs already measure:

- :mod:`storm_tpu.plan.model` — :class:`CostModel`: loads a ProfileStore
  snapshot (the live singleton or a saved ``storm-tpu profile --json``
  baseline) and predicts per-stage latency, throughput, and device
  utilization for one candidate config (bucket, batching deadline,
  parallelism, ``pipeline_depth``, ``max_inflight``),
  including compile-cost amortization for shapes not yet warm.
- :mod:`storm_tpu.plan.solver` — :func:`solve`: deterministic search
  over candidates for the cheapest config (fewest replicas) meeting a
  target ``(arrival rate, p99 SLO)``; emits a validated :class:`Plan`
  that maps onto the existing ``TopologyConfig``/``BatchConfig``/
  ``QosConfig`` knobs, or an infeasibility report that names the binding
  stage and the missing curves (``ProfileStore.coverage``).
- :mod:`storm_tpu.plan.corrector` — :class:`PlanCorrector`: the online
  half, stepped by the Observatory loop. Consumes the
  BottleneckAttributor verdict + SLO-burn tracker and moves *only the
  named limiter's* knob, one bounded step with hysteresis
  (``plan_correction`` flight events); the Autoscaler defers its global
  scale-up while a corrector is attached.

Surfaces: ``storm-tpu plan`` CLI, ``GET /api/v1/topology/{name}/plan``.
Config: ``[plan]``
(:class:`storm_tpu.config.PlanConfig`).
"""

from __future__ import annotations

from storm_tpu.plan.corrector import PlanCorrector
from storm_tpu.plan.model import Candidate, CostModel, Target, unwrap_snapshot
from storm_tpu.plan.solver import Plan, SolveResult, solve

__all__ = [
    "Candidate",
    "CostModel",
    "Plan",
    "PlanCorrector",
    "SolveResult",
    "Target",
    "solve",
    "unwrap_snapshot",
]
