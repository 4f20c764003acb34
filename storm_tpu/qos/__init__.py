"""Admission control & QoS under overload.

The framework's only overload response used to be the autoscaler
(runtime/autoscale.py) — but scale-out takes seconds and capacity is
finite; when offered load exceeds capacity, every queue grows and every
tenant's latency blows through the SLO together. This package adds the
layer in front of the engine that InferLine/BatchGen argue for
(PAPERS.md): admission at the edge, priority-aware batch formation, and
load shedding that fires *before* the autoscaler.

Wired by ``QosConfig`` (config.py):

- :mod:`storm_tpu.qos.admission` — per-tenant token-bucket rate limiting
  and tenant/lane classification at the spout edge (records ride their
  broker key as ``tenant:lane``);
- earliest-deadline-first batch formation lives in the engine's queue
  (:mod:`storm_tpu.infer.continuous`): high-priority records preempt
  queued best-effort ones instead of FIFO-queuing behind them;
- :mod:`storm_tpu.qos.shedding` — hysteresis load-shed controller driven
  by inference inbox depth, batch-wait time, and the sink's SLO-breach
  rate; publishes its level through the metrics registry (gauge
  ``("qos", "shed_level")``) so the spout and operator read it without
  new plumbing, and records every decision to the flight recorder.
"""

from storm_tpu.qos.admission import AdmissionController, TokenBucket
from storm_tpu.qos.shedding import LoadShedController, ShedPolicy

#: The metrics-registry address every QoS participant reads/writes the
#: current shed level through: controller sets, spout/operator read.
SHED_COMPONENT = "qos"
SHED_GAUGE = "shed_level"

__all__ = [
    "AdmissionController",
    "LoadShedController",
    "SHED_COMPONENT",
    "SHED_GAUGE",
    "ShedPolicy",
    "TokenBucket",
]
