"""RemoteInferenceBolt: inference operator that dispatches to the gRPC
worker instead of an in-process engine — the in-tree realization of the
north-star split (BASELINE.json): a front-end runtime (here our own; in the
reference architecture a JVM Storm bolt) keeps tuple-ack semantics while
batches cross a localhost gRPC + Arrow boundary to the TPU worker process.

Identical streaming behavior to :class:`storm_tpu.infer.InferenceBolt`
(batches cut from the engine facade's queue, deferred acks,
dead-lettering); only the engine call is remote."""

from __future__ import annotations

from typing import Optional

from storm_tpu.config import BatchConfig
from storm_tpu.infer.operator import InferenceBolt
from storm_tpu.runtime.base import TopologyContext, OutputCollector
from storm_tpu.serve.client import InferenceClient


class RemoteInferenceBolt(InferenceBolt):
    def __init__(
        self,
        target: str = "localhost:50051",
        batch: Optional[BatchConfig] = None,
        warmup: bool = False,
        qos=None,
        passthrough=(),
    ) -> None:
        # qos/passthrough forward unchanged: EDF lane formation and the
        # qos_lane ride-through happen in the queue/operator layer,
        # which is identical on both sides of the gRPC boundary — the
        # fleet scorecard's serve-path cells need per-lane e2e histograms
        # from a remote topology too.
        super().__init__(batch=batch, warmup=warmup, qos=qos,
                         passthrough=passthrough)
        self.target = target

    opens_device = False  # the engine lives in the serve worker's process

    def clone(self) -> "RemoteInferenceBolt":
        return RemoteInferenceBolt(self.target, self.batch_cfg, self._warmup,
                                   self.qos, self.passthrough)

    def prepare(self, context: TopologyContext, collector: OutputCollector) -> None:
        # Skip the in-process engine entirely; resolve shape from the worker.
        self.client = InferenceClient(self.target)
        info = self.client.info()
        self._input_shape = tuple(info["input_shape"])

        class _RemoteEngine:
            """Engine facade: predict() over gRPC; shape from Info."""

            input_shape = self._input_shape
            client = self.client

            def predict(self_inner, x):
                return self.client.predict(x)

            def warmup(self_inner):
                pass

        self._engine = _RemoteEngine()
        super().prepare(context, collector)

    def cleanup(self) -> None:
        self.client.close()
