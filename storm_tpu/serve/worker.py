"""gRPC inference worker: the co-located TPU service (north-star boundary).

Replaces the reference's in-process JNI engine (layer 4) with a service any
front-end — including a JVM Storm ``InferenceBolt`` — can dispatch batches
to over localhost gRPC, preserving tuple-ack semantics on the caller side
(BASELINE.json north star; SURVEY.md §7 step 7).

Methods (raw-bytes gRPC, no protoc codegen needed):

- ``/storm_tpu.Inference/Predict``  — Arrow IPC tensor in (N, H, W, C),
  Arrow IPC tensor out (N, K). Zero-copy marshalling both ways
  (:mod:`storm_tpu.serve.marshal`).
- ``/storm_tpu.Inference/PredictJson`` — the ``{"instances": ...}`` /
  ``{"predictions": ...}`` wire contract for HTTP-era clients.
- ``/storm_tpu.Inference/Info`` — model metadata JSON (name, input shape,
  classes, mesh) — replacing the reference's hard-coded tensor names
  (InferenceBolt.java:83-86) with discoverable metadata.

Errors map to gRPC status codes: malformed payloads -> INVALID_ARGUMENT,
engine failures -> INTERNAL.
"""

from __future__ import annotations

import json
import logging
from concurrent import futures
from typing import Optional

import grpc
import numpy as np

from storm_tpu.api.schema import SchemaError, decode_instances, encode_predictions
from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
from storm_tpu.infer.continuous import continuous_for
from storm_tpu.infer.engine import InferenceEngine, shared_engine
from storm_tpu.serve.marshal import decode_tensor, encode_tensor

log = logging.getLogger("storm_tpu.serve")

_SERVICE = "storm_tpu.Inference"


class _Handler(grpc.GenericRpcHandler):
    def __init__(self, worker: "InferenceWorker") -> None:
        self._worker = worker
        self._methods = {
            f"/{_SERVICE}/Predict": worker._predict,
            f"/{_SERVICE}/PredictJson": worker._predict_json,
            f"/{_SERVICE}/Info": worker._info,
        }

    def service(self, call_details):
        fn = self._methods.get(call_details.method)
        if fn is None:
            return None
        return grpc.unary_unary_rpc_method_handler(fn)


class InferenceWorker:
    def __init__(
        self,
        model: Optional[ModelConfig] = None,
        sharding: Optional[ShardingConfig] = None,
        batch: Optional[BatchConfig] = None,
        engine: Optional[InferenceEngine] = None,
        port: int = 50051,
        max_workers: int = 8,
    ) -> None:
        self.model_cfg = model or ModelConfig()
        batch = batch or BatchConfig()
        self.engine = engine or shared_engine(
            self.model_cfg, sharding or ShardingConfig(), batch)
        # Every RPC submits its rows into the engine's one queue
        # (infer/continuous.py) and blocks on its own slice: concurrent
        # callers — several JVM Storm executors dispatching to one
        # co-located TPU worker, the north-star deployment — coalesce into
        # one device batch there, with a topology's traffic if the process
        # hosts one.
        self._queue = continuous_for(self.engine, batch)
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=max_workers),
            options=[
                ("grpc.max_receive_message_length", 256 * 1024 * 1024),
                ("grpc.max_send_message_length", 256 * 1024 * 1024),
            ],
        )
        self._server.add_generic_rpc_handlers((_Handler(self),))
        self.port = self._server.add_insecure_port(f"[::]:{port}")

    # ---- methods -------------------------------------------------------------

    def _predict(self, request: bytes, context: grpc.ServicerContext) -> bytes:
        try:
            x = decode_tensor(request)
        except Exception as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, f"bad tensor: {e}")
        if tuple(x.shape[1:]) != self.engine.input_shape:
            context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"instance shape {tuple(x.shape[1:])} != model input "
                f"{self.engine.input_shape}",
            )
        try:
            out = self._run_predict(np.asarray(x, np.float32))
        except Exception as e:  # pragma: no cover - engine failure
            log.exception("predict failed")
            context.abort(grpc.StatusCode.INTERNAL, str(e))
        return encode_tensor(out)

    def _run_predict(self, x: np.ndarray) -> np.ndarray:
        return self._queue.submit(x, source="serve").future.result()

    def _predict_json(self, request: bytes, context: grpc.ServicerContext) -> bytes:
        try:
            inst = decode_instances(request)
            if tuple(inst.data.shape[1:]) != self.engine.input_shape:
                raise SchemaError(
                    f"instance shape {tuple(inst.data.shape[1:])} != model "
                    f"input {self.engine.input_shape}"
                )
        except SchemaError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        try:
            out = self._run_predict(inst.data)
        except Exception as e:  # pragma: no cover
            log.exception("predict failed")
            context.abort(grpc.StatusCode.INTERNAL, str(e))
        return encode_predictions(out).encode("utf-8")

    def _info(self, request: bytes, context: grpc.ServicerContext) -> bytes:
        return json.dumps(
            {
                "model": self.model_cfg.name,
                "input_shape": list(self.engine.input_shape),
                "num_classes": self.model_cfg.num_classes,
                "dtype": self.model_cfg.dtype,
                "mesh": dict(self.engine.mesh.shape),
                "buckets": list(self.engine.batch_cfg.buckets),
            }
        ).encode("utf-8")

    # ---- lifecycle -----------------------------------------------------------

    def start(self) -> "InferenceWorker":
        self._server.start()
        log.info("inference worker on port %d (model=%s)", self.port, self.model_cfg.name)
        return self

    def stop(self, grace: float = 5.0) -> None:
        self._server.stop(grace).wait()

    def wait(self) -> None:  # pragma: no cover - daemon mode
        self._server.wait_for_termination()
