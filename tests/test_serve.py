"""gRPC worker tests: Arrow tensor round trip, JSON contract, error codes,
and the remote-operator topology (north-star split)."""

import json

import grpc
import numpy as np
import pytest

from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
from storm_tpu.serve import InferenceClient, InferenceWorker
from storm_tpu.serve.marshal import decode_tensor, encode_tensor


def test_marshal_roundtrip_zero_copy():
    x = np.random.rand(4, 8, 8, 1).astype(np.float32)
    buf = encode_tensor(x)
    back = decode_tensor(buf)
    np.testing.assert_array_equal(back, x)
    assert back.dtype == np.float32


@pytest.fixture(scope="module")
def worker():
    w = InferenceWorker(
        ModelConfig(name="lenet5", dtype="float32", input_shape=(28, 28, 1)),
        ShardingConfig(data_parallel=1),
        BatchConfig(max_batch=16, buckets=(16,)),
        port=0,  # ephemeral
    ).start()
    yield w
    w.stop()


@pytest.fixture()
def client(worker):
    with InferenceClient(f"localhost:{worker.port}") as c:
        yield c


def test_worker_info(client):
    info = client.info()
    assert info["model"] == "lenet5"
    assert info["input_shape"] == [28, 28, 1]
    assert info["num_classes"] == 10


def test_worker_predict_arrow(client):
    x = np.random.rand(3, 28, 28, 1).astype(np.float32)
    out = client.predict(x)
    assert out.shape == (3, 10)
    np.testing.assert_allclose(out.sum(-1), np.ones(3), atol=1e-4)


def test_worker_predict_json(client):
    x = np.random.rand(2, 28, 28, 1)
    resp = client.predict_json(json.dumps({"instances": x.tolist()}))
    preds = json.loads(resp)["predictions"]
    assert len(preds) == 2 and len(preds[0]) == 10


def test_worker_rejects_bad_shape(client):
    with pytest.raises(grpc.RpcError) as ei:
        client.predict(np.zeros((1, 5, 5, 1), np.float32))
    assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT


def test_worker_rejects_garbage_tensor(worker):
    ch = grpc.insecure_channel(f"localhost:{worker.port}")
    call = ch.unary_unary("/storm_tpu.Inference/Predict")
    with pytest.raises(grpc.RpcError) as ei:
        call(b"not an arrow tensor")
    assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    ch.close()


def test_worker_rejects_bad_json(client):
    with pytest.raises(grpc.RpcError) as ei:
        client.predict_json('{"instances": [[1,2],[3]]}')
    assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT


def test_remote_bolt_topology(worker, run):
    """Full streaming topology where inference crosses the gRPC boundary."""
    import asyncio

    from storm_tpu.api.schema import decode_predictions
    from storm_tpu.config import Config, OffsetsConfig
    from storm_tpu.connectors import BrokerSink, BrokerSpout, MemoryBroker
    from storm_tpu.runtime import TopologyBuilder
    from storm_tpu.runtime.cluster import AsyncLocalCluster
    from storm_tpu.serve.remote_bolt import RemoteInferenceBolt

    async def go():
        broker = MemoryBroker(default_partitions=2)
        cfg = Config()
        tb = TopologyBuilder()
        tb.set_spout(
            "in", BrokerSpout(broker, "input", OffsetsConfig(policy="earliest", max_behind=None)), 1
        )
        tb.set_bolt(
            "infer",
            RemoteInferenceBolt(
                f"localhost:{worker.port}",
                BatchConfig(max_batch=8, max_wait_ms=10, buckets=(8,)),
            ),
            2,
        ).shuffle_grouping("in")
        tb.set_bolt("out", BrokerSink(broker, "output", cfg.sink), 1).shuffle_grouping("infer")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("remote", cfg, tb.build())
        for i in range(5):
            broker.produce("input", json.dumps(
                {"instances": np.random.rand(1, 28, 28, 1).tolist()}
            ))
        deadline = asyncio.get_event_loop().time() + 30
        while asyncio.get_event_loop().time() < deadline:
            if broker.topic_size("output") >= 5:
                break
            await asyncio.sleep(0.05)
        outs = broker.drain_topic("output")
        await cluster.shutdown()
        return outs

    outs = run(go(), timeout=60)
    assert len(outs) == 5
    for r in outs:
        assert decode_predictions(r.value).data.shape == (1, 10)


# ---- cross-caller batching ---------------------------------------------------


def test_cross_caller_batching_coalesces():
    """8 concurrent clients -> fewer device dispatches than calls, same
    results as unbatched."""
    import threading

    w = InferenceWorker(
        ModelConfig(name="lenet5", dtype="float32", input_shape=(28, 28, 1)),
        ShardingConfig(data_parallel=1),
        BatchConfig(max_batch=64, buckets=(64,)),
        port=0,
    ).start()
    try:
        xs = [np.random.rand(2, 28, 28, 1).astype(np.float32) for _ in range(8)]
        want = [w.engine.predict(x) for x in xs]
        before = w._queue.batches

        outs = [None] * 8
        errs = []

        def call(i):
            try:
                with InferenceClient(f"localhost:{w.port}") as c:
                    outs[i] = c.predict(xs[i])
            except Exception as e:  # pragma: no cover
                errs.append(e)

        ts = [threading.Thread(target=call, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        assert not errs
        for got, exp in zip(outs, want):
            np.testing.assert_allclose(got, exp, rtol=1e-5, atol=1e-5)
        assert 1 <= w._queue.batches - before < 8
    finally:
        w.stop()


class _HeldEngine:
    """dispatch-protocol engine whose batches the TEST finishes; a row's
    answer is its own first value, so a caller can tell its rows."""

    input_shape = (2,)
    ring_capacity = 1

    def __init__(self):
        self.batches = []  # (handle, parts)

    def dispatch(self, parts):
        from storm_tpu.infer.engine import InflightBatch

        n = sum(int(p.shape[0]) for p in parts)
        h = InflightBatch(n, n)
        h.timings = {}
        self.batches.append((h, parts))
        return h

    def finish(self, k, error=None):
        h, parts = self.batches[k]
        if error is not None:
            h.future.set_exception(error)
        else:
            h.future.set_result(np.concatenate(parts)[:, :1].repeat(3, 1))


def _wait(cond, what):
    import time

    t0 = time.perf_counter()
    while not cond():
        assert time.perf_counter() - t0 < 10.0, what
        time.sleep(0.002)


@pytest.mark.parametrize("fault", [False, True],
                         ids=["each_gets_its_rows", "error_reaches_every_caller"])
def test_concurrent_callers_share_one_device_batch(fault):
    """Two RPCs that arrive while the device works leave in ONE batch of
    the engine's queue; each caller gets back exactly its own rows, or,
    when the engine fails that batch, the engine's error."""
    import threading

    eng = _HeldEngine()
    w = InferenceWorker(
        engine=eng, port=0,
        batch=BatchConfig(max_batch=8, buckets=(8,), max_wait_ms=10_000,
                          eager=True))
    hold = w._queue.submit(np.zeros((1, 2), np.float32), source="hold")
    _wait(lambda: len(eng.batches) == 1, "the engine's one slot is taken")
    got = {}

    def call(i, rows):  # what both RPC handlers run
        try:
            got[i] = w._run_predict(np.full((rows, 2), i, np.float32))
        except Exception as e:
            got[i] = e

    callers = [threading.Thread(target=call, args=(i, rows))
               for i, rows in ((1, 2), (2, 3))]
    for t in callers:
        t.start()
    _wait(lambda: len(w._queue) == 5, "both callers' rows are queued")
    eng.finish(0)
    _wait(lambda: len(eng.batches) == 2, "the freed slot refills")
    assert sorted(p.shape[0] for p in eng.batches[1][1]) == [2, 3]
    eng.finish(1, error=RuntimeError("boom") if fault else None)
    for t in callers:
        t.join(10)
    hold.future.result(timeout=1)
    assert len(eng.batches) == 2, "one device batch served both callers"
    for i, rows in ((1, 2), (2, 3)):
        if fault:
            assert isinstance(got[i], RuntimeError) and "boom" in str(got[i])
        else:
            np.testing.assert_array_equal(
                got[i], np.full((rows, 3), i, np.float32))


def test_caller_with_more_rows_than_max_batch_gets_all_of_them():
    """One RPC of 10 rows against ``max_batch`` 4: the record ships whole
    through the engine's queue and every row is answered."""
    w = InferenceWorker(
        ModelConfig(name="lenet5", dtype="float32", input_shape=(28, 28, 1)),
        ShardingConfig(data_parallel=1),
        BatchConfig(max_batch=4, buckets=(4,)),
        port=0)
    x = np.random.rand(10, 28, 28, 1).astype(np.float32)
    out = w._run_predict(x)
    assert out.shape == (10, 10)
    np.testing.assert_allclose(out, w.engine.predict(x), rtol=1e-5, atol=1e-5)


def test_serve_cli_has_no_cross_batch_window(capsys):
    """``--cross-batch-ms`` went with the leader window it set
    (docs/MIGRATION.md): callers coalesce in the engine's queue with no
    flag, and the old one is refused by the parser."""
    from storm_tpu.main import main

    with pytest.raises(SystemExit) as e:
        main(["serve", "--cross-batch-ms", "5"])
    assert e.value.code == 2
    assert "--cross-batch-ms" in capsys.readouterr().err


# ---- JVM-boundary conformance (VERDICT r1 next #7) ---------------------------


def test_jvm_conformance_golden_fixtures():
    """The checked-in golden bytes for /storm_tpu.Inference/Predict must
    (a) decode through OUR stack to the documented arrays, (b) be accepted
    by an independent Arrow implementation (pyarrow, standing in for the
    Arrow Java reader a Storm bolt would use), and (c) be reproduced
    byte-for-byte by the production C++ marshaller — so a third party can
    implement InferenceBolt.java:80-86 against the service from the docs
    and fixtures alone (docs/JVM_CLIENT.md)."""
    import pathlib

    import numpy as np

    from storm_tpu.serve.marshal import decode_tensor, encode_tensor
    from tests.fixtures.jvm_conformance.generate import (request_array,
                                                         response_array)

    here = pathlib.Path(__file__).parent / "fixtures" / "jvm_conformance"
    req = (here / "predict_request.arrow").read_bytes()
    resp = (here / "predict_response.arrow").read_bytes()

    # (a) our decoder
    x = decode_tensor(req)
    assert x.shape == (2, 28, 28, 1) and x.dtype == np.float32
    np.testing.assert_array_equal(x, request_array())
    y = decode_tensor(resp)
    assert y.shape == (2, 10) and y.dtype == np.float32
    np.testing.assert_array_equal(y, response_array())
    np.testing.assert_allclose(y.sum(axis=1), 1.0, atol=1e-5)

    # (b) independent Arrow reader accepts our wire bytes
    pa = pytest.importorskip("pyarrow")
    np.testing.assert_array_equal(
        pa.ipc.read_tensor(pa.py_buffer(req)).to_numpy(), request_array())
    np.testing.assert_array_equal(
        pa.ipc.read_tensor(pa.py_buffer(resp)).to_numpy(), response_array())

    # (c) our encoder reproduces the fixtures exactly (wire determinism);
    # meaningful only on the production C++ path — the pyarrow fallback is
    # wire-compatible but not byte-identical (flatbuffer field order).
    from storm_tpu.native import encode_tensor_native

    if encode_tensor_native(request_array()) is not None:
        assert encode_tensor(request_array()) == req
        assert encode_tensor(response_array()) == resp


def test_jvm_conformance_service_end_to_end():
    """A 'JVM client' (pyarrow-encoded request, as Arrow Java would emit)
    calls the live Predict service; the response decodes with pyarrow and
    matches the engine's own output — the full north-star boundary."""
    pa = pytest.importorskip("pyarrow")
    import numpy as np

    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.serve.worker import InferenceWorker
    from tests.fixtures.jvm_conformance.generate import request_array

    worker = InferenceWorker(
        ModelConfig(name="lenet5", dtype="float32", input_shape=(28, 28, 1)),
        ShardingConfig(data_parallel=0),
        BatchConfig(max_batch=8, buckets=(8,)),
        port=0,
    )
    worker.start()
    try:
        import grpc

        # encode the request like a JVM Arrow writer (NOT our marshaller)
        sink = pa.BufferOutputStream()
        pa.ipc.write_tensor(pa.Tensor.from_numpy(request_array()), sink)
        req = sink.getvalue().to_pybytes()
        chan = grpc.insecure_channel(f"127.0.0.1:{worker.port}")
        out = chan.unary_unary(
            "/storm_tpu.Inference/Predict",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )(req)
        y = pa.ipc.read_tensor(pa.py_buffer(out)).to_numpy()
        assert y.shape == (2, 10)
        np.testing.assert_allclose(y.sum(axis=1), 1.0, atol=1e-4)
        want = worker.engine.predict(request_array())
        np.testing.assert_allclose(y, want, atol=1e-5)
        chan.close()
    finally:
        worker.stop()
