"""Engine tests (SURVEY.md §7 step 5); batch formation is tests/test_continuous.py."""

import jax
import numpy as np
import pytest

from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
from storm_tpu.infer.engine import InferenceEngine
from storm_tpu.models import build_model
from storm_tpu.models.registry import init_params


def test_engine_handles_oversized_batch():
    eng = InferenceEngine(
        ModelConfig(name="lenet5", dtype="float32", input_shape=(28, 28, 1)),
        ShardingConfig(data_parallel=1),
        BatchConfig(max_batch=8, buckets=(8,)),
    )
    out = eng.predict(np.zeros((11, 28, 28, 1), np.float32))  # > max_batch
    assert out.shape == (11, 10)


# ---- engine ------------------------------------------------------------------


@pytest.fixture(scope="module")
def lenet_engine():
    return InferenceEngine(
        ModelConfig(name="lenet5", dtype="float32", input_shape=(28, 28, 1)),
        ShardingConfig(data_parallel=0),  # all 8 virtual CPU devices
        BatchConfig(max_batch=16, buckets=(8, 16)),
    )


def test_engine_mesh_uses_all_devices(lenet_engine):
    assert lenet_engine.mesh.devices.size == len(jax.devices())


def test_engine_predict_matches_direct_apply(lenet_engine):
    model = build_model("lenet5")
    params, state = init_params(model, seed=0)
    x = np.asarray(
        jax.random.normal(jax.random.PRNGKey(3), (5, 28, 28, 1)), np.float32
    )
    got = lenet_engine.predict(x)
    logits, _ = model.apply(params, state, x)
    want = np.asarray(jax.nn.softmax(logits, -1))
    assert got.shape == (5, 10)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got.sum(-1), np.ones(5), atol=1e-5)


def test_engine_pads_to_mesh_divisible(lenet_engine):
    dp = lenet_engine.mesh.devices.size
    padded = lenet_engine.pad_batch(1)
    assert padded % dp == 0
    # Result sliced back to the true batch size.
    out = lenet_engine.predict(np.zeros((3, 28, 28, 1), np.float32))
    assert out.shape == (3, 10)


def test_engine_warmup_compiles_buckets(lenet_engine):
    lenet_engine.warmup()
    assert lenet_engine.pad_batch(8) in lenet_engine.compiled_batches
    assert lenet_engine.pad_batch(16) in lenet_engine.compiled_batches


def test_engine_bf16_path():
    eng = InferenceEngine(
        ModelConfig(name="lenet5", dtype="bfloat16", input_shape=(28, 28, 1)),
        ShardingConfig(data_parallel=1),
        BatchConfig(max_batch=8, buckets=(8,)),
    )
    out = eng.predict(np.random.randn(2, 28, 28, 1).astype(np.float32))
    assert out.dtype == np.float32  # probabilities come back f32
    np.testing.assert_allclose(out.sum(-1), np.ones(2), atol=1e-2)


def test_engine_uint8_transfer_matches_f32():
    """uint8 wire quantization (ModelConfig.transfer_dtype) must stay close to
    the full-precision path: inputs cross the link as 1 byte/elem + a per-batch
    (scale, offset), dequantized on device inside the jit program."""
    rng = np.random.RandomState(0)
    x = rng.rand(6, 28, 28, 1).astype(np.float32)  # pixel-like [0, 1)
    f32 = InferenceEngine(
        ModelConfig(name="lenet5", dtype="float32", input_shape=(28, 28, 1)),
        ShardingConfig(data_parallel=1),
        BatchConfig(max_batch=8, buckets=(8,)),
    )
    q8 = InferenceEngine(
        ModelConfig(
            name="lenet5", dtype="float32", input_shape=(28, 28, 1),
            transfer_dtype="uint8",
        ),
        ShardingConfig(data_parallel=1),
        BatchConfig(max_batch=8, buckets=(8,)),
    )
    want = f32.predict(x)
    got = q8.predict(x)
    assert got.shape == want.shape
    np.testing.assert_allclose(got.sum(-1), np.ones(6), atol=1e-4)
    np.testing.assert_allclose(got, want, atol=0.02)


def test_engine_uint8_constant_input_no_nan():
    """Degenerate range (hi == lo) must not divide by zero."""
    eng = InferenceEngine(
        ModelConfig(
            name="lenet5", dtype="float32", input_shape=(28, 28, 1),
            transfer_dtype="uint8",
        ),
        ShardingConfig(data_parallel=1),
        BatchConfig(max_batch=8, buckets=(8,)),
    )
    out = eng.predict(np.full((2, 28, 28, 1), 0.5, np.float32))
    assert np.isfinite(out).all()


def test_model_config_rejects_bad_transfer_dtype():
    with pytest.raises(ValueError):
        ModelConfig(transfer_dtype="int4")


# ---- weight-only int8 quantization (w8a16) -----------------------------------


def test_int8_weights_predictions_close_to_float():
    import numpy as np

    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer.engine import InferenceEngine

    x = np.random.RandomState(0).rand(4, 28, 28, 1).astype(np.float32)
    outs = {}
    for weights in ("float", "int8"):
        eng = InferenceEngine(
            ModelConfig(name="lenet5", input_shape=(28, 28, 1), dtype="float32",
                        weights=weights),
            ShardingConfig(data_parallel=0),
            BatchConfig(max_batch=4, buckets=(4,)),
        )
        outs[weights] = eng.predict(x)
    np.testing.assert_allclose(outs["float"].sum(axis=1), 1.0, atol=1e-4)
    # per-channel symmetric int8 stays close on softmax outputs
    assert np.max(np.abs(outs["float"] - outs["int8"])) < 0.05
    # argmax must agree wherever the float decision is decisive (random-init
    # outputs are near-uniform; quantization may flip exact ties)
    top2 = np.sort(outs["float"], axis=1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > 0.05
    assert np.all(
        np.argmax(outs["float"], 1)[decisive]
        == np.argmax(outs["int8"], 1)[decisive]
    )


def test_int8_weights_shrink_param_bytes():
    import jax
    import numpy as np

    from storm_tpu.infer.engine import dequantize_params, quantize_params
    from storm_tpu.models import build_model
    from storm_tpu.models.registry import init_params

    model = build_model("lenet5")
    params, _ = init_params(model, seed=0)

    def nbytes(tree):
        return sum(np.asarray(l).nbytes
                   for l in jax.tree.leaves(tree))

    q = quantize_params(params)
    assert nbytes(q) < 0.4 * nbytes(params)  # f32 -> int8 + small scales
    # dequant round trip stays within one quantization step per channel
    deq = dequantize_params(q, np.float32)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(deq)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if a.ndim >= 2:
            step = np.max(np.abs(a)) / 127.0
            assert np.max(np.abs(a - b)) <= step + 1e-6
        else:
            np.testing.assert_array_equal(a, b)  # biases untouched


def test_int8_weights_bf16_keeps_compute_dtype():
    """Non-quantized leaves are cast to the compute dtype: an f32 bias
    would promote every activation back to f32."""
    import jax
    import jax.numpy as jnp

    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer.engine import InferenceEngine, _is_qleaf

    eng = InferenceEngine(
        ModelConfig(name="lenet5", input_shape=(28, 28, 1), dtype="bfloat16",
                    weights="int8"),
        ShardingConfig(data_parallel=0),
        BatchConfig(max_batch=4, buckets=(4,)),
    )
    for leaf in jax.tree.leaves(
            eng.params, is_leaf=lambda l: _is_qleaf(l)):
        if _is_qleaf(leaf):
            assert leaf["__q"].dtype == jnp.int8
        else:
            assert leaf.dtype != jnp.float32, "f32 leaf would promote activations"


@pytest.mark.slow
def test_int8_fused_matches_int8():
    """"int8_fused" (Pallas fused dequant-matmul on TPU; jnp fallback here)
    quantizes identically to "int8" — outputs must agree tightly on a
    dense-only model (mixer: every matmul goes through layers.dense)."""
    import numpy as np

    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer.engine import InferenceEngine

    x = np.random.RandomState(1).rand(4, 32, 32, 3).astype(np.float32)
    outs = {}
    for weights in ("int8", "int8_fused"):
        eng = InferenceEngine(
            ModelConfig(name="mixer_tiny", input_shape=(32, 32, 3),
                        dtype="float32", weights=weights),
            ShardingConfig(data_parallel=0),
            BatchConfig(max_batch=4, buckets=(4,)),
        )
        outs[weights] = eng.predict(x)
    np.testing.assert_allclose(outs["int8"], outs["int8_fused"],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_int8_fused_moe_model_runs():
    """Regression: the keep-dense predicate must be path-based — MoE params
    (2-D gate/biases consumed as raw arrays, not via layers.dense) crashed
    the rank-based version."""
    import numpy as np

    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer.engine import InferenceEngine

    x = np.random.RandomState(2).rand(4, 32, 32, 3).astype(np.float32)
    eng = InferenceEngine(
        ModelConfig(name="moe_vit_tiny", input_shape=(32, 32, 3),
                    dtype="float32", weights="int8_fused"),
        ShardingConfig(data_parallel=0),
        BatchConfig(max_batch=4, buckets=(4,)),
    )
    out = eng.predict(x)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, atol=1e-4)


_CACHE_PROBE = """
import json, numpy as np
from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
from storm_tpu.infer.engine import InferenceEngine, enable_compile_cache
resolved = enable_compile_cache()
if {warm}:
    eng = InferenceEngine(
        ModelConfig(name="lenet5", input_shape=(28, 28, 1), dtype="float32"),
        ShardingConfig(data_parallel=0),
        BatchConfig(max_batch=4, buckets=(4,)))
    eng.warmup()
print(json.dumps({{"dir": resolved}}))
"""


def _cache_probe(env_dir, warm):
    """Resolve (and optionally fill) the compile cache in a fresh process:
    jax latches the directory at the first compile, so every case needs
    its own interpreter."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    p = subprocess.run([sys.executable, "-c", _CACHE_PROBE.format(warm=warm)],
                       env=env, capture_output=True, text=True, timeout=100)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])["dir"]


def _listing(path):
    import os

    return sorted(os.listdir(path)) if os.path.isdir(path) else []


@pytest.mark.parametrize("placed", [True, False],
                         ids=["env_places_it", "fixed_default"])
def test_compile_cache_dir_populated(tmp_path, placed):
    """The persistent compile cache is placed from outside. With
    JAX_COMPILATION_CACHE_DIR set, the engine's warm-up fills that
    directory and no other; unset, the cache resolves to one fixed path
    inside the checkout, equal across processes (the path is part of the
    cache key: a directory that moves never hits)."""
    import os

    from storm_tpu.infer.engine import DEFAULT_COMPILE_CACHE_DIR

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    if placed:
        cache = tmp_path / "xla-cache"
        default_before = _listing(DEFAULT_COMPILE_CACHE_DIR)
        assert _cache_probe(cache, warm=True) == str(cache)
        assert any(cache.iterdir()), "warm-up wrote nothing to the cache"
        assert _listing(DEFAULT_COMPILE_CACHE_DIR) == default_before
    else:
        first = _cache_probe(None, warm=False)
        assert first == DEFAULT_COMPILE_CACHE_DIR
        assert _cache_probe(None, warm=False) == first


def test_live_model_swap_under_traffic(run):
    """swap_model rolls a running inference component onto a new engine
    with zero downtime: traffic before, during, and after all acks; the
    new config is live; predictions change (different seed => different
    random-init weights)."""
    import asyncio
    import json as _json

    import numpy as np

    from storm_tpu.config import BatchConfig, Config, ModelConfig
    from storm_tpu.connectors import BrokerSink, BrokerSpout, MemoryBroker
    from storm_tpu.infer import InferenceBolt
    from storm_tpu.runtime import TopologyBuilder
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    async def go():
        broker = MemoryBroker()
        cfg = Config()
        tb = TopologyBuilder()
        tb.set_spout("spout", BrokerSpout(broker, "in"), parallelism=1)
        tb.set_bolt("infer", InferenceBolt(
            ModelConfig(name="lenet5", input_shape=(28, 28, 1),
                        dtype="float32", seed=0),
            BatchConfig(max_batch=8, max_wait_ms=10, buckets=(8,))),
            parallelism=2).shuffle_grouping("spout")
        tb.set_bolt("sink", BrokerSink(broker, "out", cfg.sink),
                    parallelism=1).shuffle_grouping("infer")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("swap", cfg, tb.build())

        x = np.random.RandomState(0).rand(1, 28, 28, 1).tolist()
        payload = _json.dumps({"instances": x})

        async def feed_and_collect(n):
            start = broker.topic_size("out")
            for _ in range(n):
                broker.produce("in", payload)
            for _ in range(200):
                if broker.topic_size("out") >= start + n:
                    break
                await asyncio.sleep(0.05)
            assert broker.topic_size("out") == start + n
            return _json.loads(
                broker.drain_topic("out")[-1].value)["predictions"]

        before = await feed_and_collect(4)
        new_cfg = await rt.swap_model("infer", {"seed": 123})
        assert new_cfg.seed == 123
        after = await feed_and_collect(4)
        assert not np.allclose(before, after), "new weights must be live"
        # every live instance switched
        for e in rt.bolt_execs["infer"]:
            assert e.bolt.model_cfg.seed == 123
        # unknown component / non-inference component / bad field
        with pytest.raises(KeyError):
            await rt.swap_model("nope", {"seed": 1})
        with pytest.raises(TypeError):
            await rt.swap_model("sink", {"seed": 1})
        with pytest.raises(TypeError):
            await rt.swap_model("infer", {"not_a_field": 1})
        await cluster.shutdown()

    run(go(), timeout=120)


def test_engine_inventory_tracks_coresident_models():
    """engine_inventory sums per-replica HBM param bytes across the
    process's live engines (the multi-model budget, BASELINE config 5)."""
    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer.engine import engine_inventory, shared_engine

    e1 = shared_engine(
        ModelConfig(name="lenet5", input_shape=(28, 28, 1), dtype="float32"),
        ShardingConfig(data_parallel=0), BatchConfig(max_batch=4, buckets=(4,)))
    e2 = shared_engine(
        ModelConfig(name="mixer_tiny", input_shape=(32, 32, 3),
                    dtype="float32"),
        ShardingConfig(data_parallel=0), BatchConfig(max_batch=4, buckets=(4,)))
    inv = engine_inventory()
    names = {r["model"] for r in inv["engines"]}
    assert {"lenet5", "mixer_tiny"} <= names
    assert e1.param_bytes() > 100_000  # lenet5 f32 ~ a few hundred KB
    assert inv["total_param_bytes"] >= e1.param_bytes() + e2.param_bytes()
    for r in inv["engines"]:
        assert r["param_bytes"] > 0


def test_engine_inventory_names_each_programs_kernels():
    """Per compiled bucket, the inventory says which form the ops' shape
    rules built the program with (on the CPU always the jnp path)."""
    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer.engine import engine_inventory, shared_engine

    eng = shared_engine(
        ModelConfig(name="vit_tiny", input_shape=(32, 32, 3), dtype="float32"),
        ShardingConfig(data_parallel=0),
        BatchConfig(max_batch=8, buckets=(2, 8)))
    eng.warmup()
    row = next(r for r in engine_inventory()["engines"]
               if r["model"] == "vit_tiny")
    dp = eng.mesh.shape[eng.data_axis]
    # steps this short walk the list of blocks (models/vit.py
    # _SCAN_MIN_TOKENS); a long step's program would say blocks=scan
    assert row["programs"] == {
        str(eng.pad_batch(b)): "blocks=unrolled, attention=xla"
        for b in (2, 8)}, (row, dp)
    lenet = shared_engine(
        ModelConfig(name="lenet5", input_shape=(28, 28, 1), dtype="float32"),
        ShardingConfig(data_parallel=0), BatchConfig(max_batch=4, buckets=(4,)))
    lenet.warmup()
    row = next(r for r in engine_inventory()["engines"]
               if r["model"] == "lenet5")
    assert set(row["programs"].values()) == {""}  # no op with a shape rule


def test_eager_dispatch_low_latency_and_batching_under_load(run):
    """eager=True: an idle device gets records immediately (no max_wait
    aging); when all slots are busy, arrivals accumulate into one batch."""
    import asyncio

    import numpy as np

    from storm_tpu.config import BatchConfig, Config, ModelConfig
    from storm_tpu.infer import InferenceBolt
    from storm_tpu.runtime import TopologyBuilder, Spout, Values
    from storm_tpu.runtime.cluster import AsyncLocalCluster
    import json as _json

    class TwoShotSpout(Spout):
        def open(self, ctx, col):
            super().open(ctx, col)
            self.sent = 0

        async def next_tuple(self):
            if self.sent >= 2:
                return False
            self.sent += 1
            await self.collector.emit(Values([
                _json.dumps({"instances": np.zeros((1, 28, 28, 1)).tolist()})
            ]), msg_id=self.sent)
            return True

    async def go():
        tb = TopologyBuilder()
        tb.set_spout("s", TwoShotSpout(), 1)
        # Huge deadline: only eager dispatch can flush these records fast.
        tb.set_bolt("infer", InferenceBolt(
            ModelConfig(name="lenet5", input_shape=(28, 28, 1),
                        dtype="float32"),
            BatchConfig(max_batch=64, max_wait_ms=30_000.0, buckets=(64,),
                        eager=True)),
            1).shuffle_grouping("s")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("eager", Config(), tb.build())
        import time as _time
        t0 = _time.perf_counter()
        for _ in range(100):
            snap = rt.metrics.snapshot()
            done = snap["infer"].get("instances_inferred", 0)
            if done >= 2:
                break
            await asyncio.sleep(0.1)
        dt = _time.perf_counter() - t0
        assert done >= 2, f"only {done} inferred"
        assert dt < 15.0, f"eager dispatch should beat the 30s deadline, took {dt:.1f}s"
        await cluster.shutdown()

    run(go(), timeout=120)


def test_canary_swap_single_task(run):
    """swap_model(tasks=[0]) rolls one instance only; component_stats shows
    the mixed model versions; a follow-up full swap converges everyone."""
    from storm_tpu.config import BatchConfig, Config, ModelConfig
    from storm_tpu.connectors import BrokerSpout, MemoryBroker
    from storm_tpu.infer import InferenceBolt
    from storm_tpu.runtime import TopologyBuilder
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    async def go():
        broker = MemoryBroker()
        tb = TopologyBuilder()
        tb.set_spout("s", BrokerSpout(broker, "in"), 1)
        tb.set_bolt("infer", InferenceBolt(
            ModelConfig(name="lenet5", input_shape=(28, 28, 1),
                        dtype="float32", seed=0),
            BatchConfig(max_batch=4, max_wait_ms=10, buckets=(4,))),
            2).shuffle_grouping("s")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("canary", Config(), tb.build())

        new_cfg = await rt.swap_model("infer", {"seed": 7}, tasks=[0])
        assert new_cfg.seed == 7
        seeds = {e.task_index: e.bolt.model_cfg.seed
                 for e in rt.bolt_execs["infer"]}
        assert seeds == {0: 7, 1: 0}
        # prototype unchanged: rebalance-added executors keep the majority
        assert rt.topology.specs["infer"].obj.model_cfg.seed == 0
        rows = rt.component_stats("infer")
        models = {r["task"]: r["model"] for r in rows}
        assert models[0] != models[1] and "seed=7" in models[0]
        # unknown task errors
        with pytest.raises(KeyError):
            await rt.swap_model("infer", {"seed": 9}, tasks=[5])
        # full swap converges
        await rt.swap_model("infer", {"seed": 7})
        seeds = {e.task_index: e.bolt.model_cfg.seed
                 for e in rt.bolt_execs["infer"]}
        assert set(seeds.values()) == {7}
        await cluster.shutdown()

    run(go(), timeout=120)


def test_engine_cache_unload_and_lru_eviction():
    """shared_engine's process cache must be boundable: set a byte budget
    and LRU engines are dropped on insert; unload_engine drops a specific
    engine (e.g. after a completed model swap). Regression for ADVICE r1
    (engine.py:329 — cache grew monotonically across live swaps)."""
    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer.engine import (
        _ENGINES, set_engine_cache_limit, shared_engine, unload_engine)

    scfg = ShardingConfig(data_parallel=0)
    bcfg = BatchConfig(max_batch=4, buckets=(4,))

    def eng(seed):
        return shared_engine(
            ModelConfig(name="lenet5", input_shape=(28, 28, 1),
                        dtype="float32", seed=seed), scfg, bcfg)

    import gc

    def cached_seeds():
        # seed is a stable component of the cache key (position 6)
        return {k[6] for k in _ENGINES}

    gc.collect()  # drop cycles from earlier tests so orphan-detection is crisp
    try:
        e1 = eng(101)
        one_engine_bytes = e1.param_bytes()
        # Budget fits exactly one lenet5: inserting a second wants to evict
        # the LRU — but e1 is still referenced by this frame (a live bolt),
        # so it must be SKIPPED (evicting would free nothing and force a
        # duplicate rebuild on the next lookup).
        set_engine_cache_limit(one_engine_bytes + 1)
        e2 = eng(102)
        assert e1 in list(_ENGINES.values())  # referenced -> kept
        assert e2 in list(_ENGINES.values())
        # Drop the external reference (bolt gone / swap completed): now the
        # orphan is evictable on the next insert.
        del e1
        e3 = eng(103)
        cached = list(_ENGINES.values())
        assert e2 in cached and e3 in cached  # referenced -> kept
        assert 101 not in cached_seeds()  # the orphan was evicted
        # Cache hit returns the same object and keeps it resident.
        assert eng(102) is e2

        # Explicit unload (post-swap rollback-cache cleanup).
        assert unload_engine(e2) is True
        assert e2 not in list(_ENGINES.values())
        assert unload_engine(e2) is False  # already gone
    finally:
        set_engine_cache_limit(None)



def test_shared_engine_concurrent_requests_build_once():
    """N tasks requesting the same engine concurrently (e.g. a model swap
    broadcast to every bolt task) must cost ONE build — one param copy in
    HBM, one compile — with the others waiting on the in-progress build."""
    import threading
    import time as _time

    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer import engine as eng_mod

    builds = []
    orig_init = eng_mod.InferenceEngine.__init__

    def counting_init(self, *a, **kw):
        builds.append(threading.get_ident())
        _time.sleep(0.2)  # widen the race window
        orig_init(self, *a, **kw)

    eng_mod.InferenceEngine.__init__ = counting_init
    try:
        results = []

        def go():
            results.append(eng_mod.shared_engine(
                ModelConfig(name="lenet5", input_shape=(28, 28, 1),
                            dtype="float32", seed=201),
                ShardingConfig(data_parallel=0),
                BatchConfig(max_batch=4, buckets=(4,))))

        threads = [threading.Thread(target=go) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(builds) == 1, f"expected 1 build, got {len(builds)}"
        assert len(results) == 4
        assert all(r is results[0] for r in results)
    finally:
        eng_mod.InferenceEngine.__init__ = orig_init


def test_null_engine_contract():
    from storm_tpu.infer import NullEngine

    eng = NullEngine((28, 28, 1), 10)
    assert eng.input_shape == (28, 28, 1)
    out = eng.predict(np.zeros((7, 28, 28, 1), np.float32))
    assert out.shape == (7, 10)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-5)
    eng.warmup()  # no-op, must not raise
