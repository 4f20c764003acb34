"""Model zoo tests: shapes, determinism, numerics on the CPU backend
(SURVEY.md §4: fake/CPU JAX backend for tests without TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from storm_tpu.models import build_model, registry_names
from storm_tpu.models.registry import init_params


def _fwd(name, batch=2, **kwargs):
    model = build_model(name, **kwargs)
    params, state = init_params(model, seed=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, *model.input_shape))
    logits, new_state = model.apply(params, state, x, train=False)
    return model, logits, params, state


def test_registry_contents():
    names = registry_names()
    for required in ["lenet5", "resnet20", "resnet50", "vit_b16", "vit_tiny"]:
        assert required in names
    with pytest.raises(KeyError):
        build_model("nope")


def test_lenet_shapes():
    model, logits, *_ = _fwd("lenet5")
    assert logits.shape == (2, 10)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_lenet_deterministic_init():
    m = build_model("lenet5")
    p1, _ = init_params(m, seed=0)
    p2, _ = init_params(m, seed=0)
    assert jax.tree.all(jax.tree.map(lambda a, b: bool(jnp.all(a == b)), p1, p2))


@pytest.mark.slow
def test_resnet20_shapes():
    model, logits, *_ = _fwd("resnet20")
    assert logits.shape == (2, 10)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_resnet20_train_updates_bn_state():
    model = build_model("resnet20")
    params, state = init_params(model, 0)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32, 32, 3)) * 3 + 1
    _, new_state = model.apply(params, state, x, train=True)
    stem_before = state["stem"]["bn"]["mean"]
    stem_after = new_state["stem"]["bn"]["mean"]
    assert not bool(jnp.all(stem_before == stem_after))
    # Inference must not mutate state.
    _, same_state = model.apply(params, state, x, train=False)
    assert bool(jnp.all(same_state["stem"]["bn"]["mean"] == stem_before))


@pytest.mark.slow
def test_resnet50_small_input():
    # Same code path as ImageNet config, smaller spatial dims for CI speed.
    model, logits, *_ = _fwd("resnet50", num_classes=100, input_shape=(64, 64, 3))
    assert logits.shape == (2, 100)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_vit_tiny_shapes():
    model, logits, *_ = _fwd("vit_tiny")
    assert logits.shape == (2, 10)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("sizes", [
    pytest.param(dict(input_shape=(32, 32, 3), patch=8, dim=64, depth=2,
                      num_heads=4, mlp_dim=128), id="vit_tiny"),
    # the benchmark's widths and tokens (ViT-g/14), two blocks, float32, CPU
    pytest.param(dict(input_shape=(224, 224, 3), patch=14, dim=1408, depth=2,
                      num_heads=16, mlp_dim=6144), id="g14-two-blocks"),
])
def test_vit_forward_matches_the_plain_composition(sizes):
    """``build_vit``'s apply against the encoder written out in plain jnp
    (the benchmark's reference: add, then LayerNorm, nothing flattened or
    fused), which is what ``_block`` must keep computing."""
    from benchmarks.references import vit as plain
    from storm_tpu.models.vit import build_vit

    model = build_vit("probe", 10, **sizes)
    params, state = init_params(model, seed=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, *model.input_shape))
    published = {"patch_size": sizes["patch"], "hidden_size": sizes["dim"],
                 "num_attention_heads": sizes["num_heads"],
                 "num_hidden_layers": sizes["depth"]}
    with jax.default_matmul_precision("highest"):
        got = jax.nn.softmax(model.apply(params, state, x, train=False)[0], -1)
        want = plain.forward(published, params, state, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_vit_block_keeps_its_signature_and_composes_to_apply():
    """``_block(p, x, num_heads) -> x`` is what parallel/pipeline.py, longseq
    and the vmapped expert tests call: same name, same three arguments, and
    looping it is the encoder."""
    import inspect

    from storm_tpu.models.vit import _block
    from storm_tpu.ops import layers as L

    assert list(inspect.signature(_block).parameters) == ["p", "x", "num_heads"]
    model = build_model("vit_tiny")
    params, state = init_params(model, seed=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, *model.input_shape))
    want, _ = model.apply(params, state, x, train=False)
    tok = L.conv2d(params["embed"], x, stride=8, padding="VALID").reshape(2, 16, 64)
    tok = jnp.concatenate(
        [jnp.broadcast_to(params["cls"], (2, 1, 64)), tok], axis=1) + params["pos"]
    for p in params["blocks"]:
        tok = _block(p, tok, 4)
        assert tok.shape == (2, 17, 64)
    got = L.dense(params["head"], L.layernorm(params["ln"], tok)[:, 0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_vit_g14_program_names_the_shapes_the_roofline_reader_needs():
    """``benchmarks/ops/vit.py rows_per_step`` reads a program's batch off
    operation shapes of the form ``[B,257,1408]``. Lowered shape-only (no
    parameter is allocated) at the benchmark's sizes and batch 8, the
    program must still carry the stream three-dimensional."""
    import re

    from storm_tpu.models.vit import build_vit

    model = build_vit("probe", 1000, (224, 224, 3), patch=14, dim=1408,
                      depth=40, num_heads=16, mlp_dim=6144)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params, state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16), shapes)
    x = jax.ShapeDtypeStruct((8, 224, 224, 3), jnp.bfloat16)
    text = jax.jit(
        lambda p, s, x: model.apply(p, s, x, train=False)[0]
    ).lower(params, state, x).as_text(dialect="hlo")
    found = re.findall(r"\[(\d+),257,1408\]", text)
    assert found and max(set(found), key=found.count) == "8"
    assert len(found) >= 40 * 4  # every block's stream, not one stray shape


def test_vit_patch_divisibility():
    with pytest.raises(ValueError):
        build_model("vit_tiny", input_shape=(30, 30, 3))


@pytest.mark.slow
def test_vit_b16_param_count():
    """ViT-B/16 has ~86M params — structural check against the standard
    architecture (12 layers, dim 768, heads 12, mlp 3072)."""
    model = build_model("vit_b16")
    params, _ = init_params(model, 0)
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    assert 85e6 < n < 87e6


def test_checkpoint_roundtrip(tmp_path):
    from storm_tpu.models.registry import load_or_init, save_checkpoint

    model = build_model("lenet5")
    params, state = init_params(model, 0)
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, params, state)
    params2, _ = load_or_init(model, path, seed=99)
    assert jax.tree.all(jax.tree.map(lambda a, b: bool(jnp.all(a == b)), params, params2))


def test_checkpoint_hyper_mismatch_refused(tmp_path):
    """A checkpoint records compute-relevant hyperparameters that param
    shapes can't encode (num_heads: attention projections are dim x dim
    for any head count). Loading it into a model built with different
    ones must fail loudly, not silently compute differently-partitioned
    attention (ADVICE r3 medium, longseq num_heads 8 -> 2)."""
    import pytest

    from storm_tpu.models.registry import load_or_init, save_checkpoint

    m2 = build_model("longseq_tiny")  # num_heads=4 default
    params, state = init_params(m2, 0)
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, params, state, model=m2)

    # same param shapes, different head partitioning -> refused
    m8 = build_model("longseq_tiny", num_heads=8)
    with pytest.raises(ValueError, match="num_heads"):
        load_or_init(m8, path, seed=0)

    # matching hyper loads fine
    params2, _ = load_or_init(build_model("longseq_tiny"), path, seed=99)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool(jnp.all(a == b)), params, params2))

    # pre-sidecar checkpoints (no hyper file) still load best-effort
    import os

    os.remove(os.path.join(path, "storm_tpu_hyper.json"))
    load_or_init(m8, path, seed=0)


# ---- MoE-ViT -----------------------------------------------------------------


def test_moe_vit_forward_and_softmax():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from storm_tpu.models import build_model

    model = build_model("moe_vit_tiny")
    params, state = model.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).rand(3, 32, 32, 3), jnp.float32)
    logits, st = model.apply(params, state, x, train=False)
    assert logits.shape == (3, 10)
    assert np.all(np.isfinite(np.asarray(logits)))
    # MoE blocks present in odd positions, dense in even
    assert "moe" in params["blocks"][1] and "moe" not in params["blocks"][0]


def test_moe_vit_train_surface_carries_aux():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from storm_tpu.models import build_model

    model = build_model("moe_vit_tiny")
    params, state = model.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3), jnp.float32)
    _, st = model.apply(params, state, x, train=True)
    assert float(st["moe_aux_loss"]) > 0


def test_moe_vit_serves_through_engine():
    import numpy as np

    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer.engine import InferenceEngine

    eng = InferenceEngine(
        ModelConfig(name="moe_vit_tiny", dtype="float32",
                    input_shape=(32, 32, 3), num_classes=10),
        ShardingConfig(data_parallel=1),
        BatchConfig(max_batch=4, buckets=(4,)),
    )
    out = eng.predict(np.random.rand(3, 32, 32, 3).astype(np.float32))
    assert out.shape == (3, 10)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, atol=1e-4)


@pytest.mark.slow
def test_mobilenetv2_shapes_cifar():
    model, logits, *_ = _fwd("mobilenetv2", num_classes=10,
                             input_shape=(32, 32, 3), width=0.5)
    assert logits.shape == (2, 10)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_mobilenetv2_bn_state_and_residuals():
    model = build_model("mobilenetv2", num_classes=10,
                        input_shape=(32, 32, 3), width=0.5)
    params, state = init_params(model, 0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32, 3)) * 2
    _, new_state = model.apply(params, state, x, train=True)
    before = state["stem"]["bn"]["mean"]
    after = new_state["stem"]["bn"]["mean"]
    assert not bool(jnp.all(before == after))
    # inference leaves state untouched
    _, same = model.apply(params, state, x, train=False)
    assert bool(jnp.all(same["stem"]["bn"]["mean"] == before))


def test_mixer_shapes_and_stateless():
    model, logits, params, state = _fwd("mixer_tiny")
    assert logits.shape == (2, 10)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert state == {}


def test_mixer_patch_divisibility():
    with pytest.raises(ValueError):
        build_model("mixer_tiny", input_shape=(30, 30, 3))


def test_new_families_serve_through_engine():
    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer.engine import InferenceEngine

    for name, shape in [("mobilenetv2", (32, 32, 3)), ("mixer_tiny", (32, 32, 3))]:
        eng = InferenceEngine(
            ModelConfig(name=name, input_shape=shape, num_classes=10,
                        dtype="float32",
                        extra={"width": 0.5} if name == "mobilenetv2" else {}),
            ShardingConfig(data_parallel=0),
            BatchConfig(max_batch=4, buckets=(4,)),
        )
        out = eng.predict(np.random.rand(3, *shape).astype(np.float32))
        assert out.shape == (3, 10)
        np.testing.assert_allclose(out.sum(axis=1), 1.0, atol=1e-4)


# ---- long-context serving family ---------------------------------------------


def test_longseq_tiny_shapes_and_engine():
    """Long-context encoder serves through the standard engine path:
    rank-2 instances (seq, features), softmax out, stateless."""
    import numpy as np

    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer.engine import InferenceEngine
    from storm_tpu.models import build_model
    from storm_tpu.models.registry import init_params

    model = build_model("longseq_tiny")
    params, state = init_params(model, seed=0)
    assert state == {}
    x = np.random.RandomState(0).rand(3, 64, 16).astype(np.float32)
    logits, _ = model.apply(params, state, x)
    assert logits.shape == (3, 10)

    eng = InferenceEngine(
        ModelConfig(name="longseq_tiny", dtype="float32",
                    input_shape=(64, 16)),
        ShardingConfig(data_parallel=0),
        BatchConfig(max_batch=8, buckets=(8,)),
    )
    out = eng.predict(x)
    assert out.shape == (3, 10)
    np.testing.assert_allclose(out.sum(-1), np.ones(3), atol=1e-4)


def test_longseq_tp_shards_like_the_zoo():
    """q/k/v/mlp naming means shard_params_tp applies unchanged: the
    long-context family is TP-servable out of the box."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from storm_tpu.models import build_model
    from storm_tpu.models.registry import init_params
    from storm_tpu.parallel.mesh import make_mesh
    from storm_tpu.parallel.sharding import shard_params_tp

    model = build_model("longseq_tiny")
    params, _ = init_params(model, seed=0)
    mesh = make_mesh(4, 2)
    placed = shard_params_tp(mesh, params)
    blk = placed["blocks"][0]
    assert blk["attn"]["q"]["w"].sharding.spec == P(None, "model")
    assert blk["attn"]["o"]["w"].sharding.spec == P("model", None)
    assert blk["mlp_in"]["w"].sharding.spec == P(None, "model")


def test_longseq_e2e_through_topology(run):
    """Rank-2 instances flow broker -> spout -> InferenceBolt -> sink."""
    import asyncio
    import json as _json

    import numpy as np

    from storm_tpu.config import (BatchConfig, Config, ModelConfig,
                                  OffsetsConfig, ShardingConfig)
    from storm_tpu.connectors import BrokerSink, BrokerSpout, MemoryBroker
    from storm_tpu.infer import InferenceBolt
    from storm_tpu.runtime import TopologyBuilder
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    async def main():
        broker = MemoryBroker(default_partitions=1)
        cfg = Config()
        tb = TopologyBuilder()
        tb.set_spout("s", BrokerSpout(
            broker, "in", OffsetsConfig(policy="earliest", max_behind=None)),
            1)
        tb.set_bolt("infer", InferenceBolt(
            ModelConfig(name="longseq_tiny", dtype="float32",
                        input_shape=(64, 16)),
            BatchConfig(max_batch=4, max_wait_ms=10, buckets=(4,)),
            ShardingConfig(data_parallel=0), warmup=False), 1)\
            .shuffle_grouping("s")
        tb.set_bolt("sink", BrokerSink(broker, "out", cfg.sink), 1)\
            .shuffle_grouping("infer")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("longseq", cfg, tb.build())
        rng = np.random.RandomState(0)
        for _ in range(4):
            broker.produce("in", _json.dumps(
                {"instances": rng.rand(1, 64, 16).tolist()}))
        deadline = asyncio.get_event_loop().time() + 60
        while asyncio.get_event_loop().time() < deadline:
            if broker.topic_size("out") >= 4:
                break
            await asyncio.sleep(0.05)
        await rt.drain(timeout_s=15)
        outs = broker.drain_topic("out")
        assert len(outs) == 4
        assert rt.metrics.snapshot()["s"]["tree_acked"] == 4
        await cluster.shutdown()

    run(main(), timeout=120)
