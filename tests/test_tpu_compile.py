"""Compile the main path's kernels for a *described* TPU v5e, without a chip.

The TPU compiler is installed beside the CPU backend and compiles for a
topology that is described, not attached (on-chip-measurement guide,
section 2, rehearsal 3). That catches what interpret mode cannot: a slice
not aligned to the tiling, more fast memory than a kernel may use, a
kernel the compiler refuses to place in a whole program. Nothing runs, so
these say nothing of results or times — ``chip_smoke.py`` does that on the
chip. Skipped where the topology cannot be described.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def v5e():
    """One device of a described v5e 2x2 host, as a sharding."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it cannot describe a v5e
        pytest.skip(f"TPU topology cannot be described: {e!r}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (the next compile warns and
    compiles again), so the cache is off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("shape", [(8, 2, 2048, 128), (1, 8, 4096, 64)])
def test_flash_attention_compiles_for_v5e(v5e, shape):
    """longseq_encoder's serving shape (batch 8, 2 heads of 128) and a long
    S=4096 shape with 64-wide heads (padded to the 128-lane tile)."""
    from storm_tpu.ops.flash_attention import flash_attention

    q = _spec(shape, jnp.bfloat16, v5e)
    text = flash_attention.lower(q, q, q).compile().as_text()
    assert "tpu_custom_call" in text


def test_fused_norm_compiles_for_v5e(v5e):
    """vit_b16's residual + LayerNorm at batch 64: 64 * 197 tokens x 768."""
    from storm_tpu.ops.fused_norm import _fused_fwd_pallas

    x = _spec((12608, 768), jnp.bfloat16, v5e)
    g = _spec((768,), jnp.float32, v5e)
    text = _fused_fwd_pallas.lower(x, x, g, g, eps=1e-6).compile().as_text()
    assert "tpu_custom_call" in text


def test_w8a16_matmul_compiles_for_v5e(v5e):
    """vit_b16's mlp_in (768 x 3072) at batch 64, int8 weights."""
    from storm_tpu.ops.quant_matmul import w8a16_matmul

    x = _spec((12608, 768), jnp.bfloat16, v5e)
    q = _spec((768, 3072), jnp.int8, v5e)
    s = _spec((3072,), jnp.float32, v5e)
    text = w8a16_matmul.lower(x, q, s).compile().as_text()
    assert "tpu_custom_call" in text


def test_longseq_encoder_forward_compiles_with_flash_kernel(v5e, monkeypatch):
    """The whole longseq_encoder forward at batch 8 with the kernel in it.
    On a CPU host the dispatch predicate answers False, so the kernel is
    forced on here, in the test (not through an option of the program)."""
    import storm_tpu.ops.attention as attention
    from storm_tpu.models.registry import build_model

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    model = build_model("longseq_encoder", num_classes=10,
                        input_shape=(2048, 64))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params, state = jax.tree.map(
        lambda a: _spec(a.shape, jnp.bfloat16 if a.dtype == jnp.float32
                        else a.dtype, v5e), shapes)

    def fwd(p, s, x):
        logits, _ = model.apply(p, s, x, train=False)
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    x = _spec((8, 2048, 64), jnp.bfloat16, v5e)
    compiled = jax.jit(fwd).lower(params, state, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # fits one chip's 16 GB with room to spare
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 2 << 30
