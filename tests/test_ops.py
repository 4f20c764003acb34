"""Op-level numerics: layers + attention reference path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from storm_tpu.ops import layers as L
from storm_tpu.ops.attention import attention_reference, mha_init, multi_head_attention


def test_dense_matches_numpy():
    rng = jax.random.PRNGKey(0)
    p = L.dense_init(rng, 8, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8))
    got = L.dense(p, x)
    want = np.asarray(x) @ np.asarray(p["w"]) + np.asarray(p["b"])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_conv_identity_kernel():
    # 1x1 identity conv leaves channels unchanged.
    p = {"w": jnp.eye(3).reshape(1, 1, 3, 3)}
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 5, 3))
    np.testing.assert_allclose(np.asarray(conv := L.conv2d(p, x)), np.asarray(x), atol=1e-6)


def test_pooling():
    x = jnp.arange(16, dtype=jnp.float32).reshape(1, 4, 4, 1)
    mp = L.max_pool(x)
    ap = L.avg_pool(x)
    assert mp.shape == (1, 2, 2, 1)
    assert float(mp[0, 0, 0, 0]) == 5.0
    assert float(ap[0, 0, 0, 0]) == 2.5


def test_batchnorm_train_normalizes():
    p, s = L.batchnorm_init(4)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 4)) * 5 + 3
    y, new_s = L.batchnorm(p, s, x, train=True)
    np.testing.assert_allclose(np.asarray(jnp.mean(y, 0)), np.zeros(4), atol=1e-4)
    np.testing.assert_allclose(np.asarray(jnp.std(y, 0)), np.ones(4), atol=1e-2)
    assert not np.allclose(np.asarray(new_s["mean"]), 0)


def test_layernorm():
    p = L.layernorm_init(8)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8)) * 4 + 2
    y = L.layernorm(p, x)
    np.testing.assert_allclose(np.asarray(jnp.mean(y, -1)), np.zeros((2,)), atol=1e-5)


def test_attention_reference_softmax_rows():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 4, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 4, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 4, 8))
    out = attention_reference(q, k, v)
    assert out.shape == (1, 2, 4, 8)
    # attention output is a convex combination of v rows: bounded by v range
    assert float(jnp.max(out)) <= float(jnp.max(v)) + 1e-5
    assert float(jnp.min(out)) >= float(jnp.min(v)) - 1e-5


def test_mha_shapes():
    p = mha_init(jax.random.PRNGKey(0), 32, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 32))
    y = multi_head_attention(p, x, 4)
    assert y.shape == (2, 10, 32)


@pytest.mark.slow
def test_flash_attention_matches_reference_interpret():
    """Pallas kernel (interpreter on CPU) vs the jnp reference path —
    includes the ViT-B/16 shape (197 padded) and a multi-KV-chunk case."""
    from storm_tpu.ops.flash_attention import flash_attention

    for b, h, s, d in [(1, 2, 197, 64), (2, 1, 64, 32), (1, 1, 600, 64)]:
        q, k, v = (
            jax.random.normal(jax.random.PRNGKey(i), (b, h, s, d), jnp.float32)
            for i in range(3)
        )
        want = attention_reference(q, k, v)
        got = flash_attention(q, k, v, interpret=True, block_k=256)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


def test_w8a16_matmul_matches_dequant_reference():
    """Pallas fused dequant-matmul (interpreter on CPU) vs explicit
    dequantize-then-dot, over shapes that exercise M/N/K padding and
    3-D activations (the ViT token layout)."""
    from storm_tpu.infer.engine import quantize_params
    from storm_tpu.ops.quant_matmul import w8a16_matmul

    rng = np.random.RandomState(0)
    for xshape, k, n in [
        ((4, 64), 64, 128),        # exact tiles
        ((5, 100), 100, 70),       # every axis padded
        ((2, 9, 48), 48, 200),     # 3-D activations, N > block_n
        ((1, 700), 700, 10),       # K > block_k (multi-chunk loop)
    ]:
        x = jnp.asarray(rng.randn(*xshape), jnp.float32)
        w = jnp.asarray(rng.randn(k, n), jnp.float32)
        q = quantize_params({"w": w})["w"]
        want = x @ (q["__q"].astype(jnp.float32) * q["__s"])
        got = w8a16_matmul(x, q["__q"], q["__s"], interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)


def test_dense_dispatches_on_quantized_weights():
    """layers.dense must route {"__q","__s"} weights through the fused
    path (jnp fallback off-TPU) and match the float layer closely."""
    from storm_tpu.infer.engine import quantize_params
    from storm_tpu.ops import layers as L

    rng = jax.random.PRNGKey(3)
    p = L.dense_init(rng, 32, 16)
    x = jax.random.normal(jax.random.PRNGKey(4), (6, 32), jnp.float32)
    want = L.dense(p, x)
    qp = {"w": quantize_params({"w": p["w"]})["w"], "b": p["b"]}
    got = L.dense(qp, x)
    assert np.max(np.abs(np.asarray(got - want))) < 0.05


def test_fused_residual_layernorm_kernel_matches_reference():
    """Pallas fused add+LN (interpreter) vs the jnp reference, including
    padded dims and row blocks."""
    from storm_tpu.ops.fused_norm import _fused_fwd_pallas, _reference

    rng = np.random.RandomState(0)
    for rows, d in [(6, 64), (300, 100), (5, 768)]:
        x = jnp.asarray(rng.randn(rows, d), jnp.float32)
        r = jnp.asarray(rng.randn(rows, d), jnp.float32)
        g = jnp.asarray(rng.randn(d), jnp.float32)
        b = jnp.asarray(rng.randn(d), jnp.float32)
        wy, wo = _reference(x, r, g, b, 1e-6)
        gy, go = _fused_fwd_pallas(x, r, g, b, eps=1e-6, interpret=True)
        np.testing.assert_allclose(np.asarray(gy), np.asarray(wy), atol=1e-5)
        np.testing.assert_allclose(np.asarray(go), np.asarray(wo), atol=1e-4)


@pytest.mark.slow
def test_fused_residual_layernorm_grads():
    """custom_vjp backward must match autodiff through the unfused ops —
    the training path (pjit/pipeline dryruns) differentiates blocks that
    use this kernel."""
    from storm_tpu.ops import layers as L
    from storm_tpu.ops.fused_norm import residual_layernorm

    rng = np.random.RandomState(1)
    p = {"scale": jnp.asarray(rng.randn(32), jnp.float32),
         "bias": jnp.asarray(rng.randn(32), jnp.float32)}
    x = jnp.asarray(rng.randn(4, 7, 32), jnp.float32)
    br = jnp.asarray(rng.randn(4, 7, 32), jnp.float32)

    def fused_loss(p, br, x):
        y, out = residual_layernorm(p, br, x)
        return jnp.sum(out ** 2) + jnp.sum(y ** 3)

    def ref_loss(p, br, x):
        y = x + br
        return jnp.sum(L.layernorm(p, y) ** 2) + jnp.sum(y ** 3)

    lf, gf = jax.value_and_grad(fused_loss, argnums=(0, 1, 2))(p, br, x)
    lr, gr = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(p, br, x)
    np.testing.assert_allclose(float(lf), float(lr), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# ---- Pallas-vs-reference dispatch predicate (ops/platform.py) ---------------


class _FakeDevice:
    def __init__(self, platform):
        self.platform = platform


def _raising_devices():
    raise RuntimeError("Unable to initialize backend 'tpu': ABORTED: Internal "
                       "error when accessing libtpu multi-process lockfile.")


@pytest.mark.parametrize("devices,no_pallas,want", [
    (lambda: [_FakeDevice("cpu")], "", False),
    (lambda: [_FakeDevice("tpu")], "", True),
    (lambda: [_FakeDevice("tpu")], "1", False),
    # the escape hatch is honoured before the backend is asked
    (_raising_devices, "1", False),
    # a chip that cannot be opened is an error, never the jnp path
    (_raising_devices, "", RuntimeError),
], ids=["cpu", "tpu", "tpu_forced_off", "forced_off_no_backend",
        "backend_error_propagates"])
def test_use_pallas_dispatch(monkeypatch, devices, no_pallas, want):
    from storm_tpu.ops import platform

    monkeypatch.setattr(platform.jax, "devices", devices)
    monkeypatch.setenv("STORM_TPU_NO_PALLAS", no_pallas)
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="lockfile"):
            platform.use_pallas()
    else:
        assert platform.use_pallas() is want


def test_open_devices_names_a_held_chip(monkeypatch):
    """libtpu's lockfile error tells the operator to delete the lock; the
    serving path says what really happened: another process holds the chip."""
    from storm_tpu.parallel import mesh

    monkeypatch.setattr(mesh.jax, "devices", _raising_devices)
    with pytest.raises(RuntimeError,
                       match="a chip belongs to one process at a time"):
        mesh.make_mesh()

    def other_error():
        raise RuntimeError("Unknown backend 'tpu'")

    monkeypatch.setattr(mesh.jax, "devices", other_error)
    with pytest.raises(RuntimeError, match="Unknown backend"):
        mesh.open_devices()
