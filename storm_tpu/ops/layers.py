"""Functional NN layers: pure jnp/lax functions + param initializers.

The compute vocabulary for the model zoo (:mod:`storm_tpu.models`), written
TPU-first: NHWC layouts (XLA's preferred conv layout on TPU), matmul-shaped
ops that tile onto the MXU, static shapes everywhere, and no Python control
flow inside traced code. Replaces the reference's opaque frozen-graph blob
(``SavedModelBundle.load``, InferenceBolt.java:57) with transparent param
pytrees.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# ---- initializers ------------------------------------------------------------


def he_normal(rng, shape, fan_in: int, dtype=jnp.float32):
    return jax.random.normal(rng, shape, dtype) * np.sqrt(2.0 / fan_in)


def lecun_normal(rng, shape, fan_in: int, dtype=jnp.float32):
    return jax.random.normal(rng, shape, dtype) * np.sqrt(1.0 / fan_in)


def trunc_normal(rng, shape, std: float = 0.02, dtype=jnp.float32):
    return jax.random.truncated_normal(rng, -2.0, 2.0, shape, dtype) * std


# ---- dense -------------------------------------------------------------------


def dense_init(rng, in_dim: int, out_dim: int, dtype=jnp.float32) -> dict:
    kw, _ = jax.random.split(rng)
    return {
        "w": lecun_normal(kw, (in_dim, out_dim), in_dim, dtype),
        "b": jnp.zeros((out_dim,), dtype),
    }


def dense(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    if isinstance(p["w"], dict) and "__q" in p["w"]:
        # Weight left int8 by the engine's "int8_fused" mode: run the
        # Pallas fused dequant-matmul so only int8 bytes leave HBM.
        from storm_tpu.ops.quant_matmul import qdense

        return qdense(p, x)
    # Accumulate matmuls in f32 on the MXU even for bf16 inputs.
    return jnp.dot(x, p["w"], preferred_element_type=jnp.float32).astype(x.dtype) + p["b"]


# ---- conv --------------------------------------------------------------------


def conv_init(
    rng, kh: int, kw: int, cin: int, cout: int, dtype=jnp.float32, bias: bool = True
) -> dict:
    kr, _ = jax.random.split(rng)
    p = {"w": he_normal(kr, (kh, kw, cin, cout), kh * kw * cin, dtype)}
    if bias:
        p["b"] = jnp.zeros((cout,), dtype)
    return p


def conv2d(
    p: dict,
    x: jnp.ndarray,
    stride: int | Tuple[int, int] = 1,
    padding: str | Sequence[Tuple[int, int]] = "SAME",
) -> jnp.ndarray:
    """NHWC x HWIO -> NHWC convolution (MXU path)."""
    if isinstance(stride, int):
        stride = (stride, stride)
    out = lax.conv_general_dilated(
        x,
        p["w"].astype(x.dtype),
        window_strides=stride,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    if "b" in p:
        out = out + p["b"].astype(x.dtype)
    return out


# ---- pooling -----------------------------------------------------------------


def max_pool(x: jnp.ndarray, window: int = 2, stride: int = 2) -> jnp.ndarray:
    return lax.reduce_window(
        x,
        -jnp.inf,
        lax.max,
        (1, window, window, 1),
        (1, stride, stride, 1),
        "VALID",
    )


def avg_pool(x: jnp.ndarray, window: int = 2, stride: int = 2) -> jnp.ndarray:
    s = lax.reduce_window(
        x, 0.0, lax.add, (1, window, window, 1), (1, stride, stride, 1), "VALID"
    )
    return s / (window * window)


def global_avg_pool(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(x, axis=(1, 2))


# ---- normalization -----------------------------------------------------------


def batchnorm_init(dim: int, dtype=jnp.float32) -> Tuple[dict, dict]:
    """Returns (params, state): scale/bias are learned; mean/var are running
    statistics threaded functionally (state in, state out)."""
    params = {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}
    state = {"mean": jnp.zeros((dim,), jnp.float32), "var": jnp.ones((dim,), jnp.float32)}
    return params, state


def batchnorm(
    p: dict,
    s: dict,
    x: jnp.ndarray,
    train: bool = False,
    momentum: float = 0.9,
    eps: float = 1e-5,
) -> Tuple[jnp.ndarray, dict]:
    """BatchNorm over all but the channel (last) axis. Returns (y, new_state)."""
    if train:
        axes = tuple(range(x.ndim - 1))
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=axes)
        var = jnp.var(xf, axis=axes)
        new_s = {
            "mean": momentum * s["mean"] + (1 - momentum) * mean,
            "var": momentum * s["var"] + (1 - momentum) * var,
        }
    else:
        mean, var = s["mean"], s["var"]
        new_s = s
    inv = lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    y = (x.astype(jnp.float32) - mean) * inv + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype), new_s


def layernorm_init(dim: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(p: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def rmsnorm_init(dim: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(p: dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """x / rms(x) * scale over the last axis, the statistics in float32."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * p["scale"]).astype(x.dtype)


def gated_group_rmsnorm(p: dict, x: jnp.ndarray, z: jnp.ndarray, groups: int,
                        eps: float = 1e-5) -> jnp.ndarray:
    """``RMSNorm(x * silu(z))`` with the statistics over each of ``groups``
    equal runs of the last axis and one learned scale a channel (Mamba-2's
    output norm: the gate first, then the norm)."""
    y = x.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(*y.shape[:-1], groups, y.shape[-1] // groups)
    grouped = grouped * lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return (grouped.reshape(y.shape) * p["scale"]).astype(x.dtype)


# ---- activations -------------------------------------------------------------

relu = jax.nn.relu
gelu = jax.nn.gelu
softmax = jax.nn.softmax


def relu6(x: jnp.ndarray) -> jnp.ndarray:
    """min(max(x, 0), 6) — MobileNet's quantization-friendly activation."""
    return jnp.clip(x, 0.0, 6.0)


def depthwise_conv_init(rng, kh: int, kw: int, c: int, dtype=jnp.float32) -> dict:
    """Per-channel (depthwise) kernel: HWIO with I=1, grouped over channels."""
    return {"w": he_normal(rng, (kh, kw, 1, c), kh * kw, dtype)}


def depthwise_conv2d(
    p: dict,
    x: jnp.ndarray,
    stride: int | Tuple[int, int] = 1,
    padding: str | Sequence[Tuple[int, int]] = "SAME",
) -> jnp.ndarray:
    """NHWC depthwise convolution (feature_group_count = channels)."""
    if isinstance(stride, int):
        stride = (stride, stride)
    c = x.shape[-1]
    return lax.conv_general_dilated(
        x,
        p["w"].astype(x.dtype),
        window_strides=stride,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c,
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)


# ---- bias-free feed-forwards -------------------------------------------------


def matmul(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """``x @ w`` accumulated in float32 on the matrix unit, in x's type."""
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)


def swiglu_init(rng, dim: int, hidden: int, dtype=jnp.float32) -> dict:
    kg, ku, kd = jax.random.split(rng, 3)
    return {"gate": lecun_normal(kg, (dim, hidden), dim, dtype),
            "up": lecun_normal(ku, (dim, hidden), dim, dtype),
            "down": lecun_normal(kd, (hidden, dim), hidden, dtype)}


def swiglu(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """down(silu(gate x) * up x): the bias-free gated feed-forward."""
    return matmul(jax.nn.silu(matmul(x, p["gate"])) * matmul(x, p["up"]),
                  p["down"])


def relu2_init(rng, dim: int, hidden: int, dtype=jnp.float32) -> dict:
    ku, kd = jax.random.split(rng)
    return {"up": lecun_normal(ku, (dim, hidden), dim, dtype),
            "down": lecun_normal(kd, (hidden, dim), hidden, dtype)}


def relu2(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """down(relu(up x)^2): two matrices with a squared ReLU between."""
    return matmul(jnp.square(jax.nn.relu(matmul(x, p["up"]))), p["down"])


def feed_forward(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """The feed-forward its parameters describe: SwiGLU where they have a
    ``gate``, squared ReLU over ``up`` and ``down`` where they do not."""
    return swiglu(p, x) if "gate" in p else relu2(p, x)


def row_mean(x: jnp.ndarray) -> jnp.ndarray:
    """The float32 mean over the last axis, kept as an axis of one: the
    statistic :func:`layernorm_about` is handed."""
    return jnp.mean(x.astype(jnp.float32), axis=-1, keepdims=True)


def layernorm_about(p: dict, x: jnp.ndarray, mean: jnp.ndarray,
                    eps: float = 1e-6) -> jnp.ndarray:
    """:func:`layernorm` with ``row_mean(x)`` handed in, for a caller that
    made it where ``x`` was made (models/vit.py carries it from block to
    block): the same arithmetic in the same order, ``jnp.var`` being the
    mean of the squares about that mean. Below everything else of this file:
    the compile cache's key covers an operation's source line."""
    centred = x.astype(jnp.float32) - mean
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return (centred * lax.rsqrt(var + eps) * p["scale"]
            + p["bias"]).astype(x.dtype)
