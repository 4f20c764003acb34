"""MLP-Mixer — all-MLP vision architecture (token-mixing + channel-mixing).

Widens the zoo with an attention-free transformer-era family. TPU notes:
the whole network is dense matmuls over static shapes — pure MXU work with
no gather/scatter; token mixing is a transpose + dense, which XLA fuses
into the surrounding matmuls. Stateless (LayerNorm only), so ``state`` is
an empty dict and inference threads nothing.

``mixer_s16`` is Mixer-S/16 (patch 16, dim 512, depth 8); ``mixer_tiny``
is a test-sized variant for the CPU backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from storm_tpu.models.registry import ModelDef, register
from storm_tpu.ops import layers as L


def _mlp_init(rng, dim, hidden):
    k1, k2 = jax.random.split(rng)
    return {
        "fc1": L.dense_init(k1, dim, hidden),
        "fc2": L.dense_init(k2, hidden, dim),
    }


def _mlp(p, x):
    return L.dense(p["fc2"], L.gelu(L.dense(p["fc1"], x)))


def _block_init(rng, n_tokens, dim, token_mlp, channel_mlp):
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": L.layernorm_init(dim),
        "token": _mlp_init(k1, n_tokens, token_mlp),
        "ln2": L.layernorm_init(dim),
        "channel": _mlp_init(k2, dim, channel_mlp),
    }


def _block(p, x):
    # token mixing: LN -> transpose (B, T, C) -> (B, C, T) -> MLP over T
    y = L.layernorm(p["ln1"], x)
    y = jnp.swapaxes(y, 1, 2)
    y = _mlp(p["token"], y)
    y = jnp.swapaxes(y, 1, 2)
    x = x + y
    return x + _mlp(p["channel"], L.layernorm(p["ln2"], x))


def _build_mixer(name, num_classes, input_shape, patch, dim, depth,
                 token_mlp, channel_mlp) -> ModelDef:
    h, w, c = input_shape
    if h % patch or w % patch:
        raise ValueError(f"input {h}x{w} not divisible by patch {patch}")
    n_tokens = (h // patch) * (w // patch)

    def init(rng):
        keys = jax.random.split(rng, depth + 3)
        params = {
            "stem": L.conv_init(keys[0], patch, patch, c, dim),
            "blocks": [
                _block_init(keys[1 + i], n_tokens, dim, token_mlp, channel_mlp)
                for i in range(depth)
            ],
            "ln": L.layernorm_init(dim),
            "head": L.dense_init(keys[depth + 1], dim, num_classes),
        }
        return params, {}

    def apply(params, state, x, train: bool = False):
        y = L.conv2d(params["stem"], x, stride=patch, padding="VALID")
        y = y.reshape(y.shape[0], -1, y.shape[-1])  # (B, T, C)
        for bp in params["blocks"]:
            y = _block(bp, y)
        y = L.layernorm(params["ln"], y)
        y = jnp.mean(y, axis=1)  # global average over tokens
        return L.dense(params["head"], y), state

    return ModelDef(
        name=name,
        input_shape=tuple(input_shape),
        num_classes=num_classes,
        init=init,
        apply=apply,
    )


@register("mixer_s16")
def build_mixer_s16(num_classes: int = 1000,
                    input_shape: tuple = (224, 224, 3)) -> ModelDef:
    return _build_mixer("mixer_s16", num_classes, input_shape,
                        patch=16, dim=512, depth=8,
                        token_mlp=256, channel_mlp=2048)


@register("mixer_tiny")
def build_mixer_tiny(num_classes: int = 10,
                     input_shape: tuple = (32, 32, 3)) -> ModelDef:
    return _build_mixer("mixer_tiny", num_classes, input_shape,
                        patch=4, dim=64, depth=4,
                        token_mlp=32, channel_mlp=128)
